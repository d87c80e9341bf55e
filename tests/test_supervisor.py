"""Supervised worker pool (serve/supervisor.py): crash detection, restart,
re-dispatch, and future-resolution guarantees — all on cheap echo tasks
(no jax in the workers' task path)."""
import os
import signal
import time

import pytest

from repro.faults import FaultInjector
from repro.serve import supervisor
from repro.serve.supervisor import (SupervisedWorkerPool, WorkerCrashError,
                                    WorkerPoolClosedError, pinned_env,
                                    worker_platform)

ECHO = "repro.serve.supervisor:echo_task"
FAST = {"restart_backoff_s": 0.01, "poll_s": 0.01}


def test_roundtrip_and_error_propagation():
    with SupervisedWorkerPool(2, **FAST) as pool:
        futs = [pool.submit(ECHO, {"x": i}) for i in range(8)]
        assert sorted(f.result(timeout=60)["x"] for f in futs) == list(range(8))
        bad = pool.submit(ECHO, {"raise": "kaboom"})
        with pytest.raises(ValueError, match="kaboom"):
            bad.result(timeout=60)
        s = pool.stats()
        assert s["ok"] == 8 and s["err"] == 1 and s["crashes"] == 0


def test_injected_sigkill_mid_request_future_still_resolves():
    inj = FaultInjector(fail_at={"worker_kill": (0,)})
    with SupervisedWorkerPool(2, fault_injector=inj, **FAST) as pool:
        fut = pool.submit(ECHO, {"x": 7, "sleep_s": 0.3})
        assert fut.result(timeout=60)["x"] == 7  # zero unresolved futures
        s = pool.stats()
        assert s["killed_injected"] == 1
        assert s["crashes"] >= 1
        assert s["restarts"] >= 1
        assert s["redispatched"] >= 1
        assert inj.fired and inj.fired[0][0] == "worker_kill"


def test_external_sigkill_detected_and_restarted():
    with SupervisedWorkerPool(1, **FAST) as pool:
        # a task that kills its own worker once: the pool must restart the
        # slot and the re-dispatched copy (which kills again...) must
        # eventually exhaust — but here we kill externally instead, with a
        # benign task in flight.
        fut = pool.submit(ECHO, {"x": 1, "sleep_s": 1.0})
        deadline = time.monotonic() + 10
        pid = None
        while time.monotonic() < deadline and pid is None:
            w = pool._workers[0]
            if w.task is not None and w.alive():
                pid = w.proc.pid
            else:
                time.sleep(0.01)
        assert pid is not None
        os.kill(pid, signal.SIGKILL)
        assert fut.result(timeout=60)["x"] == 1
        s = pool.stats()
        assert s["crashes"] >= 1 and s["redispatched"] >= 1


def test_repeat_crasher_fails_typed_and_transient():
    with SupervisedWorkerPool(1, max_redispatch=1, **FAST) as pool:
        fut = pool.submit(ECHO, {"die": True})
        with pytest.raises(WorkerCrashError) as ei:
            fut.result(timeout=120)
        assert ei.value.transient is True  # feeds the service retry ladder
        assert ei.value.redispatches == 1
        s = pool.stats()
        assert s["crash_failed"] == 1 and s["crashes"] >= 2
        # the pool survives its crasher: a clean task still runs
        assert pool.submit(ECHO, {"x": 5}).result(timeout=60)["x"] == 5


def test_restart_backoff_is_capped_exponential():
    with SupervisedWorkerPool(1, max_redispatch=3, restart_backoff_s=0.05,
                              restart_backoff_cap_s=0.1, poll_s=0.01) as pool:
        fut = pool.submit(ECHO, {"die": True})
        with pytest.raises(WorkerCrashError):
            fut.result(timeout=120)
        w = pool._workers[0]
        assert w.consecutive_crashes >= 4
        # a completed task resets the crash streak
        assert pool.submit(ECHO, {"x": 1}).result(timeout=60)["x"] == 1
        assert pool._workers[0].consecutive_crashes == 0


def test_close_fails_pending_futures():
    pool = SupervisedWorkerPool(1, **FAST)
    slow = pool.submit(ECHO, {"sleep_s": 30})
    queued = pool.submit(ECHO, {"x": 2})
    pool.close(wait=False)
    with pytest.raises(WorkerPoolClosedError):
        queued.result(timeout=10)
    with pytest.raises(WorkerPoolClosedError):
        slow.result(timeout=10)
    with pytest.raises(WorkerPoolClosedError):
        pool.submit(ECHO, {"x": 3})


def test_burst_with_random_kills_all_futures_resolve():
    """The acceptance criterion at pool level: under repeated injected
    SIGKILLs, every submitted future resolves (result or typed error)."""
    inj = FaultInjector(fail_at={"worker_kill": (1, 3, 5)})
    with SupervisedWorkerPool(2, fault_injector=inj, max_redispatch=3,
                              **FAST) as pool:
        futs = [pool.submit(ECHO, {"x": i, "sleep_s": 0.05})
                for i in range(12)]
        done = 0
        for f in futs:
            try:
                f.result(timeout=120)
                done += 1
            except WorkerCrashError:
                done += 1  # typed resolution still counts as resolved
        assert done == 12
        assert all(f.done() for f in futs)


# --- one chip per worker -----------------------------------------------------

def test_worker_platform_cpu_needs_no_probe(monkeypatch):
    """JAX_PLATFORMS=cpu answers without starting a child process."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")

    def no_probe(*a, **k):
        raise AssertionError("probed a child process")

    monkeypatch.setattr(supervisor.subprocess, "run", no_probe)
    assert worker_platform() == ("cpu", 1)


@pytest.mark.parametrize("chip", [0, 3])
def test_pinned_env_gives_a_tpu_worker_one_chip(chip):
    env = pinned_env("tpu", chip)
    assert env["JAX_PLATFORMS"] == "tpu"
    assert env["TPU_VISIBLE_CHIPS"] == str(chip)
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_ADDRESSES"] == f"localhost:{env['TPU_PROCESS_PORT']}"


def test_pinned_env_cpu_and_unsupported_platforms():
    assert pinned_env("cpu", 5) == {"JAX_PLATFORMS": "cpu"}
    with pytest.raises(ValueError, match="gpu"):
        pinned_env("gpu", 0)


def test_more_workers_than_chips_is_an_error():
    with pytest.raises(ValueError, match="one worker per chip"):
        SupervisedWorkerPool(3, platform=("tpu", 2), **FAST)


def test_workers_inherit_a_pinned_platform():
    """A worker is pinned to the platform the pool settled on, and a
    respawned worker is pinned again."""
    inj = FaultInjector(fail_at={"worker_kill": (0,)})
    keys = ["JAX_PLATFORMS", "TPU_VISIBLE_CHIPS"]
    with SupervisedWorkerPool(1, platform=("cpu", 1), fault_injector=inj,
                              **FAST) as pool:
        for _ in range(2):  # the first dispatch kills its worker
            env = pool.submit(ECHO, {"getenv": keys}).result(timeout=60)
            assert env == {"JAX_PLATFORMS": "cpu", "TPU_VISIBLE_CHIPS": None}
        assert pool.stats()["restarts"] >= 1
