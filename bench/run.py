#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell asks
for. The run draws its requests from ``--seed`` (the configuration's
instance family says what else the seed changes), warms every program its
window uses (set-up), measures for ``--seconds``, checks every answer
against the plain reference in ``yardstick.py``, and prints one JSON line
last on standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics, each read by ``metrics/<name>.py``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``check``: each number compared and
its limit, which also end standard error. Without a TPU, or with fewer
chips than the cell asks for, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_IMPORT = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check as C  # noqa: E402
from bench import trace as T  # noqa: E402
from bench.spec import Cell  # noqa: E402

CACHE_DIR = ".jax_cache"     # fixed, inside the checkout
TRACE_DIR = ".bench_trace"   # fixed, inside the checkout; emptied per run


class NoChip(Exception):
    pass


def process_start() -> float:
    """When this process started, on the ``time.time`` clock."""
    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1]
                    .split()[19])
        boot = next(float(x.split()[1]) for x in
                    Path("/proc/stat").read_text().splitlines()
                    if x.startswith("btime "))
        return boot + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, StopIteration, IndexError, ValueError):
        return T_IMPORT


def tpu_devices(chips: int):
    """The devices a cell runs on; raises :class:`NoChip` without them."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise NoChip(f"no TPU found: JAX's backend is {backend!r}")
    devs = jax.devices()
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs[:chips]


class Tracer:
    """The profiler over the first ``seconds`` of the window."""

    def __init__(self, log_dir: Path, seconds: float):
        self.log_dir, self.seconds = log_dir, seconds
        self._lock = threading.Lock()
        self.t_start = self.t_stop = None
        self._timer = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.log_dir))
        self.t_start = time.time()
        self._timer = threading.Timer(self.seconds, self.stop)
        self._timer.daemon = True
        self._timer.start()

    def stop(self) -> None:
        import jax
        with self._lock:
            if self.t_start is None or self.t_stop is not None:
                return
            self.t_stop = time.time()
            jax.profiler.stop_trace()
        print(f"bench: profiler stopped in {time.time() - self.t_stop:.1f} s",
              file=sys.stderr, flush=True)
        if self._timer is not None:
            self._timer.cancel()

    def reduce(self) -> T.Reduced | None:
        path = T.find_xplane(str(self.log_dir))
        if path is None or self.t_stop is None:
            return None
        t = time.time()
        reduced = T.read_xplane(path, (self.t_stop - self.t_start) * 1e9)
        print(f"bench: trace of {os.path.getsize(path)} bytes read in "
              f"{time.time() - t:.1f} s", file=sys.stderr, flush=True)
        return reduced


class Hooks:
    def __init__(self):
        self.t_setup = None

    def setup_done(self) -> None:
        self.t_setup = time.time()


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="check the control in the program's place: every "
                         "answer's J computed in bfloat16 by the reference "
                         "(never in a measured run)")
    return ap.parse_args(argv)


def run(args, root: Path = ROOT, devices=tpu_devices,
        use_cache: bool = True) -> dict:
    """One run; returns the result line. ``devices(chips)`` gives the
    devices or raises :class:`NoChip`."""
    cell = Cell(root, args.workload)
    # libtpu would log under /tmp: write nothing outside the checkout
    os.environ["TPU_LOG_DIR"] = "disabled"
    if use_cache:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / CACHE_DIR)
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    import jax
    if use_cache:
        jax.config.update("jax_compilation_cache_dir", str(root / CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = devices(cell.chips)
    from jax import monitoring

    from bench.common import Window
    spans = C.Y.CompileSpans()
    monitoring.register_event_time_span_listener(spans)
    tracer = None
    if args.trace:
        tracer = Tracer(root / TRACE_DIR, float(min(
            args.seconds, cell.traffic.get("trace_seconds", args.seconds))))
    window = Window(args.seconds, on_open=tracer.start if tracer else None)
    hooks = Hooks()
    t_proc = process_start()
    rec = cell.driver()(cell, args.seed, window, hooks)
    if tracer is not None:
        tracer.stop()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    rec.update(cell=cell, close=window.close, spans=spans,
               device_kind=devs[0].device_kind)
    if "after_window" in rec:  # program runs the check needs, after the peak
        rec.update(rec.pop("after_window")())
    rec["setup_s"] = hooks.t_setup - t_proc
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    reduced = tracer.reduce() if tracer is not None else None
    if tracer is not None:
        shutil.rmtree(tracer.log_dir, ignore_errors=True)
    rec["trace"] = reduced
    if args.control:
        for a in rec["warmup"] + rec["answers"]:
            if a.error is None:
                a.J = C.Y.bf16_J(a.req.inst, a.pe_of, rec["hierarchy"].a,
                                 rec["hierarchy"].d)
    checked = C.check_run(rec)
    line = {"correct": checked["correct"],
            "attempted": checked["attempted"], "failed": checked["failed"]}
    metrics = {}
    for m in cell.per_layer if args.trace else cell.end_to_end:
        v = cell.reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    line["metrics"] = metrics
    line["device"] = device
    if reduced is not None:
        line["breakdown"] = {"device_ops": reduced.top_ops(10),
                             "idle_gaps": reduced.idle_gaps(10)}
    line["check"] = checked["numbers"]
    return line


def main(argv=None) -> int:
    args = parse(argv)
    try:
        line = run(args)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except ImportError as e:
        print(f"bench: the program was not found in this checkout ({e})",
              file=sys.stderr)
        return 2
    for name, num in line["check"].items():
        print(f"check {name}: {num['value']!r} (limit {num['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
