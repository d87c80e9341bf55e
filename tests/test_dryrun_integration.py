"""Multi-pod dry-run integration: lower+compile a real cell under 512
forced host devices, in a SUBPROCESS (so the main test process keeps its
single-device backend). Marked slow; the full 40-cell x 2-mesh sweep is
run via `python -m repro.launch.dryrun --all` (results in EXPERIMENTS.md)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_dryrun_cell_compiles(tmp_path):
    out = tmp_path / "dryrun.jsonl"
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "llama3.2-3b", "--shape", "decode_32k", "--mesh", "pod2",
         "--out", str(out)],
        env=env, capture_output=True, text=True, timeout=1200, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text().strip().splitlines()[-1])
    assert "error" not in rec, rec.get("error")
    assert rec["chips"] == 512
    assert rec["memory"]["per_device_total"] > 0
    assert rec["hlo"]["flops_per_device"] > 0
    assert rec["roofline"]["dominant"] in ("compute_s", "memory_s", "collective_s")


@pytest.mark.slow
def test_dryrun_device_order_sharedmap(tmp_path):
    """The SharedMap-ordered mesh builds and compiles too."""
    script = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=512'\n"
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.mesh import make_production_mesh\n"
        "from jax.sharding import NamedSharding, PartitionSpec as P\n"
        "mesh = make_production_mesh(multi_pod=True, device_order='sharedmap')\n"
        "x = jax.ShapeDtypeStruct((512, 64), jnp.float32,\n"
        "    sharding=NamedSharding(mesh, P(('pod','data'), 'model')))\n"
        "c = jax.jit(lambda a: (a * 2).sum()).lower(x).compile()\n"
        "print('OK', c.cost_analysis()['flops'])\n"
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout
