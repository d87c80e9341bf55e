"""Compilation (JAX, around the planner): programs lowered inside the
window per answer, from JAX's compile spans. Each is a program the
process had not built in set-up, compiled or read back from the
persistent cache; 0 when set-up warmed every shape."""
from bench.check import in_window


def read(rec: dict):
    done = in_window(rec)
    if not done:
        return None
    return rec["spans"].lowered(rec["t0"], rec["close"]) / len(done)
