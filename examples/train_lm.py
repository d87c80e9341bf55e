"""End-to-end training driver: a ~20M-param llama-family model for a few
hundred steps on CPU (scale --layers/--batch up on real hardware; the same
driver lowers the full 72B configs in the multi-pod dry-run).

    PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import argparse
import sys

from repro.compile_cache import use_compile_cache
from repro.launch.train import main as train_main


def run():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    args = ap.parse_args()
    argv = ["--arch", "llama3.2-3b", "--smoke", "--layers", "4",
            "--steps", str(args.steps), "--batch", str(args.batch),
            "--seq", str(args.seq), "--checkpoint-every", "50",
            "--checkpoint-dir", "ckpts/train_lm"]
    for f in args.fail_at:
        argv += ["--fail-at", str(f)]
    train_main(argv)


if __name__ == "__main__":
    run()
