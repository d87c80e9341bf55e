"""Batched serving with KV caches (greedy + temperature sampling).

    PYTHONPATH=src python examples/serve_lm.py
"""
import numpy as np
import jax

from repro.compile_cache import use_compile_cache
from repro.configs.registry import get_smoke_config
from repro.models import model as M
from repro.serve.engine import Engine


def main():
    use_compile_cache()
    cfg = get_smoke_config("mixtral-8x22b")  # MoE + sliding window
    params = M.init_fn(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_len=96)
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, cfg.vocab_size, (4, 8)).astype(np.int32)
    out, stats = eng.generate(prompts, steps=32, temperature=0.8)
    print(f"arch={cfg.name} batch={prompts.shape[0]}")
    print(f"prefill: {stats.prefill_s:.2f}s  decode: {stats.decode_s:.2f}s "
          f"({stats.tok_per_s:.0f} tok/s)")
    print("sample tokens:", out[0][:16].tolist())


if __name__ == "__main__":
    main()
