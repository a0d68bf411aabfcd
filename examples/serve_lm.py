"""Serve a small model with batched requests: prefill + greedy decode against
KV/SSM caches, across three architecture families.

    PYTHONPATH=src python examples/serve_lm.py
"""
import numpy as np

from repro.configs import get_config
from repro.launch.env import enable_compile_cache
from repro.launch.serve import serve_batch

enable_compile_cache()

rng = np.random.default_rng(0)
for arch in ("llama3.2-1b", "rwkv6-1.6b", "zamba2-1.2b"):
    cfg = get_config(arch).reduced()
    prompts = rng.integers(0, cfg.vocab_size, (2, 24), dtype=np.int32)
    toks = serve_batch(arch, prompts, max_new=8)
    print(f"{arch:14s} generated: {toks.tolist()}")
