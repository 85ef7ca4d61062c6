"""Batched serving example, two modes:

  1. lockstep: prefill a batch of prompts, decode together — across three
     architecture families (attention, SSM, hybrid), one serving API;
  2. continuous batching: a slot-pool engine admits queued requests of
     different lengths mid-stream, every tick decodes all occupied slots at
     their OWN positions, finished requests free slots immediately.

  PYTHONPATH=src python examples/serve_batched.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.configs.registry import get_arch
from repro.launch.serve import serve_batch
from repro.models.schema import build_schema
from repro.models.sharding import init_from_schema
from repro.models.testing import reduced
from repro.jax_cache import enable_persistent_cache


def continuous_batching_demo():
    import numpy as np

    from repro.serve import Request, ServeEngine

    cfg = reduced(get_arch("qwen2-1.5b"))
    params = init_from_schema(jax.random.PRNGKey(0),
                              build_schema(cfg), jnp.float32)
    engine = ServeEngine(cfg, params, slots=2, max_len=48)
    rng = np.random.default_rng(0)
    for i in range(5):  # 5 requests, varied lengths, only 2 slots
        engine.submit(Request(
            i, rng.integers(0, cfg.vocab, size=int(rng.integers(6, 20))),
            max_new_tokens=int(rng.integers(3, 8))))
    stats = engine.run_until_drained()
    print(f"continuous batching: {stats.finished} requests through "
          f"{engine.slots} slots in {stats.ticks} ticks "
          f"({stats.occupancy_tokens_per_tick:.2f} tok/tick; "
          f"serial would need {stats.decoded_tokens} ticks)")


def main():
    enable_persistent_cache()
    for arch in ("qwen2-1.5b", "falcon-mamba-7b", "zamba2-1.2b"):
        cfg = reduced(get_arch(arch))
        params = init_from_schema(jax.random.PRNGKey(0),
                                  build_schema(cfg), jnp.float32)
        prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 24),
                                     0, cfg.vocab)
        t0 = time.perf_counter()
        seqs = serve_batch(cfg, params, prompts, gen_tokens=12)
        dt = time.perf_counter() - t0
        assert seqs.shape == (4, 36)
        print(f"{arch:<18} ({cfg.family.value:<7}) "
              f"4 prompts x 24 tok -> +12 tok each in {dt:5.1f}s "
              f"| continuation[0]: {list(map(int, seqs[0, 24:28]))}...")
    continuous_batching_demo()


if __name__ == "__main__":
    main()
