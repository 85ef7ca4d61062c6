"""End-to-end training driver: a ~100M-parameter dense LM trained for a
few hundred steps on the synthetic pipeline, with fault-tolerant
checkpointing (kill and re-run: it resumes).

A ~100M model at a few hundred steps is hours of CPU time; the default
here is a faithful-but-smaller ~27M twin at 300 steps (~15 min).  Pass
``--hundred-m`` for the full-size run, or tune the flags.

  PYTHONPATH=src python examples/train_100m.py [--hundred-m] [--steps N]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_arch
from repro.data.pipeline import DataConfig, batch_for_step
from repro.ft.manager import RestartManager
from repro.models.config import CellTuning
from repro.models.schema import build_schema
from repro.models.sharding import init_from_schema
from repro.optim import adamw
from repro.train.steps import make_train_step
from repro.jax_cache import enable_persistent_cache


def model_config(hundred_m: bool):
    base = get_arch("qwen2-1.5b")  # dense GQA family
    if hundred_m:
        # ~103M params: 12L x 768, 12 heads (GQA 4 kv), ff 3072, vocab 16384
        return dataclasses.replace(
            base, n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
            d_ff=3072, vocab=16384, head_dim=64)
    # ~27M params: 8L x 384, ff 1536, vocab 8192
    return dataclasses.replace(
        base, n_layers=8, d_model=384, n_heads=8, n_kv_heads=4,
        d_ff=1536, vocab=8192, head_dim=48)


def main():
    enable_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--hundred-m", action="store_true")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_100m")
    args = ap.parse_args()

    cfg = model_config(args.hundred_m)
    print(f"model: {cfg.n_layers}L x {cfg.d_model} "
          f"(~{cfg.param_count() / 1e6:.0f}M params), "
          f"{args.steps} steps @ batch {args.batch} x seq {args.seq_len}",
          flush=True)

    tuning = CellTuning(num_microbatches=2, remat=True,
                        compute_dtype="float32")
    opt_cfg = adamw.OptimizerConfig(lr=1e-2, warmup_steps=10,
                                    decay_steps=max(3 * args.steps, 300))
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, tuning))
    # data vocab smaller than the model's: at a few hundred steps every
    # token needs enough observations for the LCG structure to be learnable
    dcfg = DataConfig(vocab=min(2048, cfg.vocab), seq_len=args.seq_len,
                      global_batch=args.batch, seed=7)

    def init_fn():
        params = init_from_schema(jax.random.PRNGKey(7),
                                  build_schema(cfg), jnp.float32)
        return {"params": params, "opt": adamw.init(opt_cfg, params)}

    losses = []

    def train_one(state, step):
        batch = {k: jnp.asarray(v)
                 for k, v in batch_for_step(dcfg, step).items()}
        params, opt, metrics = step_fn(state["params"], state["opt"], batch)
        losses.append(float(metrics["loss"]))
        if (step + 1) % 20 == 0:
            print(f"step {step + 1:>4}  loss {losses[-1]:.4f}", flush=True)
        return {"params": params, "opt": opt}

    mgr = RestartManager(args.ckpt_dir, checkpoint_every=50)
    mgr.run(init_fn, train_one, num_steps=args.steps)

    first, last = np.mean(losses[:10]), np.mean(losses[-10:])
    print(f"\nloss: {first:.3f} -> {last:.3f} "
          f"({'OK: learning' if last < first - 0.3 else 'WARN: flat'})")
    print(f"checkpoints in {args.ckpt_dir} (re-run to resume)")


if __name__ == "__main__":
    main()
