"""Elastic failover: losing a pod re-plans placement through the SAME
green scheduler used at launch — fault handling and carbon-awareness share
one decision mechanism (DESIGN.md §8).

Timeline simulated here with a real (reduced) training loop:
  1. green placement assigns the train job across a 3-pod fleet;
  2. training runs with atomic checkpoints;
  3. the hosting pod FAILS mid-run: plan_elastic_mesh() re-plans the
     device mesh for the survivors, green placement re-runs WITHOUT the
     lost pod, and the job resumes from the last complete checkpoint with
     the data pipeline re-sharded — bit-identical continuation;
  4. the re-placement still avoids the dirty pod.

  PYTHONPATH=src python examples/elastic_failover.py
"""
import os
import shutil
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_arch
from repro.data.pipeline import DataConfig, batch_for_step
from repro.ft.manager import RestartManager, plan_elastic_mesh
from repro.launch.green_placement import GreenPlacement, JobSpec, PodSpec
from repro.models.config import CellTuning
from repro.models.schema import build_schema
from repro.models.sharding import init_from_schema
from repro.models.testing import reduced
from repro.optim import adamw
from repro.train.steps import make_train_step
from repro.jax_cache import enable_persistent_cache

CKPT = "/tmp/repro_elastic_demo"
ROOF = {"perf": {"compute_s": 1.2, "memory_s": 8.5, "collective_s": 3.9}}


def place(pods):
    job = JobSpec("train-job", "qwen2-1.5b", "train_4k", ROOF,
                  delay_tolerance_h=12)
    plan, out, stats = GreenPlacement().place([job], pods)
    assert plan.feasible
    return plan.node_of("train-job")


def main():
    enable_persistent_cache()
    shutil.rmtree(CKPT, ignore_errors=True)
    pods = [
        PodSpec("pod-a", "finland", carbon=80.0, cost_per_chip_hour=1.0),
        PodSpec("pod-b", "france", carbon=16.0, cost_per_chip_hour=1.3),
        PodSpec("pod-dirty", "texas", carbon=410.0, cost_per_chip_hour=0.7),
    ]
    home = place(pods)
    print(f"[t0] green placement: train-job -> {home} "
          f"(cheapest pod is pod-dirty; the green scheduler pays more)")
    assert home != "pod-dirty"

    # --- the training job itself (reduced twin, real steps) ---------------
    cfg = reduced(get_arch("qwen2-1.5b"))
    opt_cfg = adamw.OptimizerConfig(lr=1e-2, warmup_steps=5, decay_steps=200)
    tuning = CellTuning(num_microbatches=1, remat=False,
                        compute_dtype="float32")
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, tuning))
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8, seed=11)

    def init_fn():
        params = init_from_schema(jax.random.PRNGKey(11),
                                  build_schema(cfg), jnp.float32)
        return {"params": params, "opt": adamw.init(opt_cfg, params)}

    losses = []

    def make_step(n_shards):
        def train_one(state, step):
            # every shard produced independently, then concatenated — the
            # stream is identical for ANY shard count (elasticity)
            parts = [batch_for_step(dcfg, step, shard=(i, n_shards))
                     for i in range(n_shards)]
            batch = {k: jnp.asarray(np.concatenate([p[k] for p in parts]))
                     for k in parts[0]}
            params, opt, m = step_fn(state["params"], state["opt"], batch)
            losses.append(float(m["loss"]))
            return {"params": params, "opt": opt}
        return train_one

    mgr = RestartManager(CKPT, checkpoint_every=10)
    mgr.run(init_fn, make_step(n_shards=2), num_steps=25)
    print(f"[t1] trained 25 steps on {home} (2 data shards), "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}; checkpoint at step 25")

    # --- pod failure --------------------------------------------------------
    print(f"[t2] {home} FAILS. survivors re-mesh + re-place:")
    survivors = [p for p in pods if p.pod_id != home]
    mesh_plan = plan_elastic_mesh(256 * len(survivors), model=16)
    print(f"     elastic mesh for {256 * len(survivors)} chips: "
          f"(pod, data, model) = {mesh_plan}")
    new_home = place(survivors)
    print(f"     green re-placement: train-job -> {new_home}")
    assert new_home != "pod-dirty" and new_home != home

    # --- resume: one surviving data shard, same stream ----------------------
    mgr2 = RestartManager(CKPT, checkpoint_every=10)
    state, start = mgr2.resume_or_init(init_fn)
    print(f"[t3] resumed from step {start} on {new_home} "
          f"(re-sharded to 1 shard)")
    assert start == 25
    mgr2.run(init_fn, make_step(n_shards=1), num_steps=40)
    print(f"[t4] finished 40 steps, final loss {losses[-1]:.3f} "
          f"(continued the SAME deterministic stream)")
    assert losses[-1] < losses[0]
    print("elastic failover: OK")


if __name__ == "__main__":
    main()
