"""Sharding-rule unit tests + an 8-device mini dry-run in a subprocess
(device count must be fixed before jax initialises, so the multi-device
lowering check cannot run in this process)."""
import json
import os
import subprocess
import sys

import pytest

from repro.configs.registry import ARCHS
from repro.models.config import SHAPES, cell_is_supported
from repro.models.schema import build_schema
from repro.models.sharding import default_rules, schema_to_pspecs

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# --------------------------------------------------------------------------
# pure rule logic
# --------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_rules_respect_divisibility(name):
    cfg = ARCHS[name]
    rules = default_rules(cfg, model_size=16, fsdp_total=16).rules
    if rules.get("heads_q"):
        assert cfg.n_heads % 16 == 0
    if rules.get("heads_kv"):
        assert cfg.n_kv_heads % 16 == 0
    if rules.get("d_ff"):
        assert cfg.d_ff % 16 == 0
    if rules.get("embed_vocab"):
        assert cfg.vocab_padded % 16 == 0
    if cfg.moe and rules.get("experts"):
        assert cfg.moe.n_experts_padded % 16 == 0
        # EP and per-expert ff sharding are mutually exclusive
        assert rules.get("d_ff") is None


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_every_param_gets_a_spec(name):
    import jax
    cfg = ARCHS[name]
    rules = default_rules(cfg)
    schema = build_schema(cfg)
    specs = schema_to_pspecs(schema, rules)
    from jax.sharding import PartitionSpec
    from repro.models.sharding import ParamSchema
    flat_schema = jax.tree.leaves(
        schema, is_leaf=lambda x: isinstance(x, ParamSchema))
    flat_specs = jax.tree.leaves(
        specs, is_leaf=lambda s: isinstance(s, PartitionSpec))
    assert len(flat_schema) == len(flat_specs)
    assert all(isinstance(s, PartitionSpec) for s in flat_specs)


def test_vocab_always_padded_shardable():
    for cfg in ARCHS.values():
        assert cfg.vocab_padded % 256 == 0
        assert cfg.vocab_padded >= cfg.vocab


def test_long_500k_support_matrix():
    """Assignment: long_500k runs for SSM/hybrid, skipped for
    full-attention archs."""
    expect_ok = {"falcon-mamba-7b", "zamba2-1.2b"}
    for name, cfg in ARCHS.items():
        ok, why = cell_is_supported(cfg, SHAPES["long_500k"])
        assert ok == (name in expect_ok), (name, why)
        if not ok:
            assert "sub-quadratic" in why


def test_all_other_cells_supported():
    for name, cfg in ARCHS.items():
        for shape in ("train_4k", "prefill_32k", "decode_32k"):
            ok, _ = cell_is_supported(cfg, SHAPES[shape])
            assert ok, (name, shape)


# --------------------------------------------------------------------------
# mini dry-run: 8 fake devices, reduced configs, real lower+compile
# --------------------------------------------------------------------------

_MINI_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P

sys.path.insert(0, {src!r})
from repro.launch.mesh import make_mesh_from_shape
from repro.configs.registry import ARCHS
from repro.models.testing import reduced
from repro.models.model import cache_schema
from repro.models.schema import build_schema
from repro.models.sharding import (
    abstract_from_schema, default_rules, schema_to_pspecs)
from repro.models.config import CellTuning
from repro.models.ops import ShardCtx
from repro.train.steps import make_serve_step, make_train_step
from repro.optim import adamw

mesh = make_mesh_from_shape((4, 2), ("data", "model"))
results = {{}}
for name in {archs!r}:
    cfg = reduced(ARCHS[name])
    rules = default_rules(cfg, model_size=2, fsdp_total=4,
                          batch_axes=("data",))
    schema = build_schema(cfg)
    params_abs = abstract_from_schema(schema, jnp.float32)
    specs = schema_to_pspecs(schema, rules)
    ctx = ShardCtx(enabled=True, dp=("data",), tp="model",
                   heads_sharded=rules.rules.get("heads_q") is not None,
                   ff_sharded=rules.rules.get("d_ff") is not None)
    tuning = CellTuning(num_microbatches=2, remat=True)
    opt_cfg = adamw.OptimizerConfig()
    mom = jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32),
                       params_abs)
    err = jax.tree.map(lambda p: jax.ShapeDtypeStruct((), jnp.float32),
                       params_abs)
    opt_abs = adamw.OptState(step=jax.ShapeDtypeStruct((), jnp.int32),
                             mu=mom, nu=mom, error=err)
    opt_specs = adamw.OptState(step=P(), mu=specs, nu=specs,
                               error=jax.tree.map(lambda _: P(), params_abs))
    batch_abs = {{
        "tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32),
        "labels": jax.ShapeDtypeStruct((8, 16), jnp.int32),
    }}
    batch_specs = {{"tokens": P("data"), "labels": P("data")}}
    if cfg.enc_len:
        batch_abs["enc_embeds"] = jax.ShapeDtypeStruct(
            (8, cfg.enc_len, cfg.d_model), jnp.bfloat16)
        batch_specs["enc_embeds"] = P("data")
    step = make_train_step(cfg, opt_cfg, tuning, ctx)
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            step,
            in_shardings=(specs, opt_specs, batch_specs),
            out_shardings=(specs, opt_specs, P()),
        ).lower(params_abs, opt_abs, batch_abs)
        compiled = lowered.compile()

        # decode (serve_step) lowering against the sharded cache
        cs = cache_schema(cfg, 8, 32, enc_len=cfg.enc_len)
        cache_abs = abstract_from_schema(cs, jnp.bfloat16)
        cache_specs = schema_to_pspecs(cs, rules)
        toks = jax.ShapeDtypeStruct((8, 1), jnp.int32)
        serve = make_serve_step(cfg, CellTuning(), ctx)
        compiled2 = jax.jit(
            serve,
            in_shardings=(specs, cache_specs, P("data", None)),
            out_shardings=(P("data", "model"), cache_specs),
        ).lower(params_abs, cache_abs, toks).compile()
    results[name] = (compiled.memory_analysis().temp_size_in_bytes >= 0
                     and compiled2.memory_analysis().temp_size_in_bytes >= 0)
print(json.dumps(results))
"""


@pytest.mark.slow
def test_mini_multidevice_dryrun_all_families():
    """One arch per family, lowered + compiled against a real 4x2 mesh."""
    archs = ["yi-6b", "phi3.5-moe-42b-a6.6b", "falcon-mamba-7b",
             "zamba2-1.2b", "whisper-large-v3"]
    code = _MINI_DRYRUN.format(src=os.path.abspath(SRC), archs=archs)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    results = json.loads(proc.stdout.strip().splitlines()[-1])
    assert all(results.values()), results
