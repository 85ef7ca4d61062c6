"""Cell plans: everything needed to lower one (arch x shape x mesh) cell.

A CellPlan bundles the step function, abstract (ShapeDtypeStruct) inputs,
and in/out shardings.  ``dryrun`` lowers + compiles it; ``train.py`` /
``serve.py`` execute it on real devices.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.registry import get_arch
from repro.models.config import (
    ArchConfig, CellTuning, Family, Kind, SHAPES, ShapeConfig,
    cell_is_supported, cell_tuning,
)
from repro.models.model import cache_schema
from repro.models.ops import ShardCtx
from repro.models.schema import build_schema
from repro.models.sharding import (
    ShardingRules, abstract_from_schema, default_rules, schema_to_pspecs,
)
from repro.optim import adamw
from repro.train.steps import make_prefill_step, make_serve_step, make_train_step

MODEL_AXIS_SIZE = 16
DATA_AXIS_SIZE = 16
PODS = 2

# Beyond-paper optimized tuning per architecture family (§Perf): the
# paper-faithful baseline is CellTuning's defaults; these overrides are the
# hillclimbed configurations.  ``build_plan(..., optimized=True)`` applies
# them (explicit tuning_overrides still win).
OPTIMIZED_OVERRIDES = {
    # heads % 16 != 0 -> sequence-parallel attention (replicated-attention fix)
    "qwen2-1.5b": {"seq_parallel_attn": True},
    "whisper-large-v3": {"seq_parallel_attn": True},
    "granite-moe-3b-a800m": {"seq_parallel_attn": True,
                             "moe_row_dispatch": True},
    "phi3.5-moe-42b-a6.6b": {"moe_row_dispatch": True},
    # big dense: seq-parallel residual stream (fits + halves TP collectives)
    "nemotron-4-340b": {"seq_parallel_residual": True,
                        "param_dtype": "bfloat16"},
    # full-attention archs with divisible heads: recompute chunk scores
    # instead of stacking S^2 softmax residuals in the backward
    "yi-6b": {"remat_chunk_attn": True},
    "yi-9b": {"remat_chunk_attn": True},
    "llava-next-mistral-7b": {"remat_chunk_attn": True},
}


@dataclass
class CellPlan:
    arch: ArchConfig
    shape: ShapeConfig
    tuning: CellTuning
    rules: ShardingRules
    ctx: ShardCtx
    multi_pod: bool
    step_fn: Callable
    abstract_args: Tuple
    in_specs: Tuple
    out_specs: Any
    chips: int
    model_flops: float
    opt_cfg: Optional[adamw.OptimizerConfig] = None

    def lower(self):
        jitted = jax.jit(
            self.step_fn,
            in_shardings=self.in_specs,
            out_shardings=self.out_specs,
            donate_argnums=(0, 1) if self.shape.kind == Kind.TRAIN else (),
        )
        return jitted.lower(*self.abstract_args)


def _batch_axes(global_batch: int, multi_pod: bool):
    dp = ("pod", "data") if multi_pod else ("data",)
    total = PODS * DATA_AXIS_SIZE if multi_pod else DATA_AXIS_SIZE
    if global_batch % total == 0:
        return dp
    if global_batch % DATA_AXIS_SIZE == 0:
        return ("data",)
    return None  # replicate (e.g. long_500k with B = 1)


def build_plan(
    arch_name: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    opt_overrides: Optional[Dict] = None,
    tuning_overrides: Optional[Dict] = None,
    optimized: bool = False,
) -> CellPlan:
    cfg = get_arch(arch_name)
    shape = SHAPES[shape_name]
    ok, why = cell_is_supported(cfg, shape)
    if not ok:
        raise ValueError(f"unsupported cell {arch_name} x {shape_name}: {why}")
    tuning = cell_tuning(cfg, shape)
    if optimized:
        tuning = dataclasses.replace(
            tuning, **OPTIMIZED_OVERRIDES.get(arch_name, {}))
        if shape.kind != Kind.TRAIN:
            # serving flavours stream bf16 weights: decode cells are
            # parameter-bandwidth-bound, so this halves their memory term
            tuning = dataclasses.replace(tuning, param_dtype="bfloat16")
    if tuning_overrides:
        tuning = dataclasses.replace(tuning, **tuning_overrides)

    batch_axes = _batch_axes(shape.global_batch, multi_pod)
    fsdp_axes = ("pod", "data") if multi_pod else ("data",)
    fsdp_total = (PODS if multi_pod else 1) * DATA_AXIS_SIZE
    seq_shard = shape.kind == Kind.DECODE and batch_axes is None

    rules = default_rules(
        cfg,
        fsdp_axes=fsdp_axes,
        fsdp_total=fsdp_total,
        model_size=MODEL_AXIS_SIZE,
        batch_axes=batch_axes,
        seq_shard_cache=seq_shard,
    )
    ctx = ShardCtx(
        enabled=True,
        dp=batch_axes,
        tp="model",
        heads_sharded=rules.rules.get("heads_q") is not None,
        ff_sharded=rules.rules.get("d_ff") is not None,
        attention_impl=tuning.attention_impl,
        ssm_impl=tuning.ssm_impl,
        seq_parallel_attn=tuning.seq_parallel_attn,
        remat_chunk_attn=tuning.remat_chunk_attn,
        moe_row_dispatch=tuning.moe_row_dispatch,
        seq_parallel_residual=tuning.seq_parallel_residual,
    )
    chips = PODS * DATA_AXIS_SIZE * MODEL_AXIS_SIZE if multi_pod \
        else DATA_AXIS_SIZE * MODEL_AXIS_SIZE

    schema = build_schema(cfg)
    param_dtype = jnp.dtype(tuning.param_dtype)
    params_abs = abstract_from_schema(schema, param_dtype)
    params_specs = schema_to_pspecs(schema, rules)

    n_active = cfg.active_param_count()
    compute_dtype = jnp.dtype(tuning.compute_dtype)

    def batch_spec(extra_dims: int = 1):
        return P(batch_axes, *([None] * extra_dims))

    if shape.kind == Kind.TRAIN:
        opt_cfg = adamw.OptimizerConfig(
            state_dtype=tuning.opt_state_dtype,
            compress_grads=bool(multi_pod and cfg.param_count() > 5e9),
            **(opt_overrides or {}),
        )
        opt_abs, opt_specs = _abstract_opt(params_abs, params_specs, opt_cfg)
        batch_abs = {
            "tokens": jax.ShapeDtypeStruct(
                (shape.global_batch, shape.seq_len), jnp.int32),
            "labels": jax.ShapeDtypeStruct(
                (shape.global_batch, shape.seq_len), jnp.int32),
        }
        batch_specs = {"tokens": batch_spec(), "labels": batch_spec()}
        if cfg.enc_len:
            batch_abs["enc_embeds"] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.enc_len, cfg.d_model), compute_dtype)
            batch_specs["enc_embeds"] = batch_spec(2)
        step_fn = make_train_step(cfg, opt_cfg, tuning, ctx)
        model_flops = 6.0 * n_active * shape.global_batch * shape.seq_len
        if cfg.enc_len:  # add encoder forward+backward
            model_flops += 6.0 * _encoder_params(cfg) * shape.global_batch \
                * cfg.enc_len
        return CellPlan(
            cfg, shape, tuning, rules, ctx, multi_pod, step_fn,
            (params_abs, opt_abs, batch_abs),
            (params_specs, opt_specs, batch_specs),
            (params_specs, opt_specs, P()),
            chips, model_flops, opt_cfg,
        )

    if shape.kind == Kind.PREFILL:
        batch_abs = {
            "tokens": jax.ShapeDtypeStruct(
                (shape.global_batch, shape.seq_len), jnp.int32),
        }
        batch_specs = {"tokens": batch_spec()}
        if cfg.enc_len:
            batch_abs["enc_embeds"] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.enc_len, cfg.d_model), compute_dtype)
            batch_specs["enc_embeds"] = batch_spec(2)
        step_fn = make_prefill_step(cfg, tuning, ctx)
        cs = cache_schema(
            cfg, shape.global_batch, shape.seq_len, enc_len=cfg.enc_len)
        cache_specs = schema_to_pspecs(cs, rules)
        out_specs = (P(batch_axes, "model"), cache_specs)
        model_flops = 2.0 * n_active * shape.global_batch * shape.seq_len
        if cfg.enc_len:
            model_flops += 2.0 * _encoder_params(cfg) * shape.global_batch \
                * cfg.enc_len
        return CellPlan(
            cfg, shape, tuning, rules, ctx, multi_pod, step_fn,
            (params_abs, batch_abs),
            (params_specs, batch_specs),
            out_specs, chips, model_flops,
        )

    # DECODE: serve_step(params, cache, tokens)
    cs = cache_schema(
        cfg, shape.global_batch, shape.seq_len, enc_len=cfg.enc_len)
    cache_abs = abstract_from_schema(cs, compute_dtype)
    cache_specs = schema_to_pspecs(cs, rules)
    tokens_abs = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    step_fn = make_serve_step(cfg, tuning, ctx)
    out_specs = (P(batch_axes, "model"), cache_specs)
    model_flops = 2.0 * n_active * shape.global_batch
    return CellPlan(
        cfg, shape, tuning, rules, ctx, multi_pod, step_fn,
        (params_abs, cache_abs, tokens_abs),
        (params_specs, cache_specs, P(batch_axes, None)),
        out_specs, chips, model_flops,
    )


def _abstract_opt(params_abs, params_specs, opt_cfg):
    dt = jnp.bfloat16 if opt_cfg.state_dtype == "bfloat16" else jnp.float32
    mom = jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, dt), params_abs)
    if opt_cfg.compress_grads:
        err = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params_abs)
        err_specs = params_specs
    else:
        err = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct((), jnp.float32), params_abs)
        err_specs = jax.tree.map(lambda _: P(), params_abs)
    opt_abs = adamw.OptState(
        step=jax.ShapeDtypeStruct((), jnp.int32), mu=mom, nu=mom, error=err)
    opt_specs = adamw.OptState(
        step=P(), mu=params_specs, nu=params_specs, error=err_specs)
    return opt_abs, opt_specs


def _encoder_params(cfg: ArchConfig) -> int:
    """Rough encoder-only parameter count for enc-dec model FLOPs."""
    d, H, hd, ff = cfg.d_model, cfg.n_heads, cfg.hd, cfg.d_ff
    per = d * H * hd * 2 + 2 * d * cfg.n_kv_heads * hd + 2 * d * ff
    return cfg.n_layers * per
