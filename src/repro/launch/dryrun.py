import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell against the production meshes and extract memory/cost/collective
analyses for the roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only]
  PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun_results.json

Each record proves the cell fits (memory_analysis) and feeds §Roofline
(cost_analysis FLOPs/bytes + collective bytes parsed from the SPMD module).
"""
import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax

from repro.configs.registry import ARCHS
from repro.launch import hlo_analysis, hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.launch.plan import build_plan
from repro.models.config import SHAPES, cell_is_supported
from repro.obs import Tracer


def run_cell(
    arch: str, shape: str, *, multi_pod: bool,
    tuning_overrides: Optional[Dict] = None,
    optimized: bool = False,
    tracer: Optional[Tracer] = None,
) -> Dict:
    """Lower + compile one cell; returns the dry-run record.

    Pass an ``repro.obs.Tracer`` to get one ``dryrun.cell`` span per
    cell with plan/lower/compile/analyze child spans — the same trace a
    ``ContinuumRuntime`` run emits for the planner, so one timeline can
    cover planner and model launch layer together."""
    if tracer is None:
        tracer = Tracer(enabled=False)
    cfg = ARCHS[arch]
    ok, why = cell_is_supported(cfg, SHAPES[shape])
    if not ok:
        return {
            "arch": arch, "shape": shape, "multi_pod": multi_pod,
            "status": "skipped", "reason": why,
        }
    t0 = time.time()
    with tracer.span("dryrun.cell", arch=arch, shape=shape,
                     multi_pod=multi_pod):
        mesh = make_production_mesh(multi_pod=multi_pod)
        with tracer.span("dryrun.plan"):
            plan = build_plan(arch, shape, multi_pod=multi_pod,
                              tuning_overrides=tuning_overrides,
                              optimized=optimized)
        with jax.set_mesh(mesh):
            with tracer.span("dryrun.lower"):
                lowered = plan.lower()
            with tracer.span("dryrun.compile"):
                compiled = lowered.compile()
            with tracer.span("dryrun.analyze"):
                mem = compiled.memory_analysis()
                xla_cost = compiled.cost_analysis() or {}
                # XLA's cost_analysis counts while bodies ONCE (scanned
                # layers / microbatches would be undercounted ~100x); use
                # the loop-aware HLO cost model instead.
                totals = hlo_cost.analyze(compiled.as_text())

    roof = hlo_analysis.Roofline(
        flops=totals.flops,
        hbm_bytes=totals.bytes,
        coll_bytes=totals.coll_bytes,
        model_flops=plan.model_flops,
        chips=plan.chips,
    )
    record = {
        "arch": arch, "shape": shape, "multi_pod": multi_pod,
        "optimized": optimized,
        "status": "ok",
        "compile_s": round(time.time() - t0, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes_per_device": (
                mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes
            ),
        },
        "collectives": {
            "counts": totals.coll_counts,
            "bytes_by_kind": totals.coll_bytes_by_kind,
        },
        "xla_cost_analysis": {
            "flops_body_once": float(xla_cost.get("flops", 0.0)),
            "bytes_body_once": float(xla_cost.get("bytes accessed", 0.0)),
        },
        "roofline": roof.to_dict(),
    }
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2x16x16 (512-chip) mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the §Perf OPTIMIZED_OVERRIDES per arch")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--trace-out", default=None,
                    help="write dryrun.* spans as JSONL here")
    args = ap.parse_args()
    tracer = Tracer() if args.trace_out else None

    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            label = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
            try:
                rec = run_cell(arch, shape, multi_pod=mp,
                               optimized=args.optimized, tracer=tracer)
            except Exception as e:  # a failure here is a bug in the system
                failures += 1
                rec = {
                    "arch": arch, "shape": shape, "multi_pod": mp,
                    "status": "error", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:],
                }
            if rec["status"] == "ok":
                r = rec["roofline"]
                print(
                    f"[OK]   {label}: "
                    f"mem={rec['memory']['peak_bytes_per_device']/2**30:.2f}GiB/dev "
                    f"compute={r['compute_s']*1e3:.2f}ms "
                    f"memory={r['memory_s']*1e3:.2f}ms "
                    f"coll={r['collective_s']*1e3:.2f}ms "
                    f"bottleneck={r['bottleneck']} "
                    f"frac={r['roofline_fraction']:.3f} "
                    f"(compile {rec['compile_s']}s)", flush=True,
                )
            elif rec["status"] == "skipped":
                print(f"[SKIP] {label}: {rec['reason']}", flush=True)
            else:
                print(f"[FAIL] {label}: {rec['error']}", flush=True)
            if args.out:
                with open(args.out, "a") as fh:
                    fh.write(json.dumps(rec) + "\n")
    if tracer is not None:
        with open(args.trace_out, "w") as fh:
            fh.write(tracer.to_jsonl())
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()
