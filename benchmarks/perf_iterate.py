"""§Perf hillclimb driver: lower one cell with tuning overrides, print the
three roofline terms + the top-bytes breakdown.

  PYTHONPATH=src python -m benchmarks.perf_iterate --arch qwen2-1.5b \
      --shape train_4k --set seq_parallel_attn=True remat_chunk_attn=True
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time

import jax

from repro.launch import hlo_cost, hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.plan import build_plan


def parse_overrides(pairs):
    out = {}
    for p in pairs or []:
        k, v = p.split("=", 1)
        if v in ("True", "False"):
            out[k] = v == "True"
        else:
            try:
                out[k] = int(v)
            except ValueError:
                out[k] = v
    return out


def lower_cell(arch, shape, overrides, multi_pod=False):
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = build_plan(arch, shape, multi_pod=multi_pod,
                      tuning_overrides=overrides or None)
    with jax.set_mesh(mesh):
        compiled = plan.lower().compile()
        txt = compiled.as_text()
        mem = compiled.memory_analysis()
    totals = hlo_cost.analyze(txt)
    roof = hlo_analysis.Roofline(
        flops=totals.flops, hbm_bytes=totals.bytes,
        coll_bytes=totals.coll_bytes, model_flops=plan.model_flops,
        chips=plan.chips)
    return dict(
        compile_s=round(time.time() - t0, 1),
        peak_gib=(mem.argument_size_in_bytes + mem.temp_size_in_bytes
                  + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2**30,
        roof=roof, txt=txt, coll=totals.coll_bytes_by_kind,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--set", nargs="*", default=[])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--breakdown", type=int, default=12)
    ap.add_argument("--save-hlo", default=None)
    args = ap.parse_args()

    overrides = parse_overrides(args.set)
    res = lower_cell(args.arch, args.shape, overrides, args.multi_pod)
    r = res["roof"]
    print(f"== {args.arch} x {args.shape} "
          f"{'2x16x16' if args.multi_pod else '16x16'} overrides={overrides}")
    print(f"compile {res['compile_s']}s  peak {res['peak_gib']:.2f} GiB/dev")
    print(f"compute_s    {r.compute_s:10.4f}")
    print(f"memory_s     {r.memory_s:10.4f}")
    print(f"collective_s {r.collective_s:10.4f}   ({ {k: f'{v/1e9:.1f}GB' for k, v in res['coll'].items()} })")
    print(f"bottleneck   {r.bottleneck}   useful {r.useful_flops_ratio:.3f}"
          f"   roofline_fraction {r.roofline_fraction:.4f}")
    if args.breakdown:
        by_op, top = hlo_cost.breakdown(res["txt"], top=args.breakdown)
        print("-- top byte contributors --")
        for b, op, comp, name in top:
            print(f"  {b/1e9:9.1f} GB  {op:<12} {comp[:34]}/{name[:52]}")
    if args.save_hlo:
        open(args.save_hlo, "w").write(res["txt"])


if __name__ == "__main__":
    main()
