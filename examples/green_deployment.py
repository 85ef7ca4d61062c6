"""Green deployment of TPU jobs across pods (the beyond-paper layer).

Takes the dry-run roofline records of real (arch x shape) cells as the
monitoring source, derives AvoidNode/Affinity constraints with the SAME
pipeline the paper uses for microservices, and places jobs onto pods in
regions with different carbon intensities.  The disaggregated
prefill/decode pair exchanging KV caches demonstrates the Affinity path:
its traffic must stay on ICI (same pod), not DCN.

  PYTHONPATH=src python examples/green_deployment.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.green_placement import (
    GreenPlacement,
    JobSpec,
    PodSpec,
    TrafficSpec,
)
from repro.jax_cache import enable_persistent_cache

DRYRUN = os.path.join(os.path.dirname(__file__), "..",
                      "dryrun_results.jsonl")

# Bundled fallback profiles (from a committed dry-run of this repo) so the
# example runs before a local dry-run exists.
FALLBACK = {
    ("yi-9b", "train_4k"): {
        "compute_s": 1.22, "memory_s": 8.51, "collective_s": 3.86},
    ("yi-9b", "prefill_32k"): {
        "compute_s": 0.37, "memory_s": 2.50, "collective_s": 1.15},
    ("yi-9b", "decode_32k"): {
        "compute_s": 0.0003, "memory_s": 0.035, "collective_s": 0.003},
    ("granite-moe-3b-a800m", "train_4k"): {
        "compute_s": 0.22, "memory_s": 6.00, "collective_s": 1.40},
    ("falcon-mamba-7b", "long_500k"): {
        "compute_s": 0.0001, "memory_s": 0.015, "collective_s": 0.0002},
}


def roofline_lookup():
    table = dict(FALLBACK)
    if os.path.exists(DRYRUN):
        for line in open(DRYRUN):
            r = json.loads(line)
            if r.get("status") == "ok" and not r["multi_pod"]:
                f = r["roofline"]
                table[(r["arch"], r["shape"])] = {
                    "compute_s": f["compute_s"],
                    "memory_s": f["memory_s"],
                    "collective_s": f["collective_s"],
                }
    return table


def main():
    enable_persistent_cache()
    roof = roofline_lookup()

    def flavours(arch, shape, scale_eco=0.55):
        """'perf' = the measured cell; 'eco' = a reduced-clock/precision
        flavour trading throughput for energy (SADP-style flavour)."""
        base = roof[(arch, shape)]
        return {
            "perf": base,
            "eco": {k: v * scale_eco for k, v in base.items()},
        }

    jobs = [
        JobSpec("yi9b-train", "yi-9b", "train_4k",
                flavours("yi-9b", "train_4k"),
                flavours_order=("perf", "eco"), delay_tolerance_h=12),
        JobSpec("granite-train", "granite-moe-3b-a800m", "train_4k",
                flavours("granite-moe-3b-a800m", "train_4k"),
                flavours_order=("perf", "eco"), delay_tolerance_h=12),
        JobSpec("yi9b-prefill", "yi-9b", "prefill_32k",
                flavours("yi-9b", "prefill_32k"), steps_per_h=900.0),
        JobSpec("yi9b-decode", "yi-9b", "decode_32k",
                flavours("yi-9b", "decode_32k"), steps_per_h=3.6e6),
        JobSpec("mamba-long", "falcon-mamba-7b", "long_500k",
                flavours("falcon-mamba-7b", "long_500k"),
                steps_per_h=3.6e6, must_deploy=False),
    ]
    # prefill -> decode KV-cache handoff: a 32k cache of yi-9b is ~8 GB;
    # at ~900 prefills/h that is ~7 TB/h of traffic if split across pods.
    # Checkpoint cross-replication between the train jobs is light by
    # comparison — it should NOT trigger an Affinity constraint.
    traffic = [
        TrafficSpec("yi9b-prefill", "yi9b-decode", gb_per_h=7200.0),
        TrafficSpec("yi9b-train", "granite-train", gb_per_h=60.0),
    ]

    # texas: solar-heavy grid — dirty now, clean around midday (+6h).
    tx_forecast = (410.0, 390.0, 340.0, 260.0, 180.0, 130.0, 110.0,
                   140.0, 220.0, 320.0, 400.0, 420.0, 430.0)
    pods = [
        PodSpec("pod-fi", "finland", carbon=80.0, cost_per_chip_hour=1.1),
        PodSpec("pod-fr", "france", carbon=16.0, cost_per_chip_hour=1.3),
        PodSpec("pod-ie", "ireland", carbon=290.0, cost_per_chip_hour=1.0),
        PodSpec("pod-va", "virginia", carbon=350.0, cost_per_chip_hour=0.9),
        PodSpec("pod-tx", "texas", carbon=410.0, cost_per_chip_hour=0.8,
                carbon_forecast=tx_forecast),
    ]

    plan, out, stats = GreenPlacement().place(jobs, pods, traffic)

    print("=== Green-aware constraints over the TPU fleet ===")
    print(out.prolog)
    print("\n=== Job placement ===")
    for p in plan.placements:
        print(f"  {p.service:<14} [{p.flavour}] -> {p.node}")
    if plan.skipped_services:
        print(f"  skipped optional: {plan.skipped_services}")
    co = {p.service: p.node for p in plan.placements}
    same = co.get("yi9b-prefill") == co.get("yi9b-decode")
    print(f"\nprefill/decode co-located (KV on ICI): {same}")
    print(f"emissions: baseline {stats['baseline_g_per_window']:.0f} g "
          f"-> green {stats['green_g_per_window']:.0f} g "
          f"({100 * stats['saved_frac']:.1f}% saved)")
    shifts = [c for c in out.constraints if c.kind == "timeShift"]
    for c in shifts:
        print(f"timeShift: postpone {c.service} on {c.node} by "
              f"{c.shift_h}h (w={c.weight:.2f})")
    assert same, "affinity constraint must keep the KV handoff on-pod"
    assert shifts, "delay-tolerant train jobs on a solar grid must " \
                   "produce TimeShift suggestions"


if __name__ == "__main__":
    main()
