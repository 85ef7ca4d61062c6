"""Readings of the comparison that decides ``correct``, for setting limits.

  python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, in one process: set the cell up, run its window, and print
one JSON line with the numbers the comparison reads twice: once for the
program's answers (the lower readings) and once for the control, the
reference in float32 put in the program's place (the upper readings).
The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(workload: str, seed: int, seconds: float, **cell_kw) -> dict:
    """Program and control readings of one seed's window."""
    from bench.harness import Cell

    cell = Cell(workload, seed, False, **cell_kw)
    _, items, compiles, _ = cell.window(seconds)
    d = cell.driver
    program = d.judge(d.answers())
    closest = getattr(d, "closest", None)
    control = d.judge(d.control_answers())
    return {"workload": workload, "seed": seed, "items": items,
            "window_compiles": compiles,
            "program": {k: v for k, (v, _) in program.items()},
            "control": {k: v for k, (v, _) in control.items()},
            # the reference's closest call among the planner's choices and
            # at the gate, over |J| and the horizon's grams
            "closest": closest,
            "limits": {k: lim for k, (_, lim) in program.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.harness import NoChip

    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            print(json.dumps(readings(args.workload, seed, args.seconds)),
                  flush=True)
    except NoChip as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
