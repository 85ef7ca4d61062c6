"""Run one benchmark cell on the chip and print its result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (deployment and traces from the seed, program objects, warm-up of
every shape the window uses) counts from the start of this process.  The
window then runs the cell's timed items for ``--seconds``; afterwards the
reference judges the answers.  The last lines of standard error give
each number compared beside its limit; the last line of standard output
is the result.  Off the chip, or with fewer chips than the cell asks
for, the run exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
