"""Run one benchmark cell once on the chip this process finds.

    python benchmarks/chip/run_cell.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and per-layer metrics are looked
up by name in BENCHMARK.json and under benchmarks/chip/. The last line of
standard output is the result as one JSON object; the numbers the
correctness check compared, each beside its limit, are the last lines of
standard error. Exits 1 with no result when JAX finds no TPU, fewer chips
than the cell asks for, or no program beside the benchmark.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
sys.path.insert(0, BENCH_DIR)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    import harness
    try:
        result, lines = harness.run(ROOT, args.workload, args.seed,
                                    args.seconds, bool(args.trace),
                                    t_start=T_START)
    except SystemExit as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 1
    sys.stdout.flush()
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
