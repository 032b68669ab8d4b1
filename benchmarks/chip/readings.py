"""The readings a cell's correctness limits are set from (not run by the
benchmark's own runs).

    python benchmarks/chip/readings.py --workload <name> --seeds 1,2,3 \
        [--out readings.json]

For each seed, in one process: weights from the seed, the first window wave
of the cell's mix served through the engine at the cell's own size, then the
check's numbers for the served tokens (the program's readings) and for the
first choices of the reference computed in the configuration's control
precision at the same positions (the control's). Both are judged by
``check.judge`` against the configuration's limits. One JSON object per
seed is printed, then a summary: for each number the largest program
reading, the smallest control reading, and on how many seeds each side
failed; all of it is written to ``--out``.
"""
import argparse
import gc
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
sys.path.insert(0, BENCH_DIR)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    import jax
    import jax.numpy as jnp

    import check
    import harness
    import spec
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.serve import ServeRequest

    if jax.devices()[0].platform != "tpu":
        print("readings: needs a TPU", file=sys.stderr)
        return 1
    cell = spec.Cell(ROOT, args.workload, BENCH_DIR)
    vocab = cell.config["model"]["vocab_size"]
    limits = cell.config.get("check", {})
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        engine = harness.build(cell.config, seed, jax, jnp)
        out, _, _, _ = harness.serve_wave(engine, cell.mix, seed, 0, vocab,
                                          ServeRequest)
        finished = [(r.prompt, list(r.output)) for r in out
                    if r.done and len(r.output) == r.max_new_tokens]
        del engine, out
        gc.collect()
        ref = check.Reference(cell.config, seed)
        picked = check.sample(finished, seed)
        prog, per_req = check.program_numbers(ref, finished, picked)
        ctrl = check.control_numbers(ref, finished, picked)
        del ref
        gc.collect()
        _, prog_ok = check.judge(prog, limits)
        ctrl_check, ctrl_ok = check.judge(ctrl, limits)
        row = {"workload": args.workload, "seed": seed,
               "sampled_requests": len(picked),
               "sampled_tokens": sum(len(finished[i][1]) for i in picked),
               "program": prog, "program_per_request": per_req,
               "program_correct": prog_ok, "control": ctrl,
               "control_check": ctrl_check, "control_correct": ctrl_ok}
        rows.append(row)
        print(json.dumps(row), flush=True)
        _write(args.out, rows)
    summary = summarise(rows, limits)
    print(json.dumps(summary), flush=True)
    _write(args.out, rows + [summary])
    return 0


def summarise(rows, limits):
    """Per number: the lower reading (the program's largest), the upper
    reading (the control's smallest) and the limit; and on how many seeds
    the program and the control came out correct."""
    import check
    out = {"seeds": len(rows),
           "program_correct": sum(r["program_correct"] for r in rows),
           "control_correct": sum(r["control_correct"] for r in rows)}
    for name in check.NUMBERS:
        out[name] = {"lower": max(r["program"][name] for r in rows),
                     "upper": min(r["control"][name] for r in rows),
                     "limit": limits.get(name)}
    return out


def _write(path, rows):
    if path:
        with open(path, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    sys.exit(main())
