"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (one row per experiment) and writes
the full records to experiments/bench_results.json. Default is a fast
configuration (minutes); set BENCH_FULL=1 for paper-scale runs.

    PYTHONPATH=src python -m benchmarks.run [module-substring ...]

``--check`` turns the run into a CI regression gate instead of a recorder:
fresh rows are compared against the records already in
experiments/bench_results.json — ``decode_ms_per_tok`` within
``--tolerance`` (default 2.5x, generous because CI machines differ from the
recording machine), the machine-independent ``decode_dispatches`` /
``host_syncs`` counts within 1.5x, and the tenant rows' step-clock
``p99_latency_steps`` (ceiling) / ``slo_attainment`` (floor, higher is
better) — and the baseline file is left untouched. A gate failure prints
ONE line per offending row naming every out-of-band field. Exit status 1
on any regression — including a baseline row predating a newly gated
field, a baseline row whose module ran without reproducing it, or a
module that errored outright.

    PYTHONPATH=src python -m benchmarks.run bench_serve --check
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

# CPU-only test path: force a multi-device host platform BEFORE any
# benchmark module imports jax, so bench_serve's sharded-continuous rows
# run a real (4, 2) mesh on the CPU instead of a degenerate single-device
# one. No-op if jax is already imported or the flag is already set
# (REPRO_BENCH_DEVICES overrides the count); on a TPU host it changes
# nothing.
from repro.launch._bootstrap import force_host_devices, use_compile_cache

use_compile_cache()
force_host_devices(os.environ.get("REPRO_BENCH_DEVICES", "8"))

MODULES = [
    "bench_profiling",        # Fig 5
    "bench_fig1_load",        # Fig 1 / Fig 9
    "bench_fig7_8_policies",  # Fig 7, 8
    "bench_fig10_util",       # Fig 10
    "bench_fig11_split",      # Fig 11
    "bench_fig12_cpu_ratio",  # Fig 12
    "bench_fig13_bigdata",    # Fig 13
    "bench_fig6_philly",      # Fig 6 / Table 6
    "bench_opt_vs_tune",      # section 5.6
    "bench_kernels",          # substrate kernels
    "bench_serve",            # serve engines (static/continuous/sharded)
    "bench_table5_cluster",   # Table 5 (live runtime; slowest — last)
]


#: structured row fields the --check gate compares: {field: (tolerance
#: factor | None = use --tolerance, absolute slack, direction)}.
#: direction "max" fails when got > want * tol + slack (costs: lower is
#: better); "min" fails when got < want / tol - slack (scores: higher is
#: better). Wall-clock fields get a multiplicative band for machine speed
#: plus an absolute ms floor so micro-rows are not gated on scheduler
#: noise; dispatch/sync counts and the tenant rows' step-clock latency /
#: SLO-attainment fields are deterministic for a given configuration, so a
#: breached bound there is a real regression.
CHECK_FIELDS = {"decode_ms_per_tok": (None, 2.0, "max"),
                "decode_dispatches": (1.5, 0.0, "max"),
                "host_syncs": (1.5, 0.0, "max"),
                "p99_latency_steps": (1.25, 2.0, "max"),
                "slo_attainment": (1.0, 0.02, "min"),
                # chaos-replay rows: requests dropped by fault recovery
                # (deterministic for a schedule; baseline is 0 — the
                # recorded schedule must stay survivable without giving
                # up work, so any fresh drop is a regression).
                "dropped": (1.0, 0.0, "max")}


def _parse_args(argv):
    """(filters, check, tolerance): positional substrings filter modules;
    --check flips gate mode; --tolerance X (or --tolerance=X) scales the
    wall-clock bound."""
    filters, check, tolerance = [], False, 2.5
    i = 0
    try:
        while i < len(argv):
            a = argv[i]
            if a == "--check":
                check = True
            elif a == "--tolerance":
                tolerance = float(argv[i + 1])
                i += 1
            elif a.startswith("--tolerance="):
                tolerance = float(a.split("=", 1)[1])
            elif not a.startswith("-"):
                filters.append(a)
            i += 1
    except (IndexError, ValueError):
        raise SystemExit("usage: benchmarks.run [module-substring ...] "
                         "[--check] [--tolerance X]")
    return filters, check, tolerance


def _field_breaches(rec, ref, tolerance: float):
    """Every gated field of one (fresh, baseline) row pair that is out of
    band — ALL of them, not just the first, so one gate run names every
    problem a row has."""
    breaches = []
    for field, (tol, slack, direction) in CHECK_FIELDS.items():
        tol = tolerance if tol is None else tol
        got, want = rec.get(field), ref.get(field)
        if got is None and want is None:
            continue            # neither side carries it (non-tenant rows)
        if want is None:
            breaches.append(
                f"baseline predates field {field!r} — re-record it "
                f"(benchmarks.run without --check)")
            continue
        if got is None:
            breaches.append(
                f"fresh row dropped gated field {field!r} "
                f"(baseline has {float(want):.2f})")
            continue
        if direction == "min":
            bound = float(want) / tol - slack
            if float(got) < bound:
                breaches.append(
                    f"{field} {float(got):.2f} < {float(want):.2f} / "
                    f"{tol:g} - {slack:g}")
            continue
        # a zero baseline can't scale multiplicatively, but the absolute
        # slack still gates: a dropped=0 baseline breaches on ANY drop,
        # while wall-clock fields keep their ms floor.
        bound = float(want) * tol + slack
        if float(got) > bound:
            breaches.append(
                f"{field} {float(got):.2f} > {float(want):.2f} * "
                f"{tol:g} + {slack:g}")
    return breaches


def check_regressions(records, baseline, tolerance: float,
                      ran_modules=frozenset()):
    """Compare fresh rows against the recorded baseline; returns a list of
    human-readable regression strings (empty = gate passes), ONE per
    offending row, naming every out-of-band field of that row in one pass
    — a gate failure reads as the full repair list, not the first symptom.

    Rows absent from the baseline are skipped — the gate only tightens as
    the baseline file accumulates rows — but a gated FIELD carried by only
    one side of a shared row fails explicitly (a baseline row predating a
    newly added field must be re-recorded), and a BASELINE row whose
    module ran this pass without reproducing it fails too: a benchmark
    that silently stopped emitting a gated row is a regression, not a
    skip. Baseline rows without a recorded ``module`` predate that key and
    are exempt from the missing-row check."""
    base = {r.get("name"): r for r in baseline}
    fresh = {r.get("name") for r in records}
    failures = []
    for rec in records:
        ref = base.get(rec.get("name"))
        if ref is None:
            continue
        breaches = _field_breaches(rec, ref, tolerance)
        if breaches:
            failures.append(f"{rec['name']}: " + "; ".join(breaches)
                            + " (recorded baseline)")
    for ref in baseline:
        if (ref.get("name") not in fresh
                and ref.get("module") in ran_modules):
            failures.append(
                f"{ref['name']}: baseline row missing from this run "
                f"(module {ref['module']} ran but did not emit it)")
    return failures


def main() -> None:
    filters, check, tolerance = _parse_args(sys.argv[1:])
    records = []
    ran_modules, errored = set(), []
    print("name,us_per_call,derived")
    t_start = time.time()
    for mod_name in MODULES:
        if filters and not any(f in mod_name for f in filters):
            continue
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            rows = mod.run()
        except Exception:
            print(f"{mod_name},0,ERROR")
            traceback.print_exc()
            errored.append(mod_name)
            continue
        ran_modules.add(mod_name)
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.0f},\"{r['derived']}\"")
            rec = {k: v for k, v in r.items() if k != "result"}
            rec["module"] = mod_name
            records.append(rec)
        sys.stdout.flush()

    os.makedirs("experiments", exist_ok=True)
    try:
        with open("experiments/bench_results.json") as f:
            prior = json.load(f)
    except (OSError, json.JSONDecodeError):
        prior = []

    if check:
        # gate mode: compare against the recorded baseline, leave it as is.
        # A missing/corrupt baseline (or one sharing no rows with this run)
        # must FAIL — a gate that silently compares zero rows is no gate —
        # and so must a benchmark module that errored out: its rows never
        # reached the comparison at all.
        names = {r.get("name") for r in prior}
        comparable = [r for r in records if r.get("name") in names]
        if not comparable:
            print("# REGRESSION experiments/bench_results.json has no rows "
                  "matching this run — baseline missing or corrupt")
            raise SystemExit(1)
        failures = [f"module {m} raised instead of producing rows"
                    for m in errored]
        failures += check_regressions(records, prior, tolerance,
                                      ran_modules=ran_modules)
        print(f"# total wall: {time.time() - t_start:.0f}s; "
              f"--check: {len(comparable)} rows vs recorded baseline "
              f"(tolerance {tolerance:g}x)")
        if failures:
            for msg in failures:
                print(f"# REGRESSION {msg}")
            raise SystemExit(1)
        print("# bench regression gate: PASS")
        return

    # A filtered run updates its rows in place instead of clobbering the
    # other modules' records, so the trajectory file stays complete.
    if filters:
        fresh = {r["name"] for r in records}
        records = [r for r in prior if r.get("name") not in fresh] + records
    with open("experiments/bench_results.json", "w") as f:
        json.dump(records, f, indent=2, default=str)
    print(f"# total wall: {time.time() - t_start:.0f}s; "
          f"records -> experiments/bench_results.json")


if __name__ == "__main__":
    main()
