"""One run of one cell: set-up, the measured window of offline batch waves
through ``ServeEngine.run``, the correctness check, and the result line.

``run_cell.py`` is the command; this module holds the run so that the tests
can drive it without a chip (``require_tpu=False``).
"""
import gc
import glob
import json
import math
import os
import shutil
import sys
import time

import numpy as np

import check
import spec
import traffic
import trace_reduce
import work
from reference import family_module
from reference.common import make_params, seed_key


class CompileCounter:
    """Programs built inside the process (a backend compile, or a load from
    the persistent cache), from JAX's monitoring events."""

    def __init__(self, jax):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def percentile(values, q):
    """Nearest-rank percentile (inf sorts last)."""
    v = sorted(values)
    return v[max(0, math.ceil(q / 100 * len(v)) - 1)]


def load_peaks(bench_dir, kind):
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks["devices"]:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks["devices"][kind]


def build(config, seed, jax, jnp):
    """The program's config, checked against the configuration file, and
    an engine serving weights made from the seed."""
    from repro.configs import get_config
    from repro.models.api import build_model
    from repro.serve import ServeEngine

    ref = family_module(config["family"])
    cfg = get_config(config["arch"], **config["program"])
    for key, field in ref.PROGRAM_KEYS.items():
        if config["model"][key] != getattr(cfg, field):
            raise SystemExit(f"configuration {key}={config['model'][key]!r} "
                             f"but the program runs {field}="
                             f"{getattr(cfg, field)!r}")
    params = make_params(ref.param_spec(config["model"]), seed_key(seed),
                         jnp.dtype(cfg.param_dtype))
    want = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    if jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want) != got:
        raise SystemExit("the weights made for the reference do not match "
                         "the program's parameter tree")
    return ServeEngine(cfg, params=params, **config["engine"])


def serve_wave(engine, mix, seed, index, vocab, ServeRequest):
    reqs = [ServeRequest(p, max_new_tokens=n)
            for p, n in traffic.wave(mix, seed, index, vocab)]
    t0 = time.perf_counter()
    out, stats = engine.run(reqs)
    t1 = time.perf_counter()
    return out, stats, t0, t1


def warm_up(engine, config, mix, seed, vocab, ServeRequest):
    """Build every program the window can use before it starts; returns
    the seconds each part took.

    * ``counts``: the delta scatters the engine issues at admission, block
      growth and eviction are eager and take one shape per count of rows,
      and a wave can admit or evict any count up to the slots. For each
      count k, k requests are admitted and finish together. The contiguous
      engine scatters rows and freezes them whether or not they decode, so
      its requests stop at their prefill token; the paged engine uploads
      block tables only before a horizon, so its requests decode one step.
    * ``widths``: horizons gather the live rows into power-of-two widths
      and scan one of four lengths (1, 2, 4, 8 steps). At each width, a
      budget of two horizons runs every length.
    * ``prefill``: the contiguous engine prefills each prompt at its own
      length, and the counts use the mix's shortest, so one more run holds
      one request of each other length the mix sends. The paged engine
      prefills in fixed chunks; two runs of prompts of unequal chunk counts
      finish one and two of several lanes at a time (the prefill's token
      pick takes one shape per lane count and finished lanes)."""
    eng = config["engine"]
    paged = eng["cache"] == "paged"
    lengths = sorted(set(traffic.lengths(mix["prompt_tokens"],
                                         mix["wave_requests"])))
    plen = eng["block_size"] if paged else lengths[0]
    rng = np.random.default_rng([int(seed), traffic.WARMUP_WAVE])

    def req(n, budget):
        return ServeRequest(rng.integers(0, vocab, n, dtype=np.int32),
                            max_new_tokens=budget)

    n_slots, two = eng["n_slots"], 2 * eng["decode_horizon"]
    spent = {}
    t = time.perf_counter()
    for k in range(1, n_slots + 1):
        engine.run([req(plen, 2 if paged else 1) for _ in range(k)])
    spent["counts"] = time.perf_counter() - t
    t = time.perf_counter()
    k = 1
    while k <= n_slots:
        engine.run([req(plen, two) for _ in range(k)])
        k *= 2
    if n_slots & (n_slots - 1):
        engine.run([req(plen, two) for _ in range(n_slots)])
    spent["widths"] = time.perf_counter() - t
    t = time.perf_counter()
    if paged:
        for chunks in ((1, 2, 3, 4), (1, 1, 2, 2)):
            engine.run([req(c * plen, two) for c in chunks])
    else:
        engine.run([req(n, two) for n in lengths[1:]])
    spent["prefill"] = time.perf_counter() - t
    return spent


def sum_stats(waves):
    """ServeStats of the waves summed (rates weighted by their base)."""
    keys = ("prefill_s", "decode_s", "steps", "decode_dispatches",
            "prefill_dispatches", "new_tokens", "host_syncs", "preemptions",
            "wall_s")
    tot = {k: sum(getattr(st, k) for _, st, _, _ in waves) for k in keys}
    steps = tot["steps"]
    disp = tot["decode_dispatches"]
    tot["slot_utilization"] = (sum(st.slot_utilization * st.steps
                                   for _, st, _, _ in waves) / steps
                               if steps else 0.0)
    tot["mean_occupancy"] = (sum(st.mean_occupancy * st.decode_dispatches
                                 for _, st, _, _ in waves) / disp
                             if disp else 0.0)
    return tot


def run(root, workload, seed, seconds, trace, *, program_src=None,
        require_tpu=True, t_start=None):
    """One run; returns (result dict, check lines). Raises SystemExit with a
    message when the run cannot be made."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench_dir = os.path.join(root, "benchmarks", "chip")
    cell = spec.Cell(root, workload, bench_dir)
    src = program_src or os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"the program (src/repro) is not in {root}")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root,
                                                               ".jax_cache")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, but JAX found {dev.platform!r}")
    if len(devices) < cell.chips:
        raise SystemExit(f"cell {workload} needs {cell.chips} chips, "
                         f"JAX found {len(devices)}")
    sys.path.insert(0, src)
    from repro.serve import ServeRequest

    config, mix = cell.config, cell.mix
    vocab = config["model"]["vocab_size"]
    peaks = load_peaks(bench_dir, dev.device_kind) if require_tpu else None
    compiles = CompileCounter(jax)

    # -- set-up: weights, engine, every program the window can use ---------
    t_build = time.perf_counter()
    engine = build(config, seed, jax, jnp)
    spent = {"start": t_build - t_start,
             "build": time.perf_counter() - t_build}
    spent.update(warm_up(engine, config, mix, seed, vocab, ServeRequest))
    setup_s = time.perf_counter() - t_start
    print("setup " + " ".join(f"{k} {v:.3f}s" for k, v in spent.items()),
          file=sys.stderr, flush=True)

    # -- the window ---------------------------------------------------------
    # A traced run traces the window's first wave alone and reads the
    # per-layer metrics over it: every wave does the same work, and the
    # trace of a whole window takes minutes to write and read back and
    # loses device events (measured on a v5e).
    trace_dir = os.path.join(root, ".bench_trace", workload)
    opts = None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # JAX's own dispatch events label the idle gaps; the Python tracer
        # would add an event per Python call and slow the host
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
    c0 = compiles.count
    waves = []
    w0 = time.perf_counter()
    while True:
        if opts is not None and not waves:
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(f"wave {len(waves)}"):
            waves.append(serve_wave(engine, mix, seed, len(waves), vocab,
                                    ServeRequest))
        if opts is not None and len(waves) == 1:
            t = time.perf_counter()
            jax.profiler.stop_trace()
            print(f"trace stop {time.perf_counter() - t:.3f}s",
                  file=sys.stderr, flush=True)
        if time.perf_counter() - w0 >= seconds:
            break
    w1 = time.perf_counter()
    in_window = compiles.count - c0
    stats = jax.devices()[0].memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")

    # -- per-request outcome -----------------------------------------------
    finished, jcts, attempted, lost = [], [], 0, 0
    traced = []          # (prompt, output) lengths served in the first wave
    for i, (out, _, t0, _) in enumerate(waves):
        for r in out:
            attempted += 1
            ok = (r.done and not r.dropped and r.t_finished is not None
                  and len(r.output) == r.max_new_tokens)
            if ok:
                finished.append((r.prompt, list(r.output)))
                jcts.append(r.t_finished - t0)
                if i == 0:
                    traced.append((len(r.prompt), len(r.output)))
            else:
                lost += 1
                jcts.append(math.inf)
    summed = sum_stats(waves[:1])
    traced_s = waves[0][3] - waves[0][2]
    family, model = config["family"], config["model"]
    del engine, waves
    gc.collect()

    # -- correctness ----------------------------------------------------------
    ref = check.Reference(config, seed)
    picked = check.sample(finished, seed)
    nums, per_req = check.program_numbers(ref, finished, picked)
    verdict, ok = check.judge(nums, config.get("check", {}))
    gap_lim = config.get("check", {}).get("max_gap")
    failed = lost + sum(1 for g in per_req
                        if gap_lim is None or g > gap_lim)
    del ref
    correct = ok and failed == 0

    # -- metrics --------------------------------------------------------------
    window_s = w1 - w0
    done_jct = [j for j in jcts if math.isfinite(j)]
    e2e = {
        "tokens_per_s": sum(len(o) for _, o in finished) / window_s,
        "jct_mean_s": sum(done_jct) / len(done_jct) if done_jct else math.inf,
        "jct_p95_s": percentile(jcts, 95) if jcts else math.inf,
        "setup_s": setup_s,
    }
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    breakdown = None
    if not trace:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    else:
        reduced = None
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if files:
            t = time.perf_counter()
            reduced = trace_reduce.reduce_file(max(files, key=os.path.getmtime))
            print(f"trace reduce {time.perf_counter() - t:.3f}s",
                  file=sys.stderr, flush=True)
            shutil.rmtree(trace_dir, ignore_errors=True)
        served = traced
        record = {
            "family": family, "model": model, "engine": config["engine"],
            "stats": summed, "window_s": traced_s, "requests": served,
            "prompt_tokens": sum(p for p, _ in served),
            "model_flops": sum(work.model_flops(family, model, p, n)
                               for p, n in served),
            "kernel_work": work.kernel_work(family, model, served),
            "peaks": peaks, "trace": reduced,
            "compiles_in_window": in_window,
        }
        metrics = {}
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"], bench_dir)(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = reduced["breakdown"]
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = verdict            # the compared numbers come last
    lines = [f"check {k}: {v['value']!r} limit {v['limit']!r}"
             for k, v in verdict.items()]
    lines.append(f"check failed_requests: {failed!r} limit 0")
    return result, lines
