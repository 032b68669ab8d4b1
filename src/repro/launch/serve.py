"""Serving driver: static / continuous / sharded batched generation.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --engine continuous --cache paged --mesh host --slots 8 --batch 12 \
        --arrival-rate 2 --policy fcfs --verify

Engines: ``static`` runs one batch with a slot per request (one admission
round); ``continuous`` bounds the pool to ``--slots`` and joins/evicts per
decode step. ``--cache paged`` swaps the per-slot max_len cache rows for the
block-pool cache (attention families): admission is by free *blocks*
(length-proportional, ``--block-size`` positions each, ``--blocks`` total),
prompts prefill in block_size chunks packed ``--prefill-lanes`` joining
requests per jitted dispatch, shared prompt prefixes hit the content-hashed
block cache (``--no-prefix-cache`` to ablate; ``--shared-prefix N`` builds a
system-prompt-style workload and ``--min-hit-rate`` asserts the cache
worked), and decode compacts to the live slots (the summary reports the
saved rows, prefill/decode dispatch counts and wall split, the prefix-cache
hit rate, and the pool's occupancy/fragmentation).
``--decode-horizon K`` (default 8) runs K decode steps per jitted dispatch
entirely on device — on-device token selection, per-row budget/EOS stop
masks (``--eos-token``), device-resident decode state — so the summary's
``host_syncs``/``decode_dispatches`` drop ~K-fold against the per-token
loop (``--decode-horizon 1``) while outputs stay token-identical.
``--mesh host`` executes the jitted decode step TP/DP-sharded over the host
mesh (forcing an 8-device host platform when run from the CLI, like
launch/dryrun.py); decode compacts to width buckets rounded to the mesh
'data' axis on both cache backends. ``--arrival-rate R`` switches to
open-loop arrivals: request i becomes admissible at decode step i/R; 0
means all arrive at once.
``--temperature``/``--top-k`` sample on per-slot RNG lanes
(``jax.random.fold_in`` on slot id + decode step); greedy is the default.
``--verify`` re-runs the request set on a single-device static engine with a
contiguous cache and checks per-request outputs are identical — the paged
exactness invariant (greedy only).

Multi-tenant serving (serve/tenant.py): ``--tenants N`` registers tenants
t0..tN-1 and tags the request set across them (``--tenant-mix`` ratios,
round-robin interleaved); ``--slo`` / ``--slo-s`` give per-tenant latency
SLOs (comma lists, ``none`` = no target) and ``--tenant-weights`` the
fairness weights. ``--policy slo`` orders admission by SLO slack, and the
optimistic serve profiler + ``TenantAllocator`` plan per-tenant
block/lane/horizon budgets the engine enforces (``--no-tenant-alloc``
keeps the registry — tags, SLO scoring, slack policy — but drops the
budgets: the capacity-proportional baseline). The summary gains a
per-tenant block with p50/p99 latency and ``slo_attainment``; ``--verify``
still holds — tenant mechanisms reorder, they never change tokens.

Observability (src/repro/obs): ``--trace out.jsonl`` records every
scheduling decision, phase dispatch, and block-pool transition as
structured events (``--trace-format chrome`` writes a Perfetto-loadable
Chrome trace instead; ``--trace-capacity`` bounds the event ring).
``--metrics-every N`` sets the time-series sampling cadence at decode
boundaries. Analyze a JSONL trace offline with::

    PYTHONPATH=src python -m repro.launch.trace_report out.jsonl

``--profile`` attaches the dispatch profiler (obs/prof.py): per-dispatch
wall time with compile-vs-execute attribution, measured-vs-roofline
utilization gauges, and per-tenant cost shares land in the summary's
``profile`` block (and, with ``--trace``, as ``dispatch_profile`` events —
Chrome counter tracks under ``--trace-format chrome``). ``--profile-store
PATH`` closes the optimistic-profiling loop: measured per-signature costs
merge into the JSONL store, and the tenant calibrate reads MEASURED
(t_tok, t_fixed) back out of it when a fit exists (the summary's
``calibrate_source`` says which path each tenant took). Profiling is
read-only — ``--verify`` holds with it on.

Tracing and profiling off is the default and each costs one branch per
hook site, so the benchmarked decode numbers are unchanged:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --engine continuous --cache paged --mesh host --slots 8 --batch 12 \
        --tenants 2 --slo 24,none --policy slo --arrival-rate 2 --verify
"""
import os
import sys

from repro.launch._bootstrap import (force_host_devices, mesh_flag,
                                    use_compile_cache)

use_compile_cache()
# CPU-only test path: --mesh host on the CPU backend needs forced host
# devices; on a TPU host the flag changes nothing.
if mesh_flag(sys.argv) == "host":
    force_host_devices(os.environ.get("REPRO_SERVE_DEVICES", "8"))

import jax  # noqa: E402  (lock the device count before any repro import)

import argparse     # noqa: E402
import dataclasses  # noqa: E402
import json         # noqa: E402
import math         # noqa: E402

import numpy as np  # noqa: E402

from repro.configs import ARCH_IDS, get_config                    # noqa: E402
from repro.serve import (Tenant, TenantRegistry,                   # noqa: E402
                         ServeEngine, ServeRequest, plan_allocation,
                         profiles_from_requests, sharded_engine)


def make_requests(cfg, n: int, prompt_len: int, max_new: int,
                  arrival_rate: float, seed: int = 0,
                  shared_prefix: int = 0):
    """Mixed-length request set with optional open-loop arrivals.

    ``shared_prefix`` prepends the same ``shared_prefix``-token prefix to
    every prompt (a system-prompt-style workload): with the paged engine's
    prefix cache on, later requests serve those blocks from cache."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, cfg.vocab_size,
                          size=shared_prefix).astype(np.int32)
    reqs = []
    for i in range(n):
        s = int(rng.integers(max(1, prompt_len // 2), prompt_len + 1))
        arrival = (i / arrival_rate) if arrival_rate > 0 else 0.0
        tail = rng.integers(1, cfg.vocab_size, size=s).astype(np.int32)
        reqs.append(ServeRequest(
            np.concatenate([prefix, tail]) if shared_prefix else tail,
            max_new_tokens=max_new, arrival_time=arrival))
    return reqs


def _csv(spec, n: int, flag: str):
    """Comma-list tenant flag -> n values (``none``/empty entry -> None)."""
    if not spec:
        return [None] * n
    parts = [p.strip() for p in spec.split(",")]
    if len(parts) != n:
        raise SystemExit(f"{flag} needs {n} comma-separated values "
                         f"(got {len(parts)})")
    return [None if p.lower() in ("none", "") else float(p) for p in parts]


def tag_tenants(reqs, ids, mix) -> None:
    """Deterministically interleave the request set across tenants by the
    mix ratios: request i goes to the tenant with the largest deficit
    against its target share, so a 2:1 mix tags t0,t0,t1,t0,t0,t1,..."""
    total = sum(mix)
    counts = [0] * len(ids)
    for i, r in enumerate(reqs):
        j = max(range(len(ids)),
                key=lambda k: (mix[k] * (i + 1) / total - counts[k], -k))
        r.tenant = ids[j]
        counts[j] += 1


def build_tenancy(args, reqs, n_slots, store=None):
    """Registry (+ profiler-planned allocation) for ``--tenants N``.

    The optimistic serve profiler reads each tenant's class shape off its
    tagged requests (footprint in cache units, offered concurrency) and
    the allocator plans block/lane/horizon budgets for the engine's pool
    geometry. ``--no-tenant-alloc`` keeps the registry — tags, SLO
    scoring, slack policy — without budgets (the capacity-proportional
    baseline). ``store`` (an ``obs.ProfileStore`` from
    ``--profile-store``) feeds MEASURED rate constants into the calibrate
    when its records support a fit — the knees then come from real
    dispatch costs instead of the analytic defaults."""
    n = args.tenants
    slo = _csv(args.slo, n, "--slo")
    slo_s = _csv(args.slo_s, n, "--slo-s")
    wts = _csv(args.tenant_weights, n, "--tenant-weights")
    mix = _csv(args.tenant_mix, n, "--tenant-mix")
    ids = [f"t{i}" for i in range(n)]
    registry = TenantRegistry([
        Tenant(ids[i], weight=wts[i] if wts[i] is not None else 1.0,
               slo_steps=slo[i], slo_s=slo_s[i]) for i in range(n)])
    tag_tenants(reqs, ids, [m if m is not None else 1.0 for m in mix])
    if not args.tenant_alloc:
        return registry, None, None
    if args.cache == "paged":
        blocks_per_slot = -(-args.max_len // args.block_size)
        total_units = args.blocks or (n_slots or args.batch) * blocks_per_slot
        units_for = lambda r: -(-(len(r.prompt) + r.max_new_tokens)  # noqa: E731
                                // args.block_size)
        watermark_units = math.ceil(args.watermark * total_units)
    else:
        total_units = n_slots or args.batch
        units_for = lambda r: 1                                      # noqa: E731
        watermark_units = 0
    profiles = profiles_from_requests(
        registry, reqs, total_units=total_units, units_for=units_for,
        max_k=args.decode_horizon, store=store, arch=args.arch,
        backend=args.cache)
    allocation = plan_allocation(
        registry, profiles, total_units, total_lanes=args.prefill_lanes,
        max_k=args.decode_horizon, watermark_units=watermark_units)
    return registry, allocation, profiles


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=ARCH_IDS)
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--engine", default="static",
                    choices=["static", "continuous"])
    ap.add_argument("--cache", default="contiguous",
                    choices=["contiguous", "paged"])
    ap.add_argument("--mesh", default="single", choices=["single", "host"])
    ap.add_argument("--policy", default="fcfs",
                    choices=["fcfs", "sjf", "slo"])
    ap.add_argument("--tenants", type=int, default=0,
                    help="register N tenants t0..tN-1 and tag the request "
                         "set across them (0 = single-tenant)")
    ap.add_argument("--slo", default="",
                    help="per-tenant latency SLO in decode steps, comma "
                         "list ('none' = no target), e.g. --slo 24,none")
    ap.add_argument("--slo-s", default="",
                    help="per-tenant wall-clock SLO in seconds (comma list; "
                         "scored in the stats, never scheduled on)")
    ap.add_argument("--tenant-weights", default="",
                    help="per-tenant fairness weights (comma list, default 1)")
    ap.add_argument("--tenant-mix", default="",
                    help="per-tenant request-count ratios (comma list, "
                         "default equal split), e.g. --tenant-mix 2,1")
    ap.add_argument("--no-tenant-alloc", dest="tenant_alloc",
                    action="store_false",
                    help="keep tenant tags + SLO scoring but drop the "
                         "profiler-planned budgets (capacity-proportional "
                         "baseline)")
    ap.add_argument("--batch", type=int, default=8,
                    help="number of requests in the set")
    ap.add_argument("--slots", type=int, default=4,
                    help="cache-pool slots (continuous engine)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV positions per block (paged cache)")
    ap.add_argument("--blocks", type=int, default=0,
                    help="paged pool size in blocks "
                         "(0 = slots * ceil(max_len / block_size))")
    ap.add_argument("--watermark", type=float, default=0.05,
                    help="fraction of blocks reserved at admission (paged)")
    ap.add_argument("--prefill-lanes", type=int, default=4,
                    help="joining requests prefilled per jitted chunk-round "
                         "(paged cache)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false",
                    help="disable content-hashed prompt-block sharing (paged)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many common prefix tokens to every "
                         "prompt (prefix-cache workload)")
    ap.add_argument("--min-hit-rate", type=float, default=None,
                    help="fail unless the prefix-cache hit rate reaches this "
                         "fraction (CI assertion)")
    ap.add_argument("--prompt-len", type=int, default=8,
                    help="max prompt length (lengths are mixed in [len/2, len])")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--decode-horizon", type=int, default=8,
                    help="decode steps per jitted dispatch (device-resident "
                         "multi-step loop; 1 = the classic per-token loop)")
    ap.add_argument("--eos-token", type=int, default=None,
                    help="stop a request early when it emits this token id")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="open-loop arrivals per decode step (0 = all at once)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples on per-slot RNG lanes")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation for sampling (0 = full vocab)")
    ap.add_argument("--verify", action="store_true",
                    help="check outputs against a single-device static engine")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="dump a structured event trace of the run here "
                         "(analyze with repro.launch.trace_report)")
    ap.add_argument("--trace-format", default="jsonl",
                    choices=["jsonl", "chrome"],
                    help="trace file format: jsonl (trace_report) or chrome "
                         "(load in ui.perfetto.dev)")
    ap.add_argument("--trace-capacity", type=int, default=1 << 16,
                    help="event ring-buffer capacity (oldest events drop "
                         "beyond this)")
    ap.add_argument("--metrics-every", type=int, default=1,
                    help="sample the metrics time series every N decode "
                         "boundaries (0 disables series sampling)")
    ap.add_argument("--profile", action="store_true",
                    help="attach a dispatch profiler: per-dispatch wall "
                         "time with compile/execute attribution, roofline "
                         "utilization gauges, per-tenant cost shares (the "
                         "summary gains a 'profile' block; with --trace, "
                         "dispatch_profile events land in the trace)")
    ap.add_argument("--profile-store", default=None, metavar="PATH",
                    help="ProfileStore JSONL (e.g. experiments/"
                         "profiles.jsonl): read MEASURED rate constants "
                         "into the tenant calibrate when a fit exists; "
                         "with --profile, this run's per-signature costs "
                         "are merged back in")
    ap.add_argument("--elastic", action="store_true",
                    help="install an ElasticController: the engine scales "
                         "the pool up/down at horizon boundaries from the "
                         "occupancy/queue/slack gauges, re-planning tenant "
                         "budgets at every reshape")
    ap.add_argument("--elastic-max-units", type=int, default=None,
                    help="proactive scale-up ceiling in cache units "
                         "(default: the constructed pool size)")
    ap.add_argument("--elastic-min-units", type=int, default=None,
                    help="proactive scale-down floor (default: no "
                         "proactive shrink)")
    ap.add_argument("--elastic-step-units", type=int, default=8,
                    help="cache units per proactive reshape")
    ap.add_argument("--elastic-cooldown", type=float, default=16.0,
                    help="decode steps between reshapes")
    args = ap.parse_args()

    if args.verify and args.temperature > 0:
        ap.error("--verify is the greedy exactness path; drop --temperature")
    if args.policy == "slo" and args.tenants <= 0:
        ap.error("--policy slo needs --tenants N (slack comes from SLOs)")

    cfg = get_config(args.arch, smoke=args.preset == "smoke")
    n_slots = args.slots if args.engine == "continuous" else None
    n_blocks = args.blocks or None

    # requests first: the optimistic serve profiler reads each tenant's
    # class shape (footprint, concurrency) off the tagged request set.
    reqs = make_requests(cfg, args.batch, args.prompt_len, args.max_new,
                         args.arrival_rate, shared_prefix=args.shared_prefix)

    store = None
    if args.profile_store:
        from repro.obs import ProfileStore
        store = ProfileStore.load(args.profile_store)

    registry = allocation = profiles = None
    if args.tenants > 0:
        registry, allocation, profiles = build_tenancy(args, reqs, n_slots,
                                                       store=store)

    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer(capacity=args.trace_capacity)

    profiler = None
    if args.profile:
        from repro.obs import DispatchProfiler
        n_dev = jax.device_count() if args.mesh == "host" else 1
        profiler = DispatchProfiler(cfg, n_devices=n_dev)

    elastic = None
    if args.elastic:
        from repro.serve import ElasticController
        elastic = ElasticController(step_units=args.elastic_step_units,
                                    max_units=args.elastic_max_units,
                                    min_units=args.elastic_min_units,
                                    cooldown=args.elastic_cooldown)

    engine_kw = dict(cache=args.cache, block_size=args.block_size,
                     n_blocks=n_blocks, watermark=args.watermark,
                     prefill_lanes=args.prefill_lanes,
                     prefix_cache=args.prefix_cache,
                     temperature=args.temperature, top_k=args.top_k,
                     decode_horizon=args.decode_horizon,
                     eos_token=args.eos_token,
                     tenants=registry, allocation=allocation,
                     tracer=tracer, metrics_every=args.metrics_every,
                     profiler=profiler, elastic=elastic,
                     profile_store=store)

    if args.mesh == "host":
        engine = sharded_engine(cfg, n_slots=n_slots or args.batch,
                                max_len=args.max_len, policy=args.policy,
                                **engine_kw)
    else:
        engine = ServeEngine(cfg, max_len=args.max_len, n_slots=n_slots,
                             policy=args.policy, **engine_kw)

    out, stats = engine.run(reqs)

    trace_info = None
    if tracer is not None:
        if args.trace_format == "chrome":
            from repro.obs import write_chrome_trace
            write_chrome_trace(args.trace, tracer.events)
        else:
            tracer.dump_jsonl(args.trace)
        trace_info = {"path": args.trace, "format": args.trace_format,
                      "events": len(tracer), "dropped": tracer.dropped}

    record = {
        "arch": cfg.arch_id,
        "engine": args.engine,
        "cache": args.cache,
        "mesh": args.mesh,
        "policy": args.policy,
        "n_devices": jax.device_count(),
        "slots": n_slots or args.batch,
        "elastic": bool(elastic),
        **dataclasses.asdict(stats),
        "sample_output": out[0].output[:8],
    }
    if trace_info is not None:
        record["trace"] = trace_info
    if allocation is not None:
        record["tenant_budgets"] = {
            tid: dataclasses.asdict(s)
            for tid, s in sorted(allocation.shares.items())}
    if profiles is not None:
        record["calibrate_source"] = {
            tid: p.source for tid, p in sorted(profiles.items())}
    if profiler is not None:
        record["profile"] = profiler.summary()
        if args.profile_store:
            store.add_run(profiler, arch=args.arch, backend=args.cache,
                          mesh=args.mesh)
            store.save(args.profile_store)
            record["profile"]["store"] = {"path": args.profile_store,
                                          "records": len(store)}

    if args.verify:
        # the reference is the classic loop: single-device static engine,
        # contiguous cache, decode_horizon=1 — so --verify cross-checks the
        # multi-step horizon against per-token decoding too.
        ref_engine = ServeEngine(cfg, max_len=args.max_len, decode_horizon=1,
                                 eos_token=args.eos_token)
        ref = [ServeRequest(r.prompt.copy(), max_new_tokens=r.max_new_tokens)
               for r in out]
        ref, _ = ref_engine.run(ref)
        mismatches = [i for i, (a, b) in enumerate(zip(ref, out))
                      if a.output != b.output]
        record["verified"] = not mismatches
        if mismatches:
            record["mismatched_requests"] = mismatches
            print(json.dumps(record, indent=2))
            raise SystemExit(
                f"FAIL: {len(mismatches)} request(s) diverged from the "
                f"single-device static engine")

    if args.min_hit_rate is not None \
            and stats.prefix_hit_rate < args.min_hit_rate:
        print(json.dumps(record, indent=2))
        raise SystemExit(
            f"FAIL: prefix-cache hit rate {stats.prefix_hit_rate:.2f} below "
            f"the required {args.min_hit_rate:.2f}")

    print(json.dumps(record, indent=2))


if __name__ == "__main__":
    main()
