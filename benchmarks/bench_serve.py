"""Serve engines — static vs continuous vs sharded-continuous tokens/s for an
attention-family and an ssm-family architecture, plus paged-vs-contiguous
admission density at mixed prompt lengths, a shared-prefix (prefix-cache)
workload, and a decode-horizon K=1 vs K=8 ablation (smoke shapes; set
BENCH_FULL=1 for a larger request set). Rows measure the *second* run of
each engine (``_run_warm``): cold runs are compile-dominated at smoke
shapes and would bury the decode hot path.

Every row splits the blended us_per_call into prefill/decode wall time and
reports the jitted-dispatch counts (``disp=P+D``), host sync points
(``hs``), the decode horizon (``K``), and the prefix-cache hit rate, so the
trajectory captures where each engine spends its time. Rows also carry
structured ``decode_ms_per_tok`` / ``decode_dispatches`` / ``host_syncs``
fields that ``benchmarks.run --check`` gates against the recorded
baseline."""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import FAST
from repro.configs import get_config
from repro.serve import (ServeEngine, ServeRequest, Tenant, TenantRegistry,
                         plan_allocation, profiles_from_requests,
                         sharded_engine)

ARCHS = ("qwen2-0.5b", "mamba2-780m")


def _run_warm(engine, mk_requests):
    """Steady-state measurement: run once to compile every (width, horizon)
    program, then measure a second run on fresh request copies. Cold runs
    are compile-dominated at smoke shapes, which buries the decode hot path
    the trajectory (and the --check gate) cares about."""
    engine.run(mk_requests())
    return engine.run(mk_requests())


def _requests(cfg, n, max_new, seed=0, stagger=False):
    """Mixed-length request set. ``stagger`` additionally mixes the
    generation budgets so completions spread over the run — mid-run
    evictions are what exercise live-slot compaction (a uniform budget
    finishes every row on the same step and saves nothing)."""
    rng = np.random.default_rng(seed)
    return [ServeRequest(
        rng.integers(1, cfg.vocab_size,
                     size=int(rng.integers(4, 12))).astype(np.int32),
        max_new_tokens=(int(rng.integers(max(2, max_new // 4), max_new + 1))
                        if stagger else max_new),
        arrival_time=i / 2.0)
        for i in range(n)]


def _row(name, stats):
    us = 1e6 * stats.wall_s / max(stats.new_tokens, 1)
    return {"name": name, "us_per_call": us,
            # structured fields for the `benchmarks.run --check` regression
            # gate: decode wall per generated token (machine-speed bound,
            # generous tolerance) and dispatch/sync counts (deterministic).
            "decode_ms_per_tok": 1e3 * stats.decode_s
                                 / max(stats.new_tokens, 1),
            "decode_dispatches": stats.decode_dispatches,
            "host_syncs": stats.host_syncs,
            "derived": (f"tok_s={stats.tokens_per_s:.1f} "
                        f"util={stats.slot_utilization:.2f} "
                        f"lat_steps={stats.mean_latency_steps:.1f} "
                        f"prefill_ms={stats.prefill_s * 1e3:.0f} "
                        f"decode_ms={stats.decode_s * 1e3:.0f} "
                        f"disp={stats.prefill_dispatches}"
                        f"+{stats.decode_dispatches} "
                        f"hs={stats.host_syncs} "
                        f"K={stats.decode_horizon} "
                        f"hit={stats.prefix_hit_rate:.2f}")}


def run():
    n, max_new = (8, 8) if FAST else (32, 32)
    rows = []
    for arch in ARCHS:
        cfg = get_config(arch, smoke=True)

        def static_reqs():
            reqs = _requests(cfg, n, max_new)
            for r in reqs:
                r.arrival_time = 0.0
            return reqs

        static = ServeEngine(cfg, max_len=64)
        _, st = _run_warm(static, static_reqs)
        rows.append(_row(f"serve/static/{arch}", st))

        cont = ServeEngine(cfg, max_len=64, n_slots=max(2, n // 2),
                           policy="fcfs")
        _, st = _run_warm(cont, lambda: _requests(cfg, n, max_new))
        rows.append(_row(f"serve/continuous/{arch}", st))

        shard = sharded_engine(cfg, n_slots=max(2, n // 2), max_len=64)
        _, st = _run_warm(shard, lambda: _requests(cfg, n, max_new))
        row = _row(f"serve/sharded-continuous/{arch}", st)
        row["derived"] += f" ndev={jax.device_count()}"
        rows.append(row)
    rows.extend(_paged_admission_rows(n, max_new))
    rows.extend(_prefix_cache_rows(n, max_new))
    rows.extend(_horizon_rows(n, max_new))
    rows.extend(_tenant_rows())
    rows.extend(_obs_rows(n, max_new))
    rows.extend(_profiled_rows(n, max_new))
    rows.extend(_chaos_rows(n))
    rows.extend(_elastic_rows(n))
    return rows


def _chaos_rows(n):
    """Faulted vs fault-free Philly replay at EQUAL pool budget: the same
    open-loop request set (``serve.replay.philly_requests``) through the
    same paged engine, once clean and once under a seeded 3-fault schedule
    (slot kill, prefix flush, pool shrink + restore). The chaos row's
    ``recovery_s`` is the wall-clock the recovery paths cost on top of the
    clean run; its gated ``dropped`` field holds the drop count at the
    recorded baseline (0 — this schedule must stay survivable without
    giving up work) and both rows gate ``slo_attainment`` over the scored
    set as a floor. Outputs stay token-identical to the clean run for
    every non-dropped request (tests/test_chaos.py pins that); the warm
    measured run replays the identical schedule (``FaultInjector.reset``
    re-arms per run)."""
    from repro.serve import FaultInjector, FaultSchedule, philly_requests

    arch = "qwen2-0.5b"
    cfg = get_config(arch, smoke=True)
    max_len, block, n_blocks = 64, 8, 24

    def reqs():
        return philly_requests(cfg.vocab_size, n, load=2.0, seed=7,
                               prompt_len=12, max_new=8, max_len=max_len)

    spec = "slot_kill@2,prefix_flush@4,pool_shrink@6:blocks=6:restore_after=6"
    rows, walls = [], {}
    for label, injector in (
            ("replay-clean", None),
            ("replay-chaos", FaultInjector(FaultSchedule.from_spec(spec)))):
        eng = ServeEngine(cfg, max_len=max_len, n_slots=max(2, n // 2),
                          cache="paged", block_size=block, n_blocks=n_blocks,
                          injector=injector)
        _, st = _run_warm(eng, reqs)
        eng.pool.audit()
        walls[label] = st.wall_s
        row = _row(f"serve/{label}/{arch}", st)
        row["dropped"] = st.dropped
        row["slo_attainment"] = st.slo_attainment
        row["derived"] += (f" faults={st.faults_injected} "
                           f"rec={st.recoveries} drop={st.dropped} "
                           f"att={st.slo_attainment:.2f}")
        if label == "replay-chaos":
            row["derived"] += (f" recovery_s="
                               f"{st.wall_s - walls['replay-clean']:.3f}")
        rows.append(row)
    return rows


def _elastic_rows(n):
    """Elastic recovery value, at EQUAL fault budget: the same Philly
    request set through the same paged engine under the same
    ``device_fail`` (the pool revoked down to its one-block floor, mesh
    narrowed) —
    once with the scheduled ``device_join`` recovery (the pool and
    bucketing restore mid-run, parked requests admit, nothing drops) and
    once with the failure left standing (requests burn their admission
    retries against a pool that will never fit them and drop).
    Gated fields: ``dropped`` (0 with recovery — the hold-don't-drop
    admission contract) and ``slo_attainment`` over the scored set. The
    in-module assertion pins the headline: recovery must strictly beat
    no-recovery on tokens/s, else the reshape machinery is costing more
    than the capacity it returns."""
    from repro.serve import FaultInjector, FaultSchedule, philly_requests

    arch = "qwen2-0.5b"
    cfg = get_config(arch, smoke=True)
    max_len, block, n_blocks = 64, 8, 24

    def reqs():
        return philly_requests(cfg.vocab_size, n, load=1.0, seed=7,
                               prompt_len=12, max_new=12, max_len=max_len)

    fail = "device_fail@2:blocks=23"
    rows, tok_s = [], {}
    for label, spec in (("elastic-recovery", fail + ":restore_after=4"),
                        ("elastic-norecovery", fail)):
        inj = FaultInjector(FaultSchedule.from_spec(spec))
        eng = ServeEngine(cfg, max_len=max_len, n_slots=max(2, n // 2),
                          cache="paged", block_size=block, n_blocks=n_blocks,
                          injector=inj, max_admit_retries=2)
        _, st = _run_warm(eng, reqs)
        eng.pool.audit()
        tok_s[label] = st.tokens_per_s
        row = _row(f"serve/{label}/{arch}", st)
        row["dropped"] = st.dropped
        row["slo_attainment"] = st.slo_attainment
        row["derived"] += (f" ups={st.scale_ups} downs={st.scale_downs} "
                           f"drop={st.dropped} att={st.slo_attainment:.2f}")
        rows.append(row)
        if label == "elastic-recovery":
            assert st.dropped == 0, \
                f"recovery run dropped {st.dropped} requests"
            assert st.scale_ups == 1 and st.scale_downs == 1, st
    assert tok_s["elastic-recovery"] > tok_s["elastic-norecovery"], \
        (f"recovery must beat no-recovery: "
         f"{tok_s['elastic-recovery']:.2f} <= "
         f"{tok_s['elastic-norecovery']:.2f} tok/s")
    return rows


def _obs_rows(n, max_new):
    """Event tracing cost, as a gated row: the staggered paged workload
    with a full ``obs.Tracer`` attached. Its ``decode_ms_per_tok`` bound
    keeps tracing-ON overhead inside the normal tolerance band, while the
    tracing-OFF contract — hooks compiling down to one falsy branch — is
    bounded by every OTHER serve row in this module, which all run with
    the default NullTracer against the same recorded baseline."""
    arch = "qwen2-0.5b"
    cfg = get_config(arch, smoke=True)
    from repro.obs import Tracer
    eng = ServeEngine(cfg, max_len=64, n_slots=max(2, n // 2), cache="paged",
                      block_size=8, tracer=Tracer())
    _, st = _run_warm(eng, lambda: _requests(cfg, n, max_new, stagger=True))
    row = _row(f"serve/obs-traced/{arch}", st)
    row["derived"] += (f" events={len(eng.tracer)} "
                       f"qd={st.mean_queue_depth:.1f} "
                       f"occ={st.mean_occupancy:.2f}")
    return [row]


def _profiled_rows(n, max_new):
    """Dispatch-profiling cost, as a gated row: the same staggered paged
    workload as ``_obs_rows`` with a tracer AND an ``obs.DispatchProfiler``
    attached — every hook site pays its profiling branch, the roofline
    arithmetic, and the ``dispatch_profile`` event emit. The row's
    ``decode_ms_per_tok`` bound keeps profiling-ON overhead inside the
    normal ``--check`` tolerance band (profiling-OFF is bounded by every
    other serve row, which all hold the falsy ``NULL_PROFILER``)."""
    arch = "qwen2-0.5b"
    cfg = get_config(arch, smoke=True)
    from repro.obs import DispatchProfiler, Tracer
    prof = DispatchProfiler(cfg)
    eng = ServeEngine(cfg, max_len=64, n_slots=max(2, n // 2), cache="paged",
                      block_size=8, tracer=Tracer(), profiler=prof)
    _, st = _run_warm(eng, lambda: _requests(cfg, n, max_new, stagger=True))
    row = _row(f"serve/obs-profiled/{arch}", st)
    s = prof.summary()
    dec = s["phases"].get("decode", {})
    row["derived"] += (f" sigs={s['signatures']} "
                       f"prof_disp={s['dispatches']} "
                       f"compiles={dec.get('compiles', 0)} "
                       f"util={_util(st.decode_util)}")
    return [row]


def _util(u) -> str:
    """A utilization for the derived column; None (no peaks for this
    device) reads "not measured"."""
    return "not measured" if u is None else f"{u:.2e}"


def _tenant_rows():
    """Two-tenant SLO scenario at EQUAL pool/lane budget: a batch tenant
    floods the block pool at step 0 (long prompts, long budgets, no SLO)
    while a latency tenant trickles short requests in under a tight
    step-clock SLO. The ``tenant-prop`` row is the capacity-proportional
    baseline — FCFS admission, no budgets, the SLOs only SCORED — and the
    ``tenant-slo`` row turns on the Synergy-on-serve mechanisms: SLO-slack
    admission ordering plus the optimistic profiler's planned per-tenant
    block/lane/horizon budgets. The latency tenant's p99 latency (decode
    steps — deterministic, so gate-able across machines) and SLO
    attainment are the rows' structured fields; the gate holds attainment
    as a floor and p99 as a ceiling. Outputs stay token-identical either
    way (tests/test_tenant.py pins that); only WHEN each request runs
    moves."""
    arch = "qwen2-0.5b"
    cfg = get_config(arch, smoke=True)
    max_len, block = 64, 8
    n_blocks, n_slots, lanes, k = 12, 6, 2, 8
    registry = TenantRegistry([
        Tenant("lat", weight=2.0, slo_steps=12.0),
        Tenant("batch", weight=1.0)])

    def reqs():
        rng = np.random.default_rng(7)
        out = [ServeRequest(
            rng.integers(1, cfg.vocab_size, size=16).astype(np.int32),
            max_new_tokens=16, arrival_time=0.0, tenant="batch")
            for _ in range(4)]
        out += [ServeRequest(
            rng.integers(1, cfg.vocab_size, size=4).astype(np.int32),
            max_new_tokens=4, arrival_time=2.0 + 4.0 * i, tenant="lat")
            for i in range(4)]
        return out

    def units_for(r):
        return -(-(len(r.prompt) + r.max_new_tokens) // block)

    profiles = profiles_from_requests(registry, reqs(), total_units=n_blocks,
                                      units_for=units_for, max_k=k)
    allocation = plan_allocation(registry, profiles, n_blocks,
                                 total_lanes=lanes, max_k=k,
                                 watermark_units=1)

    rows = []
    for label, policy, alloc in (("tenant-prop", "fcfs", None),
                                 ("tenant-slo", "slo", allocation)):
        eng = ServeEngine(cfg, max_len=max_len, n_slots=n_slots,
                          cache="paged", block_size=block, n_blocks=n_blocks,
                          watermark=1.0 / n_blocks, prefill_lanes=lanes,
                          decode_horizon=k, policy=policy,
                          tenants=registry, allocation=alloc)
        _, st = _run_warm(eng, reqs)
        lat, bat = st.tenants["lat"], st.tenants["batch"]
        row = _row(f"serve/{label}/{arch}", st)
        row["slo_attainment"] = lat["slo_attainment"]
        row["p99_latency_steps"] = lat["p99_latency_steps"]
        row["derived"] += (f" lat_p99={lat['p99_latency_steps']:.1f} "
                           f"lat_slo={lat['slo_attainment']:.2f} "
                           f"batch_p99={bat['p99_latency_steps']:.1f} "
                           f"pre={st.preemptions}")
        rows.append(row)
    return rows


def _horizon_rows(n, max_new):
    """Decode-horizon ablation: the same continuous workload at K=1 (the
    classic per-token loop) vs K=8 (device-resident multi-step decode) on
    both cache backends — decode dispatches and host syncs should drop
    ~K-fold at identical outputs."""
    arch = "qwen2-0.5b"
    cfg = get_config(arch, smoke=True)
    rows = []
    for label, kw in (("contig", dict()),
                      ("paged", dict(cache="paged", block_size=8))):
        for k in (1, 8):
            eng = ServeEngine(cfg, max_len=64, n_slots=max(2, n // 2),
                              decode_horizon=k, **kw)
            _, st = _run_warm(
                eng, lambda: _requests(cfg, n, max_new, stagger=True))
            rows.append(_row(f"serve/horizon-K{k}-{label}/{arch}", st))
    return rows


def _paged_admission_rows(n, max_new):
    """Paged vs contiguous admission at mixed prompt lengths AND mixed
    generation budgets on EQUAL token budgets: the contiguous pool spends
    the budget as few max_len rows, the paged pool as length-proportional
    blocks — so paged admits the same request set wider (max_active) and
    finishes in fewer decode steps — and the staggered completions force
    mid-run evictions so both backends' live-slot compaction
    (``rows_saved``) does real work."""
    arch = "qwen2-0.5b"
    cfg = get_config(arch, smoke=True)
    max_len, block = 64, 8
    budget = (n // 2) * max_len                  # cache positions
    # double the generation budgets: completions must span multiple K=8
    # horizons (the bucket only shrinks at a horizon boundary), so the
    # rows_saved stat keeps exercising live-slot compaction.
    reqs = _requests(cfg, n, 2 * max_new, stagger=True)   # fresh copies
                                                 # below arrive at step 0
    def copies():
        return [ServeRequest(r.prompt.copy(),
                             max_new_tokens=r.max_new_tokens)
                for r in reqs]

    cont = ServeEngine(cfg, max_len=max_len, n_slots=budget // max_len)
    _, st = _run_warm(cont, copies)
    rows = []
    row = _row(f"serve/admission-contiguous/{arch}", st)
    row["derived"] += (f" max_active={st.max_active} steps={st.steps} "
                       f"rows_saved={st.decode_rows_saved:.2f}")
    rows.append(row)

    paged = ServeEngine(cfg, max_len=max_len, n_slots=n, cache="paged",
                        block_size=block, n_blocks=budget // block,
                        watermark=0.0)
    _, st = _run_warm(paged, copies)
    row = _row(f"serve/admission-paged/{arch}", st)
    row["derived"] += (f" max_active={st.max_active} steps={st.steps} "
                       f"rows_saved={st.decode_rows_saved:.2f} "
                       f"occ={st.block_report['occupancy']:.2f} "
                       f"frag={st.block_report['internal_fragmentation']:.2f}")
    rows.append(row)
    return rows


def _prefix_cache_rows(n, max_new):
    """Shared-prefix workload (system-prompt style): every prompt repeats
    the same 3-block prefix ahead of a unique tail. With the prefix cache
    on, every request after the first serves the shared blocks from cache
    (skipping their prefill compute); the cache-off row is the ablation."""
    arch = "qwen2-0.5b"
    cfg = get_config(arch, smoke=True)
    max_len, block = 64, 8
    rng = np.random.default_rng(3)
    prefix = rng.integers(1, cfg.vocab_size, size=3 * block).astype(np.int32)

    def reqs():
        r = np.random.default_rng(4)
        return [ServeRequest(
            np.concatenate([prefix, r.integers(1, cfg.vocab_size,
                                               size=4).astype(np.int32)]),
            max_new_tokens=max_new, arrival_time=i / 2.0)
            for i in range(n)]

    rows = []
    for label, cached in (("prefix-paged", True),
                          ("prefix-paged-nocache", False)):
        eng = ServeEngine(cfg, max_len=max_len, n_slots=n, cache="paged",
                          block_size=block, prefix_cache=cached)
        _, st = _run_warm(eng, reqs)
        rows.append(_row(f"serve/{label}/{arch}", st))
    return rows
