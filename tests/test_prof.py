"""Dispatch-profiler tests: NULL contract, compile attribution, roofline
terms and gauges, profiling-on token identity, ProfileStore persistence +
rate fits, the measured-calibrate path in serve/tenant.py, and the
downstream renderers (trace_report phase costs, Chrome counter track,
roofline table's None-safe formatting)."""
import json

import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import CHIP_PEAKS
from repro.launch.roofline import fmt_row
from repro.launch.trace_report import build_report, phase_costs
from repro.obs import (NULL_PROFILER, DispatchProfiler, NullDispatchProfiler,
                       ProfileStore, RunObs, Tracer, to_chrome_trace,
                       validate_events)
from repro.serve import ServeEngine, ServeRequest
from repro.serve.tenant import profile_class


#: explicit peaks for the utilization tests: off a chip the profiler finds
#: none, and reports no utilization
V5E = CHIP_PEAKS["TPU v5 lite"]
PEAKS = dict(peak_flops=V5E["flops_bf16"], hbm_bw=V5E["hbm_bw"])


def _requests(cfg, lengths, max_new=4, arrivals=None, tenants=None, seed=11):
    rng = np.random.default_rng(seed)
    arrivals = arrivals or [0.0] * len(lengths)
    tenants = tenants or ["default"] * len(lengths)
    return [ServeRequest(rng.integers(1, cfg.vocab_size, size=s)
                         .astype(np.int32), max_new_tokens=max_new,
                         arrival_time=a, tenant=t)
            for s, a, t in zip(lengths, arrivals, tenants)]


# ---------------------------------------------------------------------------
# NULL contract
# ---------------------------------------------------------------------------
def test_null_profiler_is_falsy_noop():
    assert not NullDispatchProfiler()
    assert not NULL_PROFILER
    NULL_PROFILER.record("decode", 0.1, width=4, k=8)      # no-op, no error
    assert NULL_PROFILER.summary() == {}
    assert NULL_PROFILER.records == [] and NULL_PROFILER.tenant_s == {}


def test_engine_defaults_to_null_profiler():
    cfg = get_config("llama3.2-1b", smoke=True)
    eng = ServeEngine(cfg, max_len=16, n_slots=2)
    assert eng.profiler is NULL_PROFILER
    assert not eng.profiler


# ---------------------------------------------------------------------------
# compile-vs-execute attribution + roofline terms
# ---------------------------------------------------------------------------
def test_compile_attribution_per_signature():
    prof = DispatchProfiler()                  # shape-free: pure attribution
    a = prof.record("decode", 0.5, width=4, k=8, full=False)
    b = prof.record("decode", 0.01, width=4, k=8, full=False)
    c = prof.record("decode", 0.4, width=4, k=8, full=True)   # new signature
    d = prof.record("decode", 0.3, width=2, k=8, full=False)  # new signature
    assert [r["compile"] for r in (a, b, c, d)] == [True, False, True, True]
    assert a["sig"] == "decode/W4/K8/gather" and c["sig"] == "decode/W4/K8/full"
    agg = prof.by_signature()["decode/W4/K8/gather"]
    assert agg["n"] == 2 and agg["compiles"] == 1
    assert agg["compile_s"] == pytest.approx(0.5)
    assert agg["mean_execute_s"] == pytest.approx(0.01)


def test_roofline_terms_nonzero_and_util_gauge():
    cfg = get_config("qwen2-0.5b", smoke=True)
    prof = DispatchProfiler(cfg, **PEAKS)
    flops, hbm = prof.roofline_terms("decode", tokens=32, k=8, kv_pos_sum=100)
    assert flops > 0 and hbm > 0
    # decode re-reads the weights every scan step: k scales the byte term
    _, hbm1 = prof.roofline_terms("decode", tokens=32, k=1, kv_pos_sum=100)
    assert hbm > hbm1
    obs = RunObs()
    prof.record("decode", 0.5, width=4, k=8, obs=obs)          # compile
    rec = prof.record("decode", 0.02, width=4, k=8, obs=obs)   # execute
    assert rec["util"] is not None and rec["util"] > 0
    assert obs.metrics.gauge("util[decode]").value == pytest.approx(rec["util"])
    assert obs.value("compile_s[decode]") == pytest.approx(0.5)
    assert obs.value("execute_s[decode]") == pytest.approx(0.02)


def test_device_without_peaks_reports_no_utilization():
    """Off the peaks table (the CPU here) there is no roofline figure: no
    chip's peaks stand in for the device the run is on."""
    from repro.launch.mesh import chip_peaks
    assert chip_peaks("cpu") is None
    prof = DispatchProfiler(get_config("qwen2-0.5b", smoke=True))
    assert prof.peak_flops is None and prof.hbm_bw is None
    obs = RunObs()
    prof.record("decode", 0.5, width=4, k=8, obs=obs)          # compile
    rec = prof.record("decode", 0.02, width=4, k=8, obs=obs)   # execute
    assert rec["flops"] > 0 and rec["util"] is None
    assert "util[decode]" not in obs.metrics.gauges


def test_tenant_cost_shares_split_by_rows():
    prof = DispatchProfiler()
    prof.record("decode", 0.4, width=4, k=2, tenants={"a": 3, "b": 1})
    prof.record("decode", 0.2, width=2, k=2, tenants={"b": 2})
    s = prof.summary()
    assert s["tenant_seconds"]["a"] == pytest.approx(0.3)
    assert s["tenant_seconds"]["b"] == pytest.approx(0.3)
    assert s["tenant_shares"]["a"] == pytest.approx(0.5)
    assert s["dispatches"] == 2 and s["signatures"] == 2


# ---------------------------------------------------------------------------
# profiling must observe, never perturb
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["llama3.2-1b", "olmoe-1b-7b"])
@pytest.mark.parametrize("cache", ["contiguous", "paged"])
def test_profiled_run_token_identity(arch, cache):
    """Traced + profiled run is token-identical to the bare run, on a dense
    and a moe arch, on both cache backends."""
    cfg = get_config(arch, smoke=True)
    kw = dict(max_len=24, n_slots=2, cache=cache)
    if cache == "paged":
        kw["block_size"] = 4
    mk = lambda: _requests(cfg, [5, 7, 4], max_new=4,  # noqa: E731
                           arrivals=[0.0, 0.0, 2.0])
    bare, s_bare = ServeEngine(cfg, **kw).run(mk())
    prof = DispatchProfiler(cfg)
    tr = Tracer()
    on, s_on = ServeEngine(cfg, tracer=tr, profiler=prof, **kw).run(mk())
    assert [r.output for r in on] == [r.output for r in bare]
    assert s_on.steps == s_bare.steps
    assert s_on.decode_dispatches == s_bare.decode_dispatches
    assert len(prof.records) > 0
    assert validate_events(tr.events) == []
    assert any(e["ev"] == "dispatch_profile" for e in tr.events)


def test_profiled_run_emits_compile_split_and_util():
    """A warm second run on the same engine yields execute records with
    nonzero utilization, surfaced as the decode_util stat."""
    cfg = get_config("qwen2-0.5b", smoke=True)
    prof = DispatchProfiler(cfg, **PEAKS)
    eng = ServeEngine(cfg, max_len=24, n_slots=2, cache="paged",
                      block_size=4, profiler=prof)
    eng.run(_requests(cfg, [5, 7]))
    _, st = eng.run(_requests(cfg, [5, 7]))
    assert any(r["compile"] for r in prof.records)
    assert any(not r["compile"] for r in prof.records)
    utils = [r["util"] for r in prof.records if r["util"] is not None]
    assert utils and all(u > 0 for u in utils)
    assert st.decode_util > 0
    s = prof.summary()
    assert s["phases"]["decode"]["compiles"] >= 1
    assert s["phases"]["decode"]["execute_s"] > 0


# ---------------------------------------------------------------------------
# ProfileStore
# ---------------------------------------------------------------------------
def _synthetic_decode(width, k, mean_s, n=4, arch="a1", backend="paged"):
    return {"source": "serve", "arch": arch, "backend": backend,
            "mesh": None, "phase": "decode", "sig": f"decode/W{width}/K{k}",
            "width": width, "k": k, "tokens": width * k, "n": n,
            "compiles": 1, "compile_s": 0.5, "mean_s": mean_s,
            "flops": 1e9, "hbm_bytes": 1e8, "util": 0.1}


def test_store_roundtrip_and_keyed_merge(tmp_path):
    path = str(tmp_path / "p.jsonl")
    store = ProfileStore()
    store.add(_synthetic_decode(4, 8, 0.020))
    store.add(_synthetic_decode(2, 8, 0.012))
    store.add(_synthetic_decode(4, 8, 0.021))      # same key: supersedes
    assert len(store) == 2
    store.save(path)
    back = ProfileStore.load(path)
    assert len(back) == 2
    rec = {r["sig"]: r for r in back.records}["decode/W4/K8"]
    assert rec["mean_s"] == pytest.approx(0.021)
    # missing file is an empty store, not an error
    assert len(ProfileStore.load(str(tmp_path / "nope.jsonl"))) == 0


def test_rate_fit_recovers_synthetic_constants():
    t_tok, t_fixed = 2.5e-4, 8e-3
    store = ProfileStore()
    for w, k in [(1, 8), (2, 8), (4, 8), (4, 4)]:
        store.add(_synthetic_decode(w, k, t_fixed + w * k * t_tok))
    fit = store.rate_fit("a1", "paged")
    assert fit is not None
    assert fit[0] == pytest.approx(t_tok, rel=1e-6)
    assert fit[1] == pytest.approx(t_fixed, rel=1e-6)
    # single dispatch size: underdetermined -> None
    one = ProfileStore([_synthetic_decode(4, 8, 0.02)])
    assert one.rate_fit("a1", "paged") is None
    # wrong arch / backend filters
    assert store.rate_fit("other") is None
    assert store.rate_fit("a1", "contiguous") is None


def test_add_dryrun_record_conversion():
    store = ProfileStore()
    store.add_dryrun_record({
        "arch": "qwen2-0.5b", "shape": "decode_32k", "mesh": "host",
        "mode": "decode_step", "compute_s": 0.001, "memory_s": 0.004,
        "collective_s": 0.0, "bottleneck": "memory",
        "flops_per_chip": 1.2e12, "bytes_per_chip": 3.4e9,
        "useful_flop_ratio": 0.41})
    (r,) = store.records
    assert r["source"] == "dryrun" and r["phase"] == "decode_step"
    assert r["sig"] == "decode_step/decode_32k"
    assert r["mean_s"] == pytest.approx(0.004)       # max of the bound times
    assert r["bottleneck"] == "memory"
    # dryrun records never satisfy the serve-side rate fit
    assert store.rate_fit("qwen2-0.5b") is None


# ---------------------------------------------------------------------------
# measured-calibrate in serve/tenant.py
# ---------------------------------------------------------------------------
def test_profile_class_measured_source_from_store():
    t_tok, t_fixed = 3e-4, 5e-3
    store = ProfileStore()
    for w, k in [(1, 8), (2, 8), (4, 8)]:
        store.add(_synthetic_decode(w, k, t_fixed + w * k * t_tok))
    p = profile_class("t", units_per_req=2, concurrency=4, total_units=8,
                      store=store, arch="a1", backend="paged")
    assert p.source == "measured"
    assert p.t_tok == pytest.approx(t_tok, rel=1e-6)
    assert p.t_fixed == pytest.approx(t_fixed, rel=1e-6)


def test_profile_class_falls_back_to_analytic():
    # no store
    p = profile_class("t", units_per_req=2, concurrency=4, total_units=8)
    assert p.source == "analytic"
    # store without a usable fit (one dispatch size)
    store = ProfileStore([_synthetic_decode(4, 8, 0.02)])
    q = profile_class("t", units_per_req=2, concurrency=4, total_units=8,
                      store=store, arch="a1", backend="paged")
    assert q.source == "analytic"
    assert q.t_tok == p.t_tok and q.t_fixed == p.t_fixed


def test_probe_wins_over_store():
    store = ProfileStore()
    for w, k in [(1, 8), (4, 8)]:
        store.add(_synthetic_decode(w, k, 5e-3 + w * k * 3e-4))
    p = profile_class("t", units_per_req=2, concurrency=4, total_units=8,
                      probe=lambda k: 100.0 * k / (1 + 0.1 * k),
                      store=store, arch="a1", backend="paged")
    assert p.source == "probed"


# ---------------------------------------------------------------------------
# renderers
# ---------------------------------------------------------------------------
def test_fmt_row_handles_missing_probe_fields():
    """Regression: multipod/host records carry useful_flop_ratio=None and
    no flops_per_chip — fmt_row must render an em dash, not crash."""
    row = fmt_row({"arch": "a", "shape": "s", "mesh": "host",
                   "compute_s": 0.001, "memory_s": 0.002,
                   "collective_s": 0.0, "bottleneck": "memory",
                   "useful_flop_ratio": None, "flops_per_chip": None,
                   "memory_stats": None})
    assert "—" in row and "None" not in row


def test_chrome_renders_dispatch_profile_counters_and_instants():
    tr = Tracer()
    tr.emit("dispatch_profile", phase="decode", sig="decode/W4/K8/gather",
            dur_s=0.5, compile=True, tokens=32, flops=1e9, hbm_bytes=1e8,
            util=None)
    tr.emit("dispatch_profile", phase="decode", sig="decode/W4/K8/gather",
            dur_s=0.02, compile=False, tokens=32, flops=1e9, hbm_bytes=1e8,
            util=0.25)
    assert validate_events(tr.events) == []
    doc = to_chrome_trace(tr.events)
    evs = doc["traceEvents"]
    inst = next(e for e in evs if e["ph"] == "i" and "compile[" in e["name"])
    assert inst["name"] == "compile[decode/W4/K8/gather]"
    ctr = next(e for e in evs if e["ph"] == "C")
    assert ctr["name"] == "util[decode]"
    assert ctr["args"]["util"] == pytest.approx(0.25)
    tracks = {e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "profile" in tracks
    json.dumps(doc)


def test_trace_report_phase_costs(tmp_path):
    cfg = get_config("qwen2-0.5b", smoke=True)
    prof = DispatchProfiler(cfg)
    tr = Tracer()
    ServeEngine(cfg, max_len=24, n_slots=2, cache="paged", block_size=4,
                tracer=tr, profiler=prof).run(_requests(cfg, [5, 7]))
    path = str(tmp_path / "t.jsonl")
    tr.dump_jsonl(path)
    with open(path) as f:
        events = [json.loads(ln) for ln in f]
    rep = build_report(events[1:])
    rows = {r["phase"]: r for r in rep["phase_costs"]}
    assert "decode" in rows and "prefill_round" in rows
    assert rows["decode"]["count"] >= 1
    assert rows["decode"]["total_ms"] > 0
    assert rows["decode"]["compiles"] >= 1


def test_phase_costs_without_profiling():
    """A trace recorded without a profiler still yields the span-derived
    columns; util stays None."""
    events = [{"ev": "decode_horizon", "step": 0.0, "t": 0.0, "k": 4,
               "width": 2, "active": 2, "full": False, "dur_s": 0.01}]
    (row,) = phase_costs(events)
    assert row["phase"] == "decode" and row["count"] == 1
    assert row["util"] is None and row["compiles"] == 0
