"""Engine spans (``RunObs.span``), request stamps and program names.

The serve engine times its layers with one hook: each span is a
``jax.profiler`` annotation, a ``span_s`` / ``span_n`` counter pair, and for
the dispatch sites the ``Tracer`` span event, all from one interval. These
tests pin the span totals to the stats they feed, the stamps' order, and
the nesting a device trace sees."""
import glob
import time

import numpy as np
import pytest

from repro.configs import get_config
from repro.models.api import build_model
from repro.obs import DispatchProfiler, RunObs, Tracer, validate_events
from repro.serve import ServeEngine, ServeRequest
from repro.serve.cache import CachePool
from repro.serve.scheduler import ContinuousScheduler

#: the spans a run at these sizes opens at least once (the engine opens
#: ``serve.run`` too, but it closes after the stats are built)
LOOP_SPANS = {"serve.step", "serve.admit", "serve.prefill",
              "serve.prefill_round", "serve.upload", "serve.grow",
              "serve.horizon", "serve.fetch", "serve.unpack"}
BACKENDS = {
    "contiguous": dict(max_len=32, n_slots=2),
    "paged": dict(max_len=32, n_slots=2, cache="paged", block_size=4),
}


def _requests(cfg, lengths, max_new=6, seed=11):
    rng = np.random.default_rng(seed)
    return [ServeRequest(rng.integers(1, cfg.vocab_size, size=s)
                         .astype(np.int32), max_new_tokens=max_new)
            for s in lengths]


@pytest.fixture(scope="module")
def cfg():
    return get_config("llama3.2-1b", smoke=True)


@pytest.fixture(scope="module")
def runs(cfg):
    """One queueing run per backend (4 requests into 2 slots), traced."""
    out = {}
    for name, kw in BACKENDS.items():
        tr = Tracer()
        reqs, st = ServeEngine(cfg, tracer=tr, **kw).run(
            _requests(cfg, [5, 9, 7, 6]))
        out[name] = (reqs, st, tr)
    return out


# ---------------------------------------------------------------------------
# the hook
# ---------------------------------------------------------------------------
def test_span_counts_and_times_into_the_registry():
    c = RunObs()
    for _ in range(3):
        with c.span("serve.x", k=2) as span:
            time.sleep(0.001)
    assert span.dur_s >= 0.001
    assert c.value("span_n[serve.x]") == 3
    assert c.value("span_s[serve.x]") >= 0.003
    assert c.spans() == {"serve.x": {"s": c.value("span_s[serve.x]"),
                                     "n": 3}}


def test_span_emits_its_event_with_the_same_interval():
    c = RunObs(Tracer())
    with c.span("serve.prefill_round", "prefill_round", lanes=2) as span:
        span.set(width=4)
    with c.span("serve.fetch"):             # no event: nothing emitted
        pass
    (ev,) = c.tracer.events
    assert ev["ev"] == "prefill_round"
    assert ev["lanes"] == 2 and ev["width"] == 4
    assert ev["dur_s"] == span.dur_s == c.value("span_s[serve.prefill_round]")
    assert validate_events(c.tracer.events) == []


def test_span_without_tracer_emits_nothing():
    c = RunObs()
    with c.span("serve.horizon", "decode_horizon", step=0.0, k=1, width=1,
                active=1, full=True):
        pass
    assert c.value("span_n[serve.horizon]") == 1
    assert not c.tracer


# ---------------------------------------------------------------------------
# engine spans feed the stats they replaced
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_phase_stats_are_the_span_totals(runs, backend):
    _, st, _ = runs[backend]
    assert st.prefill_s > 0 and st.decode_s > 0
    assert st.spans["serve.prefill"]["s"] == st.prefill_s
    assert st.spans["serve.horizon"]["s"] == st.decode_s
    assert st.spans["serve.horizon"]["n"] == st.decode_dispatches
    assert st.spans["serve.fetch"]["n"] == st.decode_dispatches
    assert st.spans["serve.unpack"]["n"] == st.decode_dispatches
    assert LOOP_SPANS <= set(st.spans)
    assert "serve.run" not in st.spans


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_boundary_spans_nest_in_the_step(runs, backend):
    """serve.step holds the whole loop: its time covers prefill and
    horizon, and its count is at least the horizons'."""
    _, st, _ = runs[backend]
    sp = st.spans
    inner = sp["serve.prefill"]["s"] + sp["serve.horizon"]["s"]
    assert sp["serve.step"]["s"] >= inner
    assert sp["serve.step"]["n"] >= sp["serve.horizon"]["n"]
    assert sp["serve.admit"]["n"] == sp["serve.step"]["n"]
    assert sp["serve.fetch"]["s"] <= sp["serve.horizon"]["s"]


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_tracer_span_events_carry_the_span_durations(runs, backend):
    _, st, tr = runs[backend]
    evs = tr.events
    assert validate_events(evs) == []
    hz = [e["dur_s"] for e in evs if e["ev"] == "decode_horizon"]
    assert len(hz) == st.decode_dispatches
    assert sum(hz) == pytest.approx(st.decode_s, rel=1e-12)
    kind = "prefill" if backend == "contiguous" else "prefill_round"
    pre = [e["dur_s"] for e in evs if e["ev"] == kind]
    assert len(pre) == st.spans["serve.prefill_round"]["n"]
    assert sum(pre) == pytest.approx(st.spans["serve.prefill_round"]["s"],
                                     rel=1e-12)


# ---------------------------------------------------------------------------
# request stamps
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_request_stamps_are_ordered(runs, backend):
    reqs, _, _ = runs[backend]
    for r in reqs:
        assert r.t_arrived <= r.t_admitted <= r.t_first_token <= r.t_finished


def test_preempt_keeps_the_first_admission_stamp(cfg):
    pool = CachePool(build_model(cfg), 1, 32)
    sched = ContinuousScheduler(pool)
    r = _requests(cfg, [5])[0]
    sched.submit(r)
    (got,) = sched.admit()
    first = got.t_admitted
    assert first is not None
    r.t_first_token = time.perf_counter()
    sched.preempt(r)
    assert r.t_admitted == first and r.t_first_token is not None
    (again,) = sched.admit()
    assert again is r and r.t_admitted == first


def test_preempted_requests_keep_their_first_stamps(cfg):
    """Under block pressure (two 16-token requests in 6 blocks of 4) a
    preempted request is admitted and prefilled twice; its stamps stay the
    first ones, so its queue and prefill times are not reset."""
    reqs, st = ServeEngine(cfg, max_len=32, n_slots=2, cache="paged",
                           block_size=4, n_blocks=6, watermark=0.0).run(
        _requests(cfg, [8, 8], max_new=8))
    assert st.preemptions >= 1
    bounced = [r for r in reqs if r.n_preempted]
    assert bounced
    for r in reqs:
        assert r.t_admitted <= r.t_first_token <= r.t_finished
    other = [r for r in reqs if not r.n_preempted]
    # both were admitted in the first round, before either finished
    for r in bounced:
        assert r.t_admitted < min(o.t_finished for o in other)


# ---------------------------------------------------------------------------
# the profiler: only dispatches that end in a fetch are costed
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend,phases", [
    ("contiguous", {"prefill", "decode"}), ("paged", {"decode"})])
def test_profiler_records_fetched_dispatches_only(cfg, backend, phases):
    prof = DispatchProfiler(cfg)
    ServeEngine(cfg, profiler=prof, **BACKENDS[backend]).run(
        _requests(cfg, [5, 9, 7, 6]))
    assert {r["phase"] for r in prof.records} == phases


# ---------------------------------------------------------------------------
# names a device trace reads
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend,prefill", [
    ("contiguous", "serve_prefill"), ("paged", "serve_prefill_round")])
def test_hot_programs_have_stable_names(cfg, backend, prefill):
    eng = ServeEngine(cfg, **BACKENDS[backend])
    assert eng._horizon.__name__ == "serve_decode_horizon"
    assert eng._prefill.__name__ == prefill


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_profile_nests_engine_spans_in_the_wave(cfg, backend, tmp_path):
    """A jax.profiler trace of one wave holds every serve.* span on the
    wave annotation's thread, inside it."""
    import jax
    from jax.profiler import ProfileData
    eng = ServeEngine(cfg, **BACKENDS[backend])
    eng.run(_requests(cfg, [5, 9, 7, 6]))           # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("wave 0"):
            eng.run(_requests(cfg, [5, 9, 7, 6]))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    planes = ProfileData.from_file(path).planes
    lines = [ln for p in planes if p.name.startswith("/host:")
             for ln in p.lines
             if any(ev.name == "wave 0" for ev in ln.events)]
    assert len(lines) == 1
    evs = [(ev.name, ev.start_ns, ev.end_ns) for ev in lines[0].events]
    (w0, w1), = [(s, e) for n, s, e in evs if n == "wave 0"]
    spans = [(n, s, e) for n, s, e in evs if n.startswith("serve.")]
    assert {n for n, _, _ in spans} == LOOP_SPANS | {"serve.run"}
    assert all(w0 <= s <= e <= w1 for _, s, e in spans)
