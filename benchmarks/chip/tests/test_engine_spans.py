"""engine_spans on a hand-made trace (checked by hand), on stats and requests
with and without the engine's spans and stamps, and on a wave the profiler
recorded on the CPU."""
import glob
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

import engine_spans
import trace_reduce
from test_trace_reduce import _trace, ev, plane


def _span_trace():
    host = plane("/host:CPU", [("python", [
        ev("wave 0", 100, 1100),
        ev("serve.run", 110, 1090),
        ev("serve.step", 120, 700),
        ev("serve.prefill", 140, 520),
        ev("PjitFunction(serve_prefill_round)", 150, 300),
        ev("serve.horizon", 540, 660),
        ev("PjitFunction(serve_decode_horizon)", 560, 640),
        ev("serve.step", 720, 1080),
        ev("serve.upload", 960, 1000),
        ev("wave 1", 1200, 1700),
    ])])
    dev = plane("/device:TPU:0", [
        ("XLA Modules", [
            ev("jit_serve_prefill_round(17)", 200, 500),
            ev("jit_serve_decode_horizon(3)", 650, 950),
            ev("jit_scatter", 1300, 1400),
        ]),
        ("XLA Ops", [
            ev("%paged_prefill_attention.3 = bf16[4] custom-call()",
               200, 400),
            ev("fusion.2", 350, 500),
            ev("%while.4 = (s32[]) while(...)", 650, 950),
            ev("scatter.1", 1300, 1400),
        ]),
    ])
    return [host, dev]


def test_module_names():
    assert engine_spans.module_name("jit_serve_decode_horizon(42)") == \
        "serve_decode_horizon"
    assert engine_spans.module_name("jit_scatter") == "scatter"
    assert engine_spans.module_name("serve_prefill") == "serve_prefill"


def test_module_time_and_idle_by_span():
    planes = _span_trace()
    r = engine_spans.reduce(planes)
    base = trace_reduce.reduce(planes)
    # modules in the window [100, 1700): 300, 300 and 100 ns
    assert r["module_s"] == {"serve_prefill_round": pytest.approx(300e-9),
                             "serve_decode_horizon": pytest.approx(300e-9),
                             "scatter": pytest.approx(100e-9)}
    assert sum(r["module_s"].values()) == pytest.approx(base["busy_s"])
    # idle gaps: [100,200) mid 150 -> prefill; [500,650) mid 575 ->
    # horizon; [950,1300) mid 1125 -> no serve span; [1400,1700) mid 1550
    # -> none (wave 1 holds no span)
    assert r["idle_by_span"] == {"prefill": pytest.approx(100e-9),
                                 "horizon": pytest.approx(150e-9),
                                 "none": pytest.approx(650e-9)}
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"])


def test_innermost_span_labels_the_gap():
    host = plane("/host:CPU", [("python", [
        ev("wave 0", 0, 1000),
        ev("serve.run", 0, 1000),
        ev("serve.step", 10, 990),
        ev("serve.upload", 400, 600),
        ev("DevicePut", 450, 550),
    ])])
    dev = plane("/device:TPU:0", [("XLA Ops", [ev("copy.1", 0, 300),
                                               ev("copy.2", 700, 1000)])])
    r = engine_spans.reduce([host, dev])
    assert r["idle_by_span"] == {"upload": pytest.approx(400e-9)}
    assert r["module_s"] == {}


def test_existing_reduction_unchanged_by_spans():
    """The spans a program adds move no key of trace_reduce except the
    labels of gaps that fall outside JAX's own events."""
    old, new = trace_reduce.reduce(_trace()), trace_reduce.reduce(
        _span_trace())
    assert set(new) == set(old)
    assert new["window_s"] == old["window_s"]


def test_program_without_spans_reads_none():
    r = engine_spans.reduce(_trace())
    assert set(r["idle_by_span"]) == {"none"}
    assert r["module_s"] == {"chunk_fn": pytest.approx(300e-9)}
    assert engine_spans.reduce(_trace()[:1]) is None
    assert engine_spans.span_totals([NS(prefill_s=1.0)]) == {}
    old_req = NS(t_finished=2.0, t_admitted=1.0)
    assert engine_spans.request_waits([old_req], 0.0) == ([], [])


def test_span_totals_and_waits():
    st = [NS(spans={"serve.step": {"s": 1.0, "n": 2}}),
          NS(spans={"serve.step": {"s": 0.5, "n": 1},
                    "serve.horizon": {"s": 0.25, "n": 1}})]
    assert engine_spans.span_totals(st) == {
        "serve.step": {"s": 1.5, "n": 3},
        "serve.horizon": {"s": 0.25, "n": 1}}
    reqs = [NS(t_admitted=10.5, t_first_token=11.0, t_finished=12.0),
            NS(t_admitted=10.0, t_first_token=10.25, t_finished=11.0),
            NS(t_admitted=None, t_first_token=None, t_finished=None)]
    q, p = engine_spans.request_waits(reqs, 10.0)
    assert q == [0.5, 0.0] and p == [0.5, 0.25]


def test_recorded_cpu_wave(tmp_path):
    """A real profile of one smoke wave: every serve.* span sits on the
    wave's thread, and a CPU trace holds no TPU plane to reduce."""
    from jax.profiler import ProfileData
    from repro.configs import get_config
    from repro.serve import ServeEngine, ServeRequest
    cfg = get_config("qwen2-0.5b", smoke=True)
    eng = ServeEngine(cfg, max_len=64, n_slots=2, cache="paged",
                      block_size=8)
    rng = np.random.default_rng(0)

    def wave():
        return [ServeRequest(rng.integers(1, 100, n).astype(np.int32),
                             max_new_tokens=4) for n in (9, 17, 5)]
    eng.run(wave())
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("wave 0"):
        out, st = eng.run(wave())
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    planes = list(ProfileData.from_file(path).planes)
    assert engine_spans.reduce(planes) is None
    names = {e.name for p in planes if p.name.startswith("/host:")
             for ln in p.lines for e in ln.events}
    assert {n for n in names if n.startswith("serve.")} >= {
        "serve.run", "serve.step", "serve.horizon", "serve.prefill_round"}
    assert engine_spans.span_totals([st])["serve.horizon"]["n"] == \
        st.decode_dispatches
    q, p = engine_spans.request_waits(out, min(r.t_admitted for r in out))
    assert len(q) == len(p) == 3 and min(q) == 0.0
