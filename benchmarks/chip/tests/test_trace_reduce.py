"""trace_reduce on a hand-made trace (interval arithmetic checked by hand)
and on a small trace recorded on the CPU (its planes and names)."""
import glob
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import pytest

import trace_reduce


def ev(name, start, end, **stats):
    return NS(name=name, start_ns=start, end_ns=end,
              duration_ns=end - start, stats=list(stats.items()))


def plane(name, lines):
    return NS(name=name, lines=[NS(name=n, events=e) for n, e in lines])


def _trace():
    host = plane("/host:CPU", [("python", [
        ev("wave 0", 100, 1100),
        ev("PjitFunction(chunk_fn)", 150, 300),
        ev("PjitFunction(horizon)", 560, 640),
        ev("wave 1", 1200, 1700),
    ])])
    dev = plane("/device:TPU:0", [
        ("XLA Modules", [ev("jit_chunk_fn", 200, 500)]),
        ("XLA Ops", [
            ev("fusion.1", 50, 150),                    # before the window
            ev("%paged_prefill_attention.3 = bf16[4,2,16,7,64] custom-call()",
               200, 400),
            ev("fusion.2", 350, 500),                   # overlaps the first
            ev("%paged_attention.7 = bf16[8,2,7,64] custom-call()", 700, 900),
            ev("fusion.2", 1300, 1400),
            ev("%while.4 = (s32[]) while(...)", 650, 950),
        ]),
    ])
    return [host, dev]


def test_busy_window_and_kernels():
    r = trace_reduce.reduce(_trace())
    # window: wave 0 start (100) to wave 1 end (1700)
    assert r["window_s"] == pytest.approx(1600e-9)
    # union in the window: [100,150) + [200,500) + [650,950) + [1300,1400)
    assert r["busy_s"] == pytest.approx((50 + 300 + 300 + 100) * 1e-9)
    assert r["kernel_s"] == {"paged_prefill": pytest.approx(200e-9),
                             "paged_decode": pytest.approx(200e-9)}
    ops = dict(r["breakdown"]["device_ops"])
    # ops are grouped by kind: fusion.1 (50 in the window) + fusion.2 (250)
    assert ops["fusion"] == pytest.approx(300e-9)
    assert ops["paged_attention"] == pytest.approx(200e-9)
    assert "while" not in ops            # a loop's interval holds its body


def test_idle_gaps_are_labelled_by_the_host():
    r = trace_reduce.reduce(_trace())
    gaps = dict(r["breakdown"]["idle_gaps"])
    # [150,200): inside chunk_fn's dispatch; [500,650): mid 575 inside
    # horizon's; [950,1300): mid 1125 in no event; [1400,1700): wave 1
    assert gaps["PjitFunction(chunk_fn)"] == pytest.approx(50e-9)
    assert gaps["PjitFunction(horizon)"] == pytest.approx(150e-9)
    assert gaps["outside the waves"] == pytest.approx(350e-9)
    assert gaps["host between JAX calls"] == pytest.approx(300e-9)
    assert sum(gaps.values()) == pytest.approx(1600e-9 - r["busy_s"])


def test_no_device_plane_gives_nothing():
    assert trace_reduce.reduce(_trace()[:1]) is None


def test_recorded_cpu_trace(tmp_path):
    """A trace the profiler really wrote: the harness's wave annotation and
    JAX's dispatch events are on one host thread, and a CPU trace holds no
    TPU plane, so the reduction reads nothing."""
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("wave 0"):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    names = {ev.name for p in planes if p.name.startswith("/host:")
             for ln in p.lines for ev in ln.events}
    assert "wave 0" in names
    assert any(n.startswith("PjitFunction(") for n in names)
    assert trace_reduce.reduce(planes) is None
