"""Metrics registry: counters, gauges, histograms, and boundary-sampled
time series.

The always-on half of the observability layer: ``ServeStats`` is built
from a per-run ``MetricsRegistry`` (counters for steps/dispatches/syncs,
gauges sampled into time series at horizon boundaries, histograms for
latency distributions), so queue-depth and occupancy summaries exist even
with event tracing off. The registry is plain Python over plain floats —
no jax, no locks (the engine loop is single-threaded) — so the hot-path
cost of a counter bump is one dict-free attribute add.

``Histogram.percentile`` implements the same linear-interpolation rule as
``numpy.percentile``'s default, pinned by ``tests/test_obs.py`` against
numpy itself.

``RunObs.span`` is the engine's one span hook: a profiler annotation on
the device trace's clock, a ``span_s[<name>]`` / ``span_n[<name>]`` counter
pair in the run's registry, and (for the dispatch sites) the ``Tracer``
span event, all three from one interval.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List, Optional, Tuple


class Counter:
    """Monotonic accumulator (float: wall-second totals share the type)."""
    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        self.value += n


class Gauge:
    """Last-value (or high-watermark, via ``hi``) instantaneous metric."""
    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)

    def hi(self, v: float) -> None:
        """High-watermark update: keep the max ever seen."""
        if v > self.value:
            self.value = float(v)


class Histogram:
    """Value distribution with exact percentiles.

    Stores raw observations (bounded by ``max_samples`` with uniform
    stride-decimation on overflow: every second sample is dropped and the
    stride doubles, so the kept set stays an unbiased subsample of the
    stream) — serve runs observe at most a few values per request, so the
    exact path is the common one.
    """
    __slots__ = ("name", "values", "count", "total", "vmin", "vmax",
                 "max_samples", "_stride", "_skip")

    def __init__(self, name: str, max_samples: int = 65536):
        self.name = name
        self.values: List[float] = []
        self.count = 0
        self.total = 0.0
        self.vmin = math.inf
        self.vmax = -math.inf
        self.max_samples = int(max_samples)
        self._stride = 1
        self._skip = 0

    def record(self, v: float) -> None:
        v = float(v)
        self.count += 1
        self.total += v
        if v < self.vmin:
            self.vmin = v
        if v > self.vmax:
            self.vmax = v
        if self._skip:
            self._skip -= 1
            return
        self._skip = self._stride - 1
        if len(self.values) >= self.max_samples:
            self.values = self.values[::2]
            self._stride *= 2
            self._skip = self._stride - 1
        self.values.append(v)

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def percentile(self, q: float) -> float:
        """Linear-interpolated percentile (numpy.percentile's default
        method) over the retained samples; 0.0 when empty."""
        if not self.values:
            return 0.0
        if not 0.0 <= q <= 100.0:
            raise ValueError(f"percentile q={q} outside [0, 100]")
        xs = sorted(self.values)
        pos = (len(xs) - 1) * q / 100.0
        lo = math.floor(pos)
        hi = math.ceil(pos)
        if lo == hi:
            return xs[int(pos)]
        return xs[lo] * (hi - pos) + xs[hi] * (pos - lo)

    def summary(self) -> dict:
        return {"count": self.count, "mean": self.mean,
                "min": self.vmin if self.count else 0.0,
                "max": self.vmax if self.count else 0.0,
                "p50": self.percentile(50), "p95": self.percentile(95),
                "p99": self.percentile(99)}


class MetricsRegistry:
    """Named counters/gauges/histograms plus boundary-sampled series.

    ``sample(step)`` snapshots every gauge AND counter into its time
    series (``series[name]`` is a list of ``(step, value)``), which is
    what turns instantaneous pool state into the occupancy / queue-depth
    timelines the stats summarize and ``trace_report`` plots.
    """

    def __init__(self):
        self.counters: Dict[str, Counter] = {}
        self.gauges: Dict[str, Gauge] = {}
        self.histograms: Dict[str, Histogram] = {}
        self.series: Dict[str, List[Tuple[float, float]]] = {}

    # -- get-or-create handles ------------------------------------------------
    def counter(self, name: str) -> Counter:
        c = self.counters.get(name)
        if c is None:
            c = self.counters[name] = Counter(name)
        return c

    def gauge(self, name: str) -> Gauge:
        g = self.gauges.get(name)
        if g is None:
            g = self.gauges[name] = Gauge(name)
        return g

    def histogram(self, name: str) -> Histogram:
        h = self.histograms.get(name)
        if h is None:
            h = self.histograms[name] = Histogram(name)
        return h

    # -- convenience mutators -------------------------------------------------
    def inc(self, name: str, n: float = 1.0) -> None:
        self.counter(name).inc(n)

    def set(self, name: str, v: float) -> None:
        self.gauge(name).set(v)

    def hi(self, name: str, v: float) -> None:
        self.gauge(name).hi(v)

    def observe(self, name: str, v: float) -> None:
        self.histogram(name).record(v)

    def value(self, name: str, default: float = 0.0) -> float:
        """Current value of a counter or gauge (counters win a name tie)."""
        if name in self.counters:
            return self.counters[name].value
        if name in self.gauges:
            return self.gauges[name].value
        return default

    # -- time series ----------------------------------------------------------
    def sample(self, step: float) -> None:
        """Snapshot every gauge and counter into its series at ``step``."""
        for name, g in self.gauges.items():
            self.series.setdefault(name, []).append((float(step), g.value))
        for name, c in self.counters.items():
            self.series.setdefault(name, []).append((float(step), c.value))

    def series_stats(self, name: str) -> Tuple[float, float]:
        """(mean, max) over a sampled series; falls back to the live
        gauge/counter value when the series is empty (a run too short to
        hit a sampling boundary still reports its last state)."""
        pts = self.series.get(name)
        if not pts:
            v = self.value(name)
            return v, v
        vals = [v for _, v in pts]
        return sum(vals) / len(vals), max(vals)

    def summary(self) -> dict:
        """One JSON-able dict of everything: counter/gauge values,
        histogram summaries, and series lengths."""
        return {
            "counters": {k: c.value for k, c in self.counters.items()},
            "gauges": {k: g.value for k, g in self.gauges.items()},
            "histograms": {k: h.summary()
                           for k, h in self.histograms.items()},
            "series": {k: len(v) for k, v in self.series.items()},
        }


class Span:
    """One open ``RunObs.span``. ``set`` adds arguments known only inside
    the span (they label the annotation and join the event payload);
    ``dur_s`` holds the span's seconds once it has closed."""
    __slots__ = ("_obs", "_name", "_event", "_args", "_ann", "_t0", "dur_s")

    def __init__(self, obs: "RunObs", name: str, event: Optional[str],
                 args: dict):
        self._obs = obs
        self._name = name
        self._event = event
        self._args = args
        # imported here so that the rest of repro.obs (and
        # launch/trace_report.py) runs without JAX
        from jax.profiler import TraceAnnotation
        self._ann = TraceAnnotation(name, **args)
        self.dur_s = 0.0

    def set(self, **args) -> None:
        self._args.update(args)
        self._ann.set_metadata(**args)

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.dur_s = dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        obs = self._obs
        obs.metrics.inc(f"span_s[{self._name}]", dt)
        obs.metrics.inc(f"span_n[{self._name}]")
        if self._event is not None and obs.tracer:
            obs.tracer.emit(self._event, dur_s=dt, **self._args)


class RunObs:
    """Per-run observability context: the metrics registry every run keeps
    (ServeStats is built from it) plus the — possibly null — event tracer.
    The engine threads one of these through its loop where the old plain
    counters dict used to travel."""
    __slots__ = ("metrics", "tracer", "block_report", "boundaries")

    def __init__(self, tracer=None):
        from repro.obs.events import NULL_TRACER
        self.metrics = MetricsRegistry()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.block_report: Optional[dict] = None
        self.boundaries = 0     # decode boundaries seen (sampling cadence)

    # counter shorthands (the engine's hot-path spellings)
    def inc(self, name: str, n: float = 1.0) -> None:
        self.metrics.inc(name, n)

    def hi(self, name: str, v: float) -> None:
        self.metrics.hi(name, v)

    def value(self, name: str, default: float = 0.0) -> float:
        return self.metrics.value(name, default)

    def span(self, name: str, event: Optional[str] = None, **args) -> Span:
        """Context manager timing one piece of engine work.

        It opens ``jax.profiler.TraceAnnotation(name, **args)``, so a
        ``jax.profiler`` trace shows the span on the host thread next to the
        device's operations, and adds its ``perf_counter`` seconds and a
        count to the registry as ``span_s[<name>]`` and ``span_n[<name>]``.
        With ``event`` (a ``Tracer`` span type) and a live tracer it emits
        that event on exit, ``args`` as its payload and ``dur_s`` from the
        same interval. Off (no profiler session, no tracer) it costs one
        annotation whose enter and exit do nothing, and two counter adds."""
        return Span(self, name, event, args)

    def spans(self) -> Dict[str, dict]:
        """Every closed span's totals: name -> {"s": seconds, "n": count}."""
        out = {}
        for key, c in self.metrics.counters.items():
            if key.startswith("span_s["):
                name = key[7:-1]
                out[name] = {"s": c.value,
                             "n": int(self.metrics.value(f"span_n[{name}]"))}
        return out
