"""Reductions of what the serve engine's own instrumentation leaves in a run:
its ``serve.*`` spans, its request stamps, and the names of its programs.

* ``reduce(planes)``: over the same window as ``trace_reduce`` (the span of
  the ``wave <i>`` annotations), ``module_s`` is device time by XLA module
  name, ``jit_`` prefix and ``(<id>)`` suffix stripped, averaged over the
  chips; ``idle_by_span`` is the first chip's idle time by the innermost
  host event whose name starts with ``serve.`` covering the gap's
  midpoint, the ``serve.`` dropped, ``none`` where no such event does.
* ``span_totals(stats)``: ``ServeStats.spans`` summed over waves.
* ``request_waits(requests, t0)``: each completed request's queue wait
  (first admission minus the wave's start ``t0``) and prefill wait (first
  token minus first admission).

A program without spans, stamps or named programs gives empty totals, no
waits and ``idle_by_span == {"none": <all idle>}``, and nothing raises.
"""
import collections
import re

import trace_reduce

SPAN = "serve."
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def module_name(name):
    """'jit_serve_decode_horizon(42)' -> 'serve_decode_horizon'."""
    return _MODULE.match(name).group(1)


def _line(plane, name):
    return next((ln for ln in plane.lines if ln.name == name), None)


def reduce(planes):
    """``{"module_s": {...}, "idle_by_span": {...}}`` in seconds, or None
    when the trace holds no device operation."""
    planes = list(planes)
    waves, spans = [], []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            evs = [(ev.start_ns, ev.end_ns, ev.name) for ev in ln.events]
            found = [(s, e) for s, e, n in evs if trace_reduce.WAVE.match(n)]
            if found:
                waves = found
                spans = sorted(x for x in evs if x[2].startswith(SPAN))
                break
        if waves:
            break
    devs = trace_reduce.device_planes(planes)
    ops = [[(ev.start_ns, ev.end_ns) for ev in ln.events]
           for ln in (_line(p, "XLA Ops") for p in devs) if ln is not None]
    if not ops or not any(ops):
        return None
    if waves:
        lo, hi = min(s for s, _ in waves), max(e for _, e in waves)
    else:
        lo = min(s for evs in ops for s, _ in evs)
        hi = max(e for evs in ops for _, e in evs)
    module_ns = collections.Counter()
    for p in devs:
        ln = _line(p, "XLA Modules")
        for ev in (ln.events if ln is not None else ()):
            if ev.end_ns > lo and ev.start_ns < hi:
                module_ns[module_name(ev.name)] += (min(ev.end_ns, hi)
                                                    - max(ev.start_ns, lo))
    busy = trace_reduce._clip(trace_reduce._union(ops[0]), lo, hi)
    idle = collections.Counter()
    for (gs, ge), label in zip(*trace_reduce._label_gaps(busy, lo, hi,
                                                         spans)):
        idle[label[len(SPAN):] if label.startswith(SPAN) else "none"] += \
            ge - gs
    n = len(ops)
    return {"module_s": {k: v / n / 1e9 for k, v in module_ns.items()},
            "idle_by_span": {k: v / 1e9 for k, v in idle.items()}}


def reduce_file(path):
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path).planes)


def span_totals(stats):
    """Summed ``{name: {"s", "n"}}`` over ``ServeStats`` objects; empty for
    a program whose stats carry no spans."""
    out = {}
    for st in stats:
        for name, v in (getattr(st, "spans", None) or {}).items():
            acc = out.setdefault(name, {"s": 0.0, "n": 0})
            acc["s"] += v["s"]
            acc["n"] += v["n"]
    return out


def request_waits(requests, t0):
    """(queue waits, prefill waits) in seconds over the completed requests
    that carry both stamps; two empty lists for a program without
    ``t_first_token``."""
    queue, prefill = [], []
    for r in requests:
        ta = getattr(r, "t_admitted", None)
        tf = getattr(r, "t_first_token", None)
        if r.t_finished is None or ta is None or tf is None:
            continue
        queue.append(ta - t0)
        prefill.append(tf - ta)
    return queue, prefill
