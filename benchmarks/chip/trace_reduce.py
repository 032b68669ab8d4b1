"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, the traced window, kernel device time by
stable name, and a breakdown of the top device operations and of the idle
gaps labelled by what the host was doing.

The window is the span of the harness's ``wave <i>`` annotations on the
host. Device time is the union of the operation intervals on each TPU's
"XLA Ops" line, averaged over the chips. A gap is labelled by the shortest
host event that covers its midpoint on the thread that holds the wave
annotations (JAX's own dispatch events, or the harness's wave span when the
host was inside none).
"""
import collections
import re

#: kernel name -> the HLO op kind its device ops carry (the op is named
#: after the jitted wrapper in kernels/ops.py: '%paged_attention.12 = ...')
KERNELS = {
    "paged_decode": "paged_attention",
    "paged_prefill": "paged_prefill_attention",
    "ssd_scan": "ssd_scan",
}
WAVE = re.compile(r"^wave \d+$")
#: ops whose interval holds other ops of the same line (a scan's loop)
CONTAINERS = ("while", "conditional", "call")
TOP = 10


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(merged, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in merged
            if e > lo and s < hi]


def device_planes(planes):
    return [p for p in planes if re.fullmatch(r"/device:TPU:\d+", p.name)]


def _ops_line(plane):
    for line in plane.lines:
        if line.name == "XLA Ops":
            return line
    return None


def op_kind(name):
    """'%copy.52 = bf16[...] copy(...)' -> 'copy': the HLO op's name
    without its text and its numeric suffix, stable across programs."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _label_gaps(busy, lo, hi, host):
    """The idle gaps of one device in [lo, hi), and for each the innermost
    host event covering its midpoint (host events on one thread nest, so a
    stack swept in start order holds the covering ones)."""
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    labels, stack, i = [], [], 0
    for gs, ge in gaps:
        mid = (gs + ge) / 2
        while i < len(host) and host[i][0] <= mid:
            while stack and stack[-1][1] < host[i][0]:
                stack.pop()
            stack.append(host[i])
            i += 1
        while stack and stack[-1][1] < mid:
            stack.pop()
        if not stack:
            labels.append("outside the waves")
        elif WAVE.match(stack[-1][2]):
            labels.append("host between JAX calls")
        else:
            labels.append(stack[-1][2])
    return gaps, labels


def reduce(planes, kernels=KERNELS):
    """``planes``: the trace's planes (``ProfileData.planes``). Returns a
    dict with ``busy_s``, ``window_s``, ``kernel_s`` and ``breakdown``, or
    None when the trace holds no device operation."""
    planes = list(planes)
    host_lines = [ln for p in planes if p.name.startswith("/host:")
                  for ln in p.lines]
    waves, host = [], []
    for ln in host_lines:
        evs = [(ev.start_ns, ev.end_ns, ev.name) for ev in ln.events]
        spans = [(s, e) for s, e, name in evs if WAVE.match(name)]
        if spans:
            waves, host = spans, sorted(evs)
            break
    devs = [_ops_line(p) for p in device_planes(planes)]
    # each event is read from the trace once: a traced window holds
    # millions of device ops, and every attribute read builds an object
    devs = [[(ev.start_ns, ev.end_ns, ev.name) for ev in line.events]
            for line in devs if line is not None]
    if not devs or not any(devs):
        return None
    if waves:
        lo, hi = min(s for s, _ in waves), max(e for _, e in waves)
    else:
        lo = min(s for evs in devs for s, _, _ in evs)
        hi = max(e for evs in devs for _, e, _ in evs)
    kind_to_kernel = {kind: k for k, kind in kernels.items()}
    kinds = {}
    kernel_ns, op_ns = collections.Counter(), collections.Counter()
    per_dev = []
    for evs in devs:
        per_dev.append(_union([(s, e) for s, e, _ in evs]))
        for start, end, name in evs:
            if end <= lo or start >= hi:
                continue
            dur = min(end, hi) - max(start, lo)
            kind = kinds.get(name)
            if kind is None:
                kind = kinds[name] = op_kind(name)
            if kind not in CONTAINERS:
                op_ns[kind] += dur
            if kind in kind_to_kernel:
                kernel_ns[kind_to_kernel[kind]] += dur
    n = len(devs)
    busy = [_clip(m, lo, hi) for m in per_dev]
    busy_ns = sum(e - s for m in busy for s, e in m) / n
    gaps = collections.Counter()
    for (gs, ge), label in zip(*_label_gaps(busy[0], lo, hi, host)):
        gaps[label] += ge - gs
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (hi - lo) / 1e9,
        "kernel_s": {k: v / 1e9 for k, v in kernel_ns.items()},
        "breakdown": {
            "device_ops": [[k, v / 1e9] for k, v in op_ns.most_common(TOP)],
            "idle_gaps": [[k, v / 1e9] for k, v in gaps.most_common(TOP)],
        },
    }


def reduce_file(path, kernels=KERNELS):
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path).planes, kernels)
