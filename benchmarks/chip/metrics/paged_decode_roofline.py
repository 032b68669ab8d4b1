"""Kernels: the paged_decode kernel's share of its roofline: the least time the
chip needs for the work the served requests require (work.py; the larger
of FLOPs over peak FLOP/s and bytes over peak bytes/s), over the kernel's
summed device time in the trace. None when the trace holds no such
kernel."""


def read(rec):
    trace = rec["trace"]
    if trace is None or not trace["kernel_s"].get("paged_decode"):
        return None
    flops, nbytes = rec["kernel_work"]["paged_decode"]
    peaks = rec["peaks"]
    least = max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / trace["kernel_s"]["paged_decode"]
