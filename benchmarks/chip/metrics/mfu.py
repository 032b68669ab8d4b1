"""Kernels: the model FLOPs of the served requests (work.py: every prompt
position and decode step through every layer, attention over its context,
and the unembeddings that choose tokens) over the chip's peak FLOP/s times
the traced window."""


def read(rec):
    trace = rec["trace"]
    if trace is None or not rec["model_flops"]:
        return None
    return 100.0 * rec["model_flops"] / (rec["peaks"]["flops_per_s"]
                                         * trace["window_s"])
