"""Scheduler: share of the window's wall time outside the engine's prefill
and decode dispatches (each timed by the engine on the host clock around a
dispatch that ends in a host fetch)."""


def read(rec):
    st = rec["stats"]
    return 100.0 * (1.0 - (st["prefill_s"] + st["decode_s"]) / rec["window_s"])
