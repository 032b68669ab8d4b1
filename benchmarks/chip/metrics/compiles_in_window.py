"""Device: programs built inside the measured window (backend compiles and
loads from the persistent cache, from JAX's monitoring events); should be
0."""


def read(rec):
    return rec["compiles_in_window"]
