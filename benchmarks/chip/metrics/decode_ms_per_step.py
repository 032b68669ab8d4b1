"""Model: host-clock milliseconds of decode dispatch per decode step."""


def read(rec):
    st = rec["stats"]
    if not st["steps"]:
        return None
    return 1000.0 * st["decode_s"] / st["steps"]
