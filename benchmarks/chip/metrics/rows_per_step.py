"""Scheduler: mean live rows per decode step (the engine's slot
utilization times its slots)."""


def read(rec):
    st = rec["stats"]
    if not st["steps"]:
        return None
    return st["slot_utilization"] * rec["engine"]["n_slots"]
