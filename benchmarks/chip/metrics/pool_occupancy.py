"""Cache: mean share of the paged K/V block pool in use at decode
boundaries. None for a contiguous pool, where it repeats rows_per_step."""


def read(rec):
    if rec["engine"]["cache"] != "paged" or not rec["stats"]["decode_dispatches"]:
        return None
    return 100.0 * rec["stats"]["mean_occupancy"]
