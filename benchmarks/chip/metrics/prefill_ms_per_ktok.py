"""Model: host-clock milliseconds of prefill dispatch per 1,000 prompt
tokens."""


def read(rec):
    if not rec["prompt_tokens"]:
        return None
    return 1000.0 * rec["stats"]["prefill_s"] / (rec["prompt_tokens"] / 1000.0)
