"""The one traffic generator: a mix is a JSON file of parameters under
``traffic/``, read by name.

A mix describes offline batch *waves*: ``wave_requests`` requests that are
all due when the wave starts. Prompt and output lengths each follow a
lognormal (``median``, ``sigma``) clipped to [``min``, ``max``] and rounded up
to a ``multiple``. So that every seed does the same work, each wave holds
the same lengths, the distribution's quantiles at (i + 1/2) / n; the seed and
the wave index choose only which prompt length goes with which output length,
the order of the requests, and the token ids (uniform over the vocabulary).
A mix with ``"order": "fixed"`` takes the pairing and the order from the wave
index alone, so that every seed sends the same requests in the same order
and the seed chooses the token ids only: under first-come-first-served
admission the order moves every completion time.
"""
import json
import math
import os
from statistics import NormalDist

import numpy as np

#: the wave index of the warm-up wave, which no window reaches
WARMUP_WAVE = 2 ** 32 - 1


def load(bench_dir, name):
    with open(os.path.join(bench_dir, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    for key in ("wave_requests", "prompt_tokens", "output_tokens"):
        if key not in mix:
            raise ValueError(f"traffic {name!r} has no {key!r}")
    return mix


def lengths(dist, n):
    """The n quantile lengths of one length distribution, ascending."""
    nd = NormalDist()
    out = []
    for i in range(n):
        x = dist["median"] * math.exp(dist["sigma"] * nd.inv_cdf((i + 0.5) / n))
        x = min(max(x, dist["min"]), dist["max"])
        q = dist.get("multiple", 1)
        out.append(int(math.ceil(round(x, 6) / q) * q))
    return out


def wave(mix, seed, index, vocab):
    """One wave: a list of (prompt token ids [int32], output budget)."""
    n = mix["wave_requests"]
    prompts = lengths(mix["prompt_tokens"], n)
    outputs = lengths(mix["output_tokens"], n)
    rng = np.random.default_rng([int(seed), int(index)])
    shape_rng = (np.random.default_rng([int(index)])
                 if mix.get("order") == "fixed" else rng)
    outputs = [outputs[i] for i in shape_rng.permutation(n)]
    order = shape_rng.permutation(n)
    return [(rng.integers(0, vocab, prompts[i], dtype=np.int32), outputs[i])
            for i in order]
