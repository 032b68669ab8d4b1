"""How ``correct`` is decided for a served model.

Once the window has closed and the engine is freed, a sample of the
requests the window finished, drawn from the seed and holding the longest
one, is run through the plain float32 reference over its prompt and its
served tokens. At each served token the reference's logits give the gap by
which the served token lies below the reference's best token (0 where the
two agree). Two numbers are compared with the limits of the configuration
file's ``check``:

* ``max_gap``: the widest gap over the sampled tokens;
* ``mismatch_share``: the share of sampled tokens that are not the
  reference's best.

A configuration compares the numbers its ``check`` gives a limit for.

Greedy decoding in the configuration's precision only departs from the
reference at near-ties, by little; a lower precision departs more often and
by more. The control (``control_numbers``) reads the same two numbers for
the token that the reference computed in the configuration's ``control``
precision (int8 or fp8, both below bfloat16) puts first at each position.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import family_module
from reference.common import make_params, seed_key

#: served tokens the sample reaches at least
SAMPLE_TOKENS = 512
#: sequences are padded to a multiple of this (one program per multiple)
SEQ_PAD = 512
#: scored positions are padded to a multiple of this
POS_PAD = 128

NUMBERS = ("max_gap", "mismatch_share")


def sample(finished, seed, target=SAMPLE_TOKENS):
    """Indices into ``finished`` [(prompt, output)]: the longest request,
    then others in an order drawn from the seed, until the sample holds
    ``target`` served tokens."""
    if not finished:
        return []
    sizes = [len(p) + len(o) for p, o in finished]
    first = int(np.argmax(sizes))
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    picked, tokens = [first], len(finished[first][1])
    for i in rng.permutation(len(finished)):
        if tokens >= target:
            break
        if int(i) != first:
            picked.append(int(i))
            tokens += len(finished[int(i)][1])
    return picked


class Reference:
    """The configuration's reference with its own weights, made again from
    the seed."""

    def __init__(self, config, seed):
        self.mod = family_module(config["family"])
        self.model = config["model"]
        dtype = jnp.dtype(config["program"]["param_dtype"])
        self.params = make_params(self.mod.param_spec(self.model),
                                  seed_key(seed), dtype)
        self.control = config.get("check", {}).get("control", "int8")
        self._fn = jax.jit(functools.partial(self.mod.logits, self.model),
                           static_argnames=("quant",))

    def logits(self, prompt, output, quant=None):
        """float32 logits [n, V] that choose each of the n served tokens."""
        p, n = len(prompt), len(output)
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(output[:-1], np.int32)])
        s = -(-len(seq) // SEQ_PAD) * SEQ_PAD
        seq = np.pad(seq, (0, s - len(seq)))
        m = -(-n // POS_PAD) * POS_PAD
        pos = np.minimum(np.arange(m) + p - 1, p + n - 2)
        out = self._fn(self.params, jnp.asarray(seq), jnp.asarray(pos),
                       quant=quant)
        return np.asarray(out)[:n]


def gaps(ref_logits, tokens):
    """Per position: the reference's best logit minus its logit of the
    chosen token."""
    tokens = np.asarray(tokens)
    chosen = ref_logits[np.arange(len(tokens)), tokens]
    return ref_logits.max(axis=-1) - chosen


def numbers(gap_lists):
    g = np.concatenate(gap_lists) if gap_lists else np.zeros(0)
    if g.size == 0:
        return {"max_gap": math.inf, "mismatch_share": math.inf}
    return {"max_gap": float(g.max()),
            "mismatch_share": float(np.mean(g > 0))}


def program_numbers(ref, finished, picked):
    """(numbers, per-request max gaps) of the served tokens."""
    per = [gaps(ref.logits(*finished[i]), finished[i][1]) for i in picked]
    return numbers(per), [float(g.max()) for g in per]


def control_numbers(ref, finished, picked):
    """The same numbers for the first choice of the reference computed in
    the control precision, at each position of the same prompts and served
    tokens."""
    per = []
    for i in picked:
        prompt, output = finished[i]
        best = ref.logits(prompt, output, quant=ref.control).argmax(axis=-1)
        per.append(gaps(ref.logits(prompt, output), best))
    return numbers(per)


def judge(nums, limits):
    """{name: {"value", "limit"}} for each number the configuration sets a
    limit for, and whether every one is within it (a limit left at null,
    or no limit at all, fails)."""
    out = {name: {"value": nums[name], "limit": limits[name]}
           for name in NUMBERS if name in limits}
    ok = bool(out) and all(v["limit"] is not None and v["value"] <= v["limit"]
                           for v in out.values())
    return out, ok
