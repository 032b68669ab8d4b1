"""The check catches what a broken timed path would serve: each fault is
planted underneath a smoke-size run, and ``correct`` must come out false.
The control, the int8 reference put in the program's place, must fail it
too."""
import jax.numpy as jnp
import pytest

import check
import harness
from conftest import REPO, make_root
from repro.models import mamba2, transformer
from repro.serve.engine import ServeEngine


def _run(tmp_path, family, dtype="float32", seed=11):
    root = make_root(str(tmp_path), dtype=dtype)
    return harness.run(root, f"smoke-{family}.tiny", seed, 0.01, False,
                       require_tpu=False, program_src=f"{REPO}/src")


def _frozen_paged(orig):
    def step(cfg, params, cache, *a, **kw):
        logits, _ = orig(cfg, params, cache, *a, **kw)
        return logits, cache              # the K/V write is lost
    return step


def _frozen_recurrent(orig):
    def step(cfg, params, cache, *a, **kw):
        logits, _ = orig(cfg, params, cache, *a, **kw)
        return logits, cache              # the state never advances
    return step


def _half_rows(orig):
    def step(cfg, params, cache, tokens, *a, **kw):
        logits, new = orig(cfg, params, cache, tokens, *a, **kw)
        keep = (jnp.arange(logits.shape[0]) % 2 == 0)[:, None, None]
        return jnp.where(keep, logits, 0.0), new   # odd rows left out
    return step


def _altered_pick(orig):
    def pick_fn(self):
        pick = orig(self)

        def altered(logits, slots, step):
            tok = pick(logits, slots, step)
            bump = (step % 3 == 1).astype(tok.dtype)  # every third step
            return (tok + bump) % logits.shape[-1]
        return altered
    return pick_fn


FAULTS = {
    ("dense", "state_unchanged"): (transformer, "paged_decode_step",
                                   _frozen_paged),
    ("ssm", "state_unchanged"): (mamba2, "decode_step", _frozen_recurrent),
    ("dense", "half_batch"): (transformer, "paged_decode_step", _half_rows),
    ("ssm", "half_batch"): (mamba2, "decode_step", _half_rows),
    ("dense", "token_altered"): (ServeEngine, "_pick_fn", _altered_pick),
    ("ssm", "token_altered"): (ServeEngine, "_pick_fn", _altered_pick),
}


@pytest.mark.parametrize("family,fault", sorted(FAULTS))
def test_fault_fails_the_check(tmp_path, monkeypatch, family, fault):
    owner, attr, wrap = FAULTS[(family, fault)]
    monkeypatch.setattr(owner, attr, wrap(getattr(owner, attr)))
    result, lines = _run(tmp_path, family)
    assert not result["correct"], lines
    assert result["failed"] > 0


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_control_fails_the_check(tmp_path, monkeypatch, family):
    """The int8 reference's first choices, read against the float32
    reference, in place of the served tokens."""
    def control_in_place(ref, finished, picked):
        nums = check.control_numbers(ref, finished, picked)
        return nums, [nums["max_gap"]] * len(picked)
    monkeypatch.setattr(check, "program_numbers", control_in_place)
    result, lines = _run(tmp_path, family)
    assert not result["correct"], lines


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_bfloat16_program_and_control_readings_separate(tmp_path, family):
    """At smoke size, on three seeds, the bfloat16 engine's widest gap (the
    lower reading) and the int8 control's (the upper reading) lie at least
    three times apart, as a limit between them needs."""
    root = make_root(str(tmp_path), dtype="bfloat16")
    program, control = [], []
    orig = check.program_numbers

    def both(ref, finished, picked):
        out = orig(ref, finished, picked)
        program.append(out[0]["max_gap"])
        control.append(check.control_numbers(ref, finished, picked)["max_gap"])
        return out
    try:
        check.program_numbers = both
        for seed in (5, 6, 7):
            harness.run(root, f"smoke-{family}.tiny", seed, 0.01, False,
                        require_tpu=False, program_src=f"{REPO}/src")
    finally:
        check.program_numbers = orig
    assert len(program) == 3
    assert min(control) >= 3 * max(program), (program, control)
