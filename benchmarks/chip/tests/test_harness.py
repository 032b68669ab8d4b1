"""The harness end to end at smoke size on the CPU: the reference agrees
with the engine, and the check fails under the control and under faults
planted in the timed path."""
import pytest

import harness
from conftest import REPO, make_root


def _run(root, family, seed=7, trace=False):
    return harness.run(root, f"smoke-{family}.tiny", seed, 0.01, trace,
                       require_tpu=False, program_src=f"{REPO}/src")


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_reference_agrees_with_engine(tmp_path, family):
    """In float32 the served greedy tokens are the reference's own: the
    widest gap is rounding, far under the limit."""
    root = make_root(str(tmp_path))
    result, lines = _run(root, family)
    assert result["correct"], lines
    assert result["failed"] == 0
    assert result["check"]["max_gap"]["value"] < 1e-3
    assert set(result["metrics"]) == {"tokens_per_s", "jct_mean_s",
                                      "jct_p95_s", "setup_s"}
    assert list(result)[-1] == "check"


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_warm_up_leaves_nothing_to_compile_in_the_window(tmp_path, family):
    """The warm-up builds every program a wave of the mix can use: a
    traced run builds none inside its window."""
    root = make_root(str(tmp_path))
    result, lines = _run(root, family, seed=8, trace=True)
    assert result["correct"], lines
    assert result["metrics"]["compiles_in_window"]["value"] == 0


def test_readings_summary_judges_both_sides():
    import readings
    limits = {"control": "int8", "max_gap": 0.5}
    rows = [{"program": {"max_gap": g, "mismatch_share": 0.1},
             "control": {"max_gap": c, "mismatch_share": 0.4},
             "program_correct": g <= 0.5, "control_correct": c <= 0.5}
            for g, c in ((0.1, 1.2), (0.2, 0.4))]
    out = readings.summarise(rows, limits)
    assert out["max_gap"] == {"lower": 0.2, "upper": 0.4, "limit": 0.5}
    assert out["mismatch_share"]["limit"] is None
    assert (out["program_correct"], out["control_correct"]) == (2, 1)
