"""A later change adds a cell and a per-layer metric as data alone: a new
traffic file and a new metric reader dropped into the directories are
found by name, with no edit to any code."""
import json
import os

import harness
import spec
import traffic
from conftest import REPO, make_root

NEW_MIX = {
    "wave_requests": 5,
    "prompt_tokens": {"median": 50, "sigma": 0.3, "min": 40, "max": 60},
    "output_tokens": {"median": 9, "sigma": 0.3, "min": 8, "max": 12},
}
NEW_METRIC = '''
def read(rec):
    st = rec["stats"]
    return st["host_syncs"] / st["decode_dispatches"]
'''


def test_new_traffic_and_metric_are_found_by_name(tmp_path):
    root = make_root(str(tmp_path))
    bench = os.path.join(root, "benchmarks", "chip")
    with open(os.path.join(bench, "traffic", "later-mix.json"), "w") as f:
        json.dump(NEW_MIX, f)
    with open(os.path.join(bench, "metrics", "syncs_per_horizon.py"),
              "w") as f:
        f.write(NEW_METRIC)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["workloads"].append({"name": "smoke-dense.later-mix",
                           "config": "smoke-dense", "traffic": "later-mix",
                           "chips": 1, "why": "added as data"})
    b["per_layer"].append({"name": "syncs_per_horizon", "unit": "count",
                           "better": "lower", "source": "program_counter",
                           "layer": "scheduler", "moves": "tokens_per_s",
                           "workloads": ["smoke-dense.later-mix"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)

    cell = spec.Cell(root, "smoke-dense.later-mix", bench)
    assert cell.mix == NEW_MIX
    assert [m["name"] for m in cell.per_layer][-1] == "syncs_per_horizon"
    wave = traffic.wave(cell.mix, 3, 0, 512)
    assert sorted(len(p) for p, _ in wave) == traffic.lengths(
        NEW_MIX["prompt_tokens"], 5)

    result, _ = harness.run(root, "smoke-dense.later-mix", 3, 0.01, True,
                            require_tpu=False, program_src=f"{REPO}/src")
    assert result["correct"]
    assert result["metrics"]["syncs_per_horizon"]["value"] >= 1.0
    # the warm-up built every program the window used
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    # the older cell does not report the metric that names only the new one
    old = spec.Cell(root, "smoke-dense.tiny", bench)
    assert "syncs_per_horizon" not in [m["name"] for m in old.per_layer]


def test_waves_repeat_their_lengths_across_seeds():
    mix = traffic.load(spec.BENCH_DIR, "conv-batch")
    a = traffic.wave(mix, 1, 0, 1000)
    b = traffic.wave(mix, 2 ** 40 + 5, 3, 1000)
    assert sorted(len(p) for p, _ in a) == sorted(len(p) for p, _ in b)
    assert sorted(n for _, n in a) == sorted(n for _, n in b)
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]
    again = traffic.wave(mix, 1, 0, 1000)
    assert all((p == q).all() and n == m
               for (p, n), (q, m) in zip(a, again))


def test_fixed_order_mix_sends_every_seed_the_same_requests():
    fixed = traffic.load(spec.BENCH_DIR, "conv-batch")
    seeded = traffic.load(spec.BENCH_DIR, "conv-batch-q256")
    assert fixed.get("order") == "fixed" and "order" not in seeded
    a, b = (traffic.wave(fixed, s, 1, 1000) for s in (1, 2 ** 40 + 5))
    assert [(len(p), n) for p, n in a] == [(len(p), n) for p, n in b]
    assert any((p != q).any() for (p, _), (q, _) in zip(a, b))
    a, b = (traffic.wave(seeded, s, 1, 1000) for s in (1, 2 ** 40 + 5))
    assert [(len(p), n) for p, n in a] != [(len(p), n) for p, n in b]
