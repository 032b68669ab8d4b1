"""Smoke-size cells for the benchmark's own tests, run on the CPU by hand:

    python -m pytest benchmarks/chip/tests

A fixture copies the benchmark into a temporary checkout and adds tiny
configurations (the program's smoke widths, two layers) and a tiny mix, so
that the harness runs end to end without a chip."""
import copy
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(REPO, "src"))

SMOKE_MODELS = {
    "dense": {
        "arch": "qwen2-0.5b",
        "model": {"num_hidden_layers": 2, "hidden_size": 256,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "head_dim": 64, "intermediate_size": 512,
                  "vocab_size": 512, "rope_theta": 1000000.0,
                  "rms_norm_eps": 1e-05, "tie_word_embeddings": True,
                  "qkv_bias": True},
        "program": {"n_layers": 2, "d_model": 256, "n_heads": 4,
                    "n_kv_heads": 2, "head_dim": 64, "d_ff": 512,
                    "vocab_size": 512},
        "engine": {"cache": "paged", "block_size": 16, "n_slots": 4,
                   "decode_horizon": 8, "prefill_lanes": 2,
                   "max_len": 128, "policy": "fcfs"},
    },
    "ssm": {
        "arch": "mamba2-780m",
        "model": {"n_layer": 2, "d_model": 256, "vocab_size": 512,
                  "d_state": 16, "expand": 2, "headdim": 32, "d_conv": 4,
                  "ngroups": 1, "chunk_size": 32, "norm_eps": 1e-05,
                  "tie_embeddings": True},
        "program": {"n_layers": 2, "d_model": 256, "vocab_size": 512,
                    "ssm_state": 16, "ssm_headdim": 32, "ssm_chunk": 32},
        "engine": {"cache": "contiguous", "n_slots": 4, "decode_horizon": 8,
                   "max_len": 128, "policy": "fcfs"},
    },
}

SMOKE_MIX = {
    "wave_requests": 8,
    "prompt_tokens": {"median": 40, "sigma": 0.5, "min": 32, "max": 64,
                      "multiple": 32},
    "output_tokens": {"median": 40, "sigma": 0.5, "min": 24, "max": 64,
                      "multiple": 1},
}


def make_root(tmp, dtype="float32", use_pallas=True, check=None):
    """A checkout in ``tmp`` holding the benchmark and one smoke cell per
    family, ``smoke-<family>.tiny``; returns its path."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(BENCH, os.path.join(root, "benchmarks", "chip"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = os.path.join(root, "benchmarks", "chip")
    with open(os.path.join(bench, "traffic", "tiny.json"), "w") as f:
        json.dump(SMOKE_MIX, f)
    configs, workloads = [], []
    for fam, sm in SMOKE_MODELS.items():
        name = f"smoke-{fam}"
        prog = dict(sm["program"], dtype=dtype, param_dtype=dtype,
                    use_pallas=use_pallas)
        if fam == "dense":
            prog["rope_theta"] = 1000000.0
        conf = {"name": name, "family": fam, "arch": sm["arch"],
                "model": copy.deepcopy(sm["model"]), "program": prog,
                "engine": dict(sm["engine"]),
                "check": dict(check or {"max_gap": 1e-3,
                                        "mismatch_share": 0.05})}
        path = f"benchmarks/chip/configs/{name}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(conf, f)
        configs.append({"name": name, "source": "smoke", "file": path,
                        "reduced": [], "why": "smoke"})
        workloads.append({"name": f"{name}.tiny", "config": name,
                          "traffic": "tiny", "chips": 1, "why": "smoke"})
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench_json = json.load(f)
    bench_json["configs"], bench_json["workloads"] = configs, workloads
    for m in bench_json["end_to_end"] + bench_json["per_layer"]:
        m.pop("workloads", None)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench_json, f)
    return root


@pytest.fixture
def smoke_root(tmp_path):
    return make_root(str(tmp_path))
