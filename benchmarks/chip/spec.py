"""What the harness finds by name: the cell in ``BENCHMARK.json``, its
configuration file, its traffic mix, and one reader per per-layer metric
(``metrics/<name>.py``, a ``read(record)`` function)."""
import importlib.util
import json
import os

import traffic

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def load_benchmark(root):
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise SystemExit(f"no BENCHMARK.json in {root}")
    with open(path) as f:
        return json.load(f)


def _applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, root, name, bench_dir=BENCH_DIR):
        bench = load_benchmark(root)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = self.entry["chips"]
        self.bench_dir = bench_dir
        configs = {c["name"]: c for c in bench["configs"]}
        with open(os.path.join(root, configs[self.entry["config"]]["file"])) as f:
            self.config = json.load(f)
        self.mix = traffic.load(bench_dir, self.entry["traffic"])
        self.end_to_end = [m for m in bench["end_to_end"]
                           if _applies(m, name)]
        self.per_layer = [m for m in bench["per_layer"] if _applies(m, name)]


def metric_reader(name, bench_dir=BENCH_DIR):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"per-layer metric {name!r} has no reader {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
