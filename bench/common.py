"""Finding a cell's files by name.

Everything that belongs to one configuration, traffic mix, per-layer
metric, kernel or cell sits in a file of its own, found from the names in
``BENCHMARK.json``:

    bench/configs/<file named by the config entry>   sizes, as run
    bench/blocks/<the config file's "block">.py       its model block:
                                                      weights, reference,
                                                      FLOPs
    bench/traffic/<traffic>.json                      the traffic mix
    bench/metrics/<metric>.py                         a per-layer reader
    bench/kernels/<kernel>.py                         a kernel's cost
    bench/limits/<workload>.json                      the limits of `correct`

A new cell adds files and entries; nothing here names a cell.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                     f"(has {[w['name'] for w in spec['workloads']]})")


def config_entry(spec: dict, name: str) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return c
    raise SystemExit(f"no config {name!r} in BENCHMARK.json")


def load_config(spec: dict, name: str, root: Path = ROOT) -> dict:
    return json.loads((Path(root) / config_entry(spec, name)["file"])
                      .read_text())


def block(cfg: dict, root: Path = ROOT):
    """The model block that configuration ``cfg`` names
    (``bench/blocks/__init__.py`` lists what one defines)."""
    path = Path(root) / "bench" / "blocks" / f"{cfg['block']}.py"
    if not path.exists():
        raise SystemExit(f"configuration {cfg['name']!r} names block "
                         f"{cfg['block']!r}: no file {path}")
    return load_module(path, "block_" + cfg["block"])


def load_traffic(name: str, root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def load_limits(workload_name: str, root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "bench" / "limits"
                       / f"{workload_name}.json").read_text())


def load_module(path: Path, name: str):
    """Import a file by path: names may hold '.' and '-'."""
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    return load_module(Path(root) / "bench" / "metrics" / f"{name}.py", name)


def kernel_cost(name: str, root: Path = ROOT):
    return load_module(Path(root) / "bench" / "kernels" / f"{name}.py",
                       "kernel_" + name)


def metrics_of(spec: dict, workload_name: str, kind: str) -> list:
    """The cell's metric entries of ``kind`` ("end_to_end" | "per_layer"):
    those without a ``workloads`` key, and those that list this cell."""
    return [m for m in spec[kind]
            if "workloads" not in m or workload_name in m["workloads"]]
