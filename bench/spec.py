"""Resolve the names in ``BENCHMARK.json`` to the files that hold them.

Everything that belongs to one configuration, traffic mix, cell or per-layer
metric lives in a file of its own, found by its name.  So does everything
that depends on a model's layers: a configuration file's ``"model"`` key
names its model module, ``models/<model>.py`` (``decoder`` where the key is
absent), which holds the model's weights, plain reference, operation counts
and state-insert warm-up (the contract is in ``models/decoder.py``).
Adding a configuration, even one with layers of a new kind, is adding files
and entries, never editing the harness; the program under test has to
serve the model.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
REPO_DIR = BENCH_DIR.parent
BENCHMARK_JSON = REPO_DIR / "BENCHMARK.json"
DEFAULT_MODEL = "decoder"


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(path=BENCHMARK_JSON) -> dict:
    return load_json(path)


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def config_file(bench: dict, name: str) -> pathlib.Path:
    return REPO_DIR / config_entry(bench, name)["file"]


def traffic_file(name: str) -> pathlib.Path:
    return BENCH_DIR / "traffic" / f"{name}.json"


def cell_file(name: str) -> pathlib.Path:
    return BENCH_DIR / "cells" / f"{name}.json"


def metric_file(name: str) -> pathlib.Path:
    return BENCH_DIR / "metrics" / f"{name}.py"


def _exec_file(path: pathlib.Path, prefix: str):
    """The module of one Python file, named ``prefix`` + its stem."""
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The ``read(run) -> float | None`` function of one per-layer metric."""
    return _exec_file(metric_file(name), "bench_metric_").read


def model_file(name: str) -> pathlib.Path:
    return BENCH_DIR / "models" / f"{name}.py"


@functools.lru_cache(maxsize=None)
def _model_at(path: pathlib.Path):
    # One module object per file, so that its jitted functions, which the
    # check takes as a static argument, compile once per process.
    return _exec_file(path, "bench_model_")


def model_module(conf: dict):
    """The model module of a configuration file's dict ``conf``."""
    return _model_at(model_file(conf.get("model", DEFAULT_MODEL)))


def end_to_end(bench: dict, cell_name: str) -> list[dict]:
    """The cell's end-to-end metrics (those listing it, or listing none)."""
    return [m for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def per_layer(bench: dict, cell_name: str) -> list[dict]:
    """The per-layer metrics read in the cell's traced run: those listing
    it, or without a list, those whose moved metric the cell reports."""
    moved = {m["name"] for m in end_to_end(bench, cell_name)}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif m["moves"] in moved:
            out.append(m)
    return out


@dataclasses.dataclass(frozen=True)
class CellSpec:
    """One cell resolved: its entry and the contents of its files."""
    name: str
    entry: dict
    config: dict        # configs/<config>.json
    traffic: dict       # traffic/<mix>.json
    engine: dict        # cells/<cell>.json


def resolve(bench: dict, cell_name: str) -> CellSpec:
    entry = cell(bench, cell_name)
    return CellSpec(name=cell_name, entry=entry,
                    config=load_json(config_file(bench, entry["config"])),
                    traffic=load_json(traffic_file(entry["traffic"])),
                    engine=load_json(cell_file(cell_name)))
