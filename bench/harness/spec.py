"""Everything the harness knows about a cell, found by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
configuration is ``bench/configs/<config>.json``, the mix
``bench/traffic/<traffic>.json`` and each per-layer metric
``bench/metrics/<metric>.py``.  A configuration names its family under
``reference.module``: its plain reference,
``bench/reference/<module>.py``, and the module that owns what depends
on the model's structure (the program's arch and its check, the weights
handed to the program, the cut for the CPU tests and the needed work),
``bench/harness/families/<module>.py``.
Adding a file of any of these kinds and an entry for it in
``BENCHMARK.json`` needs no edit to any other file.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def config(name: str) -> dict:
    return _json(os.path.join(BENCH_DIR, "configs", f"{name}.json"))


def traffic(name: str) -> dict:
    return _json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


_LOADED: dict = {}


def _module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded once per path."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if path not in _LOADED:
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind.replace('/', '_')}_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def metric_module(name: str):
    """The reader of a per-layer metric: a module with ``LAYER``, ``UNIT``,
    ``MOVES`` and ``read(record, trace) -> float | None``."""
    return _module("metrics", name)


def reference_module(cfg: dict):
    """The configuration's plain reference: ``dims(cfg)``,
    ``make_weights(m, seed)`` and ``logits_at(w, m, tokens, read, quant=)``."""
    return _module("reference", cfg["reference"]["module"])


def family_module(cfg: dict):
    """Everything in the harness that depends on the model's structure:
    ``arch_config(cfg)``, ``check(arch, m)``, ``to_program(w)``,
    ``small_cut(cfg)``, ``prefill_work(m, start, end, commit)`` and
    ``decode_work(m, pos)`` (``families/dense_gqa.py`` says what each
    does)."""
    return _module("harness/families", cfg["reference"]["module"])


def peaks(device_kind: str) -> dict:
    table = _json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"device {device_kind!r} is not in bench/peaks.json")
    return table[device_kind]


def _applies(metric: dict, cell: str, e2e_names: set) -> bool:
    """A per-layer metric is read in the cells it lists, or else in every
    cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple        # metric entries of BENCHMARK.json
    per_layer: tuple
    run_seconds: int


def cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"unknown workload {name!r}; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    e2e = tuple(m for m in bench["end_to_end"]
                if "workloads" not in m or name in m["workloads"])
    names = {m["name"] for m in e2e}
    per = tuple(m for m in bench["per_layer"] if _applies(m, name, names))
    return Cell(name, entry["chips"], config(entry["config"]),
                traffic(entry["traffic"]), e2e, per, bench["run_seconds"])
