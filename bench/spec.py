"""Name-to-file lookup for the benchmark: cells, configurations, traffic
mixes, per-layer metric readers, references and correctness limits.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found by the name ``BENCHMARK.json`` gives it:

    bench/configs/<config>.json      sizes of one deployment
    bench/traffic/<traffic>.json     parameters of one traffic mix
    bench/metrics/<metric>.py        ``read(ctx)`` -> number or None
    bench/references/<ref>.py        plain reference named by a config
    bench/limits/<cell>.json         the correctness limits of one cell

A later change adds a cell, a configuration or a metric by adding files
and entries; nothing here needs an edit.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _name(kind: str, name: str) -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    return name


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                   f"{[c['name'] for c in bench['workloads']]}")


def load_config(name: str, bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    return _json(bench_dir / "configs" / f"{_name('config', name)}.json")


def load_traffic(name: str, bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    return _json(bench_dir / "traffic" / f"{_name('traffic', name)}.json")


def load_limits(cell: str, bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    return _json(bench_dir / "limits" / f"{_name('workload', cell)}.json")


def _module(path: pathlib.Path, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str, bench_dir: pathlib.Path = BENCH_DIR):
    """The reader module of a per-layer metric; its ``read(ctx)`` returns a
    number, or None where the run gave it nothing to read."""
    path = bench_dir / "metrics" / f"{_name('metric', name)}.py"
    return _module(path, f"bench_metric_{name.replace('.', '_')}")


def load_reference(name: str, bench_dir: pathlib.Path = BENCH_DIR):
    path = bench_dir / "references" / f"{_name('reference', name)}.py"
    return _module(path, f"bench_reference_{name.replace('.', '_')}")


def cell_metrics(bench: dict, cell: dict, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that this cell reports:
    those without a ``workloads`` key, and those that list the cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell["name"] in m["workloads"]]
