"""Finding a cell's pieces by name, from files alone.

``BENCHMARK.json`` at the root lists the cells and metrics.  Everything
that belongs to one cell, configuration or metric sits in a file of its
own under ``bench/``, found by the name ``BENCHMARK.json`` gives it:

    bench/configs/<config>.json     sizes, source, ``reduced``, ``assumed``,
                                    its generator, op, kernels and the limits
                                    of the numbers its reference compares
    bench/traffic/<traffic>.json    the tier X lives on, the traced span
    bench/gen/<generator>.py        ``generate(config, seed, rows)``
    bench/ops/<op>.py               ``Op(mats, config, seed)`` with ``step()``
    bench/ref/<op>.py               ``compare(data, config, samples)``
    bench/e2e/<metric>.py           ``read(run)`` of an end-to-end metric
    bench/metrics/<metric>.py       ``read(run)`` of a per-layer metric

A cell is a pair of configuration and traffic.  So a later change adds a
cell, configuration, traffic or metric by adding files and entries, and
edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_module(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``; names may hold dots."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with what its files say."""
    name: str
    chips: int
    traffic: dict             # bench/traffic/<traffic>.json
    config: dict              # bench/configs/<config>.json
    end_to_end: list          # BENCHMARK.json entries reported by this cell
    per_layer: list


def load_cell(name: str) -> Cell:
    bench = _load_json(ROOT / "BENCHMARK.json")
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    entry = entries[0]
    return Cell(name=name, chips=int(entry["chips"]),
                traffic=_load_json(BENCH_DIR / "traffic"
                                   / f"{entry['traffic']}.json"),
                config=_load_json(BENCH_DIR / "configs"
                                  / f"{entry['config']}.json"),
                end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if _applies(m, name)])
