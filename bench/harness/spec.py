"""Find a cell's pieces by name.

``BENCHMARK.json`` names every cell, configuration, traffic mix and metric.
Each configuration and traffic mix is a JSON file, each per-layer metric a
Python file with one ``read(run)`` function; this module finds them under
the benchmark root by the names in ``BENCHMARK.json``, so a new piece is a
new file plus a new entry, never an edit of an existing file.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Optional


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple        # metric entries this cell reports untraced
    per_layer: tuple         # metric entries this cell reports traced
    bench_dir: str           # the directory holding configs/, traffic/, ...


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(path: str) -> dict:
    """Read one JSON file, naming it in the error when it is missing."""
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing file: {path}") from e


def load_cell(root: str, name: str, bench_dir: Optional[str] = None) -> Cell:
    """Resolve workload ``name`` of ``<root>/BENCHMARK.json``.

    ``bench_dir`` is where ``configs/``, ``traffic/`` and ``metrics/`` live
    (default: the directory of this package's parent). A configuration's
    ``chips`` (default 1) has to equal the workload's, and its
    ``n_passages``, the deployment's global count, has to divide into that
    many block shards."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    bench_dir = bench_dir or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; known: "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    chips = int(w["chips"])
    if int(config.get("chips", 1)) != chips:
        raise SpecError(f"workload {name!r} asks for {chips} chips but its "
                        f"config {w['config']!r} states "
                        f"{config.get('chips', 1)}")
    if int(config["n_passages"]) % chips:
        raise SpecError(f"config {w['config']!r}: n_passages "
                        f"{config['n_passages']} does not divide into "
                        f"{chips} shards")
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     w["traffic"] + ".json"))
    return Cell(
        name=name, chips=chips, config=config, traffic=traffic,
        end_to_end=tuple(m for m in spec["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in spec["per_layer"] if _applies(m, name)),
        bench_dir=bench_dir)


def load_metric_reader(bench_dir: str, name: str) -> Callable:
    """The ``read(run)`` function of ``<bench_dir>/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    if not os.path.exists(path):
        raise SpecError(f"per-layer metric {name!r} has no reader at {path}")
    mod_name = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(bench_dir: str, device_kind: str) -> dict:
    """Peak FLOP/s and HBM bytes/s of ``device_kind`` from ``peaks.json``;
    an unknown kind is an error, never a default."""
    table = load_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SpecError(f"device kind {device_kind!r} is not in peaks.json "
                        f"(known: {sorted(table['devices'])})")
    return table["devices"][device_kind]
