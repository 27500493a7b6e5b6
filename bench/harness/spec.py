"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

* configuration  -> the ``file`` its entry names (``bench/configs/``);
* traffic mix    -> ``bench/traffic/<traffic>.json``;
* the cell's offered rate and correctness limits -> ``bench/cells/<cell>.json``;
* per-layer metric -> ``bench/metrics/<metric>.py`` (a ``read(ctx)``);
* plain reference  -> ``bench/references/<config["reference"]>.py``;
* device peaks     -> ``bench/peaks.json``, keyed by ``device_kind``.

A later cell, mix or metric is added with new files and entries alone.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    rate: float
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    reference: object
    readers: Dict[str, Callable]


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: Path = ROOT) -> Cell:
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    mix = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    own = load_json(root / "bench" / "cells" / f"{name}.json")
    per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
    readers = {
        m["name"]: load_module(root / "bench" / "metrics" / f"{m['name']}.py").read
        for m in per_layer
    }
    reference = load_module(
        root / "bench" / "references" / f"{config['reference']}.py")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, mix=mix,
        rate=float(own["rate"]), limits=own["limits"],
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=per_layer, reference=reference, readers=readers,
    )


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    table = load_json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in bench/peaks.json; "
            f"have {sorted(table)}")
    return table[device_kind]
