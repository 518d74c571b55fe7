"""What the benchmark runs, read from files by name.

Everything that belongs to one configuration, one traffic mix, one cell or
one metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

  configs/<config>.json     sizes, source, what was changed, precision
  traffic/<traffic>.json    the job: batch, lengths, pool, strategy, optimizer
  workloads/<cell>.json     config + traffic + chips + mesh + why + limits
  metrics/<metric>.py       a reader: UNIT, LAYER and read(run) -> number|None

A new cell, configuration or metric is a new file; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List

#: the benchmark's own directory; the checkout's root is its parent
HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its files read."""
    name: str
    chips: int
    mesh: tuple              # (data, model)
    why: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]


def load_cell(name: str, base: Path = HERE) -> Cell:
    """Read ``workloads/<name>.json`` and the config and traffic it names."""
    w = _load_json(base / "workloads" / f"{name}.json")
    if w["name"] != name:
        raise ValueError(f"workloads/{name}.json names itself {w['name']!r}")
    config = _load_json(base / "configs" / f"{w['config']}.json")
    traffic = _load_json(base / "traffic" / f"{w['traffic']}.json")
    mesh = tuple(w.get("mesh", (w["chips"], 1)))
    if mesh[0] * mesh[1] != w["chips"]:
        raise ValueError(f"{name}: mesh {mesh} does not hold "
                         f"{w['chips']} chips")
    return Cell(name=name, chips=int(w["chips"]), mesh=mesh, why=w["why"],
                config=config, traffic=traffic, limits=dict(w["limits"]))


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return _load_json(root / "BENCHMARK.json")


def metrics_for(bench: Dict[str, Any], cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports.

    A metric with a ``workloads`` list is reported in those cells; a
    per-layer metric without one follows the end-to-end metric it moves.
    """
    e2e = bench["end_to_end"]

    def e2e_in(m):
        return "workloads" not in m or cell in m["workloads"]
    if kind == "end_to_end":
        return [m for m in e2e if e2e_in(m)]
    moved = {m["name"] for m in e2e if e2e_in(m)}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif m["moves"] in moved:
            out.append(m)
    return out


def load_reader(name: str, base: Path = HERE):
    """Import ``metrics/<name>.py``; it defines ``UNIT``, ``LAYER`` and
    ``read(run)``, which returns a number or ``None`` when the run holds
    nothing for it to read."""
    path = base / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str, base: Path = HERE) -> Dict[str, Any]:
    """Published peaks of one chip of ``device_kind``; a kind that is not
    in ``peaks.json`` is an error, never a default."""
    table = _load_json(base / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"({sorted(table)})")
    return table[device_kind]


def known_device_kinds(base: Path = HERE) -> List[str]:
    return sorted(_load_json(base / "peaks.json")["devices"])

