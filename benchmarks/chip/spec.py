"""Finds every piece of a cell by its name, from files alone.

``BENCHMARK.json`` names the cells and metrics.  A configuration is
``configs/<name>.json``, a traffic mix ``traffic/<name>.json``, the loop
a mix names ``loops/<loop>.py``, the limits of a cell's comparison
``limits/<cell>.json`` and the reader of a metric
``metrics/<metric>.py``.  Nothing here lists them: a new cell, mix,
loop or metric is a new file and a new entry in ``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Dict, List

#: this directory: the yardstick's own files
HERE = Path(__file__).resolve().parent


def load_benchmark(root: Path) -> dict:
    """``BENCHMARK.json`` at the root of a checkout."""
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(have {[c['name'] for c in bench['workloads']]})")


def _json(kind: str, name: str, base: Path) -> dict:
    path = base / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no file {path} for {name!r}")
    return json.loads(path.read_text())


def config(name: str, base: Path = HERE) -> dict:
    """The configuration ``configs/<name>.json``."""
    cfg = _json("configs", name, base)
    if cfg["name"] != name:
        raise ValueError(f"configs/{name}.json names itself {cfg['name']!r}")
    return cfg


def traffic(name: str, base: Path = HERE) -> dict:
    """The traffic mix ``traffic/<name>.json``: parameters of the loop
    it names (``loadgen``)."""
    return _json("traffic", name, base)


def limits(cell: str, base: Path = HERE) -> Dict[str, float]:
    """The limit of each number the cell's comparison reads,
    ``limits/<cell>.json``."""
    return {k: float(v) for k, v in _json("limits", cell, base)[
        "limits"].items()}


def _module(kind: str, name: str, base: Path, needs: tuple) -> ModuleType:
    path = base / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no file {path} for {name!r}")
    mod_name = f"chipbench_{kind}_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in needs if not callable(getattr(mod, f, None))]
    if missing:
        raise TypeError(f"{path} defines no {', '.join(missing)}")
    return mod


def loop(name: str, base: Path = HERE) -> ModuleType:
    """The loop ``loops/<name>.py`` that drives a mix (see ``loadgen``)."""
    return _module("loops", name, base, ("setup", "drive", "readings",
                                         "counts", "notes",
                                         "control_record"))


def metric_reader(name: str, base: Path = HERE) -> ModuleType:
    """The reader of metric ``name``: ``metrics/<name>.py``, a module
    whose ``read(rec)`` returns the metric's value, or ``None`` where the
    run holds nothing to read."""
    return _module("metrics", name, base, ("read",))


def cell_metrics(bench: dict, cell: str, per_layer: bool) -> List[dict]:
    """The metrics a cell reports: with ``per_layer`` False the
    end-to-end metrics whose ``workloads`` list it (or that have no such
    list), else the per-layer metrics that list it (or, with no list,
    that move an end-to-end metric the cell reports)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not per_layer:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]
