"""Find one cell's files by name.

``BENCHMARK.json`` names the cell's configuration, traffic mix and chips;
the rest sits in files of its own that a later PR adds beside these:

- ``configs/<config>.json``: the deployment (topology, sizes, seeds);
- ``topologies/<topology>.py``: ``csr(config)``, the overlay's builder;
- ``traffic/<traffic>.json``: the mix's parameters, with its ``kind``;
- ``kinds/<kind>.py``: the kind's generator, ``mix`` and ``schedules``, and
  its plain reference semantics, ``reference``, ``expected``, ``control``;
- ``paths/<engine>.py``: ``Path``, the engine path a call drives;
- ``workloads/<cell>.json``: only where a cell drives another path than
  ``sync``, the path's name under ``engine`` and its parameters;
- ``metrics/<metric>.py``: ``read(readings)``, one per per-layer metric.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DEFAULT_ENGINE = {"engine": "sync"}


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    engine: dict
    end_to_end: list  # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # A per-layer metric without a list is reported wherever the metric
    # it moves is.
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json ({known})")
    here = os.path.join(root, "benchmark")
    engine = os.path.join(here, "workloads", name + ".json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    names = {m["name"] for m in e2e}
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=_json(os.path.join(here, "configs", entry["config"] + ".json")),
        traffic=_json(os.path.join(here, "traffic", entry["traffic"] + ".json")),
        engine=_json(engine) if os.path.exists(engine) else dict(DEFAULT_ENGINE),
        end_to_end=e2e,
        per_layer=[m for m in bench["per_layer"] if _applies(m, name, names)],
    )


def module(kind: str, name: str, root: str = ROOT):
    """``benchmark/<kind>/<name>.py``, loaded once by its file name."""
    path = os.path.join(root, "benchmark", kind, name + ".py")
    key = f"_bench_{kind}_{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


def load_reader(metric: str, root: str = ROOT):
    """``read(readings) -> float | None`` from ``metrics/<metric>.py``."""
    return module("metrics", metric, root).read
