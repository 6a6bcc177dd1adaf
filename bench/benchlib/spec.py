"""Find a cell's configuration, traffic mix and per-layer metrics by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found from the names in
``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the configuration's sizes;
* ``bench/traffic/<traffic>.json``: the mix's parameters; its ``kind``
  names the driver ``benchlib/kinds/<kind>.py`` that generates and runs it;
* ``bench/metrics/<metric>.py``: a reader with ``read(run) -> float | None``.

So a later cell, mix or metric is added by adding files and entries, never
by editing one.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC_FILE = ROOT / "BENCHMARK.json"


class SpecError(ValueError):
    """A name in ``BENCHMARK.json`` that no file answers."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple   # metric entries that this cell reports with --trace 0
    per_layer: tuple    # metric entries that this cell reports with --trace 1


def load_spec(path: pathlib.Path = SPEC_FILE) -> dict:
    return json.loads(path.read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no {kind} file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def find_cell(name: str, spec: dict | None = None) -> Cell:
    spec = spec if spec is not None else load_spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json("configs", w["config"]),
        traffic=load_json("traffic", w["traffic"]),
        end_to_end=tuple(m for m in spec["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in spec["per_layer"] if _applies(m, name)))


def driver(kind: str):
    """The module that generates and runs traffic of ``kind``."""
    if not (BENCH / "benchlib" / "kinds" / f"{kind}.py").is_file():
        raise SpecError(f"no driver benchlib/kinds/{kind}.py")
    return importlib.import_module(f"benchlib.kinds.{kind}")


def reader(metric: str):
    """``read(run)`` of the per-layer metric ``metric``
    (``bench/metrics/<metric>.py``; the name may hold dots)."""
    path = BENCH / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path.relative_to(ROOT)}")
    mod_name = "benchmetric_" + metric.replace(".", "_").replace("-", "_")
    mod_spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise SpecError(f"device_kind {device_kind!r} is not in "
                        f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]
