"""``BENCHMARK.json`` and the data files its names point at.

The harness finds everything by name, so that a later PR adds a cell, a
configuration, a traffic mix, a runner kind or a per-layer metric by
adding files and ``BENCHMARK.json`` entries, and edits no file that is
there:

- configuration ``<c>``   -> its ``file`` (``benchmark/configs/<c>.json``)
- traffic mix ``<t>``     -> ``benchmark/traffic/<t>.json``, whose ``kind``
                             names ``benchmark/runners/<kind>.py``
- per-layer metric ``<m>``-> ``benchmark/layer_metrics/<m>.json``, whose
                             ``reader`` names ``module:function`` and whose
                             ``args`` are passed to it by keyword

Unit, layer, ``moves`` and the cells a metric belongs to are stated once,
in ``BENCHMARK.json``.
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass
from importlib import import_module

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
# what the driver takes as a layer's name (it refused "fit loop", PR 22)
_LAYER_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


class ManifestError(ValueError):
    """``BENCHMARK.json`` names something that is not there."""


class UnknownDevice(KeyError):
    """The device is not in ``peaks.json``: an error, never a default."""


def resolve(name: str):
    """The object a data file names as ``module:attribute``."""
    module, attribute = name.split(":")
    return getattr(import_module(module), attribute)


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    run_seconds: int        # the manifest's, for a run that is given none
    config: dict
    traffic: dict
    end_to_end: tuple       # metric entries of BENCHMARK.json for this cell
    per_layer: tuple        # the same, each with its "reader" and "args"


def _read(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise ManifestError(f"{what}: no file {path}")
    with open(path) as f:
        try:
            return json.load(f)
        except json.JSONDecodeError as e:
            raise ManifestError(f"{what}: {path} is not JSON: {e}") from e


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str, bench_dir: str = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files read.
    Raises ManifestError when the cell, or a file it needs, is missing."""
    manifest = _read(os.path.join(root, "BENCHMARK.json"), "manifest")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise ManifestError(
            f"no workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    entry = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    if entry["config"] not in configs:
        raise ManifestError(
            f"workload {name!r} names config {entry['config']!r}, which "
            "BENCHMARK.json does not list")
    config = _read(os.path.join(root, configs[entry["config"]]["file"]),
                   f"config {entry['config']!r}")
    traffic = _read(os.path.join(bench_dir, "traffic",
                                 entry["traffic"] + ".json"),
                    f"traffic {entry['traffic']!r}")
    runner = os.path.join(bench_dir, "runners", f"{traffic.get('kind')}.py")
    if not os.path.isfile(runner):
        raise ManifestError(
            f"traffic {entry['traffic']!r} has kind {traffic.get('kind')!r} "
            f"and there is no {runner}")
    end_to_end = tuple(m for m in manifest["end_to_end"]
                       if _in_cell(m, name))
    moved = {m["name"] for m in end_to_end}
    per_layer = []
    for m in manifest["per_layer"]:
        if not _in_cell(m, name):
            continue
        if m["moves"] not in moved:
            raise ManifestError(
                f"per-layer metric {m['name']!r} moves {m['moves']!r}, "
                f"which cell {name!r} does not report")
        if not _LAYER_NAME.fullmatch(m["layer"]):
            raise ManifestError(
                f"per-layer metric {m['name']!r}: layer {m['layer']!r} is "
                "not 1 to 64 letters, digits, '_', '.' and '-'")
        spec = _read(os.path.join(bench_dir, "layer_metrics",
                                  m["name"] + ".json"),
                     f"per-layer metric {m['name']!r}")
        if ":" not in spec.get("reader", ""):
            raise ManifestError(
                f"per-layer metric {m['name']!r}: \"reader\" must be "
                "\"module:function\"")
        per_layer.append({**m, "reader": spec["reader"],
                          "args": spec.get("args", {})})
    return Cell(name=name, chips=int(entry["chips"]),
                run_seconds=manifest["run_seconds"], config=config,
                traffic=traffic, end_to_end=end_to_end,
                per_layer=tuple(per_layer))


def workload_names(root: str) -> list:
    manifest = _read(os.path.join(root, "BENCHMARK.json"), "manifest")
    return [w["name"] for w in manifest["workloads"]]


def load_peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    table = _read(os.path.join(bench_dir, "peaks.json"), "peaks")
    if device_kind not in table:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in peaks.json "
            f"(it has {sorted(table)}); add its published peaks with "
            "their source")
    return table[device_kind]
