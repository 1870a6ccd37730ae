"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix or metric lives in
a file of its own, found by the name the manifest gives it:

- ``configs/<config>.json``: the configuration's sizes, as run;
- ``configs/<config>.py``: its module (``setup``, ``window``, ``check``);
- ``traffic/<mix>.json``: the mix, read by `chipbench.traffic`;
- ``end_to_end/<metric>.py`` and ``layer_metrics/<metric>.py``: one reader
  each, ``read(ctx) -> float | None``.

A later PR adds a cell, a mix or a metric by adding files and entries.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import List

PACKAGE = Path(__file__).resolve().parent
ROOT = PACKAGE.parent


def load(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in manifest['workloads']]}")


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def metrics_for(manifest: dict, section: str, cell_name: str) -> List[dict]:
    """Entries of ``end_to_end`` or ``per_layer`` that the cell reports."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_module(path: Path) -> ModuleType:
    """Import a file by path (names may hold '-' and '.')."""
    path = Path(path).resolve()
    name = "chipbench._files." + "".join(
        c if c.isalnum() else "_" for c in str(path.with_suffix("")))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def config_file(manifest: dict, name: str) -> dict:
    return json.loads((ROOT / config_entry(manifest, name)["file"]).read_text())


def config_module(name: str, package: Path = PACKAGE) -> ModuleType:
    return load_module(package / "configs" / f"{name}.py")


def reader(section: str, name: str, package: Path = PACKAGE) -> ModuleType:
    folder = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}[section]
    return load_module(package / folder / f"{name}.py")
