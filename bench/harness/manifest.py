"""Find what a cell needs by name.

Nothing here knows a cell, configuration, traffic mix or metric: each is a
file of its own, found from the names in ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` — the file that the manifest's
  configuration entry names;
* ``bench/schemes/<scheme>.py`` — what the configuration's ``scheme``
  names: the system under test, its control and its plain reference;
* ``bench/traffic/<traffic>.json`` — the parameters of one traffic mix;
* ``bench/loops/<loop>.py`` — the closed loop the mix's ``loop`` names;
* ``bench/metrics/<metric>.py`` — one reader, ``read(ctx) -> float | None``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

#: the checkout root: ``bench/harness/manifest.py`` → two levels up
ROOT = Path(__file__).resolve().parents[2]


class ManifestError(KeyError):
    """A name in ``BENCHMARK.json`` has no entry or no file."""


def load(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _entry(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(manifest: dict, name: str) -> dict:
    return _entry(manifest["workloads"], name, "workload")


def config(manifest: dict, name: str, root: Path = ROOT) -> dict:
    """The configuration file that the manifest's entry ``name`` names."""
    entry = _entry(manifest["configs"], name, "config")
    with open(Path(root) / entry["file"]) as f:
        return json.load(f)


def traffic(name: str, root: Path = ROOT) -> dict:
    path = Path(root) / "bench" / "traffic" / f"{name}.json"
    if not path.is_file():
        raise ManifestError(f"no traffic file {path}")
    with open(path) as f:
        return json.load(f)


def _module(kind: str, name: str, root: Path):
    """The Python file ``bench/<kind>/<name>.py``, loaded."""
    path = Path(root) / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise ManifestError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader(name: str, root: Path = ROOT):
    """The ``read(ctx)`` function of metric ``name``."""
    return _module("metrics", name, root).read


def scheme(config: dict, root: Path = ROOT):
    """The module that the configuration's ``scheme`` names."""
    if "scheme" not in config:
        raise ManifestError(f"configuration {config.get('name')!r} names no scheme")
    return _module("schemes", config["scheme"], root)


def loop(traffic: dict, root: Path = ROOT):
    """The ``Loop`` class that the traffic mix's ``loop`` names."""
    if "loop" not in traffic:
        raise ManifestError("the traffic mix names no loop")
    return _module("loops", traffic["loop"], root).Loop


def _reports(metric: dict, cell_name: str) -> bool:
    return cell_name in metric.get("workloads", [cell_name])


def metrics(manifest: dict, cell_name: str, traced: bool) -> list:
    """The metric entries a run of ``cell_name`` reports: its end-to-end
    metrics untraced, its per-layer metrics traced.  A per-layer metric
    without ``workloads`` goes wherever the end-to-end metric it moves is
    reported."""
    e2e = [m for m in manifest["end_to_end"] if _reports(m, cell_name)]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [
        m
        for m in manifest["per_layer"]
        if (cell_name in m["workloads"] if "workloads" in m else m["moves"] in moved)
    ]
