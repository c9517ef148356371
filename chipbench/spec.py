"""Find everything a cell needs by its name in ``BENCHMARK.json``.

Each configuration, traffic mix, workload, per-layer metric, kernel cost
and model-family reference is a file of its own under ``chipbench/``:

    configs/<config>.json      published sizes, source, reduced, assumed
    workloads/<cell>.json      engine sizes, the correctness limit, why
    traffic/<traffic>.json     parameters of the one traffic generator
    metrics/<metric>.py        ``read(run) -> float | None``
    costs/<kernel>.py          operations and bytes of one kernel call
    references/<family>.py     the plain float32 forward of a family
    peaks.json                 published peaks keyed by ``device_kind``

A later cell, configuration, metric or kernel cost is new files plus new
``BENCHMARK.json`` entries: nothing here is edited for it.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(REPO / "BENCHMARK.json")


def _module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.parent.name + "_" + path.stem.replace(".", "_")
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(name: str) -> dict:
    return _json(HERE / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return _json(HERE / "traffic" / f"{name}.json")


def metric(name: str):
    return _module(HERE / "metrics" / f"{name}.py")


def cost(kernel: str):
    return _module(HERE / "costs" / f"{kernel}.py")


def reference(family: str):
    return _module(HERE / "references" / f"{family}.py")


def peaks(device_kind: str) -> dict:
    table = _json(HERE / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r} in chipbench/peaks.json")
    return table["devices"][device_kind]


def _applies(metric_entry: dict, cell: str) -> bool:
    return "workloads" not in metric_entry or cell in metric_entry["workloads"]


def cell(name: str, bench: dict | None = None) -> dict:
    """The cell's ``BENCHMARK.json`` entry joined with its workload file,
    its configuration, its traffic mix and the metrics it reports."""
    bench = bench or benchmark()
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[0]
    wl = _json(HERE / "workloads" / f"{name}.json")
    for key in ("config", "traffic"):
        if wl[key] != entry[key]:
            raise ValueError(f"workloads/{name}.json names {key} "
                             f"{wl[key]!r}, BENCHMARK.json {entry[key]!r}")
    return {
        "name": name, "chips": entry["chips"], "workload": wl,
        "config": config(entry["config"]),
        "traffic": traffic(entry["traffic"]),
        "end_to_end": [m for m in bench["end_to_end"] if _applies(m, name)],
        "per_layer": [m for m in bench["per_layer"] if _applies(m, name)],
    }
