"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
Everything else is a file found by name, so that a later change adds a
configuration, a cell or a metric by adding files and entries only:

* ``configs/<config>.json``     the published config as run (``file`` in
                                ``BENCHMARK.json``); its ``arch`` names
                                ``arch/<arch>.py`` (program adapter, weights,
                                FLOPs), whose ``REFERENCE`` names
                                ``reference/<name>.py``;
* ``traffic/<traffic>.json``    mesh, sequence length and batch of the step;
* ``checks/<cell>.json``        the limit of each number the check compares;
* ``metrics/<metric>.py``       the reader of one per-layer metric;
* ``peaks.json``                the chip's peaks, keyed by ``device_kind``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.relpath(HERE, ROOT)


def _load_module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Dict[str, float]
    arch: ModuleType
    reference: ModuleType
    end_to_end: List[dict]
    per_layer: List[dict]        # BENCHMARK.json entries that this cell reads
    readers: Dict[str, ModuleType]
    bench_dir: str

    @property
    def mesh_shape(self):
        return (self.traffic["mesh"]["data"], self.traffic["mesh"]["model"])

    @property
    def global_batch(self) -> int:
        return self.traffic["batch_per_data_shard"] * self.mesh_shape[0]

    @property
    def seq(self) -> int:
        return self.traffic["seq"]


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(entries)}")
    w = entries[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    bench_dir = os.path.join(root, BENCH_DIR)
    config = _json(os.path.join(root, cfg_entry["file"]))
    traffic = _json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))
    mesh = traffic["mesh"]
    if mesh["data"] * mesh["model"] != w["chips"]:
        raise ValueError(f"{name}: mesh {mesh} does not hold "
                         f"{w['chips']} chips")
    arch = _load_module(
        os.path.join(bench_dir, "arch", config["arch"] + ".py"),
        "bench_arch_" + config["arch"])
    reference = _load_module(
        os.path.join(bench_dir, "reference", arch.REFERENCE + ".py"),
        "bench_reference_" + arch.REFERENCE)
    per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
    readers = {m["name"]: _load_module(
        os.path.join(bench_dir, "metrics", m["name"] + ".py"),
        "bench_metric_" + m["name"].replace(".", "_")) for m in per_layer}
    return Cell(
        name=name, chips=w["chips"], config=config, traffic=traffic,
        limits=_json(os.path.join(bench_dir, "checks", name + ".json")),
        arch=arch, reference=reference,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=per_layer, readers=readers, bench_dir=bench_dir)


def peaks(device_kind: str, bench_dir: str = HERE) -> dict:
    table = _json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; known: {sorted(table)}")
    return table[device_kind]
