"""A copy of the benchmark with small cells that run on the CPU.

``make_root`` copies ``BENCHMARK.json`` and the benchmark's directory into a
temporary root and adds, as files only, two small configurations of the
dense GQA family, their traffic, their limits, and the CPU's peaks (a
stand-in so that the readers run; no number read with it is a chip's).
"""
from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks", "chip")

TINY = {
    "name": "tiny-gqa", "source_url": "test", "arch": "dense_gqa",
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "use_sliding_window": False,
    "vocab_size": 500, "architecture": {"qkv_bias": True},
}
TINY_WINDOWED = dict(TINY, name="tiny-gqa-windowed", tie_word_embeddings=False,
                     use_sliding_window=True, sliding_window=48,
                     architecture={"qkv_bias": False})
LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.05, "change_gap": 0.1}


def _dump(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def add_cell(root, name, config, traffic_name, traffic, chips):
    """Add a cell to the copy at ``root`` by files and entries only."""
    bench_path = os.path.join(root, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    cfg_file = f"benchmarks/chip/configs/{config['name']}.json"
    _dump(os.path.join(root, cfg_file), config)
    _dump(os.path.join(root, "benchmarks/chip/traffic",
                       traffic_name + ".json"), traffic)
    _dump(os.path.join(root, "benchmarks/chip/checks", name + ".json"), LIMITS)
    if config["name"] not in {c["name"] for c in bench["configs"]}:
        bench["configs"].append({"name": config["name"], "source": "test",
                                 "file": cfg_file, "reduced": [],
                                 "why": "test"})
    bench["workloads"].append({"name": name, "config": config["name"],
                               "traffic": traffic_name, "chips": chips,
                               "why": "test"})
    _dump(bench_path, bench)


def make_root(tmp):
    root = str(tmp)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks", "chip"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    peaks_path = os.path.join(root, "benchmarks/chip/peaks.json")
    with open(peaks_path) as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = dict(peaks["devices"]["TPU v5 lite"])
    _dump(peaks_path, peaks)
    add_cell(root, "tiny-gqa.train.s64", TINY, "train.tiny.s64",
             {"kind": "train", "mesh": {"data": 1, "model": 1}, "seq": 64,
              "batch_per_data_shard": 4}, 1)
    add_cell(root, "tiny-gqa-windowed.train.dp2tp2.s64", TINY_WINDOWED,
             "train.tiny.dp2tp2.s64",
             {"kind": "train", "mesh": {"data": 2, "model": 2}, "seq": 64,
              "batch_per_data_shard": 2}, 4)
    return root


def import_harness():
    """The benchmark's modules, as ``run.py`` imports them."""
    for p in (BENCH, os.path.join(REPO, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import harness
    import spec
    return harness, spec
