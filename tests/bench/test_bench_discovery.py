"""Cells, configurations and per-layer metrics are found as files by name:
adding them to a copy of the benchmark takes no edit of its code."""
import os
import time

from bench_fixtures import TINY, add_cell, import_harness, make_root

harness, spec = import_harness()

METRIC = '''
LAYER = "input"
UNIT = "count"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(r):
    return float(len(r.data_wait_s))
'''


def test_new_cell_and_metric_are_added_by_files_only(tmp_path):
    import json
    root = make_root(tmp_path)
    add_cell(root, "tiny-gqa.train.s32", dict(TINY), "train.tiny.s32",
             {"kind": "train", "mesh": {"data": 1, "model": 1}, "seq": 32,
              "batch_per_data_shard": 2}, 1)
    with open(os.path.join(root, "benchmarks/chip/metrics/feeds_seen.py"),
              "w") as f:
        f.write(METRIC)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["per_layer"].append({
        "name": "feeds_seen", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "input",
        "moves": "train_tokens_per_s", "workloads": ["tiny-gqa.train.s32"]})
    with open(path, "w") as f:
        json.dump(bench, f)

    cell = spec.load_cell("tiny-gqa.train.s32", root)
    assert cell.seq == 32 and cell.global_batch == 2
    assert "feeds_seen" in cell.readers
    res = harness.run(cell, 9, 0.3, True, t_start=time.time(),
                      require_tpu=False)
    assert res["correct"], res["check"]
    assert res["metrics"]["feeds_seen"]["value"] >= 3
    assert res["device"]["window_s"] > 0
