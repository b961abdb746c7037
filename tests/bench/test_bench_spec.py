"""BENCHMARK.json against the files it names, and the command's refusals."""
import json
import os
import re
import shutil
import subprocess

import pytest

from bench_fixtures import BENCH, REPO, import_harness

import_harness()
import spec  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_names_and_files_follow_the_contract():
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    assert "setup_s" in e2e
    for entry in (BENCHMARK["configs"] + BENCHMARK["workloads"]
                  + BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
    for c in BENCHMARK["configs"]:
        assert os.path.isfile(os.path.join(REPO, c["file"]))
        assert c["file"].startswith(BENCHMARK["paths"][0] + "/")
    for w in BENCHMARK["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert sum(w["chips"] == 4 for w in BENCHMARK["workloads"]) <= max(
        1, len(BENCHMARK["workloads"]) // 2)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCHMARK["per_layer"]])
def test_metric_reader_agrees_with_its_entry(metric):
    entry = {m["name"]: m for m in BENCHMARK["per_layer"]}[metric]
    mod = spec._load_module(os.path.join(BENCH, "metrics", metric + ".py"),
                            "bench_metric_test_" + metric)
    assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
        entry["unit"], entry["layer"], entry["moves"], entry["source"])
    assert entry["moves"] in {m["name"] for m in BENCHMARK["end_to_end"]}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_every_cell_loads_with_its_files(cell):
    c = spec.load_cell(cell)
    assert c.global_batch * c.seq > 0
    assert set(c.limits) == {"loss_gap", "grad_gap", "change_gap"}
    assert {m["name"] for m in c.end_to_end} >= {"setup_s",
                                                 "train_tokens_per_s"}
    assert c.per_layer


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def _command(cwd, env):
    cell = BENCHMARK["workloads"][0]["name"]
    return subprocess.run(
        BENCHMARK["command"] + ["--workload", cell, "--seed", "3",
                                "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_tpu():
    proc = _command(REPO, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_command_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in BENCHMARK["paths"]:
        shutil.copytree(os.path.join(REPO, p), os.path.join(tmp_path, p),
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = _command(str(tmp_path), dict(env, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
