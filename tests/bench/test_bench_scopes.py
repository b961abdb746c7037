"""Charging device time to the program's blocks and phases (``scopes.py``).

The op-name rule on the forms JAX writes; the train step compiled on the CPU
for a small dense GQA configuration with remat on, its instructions laid out
as synthetic 1 us op events; and the trace of the 2x2 cell recorded on a TPU
v5e, read with the op names of that step as the TPU compiler compiles it
with the scopes in.
"""
import gzip
import json
import os
import re

import jax
import pytest

from bench_fixtures import BENCH, REPO, import_harness, make_root

harness, spec = import_harness()
import devtrace as tr  # noqa: E402
import hlo  # noqa: E402
import scopes  # noqa: E402

BLOCK_METRICS = ("attention_ms", "ffn_ms", "vocab_ms", "optimizer_ms",
                 "unscoped_ms")
PHASE_METRICS = ("forward_ms", "backward_ms", "optimizer_ms")
METRICS = BLOCK_METRICS + ("forward_ms", "backward_ms", "recompute_ms")
CELL_2X2 = "h2o-danube-1.8b.train.dp2tp2.s4096"
RECORDED = os.path.join(BENCH, "testdata", CELL_2X2 + ".trace.json.gz")
OP_NAMES = os.path.join(BENCH, "testdata", CELL_2X2 + ".op_names.json.gz")

# The TPU compiler names an instruction it merges from two reshapes after the
# tail of their op names: "closed_call/reshape;reshape" gave reshape_reshape.22
# without the scopes, "closed_call/attention/reshape;attention/reshape" gives
# a plain reshape, and the reshapes numbered after it shift.  The step is the
# same instruction for instruction; these are the recorded trace's only names
# that the scopes changed.
RENAMED = {"reshape.561", "reshape.654", "reshape.655", "reshape_reshape.22",
           "reshape_reshape.23"}


def _line(op_name):
    meta = (f', metadata={{op_name="{op_name}" source_file="m.py" '
            f'source_line=3}}' if op_name is not None else "")
    return f"  %fusion.1 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f{meta}"


@pytest.mark.parametrize("op_name, block, phase", [
    ("jit(train_step)/jvp()/while/body/closed_call/attention/dot_general",
     "attention", "forward"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "ffn/mul", "ffn", "backward"),
    ("jit(train_step)/jvp(vocab)/reduce_sum", "vocab", "forward"),
    ("jit(train_step)/transpose(jvp(vocab))/mul", "vocab", "backward"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/while/body/dynamic_update_slice",
     "attention", "recompute"),
    ("jit(train_step)/jvp()/while/body/closed_call/moe/dot_general", "moe",
     "forward"),
    ("jit(train_step)/optimizer/sub", "optimizer", "optimizer"),
    ("jit(train_step)/optimizer/mul;jit(train_step)/transpose(jvp(vocab))/mul",
     "optimizer", "optimizer"),
    ("jit(train_step)/jvp()/while/body/closed_call/ssm/mul;"
     "jit(train_step)/jvp()/while/body/closed_call/ffn/mul", "ssm", "forward"),
    ("jit(train_step)/transpose(jvp())/while/body/dynamic_update_slice",
     "unscoped", "backward"),
    ("jit(train_step)/jvp()/while/body/dynamic_slice", "unscoped", "forward"),
    (None, "unscoped", "forward"),
])
def test_op_name_rule(op_name, block, phase):
    name = scopes.op_names(_line(op_name)).get("fusion.1", "")
    assert (scopes.block(name), scopes.phase(name)) == (block, phase)


def _readers(cell=CELL_2X2, root=REPO):
    readers = spec.load_cell(cell, root).readers
    return {m: readers[m] for m in METRICS}


def _reading(events, classes, hlo_text, steps=1):
    return harness.Reading(
        events=events, classes=classes, hlo_text=hlo_text, steps=steps,
        data_wait_s=[], model_flops_per_step=1.0, matmul_flops_per_step=1.0,
        chips=len(events["devices"]), peak={})


def _top_level(prog: hlo.Program, text: str):
    """Instructions of the entry computation and of the loops it runs, in
    order, loops and calls left out."""
    entry = re.search(r"^ENTRY %?([\w.\-]+)", text, re.M).group(1)
    out, todo, seen = [], [entry], set()
    while todo:
        comp = todo.pop(0)
        if comp in seen:
            continue
        seen.add(comp)
        for name, op, _, line in prog.comps[comp]:
            if op in hlo.CONTROL_OPS:
                todo += re.findall(r"\b(?:body|condition|to_apply)=%?([\w.\-]+)",
                                   line)
            else:
                out.append(name)
    return out


@pytest.fixture(scope="module")
def compiled_tiny(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("bench"))
    cell = spec.load_cell("tiny-gqa.train.s64", root)
    trainer = harness.Trainer(cell, 5, jax.devices())
    assert trainer.tcfg.remat
    trainer.start()
    return root, trainer.compiled.as_text()


def test_synthetic_trace_of_compiled_step_partitions(compiled_tiny):
    root, text = compiled_tiny
    prog = hlo.Program(text)
    ops = _top_level(prog, text)
    evs = [(n, 1e3 * i, 1e3 * (i + 1)) for i, n in enumerate(ops)]
    events = {"devices": {"/device:TPU:0": evs},
              "host": [("window", 0.0, 1e3 * len(ops))]}
    r = _reading(events, prog.op_classes(), text)
    got = {m: mod.read(r) for m, mod in _readers("tiny-gqa.train.s64",
                                                 root).items()}
    assert all(v is not None for v in got.values()), got
    for m in ("attention_ms", "ffn_ms", "vocab_ms", "optimizer_ms",
              "recompute_ms"):
        assert got[m] > 0, m
    busy_ms = len(ops) * 1e-3
    assert sum(got[m] for m in BLOCK_METRICS) == pytest.approx(busy_ms,
                                                               rel=1e-12)
    assert sum(got[m] for m in PHASE_METRICS) == pytest.approx(busy_ms,
                                                               rel=1e-12)
    # Instructions are not time: the CPU compile's no-op bookkeeping counts
    # as much as a matmul here, so the coverage bound is the recorded
    # trace's.  What is checked here is that no op of a layer's body escaped
    # its block: the body's unscoped ops are the scan's remat copies only.
    names = scopes.op_names(text)
    escaped = [(n, names[n]) for n in ops if n in names
               and scopes.block(names[n]) == scopes.UNSCOPED
               and "closed_call/" in names[n]
               and not names[n].endswith("closed_call/remat2")]
    assert not escaped


def test_readers_are_silent_without_scopes():
    """A program built without the scopes carries none: nothing to read,
    nothing raised."""
    data = tr.load(RECORDED)
    r = _reading({"devices": data["devices"], "host": data["host"]},
                 data["classes"], "ENTRY %main.1 () -> f32[] {\n}\n")
    assert {m: mod.read(r) for m, mod in _readers().items()} == {
        m: None for m in METRICS}


@pytest.fixture(scope="module")
def recorded_with_scopes():
    data = tr.load(RECORDED)
    with gzip.open(OP_NAMES, "rt") as f:
        names = json.load(f)
    return data, names


def test_recorded_trace_keeps_its_instruction_names(recorded_with_scopes):
    data, names = recorded_with_scopes
    seen = {e[0] for evs in data["devices"].values() for e in evs}
    assert seen - set(names) == RENAMED


def test_recorded_trace_partitions_by_block_and_phase(recorded_with_scopes):
    data, names = recorded_with_scopes
    events = {"devices": data["devices"], "host": data["host"]}
    text = "\n".join(f'  %{k} = f32[] custom-call(), metadata={{op_name="{v}"}}'
                     for k, v in names.items())
    r = _reading(events, data["classes"], text)
    got = {m: mod.read(r) for m, mod in _readers().items()}
    busy_ms = sum(tr.busy_ns(events).values()) / len(events["devices"]) / 1e6
    for m in ("attention_ms", "ffn_ms", "vocab_ms", "optimizer_ms",
              "recompute_ms"):
        assert got[m] > 0, m
    assert got["recompute_ms"] < got["backward_ms"]
    assert sum(got[m] for m in BLOCK_METRICS) == pytest.approx(busy_ms,
                                                               rel=1e-3)
    assert sum(got[m] for m in PHASE_METRICS) == pytest.approx(busy_ms,
                                                               rel=1e-3)
    assert got["unscoped_ms"] < 0.1 * busy_ms
