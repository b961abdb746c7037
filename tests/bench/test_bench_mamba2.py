"""The Mamba-2 configuration of the benchmark, at a small size on the CPU.

A tiny cell of the ``mamba2`` arch (2 layers, d 64, state 16, head size 16,
8 heads; 512 tokens a row, so that the program's 256-token chunks pass a
state from one to the next) is added to a copy of the benchmark by files
only.  The program's steps agree with ``reference/mamba2.py``; the state
dropped between chunks, half the batch and an unchanged state each turn the
check false; the FLOP counts equal hand counts and the dots of the compiled
step; the scan's ops carry the ``ssd`` scope and its readers read them.
"""
import contextlib
import json
import os
import re
import time
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from bench_fixtures import BENCH, add_cell, import_harness, make_root
from test_bench_scopes import _reading, _top_level

harness, spec = import_harness()
import check  # noqa: E402
import faults  # noqa: E402
import hlo  # noqa: E402
import scopes  # noqa: E402

with open(os.path.join(BENCH, "configs", "mamba2-130m.json")) as _f:
    PUBLISHED = json.load(_f)
TINY = dict(PUBLISHED, name="tiny-mamba2", n_layer=2, d_model=64,
            vocab_size=500,
            assumed=dict(PUBLISHED["assumed"], d_state=16, headdim=16))
CELL = "tiny-mamba2.train.s512"
SEED = 2 ** 33 + 17
# Float32 on the CPU, where the program and the reference agree to about
# 1e-6: each limit a tenth or less of what the dropped state reads here
# (grad_gap 0.017, change_gap 0.016; loss_gap 1e-5 moves too little).
LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "change_gap": 1e-3}
ARCH = spec._load_module(os.path.join(BENCH, "arch", "mamba2.py"),
                         "bench_arch_mamba2_test")


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("root"))
    add_cell(root, CELL, TINY, "train.tiny.s512",
             {"kind": "train", "mesh": {"data": 1, "model": 1}, "seq": 512,
              "batch_per_data_shard": 2}, 1)
    with open(os.path.join(root, "benchmarks/chip/checks", CELL + ".json"),
              "w") as f:
        json.dump(LIMITS, f)
    return spec.load_cell(CELL, root)


def test_program_agrees_with_reference(cell):
    res = harness.run(cell, SEED, 0.3, False, t_start=time.time(),
                      require_tpu=False)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "mfu",
                                   "step_ms_p95", "setup_s"}


def dropped_state(ssd_chunked):
    """The fault of the new mechanism: the state carried between chunks
    dropped, so that every chunk starts from zero (each chunk run as a row
    of its own)."""
    def fault(x, dt, a, b, c, *, chunk, h0=None):
        bsz, l, h, p = x.shape
        q = min(chunk, l)
        rows = lambda v: v.reshape(bsz * (l // q), q, *v.shape[2:])
        y, last = ssd_chunked(rows(x), rows(dt), a, rows(b), rows(c),
                              chunk=q)
        return y.reshape(x.shape), last
    return fault


def _steps(cell, variant):
    """Steps 1-3 of the program, sound or with one fault planted."""
    import repro.models.ssm as ssm
    from repro.train.step import make_train_step
    make_step = (faults.unchanged(make_train_step) if variant == "unchanged"
                 else None)
    t = harness.Trainer(cell, SEED, jax.devices(), make_step=make_step)
    with (mock.patch.object(ssm, "ssd_chunked", dropped_state(
            ssm.ssd_chunked)) if variant == "dropped_state"
          else contextlib.nullcontext()):
        t.start()
    prog = t.first_steps(faults.half_batch if variant == "half_batch"
                         else None)
    t.free()
    return prog, t.shapes


@pytest.fixture(scope="module")
def readings(cell):
    """The check's numbers of each variant against one reference run."""
    progs = {v: _steps(cell, v) for v in ("sound", "dropped_state",
                                          "half_batch", "unchanged")}
    prog, shapes = progs["sound"]
    ref = harness.reference_readings(cell, shapes, SEED, prog["batches"],
                                     jax.devices()[:1])
    names = check.slice_names(shapes)
    return {v: check.numbers(p, ref, names) for v, (p, _) in progs.items()}


@pytest.mark.parametrize("variant, correct", [
    ("sound", True), ("dropped_state", False), ("half_batch", False),
    ("unchanged", False)])
def test_check_separates_faults(cell, readings, variant, correct):
    assert check.judge(readings[variant], cell.limits) is correct, \
        readings[variant]


def test_dropped_state_fault_changes_only_later_chunks():
    import repro.models.ssm as ssm
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (2, 512, 4, 8))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, 512, 4)) - 3.0)
    b, c = (jax.random.normal(k, (2, 512, 8)) for k in ks[2:])
    a = -jnp.arange(1.0, 5.0)
    y, _ = ssm.ssd_chunked(x, dt, a, b, c, chunk=256)
    yf, _ = dropped_state(ssm.ssd_chunked)(x, dt, a, b, c, chunk=256)
    assert jnp.allclose(y[:, :256], yf[:, :256], rtol=1e-6, atol=1e-6)
    assert not jnp.allclose(y[:, 256:], yf[:, 256:], rtol=1e-2, atol=1e-3)


# mamba2-130m, per layer: in-projection 768 x (2 x 1536 + 2 x 128 + 24) =
# 2,574,336; out-projection 1536 x 768 = 1,179,648; sum 3,753,984.  Tied
# head 768 x 50,288 = 38,621,184.  SSD forward a token a layer at Q = 256,
# N = 128, H = 24, P = 64: 128.5 x 256 + 128.5 x 3,072 + 4 x 196,608 =
# 32,896 + 394,752 + 786,432 = 1,214,080.
MAMBA2_S2048 = 6 * (24 * 3_753_984 + 38_621_184) + 3 * 24 * 1_214_080


def test_model_flops_per_token_matches_hand_count():
    assert MAMBA2_S2048 == 859_714_560
    assert ARCH.model_flops_per_token(PUBLISHED, 2048) == MAMBA2_S2048
    assert ARCH.program_config(PUBLISHED).vocab_size == 50_288


def test_executed_matmul_flops_matches_hand_count():
    # a token a layer, forward: projections 2 x 3,753,984 = 7,507,968; the
    # full 256 x 256 C.B^T, 2 x 256 x 128 = 65,536; the intra-chunk output,
    # 2 x 256 x 1,536 = 786,432; states and state output, 4 x 196,608 =
    # 786,432.  Sum 9,146,368.  Backward twice that plus 4 x 24 x (128 + 64)
    # = 18,432; the recompute all but the out-projection (2,359,296).
    fwd = 7_507_968 + 65_536 + 786_432 + 786_432
    layer = fwd + (2 * fwd + 18_432) + (fwd - 2_359_296)
    t = 16 * 2048
    head = 2 * t * 768 * 50_432             # the padded vocabulary
    assert ARCH.executed_matmul_flops(
        PUBLISHED, 16, 2048, remat=True, padded_vocab=50_432) == (
        t * 24 * layer + 3 * head)
    assert ARCH.executed_matmul_flops(
        PUBLISHED, 16, 2048, remat=False, padded_vocab=50_432) == (
        t * 24 * (layer - fwd + 2_359_296) + 3 * head)


@pytest.fixture(scope="module")
def compiled(cell):
    """The tiny cell's step as the harness compiles it (remat on)."""
    t = harness.Trainer(cell, 5, jax.devices())
    assert t.tcfg.remat
    t.start()
    return t, t.compiled.as_text()


_SHAPE_RE = re.compile(r"\w+\[([\d,]*)\]")


def _dims(line_rest):
    return [int(d) for d in _SHAPE_RE.search(line_rest).group(1).split(",")
            if d]


def dot_flops(text: str) -> int:
    """2 x output elements x contracted size of every dot of the compiled
    module, each times the trip count of the loops around it."""
    prog = hlo.Program(text)
    trips = prog.trip_counts()
    total = 0
    for comp, instrs in prog.comps.items():
        shapes = {n: _dims(l.split("=", 1)[1]) for n, _, _, l in instrs
                  if _SHAPE_RE.search(l.split("=", 1)[1])}
        for _, op, _, line in instrs:
            if op != "dot":
                continue
            lhs = shapes[re.search(r"dot\(%?([\w.\-]+)", line).group(1)]
            dims = re.search(r"lhs_contracting_dims=\{([\d,]*)\}",
                             line).group(1)
            k = 1
            for i in dims.split(","):
                k *= lhs[int(i)] if i else 1
            n = 1
            for d in _dims(line.split("=", 1)[1]):
                n *= d
            total += 2 * n * k * trips[comp]
    return total


def test_executed_matmul_flops_equals_compiled_dots(cell, compiled):
    """Within 1%: the out-projection left out of the recompute is 2.6% of
    the count at this size, the four small gradient products 0.2%.  (The
    two agree exactly with JAX 0.9's einsum paths.)"""
    t, text = compiled
    counted = ARCH.executed_matmul_flops(
        cell.config, cell.global_batch, cell.seq, remat=True,
        padded_vocab=t.cfg.padded_vocab)
    assert counted == pytest.approx(dot_flops(text), rel=0.01)


def _ssd(op_name):
    return "/ssd/" in op_name


def test_scan_ops_carry_ssd_scope_in_every_phase(compiled):
    names = scopes.op_names(compiled[1])
    ssd = [n for n in names.values() if _ssd(n)]
    assert {scopes.phase(n) for n in ssd} == {"forward", "backward",
                                              "recompute"}
    assert {scopes.block(n) for n in ssd} == {"ssm"}
    assert any("transpose(" in n for n in ssd)


def _synthetic(text):
    prog = hlo.Program(text)
    ops = _top_level(prog, text)
    events = {"devices": {"/device:TPU:0": [
        (n, 1e3 * i, 1e3 * (i + 1)) for i, n in enumerate(ops)]},
        "host": [("window", 0.0, 1e3 * len(ops))]}
    return _reading(events, prog.op_classes(), text), len(ops) * 1e-3


@pytest.fixture(scope="module")
def readers():
    """The readers of the benchmark's Mamba-2 cell."""
    return spec.load_cell("mamba2-130m.train.s2048").readers


def test_ssm_and_ssd_readers_on_synthetic_trace(readers, compiled):
    """The compiled step's instructions laid out as 1 us op events: both
    readers read, the scan inside its block, the block inside the step."""
    r, busy_ms = _synthetic(compiled[1])
    ssm_ms = readers["ssm_ms"].read(r)
    ssd_ms = readers["ssd_ms"].read(r)
    assert 0 < ssd_ms < ssm_ms <= busy_ms
    assert readers["attention_ms"].read(r) == 0.0


def test_ssd_reader_silent_without_ssd_scope(readers, compiled):
    """A program built before the scan had its scope (the ``ssm`` block
    only): ``ssd_ms`` has nothing to read and raises nothing."""
    r, _ = _synthetic(compiled[1].replace("/ssd/", "/"))
    assert readers["ssd_ms"].read(r) is None
    assert readers["ssm_ms"].read(r) > 0
