"""The check that decides ``correct``, at a small size on the CPU.

The program's step agrees with the plain reference; the control (the same
step with bfloat16 weights, the precision below the configuration's
float32) and each planted fault of a one-chip training cell turn
``correct`` false.  Every run here skips only the harness's look for a
chip; the rest of a run, window and reference included, is the real one.
"""
import time

import jax.numpy as jnp
import pytest

from bench_fixtures import import_harness, make_root

harness, spec = import_harness()
import calibrate  # noqa: E402
import check  # noqa: E402
import faults  # noqa: E402

CELL = "tiny-gqa.train.s64"


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return spec.load_cell(CELL, make_root(tmp_path_factory.mktemp("root")))


def _run(cell, **kw):
    return harness.run(cell, 2 ** 33 + 17, 0.3, False, t_start=time.time(),
                       require_tpu=False, **kw)


def test_sound_step_agrees_with_reference(cell):
    res = _run(cell)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"       # the numbers compared come last
    assert set(res["metrics"]) == {"train_tokens_per_s", "mfu",
                                   "step_ms_p95", "setup_s"}


def test_control_in_bfloat16_fails_the_check(cell):
    import jax
    nums = calibrate.readings(cell, 5, jax.devices()[:1],
                              ["program", "control"])
    assert check.judge(nums["program"], cell.limits)
    assert not check.judge(nums["control"], cell.limits)
    # bfloat16 weights swallow AdamW's first small steps
    assert nums["control"]["change_gap"]["value"] > cell.limits["change_gap"]


@pytest.mark.parametrize("fault", ["unchanged", "layer_lost", "half_batch"])
def test_planted_fault_turns_correct_false(cell, fault):
    from repro.train.step import make_train_step
    if fault == "half_batch":
        res = _run(cell, labels_fault=faults.half_batch)
    else:
        res = _run(cell, make_step=getattr(faults, fault)(make_train_step))
    assert not res["correct"], res["check"]


def test_half_batch_fault_keeps_one_row_half_its_positions():
    import numpy as np
    one = faults.half_batch(np.zeros((1, 8), np.int32))
    assert (one[0, :4] == 0).all() and (one[0, 4:] == -1).all()
    two = faults.half_batch(np.zeros((4, 8), np.int32))
    assert (two[:2] == 0).all() and (two[2:] == -1).all()
    assert jnp.asarray(two).dtype == jnp.int32
