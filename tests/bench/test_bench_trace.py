"""The reduction from a trace to numbers, on a trace recorded on the chip.

``benchmarks/chip/testdata/`` keeps, in ``devtrace.load_xplane``'s form, a
short stretch of a traced window of the 2x2 cell on a TPU v5e (four TPU
cores' op events, the harness's host spans) with the class of each op read
from that run's compiled step.
"""
import os

import pytest

from bench_fixtures import BENCH, import_harness

import_harness()
import devtrace as tr  # noqa: E402

RECORDED = os.path.join(BENCH, "testdata",
                        "h2o-danube-1.8b.train.dp2tp2.s4096.trace.json.gz")


@pytest.fixture(scope="module")
def recorded():
    data = tr.load(RECORDED)
    events = {"devices": data["devices"], "host": data["host"]}
    return events, data["classes"], data["expect"]


def test_union_merges_overlapping_and_nested_events():
    evs = [("a", 0, 10), ("loop", 5, 30), ("b", 12, 20), ("c", 40, 50)]
    assert tr.union(evs) == [(0, 30), (40, 50)]


def test_busy_and_idle_share_of_recorded_trace(recorded):
    events, _, expect = recorded
    lo, hi = tr.window(events)
    busy = tr.busy_ns(events)
    assert len(busy) == 4
    for dev, ns in busy.items():
        assert 0 < ns <= hi - lo
        assert ns == pytest.approx(expect["busy_ns"][dev], rel=1e-9)


def test_collective_and_matmul_time_of_recorded_trace(recorded):
    events, classes, expect = recorded
    coll = tr.class_ns(events, classes, "collective")
    mm = tr.class_ns(events, classes, "matmul")
    busy = tr.busy_ns(events)
    for dev in busy:
        assert 0 < coll[dev] < busy[dev]
        assert 0 < mm[dev] < busy[dev]
        assert coll[dev] == pytest.approx(expect["collective_ns"][dev],
                                          rel=1e-9)


def test_breakdown_of_recorded_trace(recorded):
    events, classes, expect = recorded
    top = tr.top_ops(events, classes)
    assert 0 < len(top) <= 10
    assert all(t > 0 for _, t in top)
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)
    assert not any(name.endswith("(control)") for name, _ in top)
    gaps = tr.idle_gaps(events)
    assert len(gaps) <= 10
    assert all(label in ("data", "dispatch", "wait", "host")
               for label, _ in gaps)
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)
