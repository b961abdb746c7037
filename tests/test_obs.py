"""Observability layer (ISSUE 8): trace recorder + Chrome Trace export,
deterministic meters, search/FlowSim/dynamics telemetry, the export CLI,
and measured-vs-modeled collective probes."""
import itertools
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.paper_claims import _placement_search_problem

from repro.ccl.cost import CostParams, algo_cost, cost_terms
from repro.ccl.select import FlowSim
from repro.codesign import (ClusterDynamics, CodesignProblem, DynamicsReport,
                            Event, JobSpec, PlanSpace, SearchResult, plan,
                            plan_cluster, search)
from repro.codesign.report import CodesignReport
from repro.configs import get_config
from repro.core.demand import CommDemand, CommTask, ComputeTask
from repro.core.demand_builder import DemandParams, build_demand
from repro.core.types import MeshConfig, SHAPES_BY_NAME
from repro.net.simulate import link_rate_series
from repro.net.topology import dgx_cluster, fat_tree, ring
from repro.obs import (EXPOSED_CNAME, Meters, Trace, timeline_tracks,
                       trace_from_cluster, trace_from_dynamics,
                       trace_from_report, trace_from_search, validate_chrome)
from repro.obs.export import build_trace, detect_kind, export_file
from repro.obs.export import main as export_main
from repro.sched.flows import JobProfile, stagger_jobs
from repro.sched.tasks import simulate_iteration
from repro.ccl.select import select_for_task

CFG = get_config("qwen2-0.5b")
SHAPE = SHAPES_BY_NAME["train_4k"]
DP2_TP8 = MeshConfig(shape=(2, 8), axis_names=("data", "model"))


@pytest.fixture(scope="module")
def dgx_plan():
    topo = dgx_cluster(2)
    rep = plan(CodesignProblem(CFG, SHAPE, DP2_TP8, topo,
                               space=PlanSpace().pinned(policy="priority")))
    return rep, topo


@pytest.fixture(scope="module")
def placement_search_result():
    problem = _placement_search_problem()
    return search(problem, budget=6), problem.topo


# ---------------------------------------------------------------------------
# Meters
# ---------------------------------------------------------------------------


def test_meters_counters_and_observations():
    m = Meters()
    m.incr("a")
    m.incr("a", 2.0)
    m.incr("b")
    assert m.get("a") == 3.0 and m.get("b") == 1.0 and m.get("zzz") == 0.0
    assert m.ratio("a", "b") == 0.75  # a / (a + b)
    assert m.ratio("nope", "also_nope") is None
    m.observe("x", 2.0)
    m.observe("x", 4.0)
    snap = m.snapshot()
    assert snap["x.count"] == 2.0 and snap["x.sum"] == 6.0
    assert snap["x.min"] == 2.0 and snap["x.max"] == 4.0
    assert list(snap) == sorted(snap)  # key-sorted flat dict


def test_meters_time_uses_injected_clock():
    ticks = itertools.count()
    m = Meters(clock=lambda: float(next(ticks)))
    with m.time("work"):
        pass
    snap = m.snapshot()
    assert snap["work.count"] == 1.0 and snap["work.sum"] == 1.0


def test_meters_merge():
    a, b = Meters(), Meters()
    a.incr("n", 2.0)
    b.incr("n", 3.0)
    b.observe("o", 1.0)
    a.merge(b)
    snap = a.snapshot()
    assert snap["n"] == 5.0 and snap["o.count"] == 1.0


# ---------------------------------------------------------------------------
# Trace recorder + validator
# ---------------------------------------------------------------------------


def test_trace_event_format_and_ordering():
    tr = Trace()
    tr.process(2, "late", sort_index=5)
    tr.process(1, "early")
    tr.thread(1, 0, "t0")
    tr.span("s", 1e-6, 2e-6, pid=1, tid=0, cat="c", args={"k": 1})
    tr.counter("cnt", 0.0, {"b": 2.0, "a": 1.0}, pid=1, tid=1)
    tr.instant("i", 0.0, pid=2, tid=0, scope="p")
    evs = tr.events()
    # metadata first, then events sorted by (pid, tid, ts, ph, name)
    metas = [e for e in evs if e["ph"] == "M"]
    assert evs[:len(metas)] == metas
    assert [e["name"] for e in metas] == ["process_name", "process_name",
                                         "process_sort_index", "thread_name"]
    span = next(e for e in evs if e["ph"] == "X")
    assert span["ts"] == 1.0 and span["dur"] == 2.0  # seconds -> us
    assert validate_chrome(tr.to_chrome()) == []
    # negative durations are clamped at record time
    tr.span("neg", 0.0, -1.0, pid=1, tid=0)
    assert validate_chrome(tr.to_chrome()) == []


def test_validate_chrome_catches_malformed_docs():
    assert validate_chrome({}) == ["traceEvents missing or not a list"]
    bad = {"traceEvents": [
        {"ph": "Z", "name": "x", "pid": 0, "tid": 0, "ts": 0},
        {"ph": "X", "pid": 0, "tid": 0, "ts": 0, "dur": 1},
        {"ph": "X", "name": "x", "pid": 0, "tid": 0, "ts": "soon", "dur": 1},
        {"ph": "X", "name": "x", "pid": 0, "tid": 0, "ts": 0, "dur": -5},
        {"ph": "i", "name": "x", "pid": 0, "tid": 0, "ts": 0, "s": "q"},
    ]}
    problems = validate_chrome(bad)
    assert len(problems) == 5
    # overlapping spans on one (pid, tid) track
    overlap = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 0, "tid": 0, "ts": 0.0, "dur": 10.0},
        {"ph": "X", "name": "b", "pid": 0, "tid": 0, "ts": 5.0, "dur": 10.0},
    ]}
    assert any("overlaps" in p for p in validate_chrome(overlap))
    # same spans on different tracks: fine
    ok = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 0, "tid": 0, "ts": 0.0, "dur": 10.0},
        {"ph": "X", "name": "b", "pid": 0, "tid": 1, "ts": 5.0, "dur": 10.0},
    ]}
    assert validate_chrome(ok) == []


def test_timeline_tracks_exposed_spans():
    tr = Trace()
    timeline = [("comp:c0", 0.0, 1.0), ("comm:g", 0.0, 2.0),
                ("comp:c1", 2.0, 3.0)]
    timeline_tracks(tr, 1, "job", timeline, task_exposed_s={"g": 1.0})
    evs = tr.events()
    exposed = [e for e in evs if e["ph"] == "X"
               and e["name"] == "exposed:g"]
    assert len(exposed) == 1
    # stall interval = the last exposed_s seconds before the comm retires
    assert exposed[0]["ts"] == 1.0 * 1e6 and exposed[0]["dur"] == 1.0 * 1e6
    assert exposed[0]["cname"] == EXPOSED_CNAME
    comm = next(e for e in evs if e["ph"] == "X" and e["name"] == "g")
    assert comm["args"]["exposed_s"] == 1.0


# ---------------------------------------------------------------------------
# Report -> trace: determinism, round-trip, link counters
# ---------------------------------------------------------------------------


def test_report_trace_deterministic_and_roundtrips(dgx_plan):
    rep, topo = dgx_plan
    assert rep.timeline, "plan() must persist the executed timeline"
    doc = rep.to_trace(topo=topo).to_chrome()
    assert validate_chrome(doc) == []
    phs = {e["ph"] for e in doc["traceEvents"]}
    assert {"M", "X", "C"} <= phs
    cats = {e.get("cat") for e in doc["traceEvents"]}
    assert {"compute", "comm"} <= cats
    # deterministic: same report, same bytes
    assert rep.to_trace(topo=topo).to_json() == \
        rep.to_trace(topo=topo).to_json()
    # from_dict-loaded report renders the identical trace (sim=None)
    loaded = CodesignReport.from_dict(json.loads(json.dumps(rep.to_dict())))
    assert loaded.sim is None
    assert loaded.to_trace(topo=topo).to_json() == \
        rep.to_trace(topo=topo).to_json()
    # without the live topology there are no counter tracks, still valid
    bare = loaded.to_trace().to_chrome()
    assert validate_chrome(bare) == []
    assert not any(e["ph"] == "C" for e in bare["traceEvents"])


def test_report_trace_link_counters(dgx_plan):
    rep, topo = dgx_plan
    doc = rep.to_trace(topo=topo, max_links=4).to_chrome()
    counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert counters and all("bytes_per_s" in e["args"] for e in counters)
    names = {e["name"] for e in counters}
    assert all(n.startswith("link ") and n.endswith(" B/s") for n in names)
    assert len(names) <= 4


def test_sim_result_to_trace(dgx_plan):
    rep, _ = dgx_plan
    assert rep.sim is not None
    doc = rep.sim.to_trace(label="iter").to_chrome()
    assert validate_chrome(doc) == []
    assert any(e["ph"] == "X" for e in doc["traceEvents"])


# ---------------------------------------------------------------------------
# Scheduler timeline invariants under preemption
# ---------------------------------------------------------------------------


def test_preempt_truncates_stale_timeline_spans():
    """A preempted comm task's first timeline segment must end at the
    preemption point — the old code left the full-duration span in
    place, overlapping the preemptor on the single network resource."""
    dem = CommDemand()
    dem.compute_tasks = [ComputeTask("c0", 0, 10e-3)] + [
        ComputeTask(f"c{i}", 0, 25e-3) for i in range(1, 6)
    ] + [ComputeTask("opt", 0, 1e-3)]
    # grad starts right after c0; the blocking a2a only becomes ready
    # mid-grad (after c1), so the preemption truncates a span that has
    # genuinely run for a while
    dem.comm_tasks = [
        CommTask("grad", "all_reduce", int(100e-3 * 50e9), (0, 1),
                 after_compute=("c0",), before_compute="opt", slack=1.0),
        CommTask("a2a", "all_to_all", int(20e-3 * 50e9 * 2), (0, 1),
                 after_compute=("c1",), before_compute="c2", slack=0.0),
    ]
    cp = CostParams(alpha=1e-6, link_bw=50e9)

    def cost(t):
        algo = "direct" if t.primitive == "all_to_all" else "ring"
        return algo_cost(t.primitive, algo, t.size_bytes, len(t.group), cp)

    r = simulate_iteration(dem, cost, "preempt")
    comm = sorted((s, e, n) for n, s, e in r.timeline
                  if n.startswith("comm:"))
    assert len(comm) >= 3  # grad split around the preempting a2a
    for (s0, e0, n0), (s1, e1, n1) in zip(comm, comm[1:]):
        assert s1 >= e0 - 1e-12, f"{n1} overlaps {n0}"
    assert validate_chrome(r.to_trace().to_chrome()) == []


# ---------------------------------------------------------------------------
# net.simulate.link_rate_series
# ---------------------------------------------------------------------------


def test_link_rate_series_integrates_to_bytes():
    topo = ring(4)
    task = CommTask("ar", "all_reduce", 1 << 20, tuple(topo.accelerators))
    from repro.ccl.select import flows_on_topology
    fs = flows_on_topology(topo, task, "ring")
    series = link_rate_series(topo, [(fs, 0.0, 2.0), (fs, 3.0, 4.0)])
    assert series, "ring all-reduce must load some links"
    for points, ts in ((list(v), [t for t, _ in v])
                       for v in series.values()):
        assert ts == sorted(ts)          # breakpoints sorted
        assert points[-1][1] == 0.0      # closes back at zero rate
        assert all(r >= 0.0 for _, r in points)
    # integral over time recovers 2x the per-link bytes of one pass
    from repro.net.simulate import link_utilization
    util = link_utilization(topo, fs)
    for link, points in series.items():
        integral = sum(r * (points[i + 1][0] - t)
                       for i, (t, r) in enumerate(points[:-1]))
        assert integral == pytest.approx(2.0 * util[link], rel=1e-9)


# ---------------------------------------------------------------------------
# FlowSim memoization counters
# ---------------------------------------------------------------------------


def test_flowsim_cache_stats():
    topo = dgx_cluster(2)
    model = FlowSim(topo)
    task = CommTask("g", "all_reduce", 1 << 20, tuple(topo.accelerators))
    model.cost(task, "ring")
    model.cost(task, "ring")
    model.cost(task, "bidir_ring")
    stats = model.cache_stats()
    assert stats["flowsim[cap=None].cost.miss"] == 2.0
    assert stats["flowsim[cap=None].cost.hit"] == 1.0
    assert stats["flowsim[cap=None].cost.hit_rate"] == pytest.approx(1 / 3)
    assert stats["flowsim[cap=None].cost.entries"] == 2.0
    capped = FlowSim(topo, switch_capacity=4)
    capped.cost(task, "ring")
    assert "flowsim[cap=4].cost.miss" in capped.cache_stats()


# ---------------------------------------------------------------------------
# Search telemetry: per-candidate records + JSON round-trip
# ---------------------------------------------------------------------------


def test_search_telemetry_and_roundtrip(placement_search_result):
    res, topo = placement_search_result
    tel = res.telemetry
    assert tel["plan_evals"] == len(res.frontier)
    assert tel["charged_evals"] <= res.evaluated + tel["memo_hits"]
    assert tel["infeasible"] == sum(1 for c in res.frontier
                                    if not c.feasible)
    assert any(k.startswith("flowsim[") for k in tel["counters"])
    for c in res.frontier:
        assert c.phase in ("sweep", "hillclimb", "baseline")
        assert c.requests >= 1
        assert (c.reason is None) == c.feasible
    # JSON round-trip preserves the per-candidate telemetry
    d = json.loads(json.dumps(res.to_dict()))
    res2 = SearchResult.from_dict(d)
    assert res2.telemetry == tel
    assert [(c.phase, c.requests, c.reason) for c in res2.frontier] == \
        [(c.phase, c.requests, c.reason) for c in res.frontier]
    assert res2.to_dict() == d
    # search trace: winner tracks + frontier instants + jct counters,
    # identical when rebuilt from the persisted dict
    tr = res.to_trace(topo=topo)
    assert validate_chrome(tr.to_chrome()) == []
    evs = tr.to_chrome()["traceEvents"]
    assert sum(1 for e in evs if e["ph"] == "i"
               and e["name"] == "candidate") == len(res.frontier)
    assert any(e["ph"] == "i" and e["name"] == "telemetry" for e in evs)
    assert any(e["ph"] == "C" and e["name"] == "frontier jct" for e in evs)
    assert res2.to_trace(topo=topo).to_json() == tr.to_json()


def test_search_infeasible_candidates_carry_reason(
        placement_search_result):
    import dataclasses
    from repro.codesign import Objective
    res, _ = placement_search_result
    # a link-imbalance cap between the frontier's best and worst rules
    # out some candidates but keeps the winner feasible, so search()
    # returns and the pruned candidates carry their reason strings
    caps = sorted({c.worst_link_bytes for c in res.frontier})
    assert len(caps) >= 2, "fixture frontier must spread worst-link bytes"
    cap = (caps[0] + caps[-1]) / 2.0
    tight = dataclasses.replace(
        _placement_search_problem(),
        objective=Objective(max_worst_link_bytes=cap))
    tres = search(tight, budget=6)
    pruned = [c for c in tres.frontier if not c.feasible]
    assert pruned and tres.telemetry["infeasible"] == len(pruned)
    assert all("worst_link_bytes" in c.reason for c in pruned)
    assert all(c.reason is None for c in tres.frontier if c.feasible)


# ---------------------------------------------------------------------------
# Cluster + dynamics: stagger meters, fake clock, trace round-trips
# ---------------------------------------------------------------------------


def test_stagger_jobs_counts_evals():
    jobs = [JobProfile("a", 0.012, 0.008), JobProfile("b", 0.010, 0.010)]
    m = Meters()
    stagger_jobs(jobs, grid=5, meters=m)
    # zero-phase baseline + the 5-point grid over job b's phase
    assert m.get("flows.stagger.evals") == 6.0


def _dyn_setup():
    DP2 = MeshConfig(shape=(2,), axis_names=("data",), data_axes=("data",),
                     model_axes=())
    dpp = DemandParams(zero1=False)
    topo = fat_tree(num_hosts=4, gpus_per_host=1, hosts_per_rack=1,
                    racks_per_pod=1, agg_redundancy=2, nic_bw=2e9,
                    agg_bw=8e9, oversub=4.0, pcie_bw=4e9)
    jobs = [JobSpec("a", CFG, SHAPE, DP2, policy="serial", devices=(0, 2),
                    dp_params=dpp),
            JobSpec("b", CFG, SHAPE, DP2, policy="serial", devices=(1, 3),
                    dp_params=dpp)]
    return jobs, topo


def test_dynamics_injected_clock_is_deterministic():
    jobs, topo = _dyn_setup()
    ticks = itertools.count()
    dyn = ClusterDynamics(jobs, topo, grid=4, horizon_iters=6,
                          compare_full=True,
                          clock=lambda: float(next(ticks)))
    rep = dyn.run([Event("link_degrade", time=1.0,
                         link=("tor0", "agg0.0"), factor=0.5),
                   Event("straggler", time=2.0, name="a", factor=2.0)])
    # the fake clock advances 1.0 per call: replan_s and full_replan_s
    # are exact, not wall-clock noise
    assert [r.replan_s for r in rep.records] == [1.0, 1.0]
    assert [r.full_replan_s for r in rep.records] == [1.0, 1.0]
    tel = rep.telemetry
    assert tel["dynamics.mode.incremental"] == 2.0
    assert tel["dynamics.event.link_degrade"] == 1.0
    assert tel["dynamics.dirty_jobs.count"] == 2.0
    # report + trace round-trip through JSON
    d = json.loads(json.dumps(rep.to_dict()))
    rep2 = DynamicsReport.from_dict(d, {s.name: s for s in jobs})
    assert rep2.telemetry == tel and rep2.to_dict() == d
    tr = rep.to_trace(topo=topo)
    assert validate_chrome(tr.to_chrome()) == []
    assert rep2.to_trace(topo=topo).to_json() == tr.to_json()
    evs = tr.to_chrome()["traceEvents"]
    assert any(e["name"] == "link_degrade:tor0->agg0.0" for e in evs)
    assert any(e["ph"] == "X" and e["name"] == "replan[incremental]"
               for e in evs)
    assert any(e["ph"] == "C" and e["name"] == "worst stretch"
               for e in evs)


def test_cluster_report_trace(dgx_plan):
    jobs, topo = _dyn_setup()
    rep = plan_cluster(jobs, topo, grid=4, horizon_iters=6)
    tr = rep.to_trace(topo=topo)
    assert validate_chrome(tr.to_chrome()) == []
    evs = tr.to_chrome()["traceEvents"]
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert any(n.startswith("a phase=") for n in names)
    assert any(n.startswith("b phase=") for n in names)
    assert "cluster" in names


# ---------------------------------------------------------------------------
# cost_terms decomposition
# ---------------------------------------------------------------------------


def test_cost_terms_sum_to_algo_cost():
    cp = CostParams(alpha=1e-6, link_bw=50e9)
    for algo in ("ring", "bidir_ring", "halving_doubling", "ring+q8"):
        terms = cost_terms("all_reduce", algo, 1 << 24, 8, cp)
        total = algo_cost("all_reduce", algo, 1 << 24, 8, cp)
        assert terms["total_s"] == pytest.approx(total)
        assert terms["latency_s"] + terms["bandwidth_s"] + \
            terms["codec_s"] == pytest.approx(total)
        assert terms["latency_s"] >= 0 and terms["bandwidth_s"] >= 0
    assert cost_terms("all_reduce", "ring+q8", 1 << 24, 8,
                      cp)["codec_s"] > 0
    assert cost_terms("all_reduce", "ring", 1 << 24, 1, cp) == {
        "latency_s": 0.0, "bandwidth_s": 0.0, "codec_s": 0.0,
        "total_s": 0.0}


# ---------------------------------------------------------------------------
# Probes (single-device degenerate case; the 8-device path runs in the
# paper_claims smoke via run_multidevice)
# ---------------------------------------------------------------------------


def test_probe_all_reduce_local():
    from repro.obs.probe import (CollectiveProbe, model_vs_measured,
                                 probe_all_reduce, probes_to_trace)
    pr = probe_all_reduce("ring", 1 << 12, repeats=2, warmup=1)
    assert pr.measured_s > 0 and len(pr.runs_s) == 2
    assert pr.algorithm == "ring"
    d = pr.to_dict()
    assert CollectiveProbe.from_dict(d).to_dict() == d
    doc = probes_to_trace([pr]).to_chrome()
    assert validate_chrome(doc) == []
    # measured and modeled land on separate threads of one process
    tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert tids == {0, 1}
    mm = model_vs_measured([pr])
    assert mm["count"] == 1
    if pr.world > 1:
        assert pr.modeled_s > 0 and mm["rows"][0]["ratio"] == pr.ratio
    with pytest.raises(ValueError):
        probe_all_reduce("nope", 1 << 12)


# ---------------------------------------------------------------------------
# Export CLI
# ---------------------------------------------------------------------------


def test_detect_kind_and_export_file(tmp_path, dgx_plan):
    rep, topo = dgx_plan
    d = rep.to_dict()
    assert detect_kind(d) == "report"
    assert detect_kind({"best": d, "frontier": []}) == "search"
    assert detect_kind({"jobs": [], "staggered_jct": {}}) == "cluster"
    assert detect_kind({"records": [], "final": {}}) == "dynamics"
    with pytest.raises(ValueError):
        detect_kind({"mystery": 1})
    # build_trace == the report's own to_trace (minus link counters,
    # which need the live topology)
    assert build_trace(d).to_json() == rep.to_trace().to_json()
    src = tmp_path / "rep.json"
    src.write_text(json.dumps(d))
    out = export_file(str(src))
    assert out == str(tmp_path / "rep.trace.json")
    doc = json.loads((tmp_path / "rep.trace.json").read_text())
    assert validate_chrome(doc) == []
    # CLI entry point with explicit output path
    dst = tmp_path / "explicit.trace.json"
    assert export_main([str(src), "-o", str(dst)]) == 0
    assert json.loads(dst.read_text()) == doc


def test_export_cli_subprocess(tmp_path, dgx_plan):
    rep, _ = dgx_plan
    src = tmp_path / "rep.json"
    src.write_text(json.dumps(rep.to_dict()))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.obs.export", str(src)],
        capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stderr
    assert (tmp_path / "rep.trace.json").exists()
