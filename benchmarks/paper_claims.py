"""One benchmark per Table-I row / survey claim.

Each function returns (derived_metric, details) where ``derived`` is the
headline number comparable against the paper's reported effect.  The paper
is a survey, so 'reproduction' means: our implementation of each row's
MECHANISM must show the claimed effect direction and magnitude within our
cost/simulation models (EXPERIMENTS.md §Paper-claims records the comparison).
"""
from __future__ import annotations

import json
import math
import os
import sys
from typing import Dict, Optional, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.ccl.algorithms import generate_flows
from repro.ccl.cost import CostParams, algo_cost
from repro.ccl.select import (AlphaBeta, FlowSim, select_algorithm,
                              select_for_task)
from repro.ccl.synth import Sketch, synthesize, synthesize_schedule
from repro.codesign import (Choice, ClusterDynamics, CodesignProblem,
                            CotenantPulse, Event, JobSpec, PlanSpace,
                            Search, ServingSLO, ServingSpec, plan,
                            plan_cluster, plan_iteration, search,
                            serving_problem)
from repro.configs import get_config
from repro.core.demand import CommTask
from repro.core.demand_builder import (DemandParams, build_demand,
                                       janus_traffic_ratio)
from repro.core.types import (MeshConfig, SHAPES_BY_NAME, SINGLE_POD_MESH,
                              ShapeConfig)
from repro.core.types import ModelConfig
from repro.net.simulate import simulate_flowset
from repro.net.topology import (dgx_cluster, fat_tree, full_mesh, ring,
                                torus2d, torus3d)
from repro.parallel.pipeline import bubble_fraction, iteration_time
from repro.sched.arrivals import Arrival, TraceArrivals
from repro.sched.atp import atp_traffic
from repro.sched.flows import JobProfile, stagger_jobs
from repro.sched.tasks import simulate_iteration

CP_ICI = CostParams(alpha=1e-6, link_bw=50e9)
CP_IB = CostParams(alpha=5e-6, link_bw=25e9)


def _cost_fn(cp: CostParams):
    def cost(t: CommTask) -> float:
        if t.primitive == "all_reduce":
            return select_algorithm(t.primitive, t.size_bytes, len(t.group),
                                    cp)[1]
        algo = "direct" if t.primitive == "all_to_all" else "ring"
        return algo_cost(t.primitive, algo, t.size_bytes, len(t.group), cp)
    return cost


# ---------------------------------------------------------------------------
# Row: Megatron-LM — 74% of linear scaling on 512 GPUs
# ---------------------------------------------------------------------------


def bench_megatron_tp_scaling() -> Tuple[float, Dict]:
    """8.3B-param GPT, TP within 8-GPU hosts + DP across hosts.  Scaling
    efficiency at 512 GPUs = per-GPU throughput / single-host per-GPU
    throughput, from the task-graph sim with NVLink intra / IB inter costs.
    Paper: 77% at 8 GPUs (vs linear), 74% at 512."""
    import dataclasses
    cfg = dataclasses.replace(
        get_config("granite-3-8b"), name="megatron-8.3b", num_layers=72,
        d_model=3072, num_heads=32, num_kv_heads=32, head_dim=96,
        d_ff=12288, vocab_size=51200, ffn_act="gelu")
    shape = SHAPES_BY_NAME["train_4k"]
    nvlink = CostParams(alpha=1e-6, link_bw=150e9)

    def efficiency(n_gpus: int) -> float:
        mesh = MeshConfig(shape=(n_gpus // 8, 8),
                          axis_names=("data", "model"))
        dem = build_demand(cfg, shape, mesh, DemandParams(mfu=0.52))

        def cost(t):
            cp = nvlink if t.primitive == "all_reduce" and \
                len(t.group) <= 8 else CP_IB
            return _cost_fn(cp)(t)

        r = simulate_iteration(dem, cost, "priority")
        # fraction of ideal (communication-free) linear scaling
        return r.compute_time / r.jct

    eff8, eff512 = efficiency(8), efficiency(512)
    return eff512, {"paper_512": 0.74, "paper_8": 0.77,
                    "ours_8": round(eff8, 3), "ours_512": round(eff512, 3),
                    "basis": "compute / JCT (ideal-linear fraction)"}


# ---------------------------------------------------------------------------
# Row: PTD-P — interleaved pipeline; 52% of peak on 3072 GPUs
# ---------------------------------------------------------------------------


def bench_ptdp_interleaved() -> Tuple[float, Dict]:
    """Interleaved schedule shrinks the bubble (p-1)/m -> (p-1)/(m*v).
    Derived: bubble reduction factor at PTD-P's setting (p=8, m=8, v=4)
    and the resulting iteration-time speedup including the extra comm."""
    p, m, v = 8, 8, 4
    b1 = bubble_fraction(p, m, 1)
    bv = bubble_fraction(p, m, v)
    t_chunk, t_comm = 10e-3, 0.4e-3
    t1 = iteration_time(p, m, 1, t_chunk, t_comm)
    tv = iteration_time(p, m, v, t_chunk, t_comm)
    return b1 / bv, {"bubble_v1": b1, "bubble_v4": bv,
                     "iter_speedup": round(t1 / tv, 3),
                     "paper": "bubble / v; interleaving trades bubble for comm"}


# ---------------------------------------------------------------------------
# Row: Lina — prioritize All-to-All; up to 1.73x
# ---------------------------------------------------------------------------


def bench_lina_priority() -> Tuple[float, Dict]:
    """Lina row, two parts.
    (a) dbrx-132b end-to-end: overlap policies vs no-overlap across fabric
        speeds — in homogeneous per-layer MoE traffic FIFO is already
        near-optimal, so the gain is the hide-the-gradients effect.
    (b) the preemption mechanism itself (Lina's actual contribution:
        All-to-All preempts a long gradient sync): an adversarial micro
        task graph where FIFO strands a blocking A2A behind a gradient."""
    cfg = get_config("dbrx-132b")
    shape = SHAPES_BY_NAME["train_4k"]
    dem = build_demand(cfg, shape, SINGLE_POD_MESH,
                       DemandParams(mfu=0.5, grad_bytes=4))
    best = {"e2e_speedup": 1.0}
    for bw in (25e9, 12e9, 8e9, 5e9):
        cost = _cost_fn(CostParams(alpha=5e-6, link_bw=bw))
        serial = simulate_iteration(dem, cost, "serial")
        pre = simulate_iteration(dem, cost, "preempt")
        sp = serial.jct / pre.jct
        if sp > best["e2e_speedup"]:
            best = {"e2e_speedup": round(sp, 3), "bw_GBps": bw / 1e9,
                    "serial_s": round(serial.jct, 2),
                    "preempt_s": round(pre.jct, 2)}

    # (b) preemption micro-benchmark: a long gradient sync starts just
    # before a blocking A2A becomes ready; enough downstream compute exists
    # to hide the paused gradient's remainder.
    from repro.core.demand import CommDemand, CommTask, ComputeTask
    micro = CommDemand()
    micro.compute_tasks = [ComputeTask("c0", 0, 10e-3)] + [
        ComputeTask(f"c{i}", 0, 25e-3) for i in range(1, 6)
    ] + [ComputeTask("opt", 0, 1e-3)]
    micro.comm_tasks = [
        CommTask("grad", "all_reduce", int(100e-3 * 50e9), (0, 1),
                 after_compute=("c0",), before_compute="opt", slack=1.0),
        CommTask("a2a", "all_to_all", int(20e-3 * 50e9 * 2), (0, 1),
                 after_compute=("c0",), before_compute="c1", slack=0.0),
    ]
    cost = _cost_fn(CostParams(alpha=1e-6, link_bw=50e9))
    fifo = simulate_iteration(micro, cost, "fifo").jct
    pre = simulate_iteration(micro, cost, "preempt").jct
    best["micro_fifo_ms"] = round(fifo * 1e3, 1)
    best["micro_preempt_ms"] = round(pre * 1e3, 1)
    best["micro_preempt_speedup"] = round(fifo / pre, 2)
    return max(best["e2e_speedup"], best["micro_preempt_speedup"]), \
        dict(best, paper="up to 1.73x")


# ---------------------------------------------------------------------------
# Row: Janus — data-centric MoE; up to 16x traffic reduction
# ---------------------------------------------------------------------------


def bench_janus_data_centric() -> Tuple[float, Dict]:
    shape = SHAPES_BY_NAME["train_4k"]
    out = {}
    for arch in ("dbrx-132b", "deepseek-v2-236b", "jamba-1.5-large-398b"):
        r = janus_traffic_ratio(get_config(arch), shape, SINGLE_POD_MESH)
        out[arch] = round(r["ratio"], 2)
    return max(out.values()), dict(out, paper="up to 16x when experts < data")


# ---------------------------------------------------------------------------
# Rows: NCCL / SCCL — algorithm selection & synthesis speedups
# ---------------------------------------------------------------------------


def bench_nccl_selection() -> Tuple[float, Dict]:
    """Auto-selection vs always-ring across message sizes (NCCL row).
    Derived: max speedup of selected vs ring (small messages)."""
    worst = 1.0
    cross = None
    for exp in range(10, 31):
        n = 2 ** exp
        best_name, best_cost, costs = select_algorithm(
            "all_reduce", n, 16, CP_ICI)
        sp = costs["ring"] / best_cost
        worst = max(worst, sp)
        if cross is None and best_name in ("ring", "bidir_ring"):
            cross = n  # smallest size where bandwidth-optimal wins
    return worst, {"max_speedup_vs_ring": round(worst, 2),
                   "bandwidth_crossover_bytes": cross,
                   "paper": "NCCL picks latency-optimal for small msgs"}


def bench_sccl_synthesis() -> Tuple[float, Dict]:
    """Synthesized All-Gather vs ring All-Gather on the heterogeneous DGX
    topology (SCCL: 1.14-2.2x on All-Gather).  Simulated completion time."""
    topo = dgx_cluster(2)
    group = tuple(topo.accelerators)
    speedups = {}
    for size in (2 ** 16, 2 ** 20, 2 ** 24):
        task = CommTask("ag", "all_gather", size, group)
        ring_fs = generate_flows(task, "ring")
        t_ring = simulate_flowset(topo, ring_fs)
        syn_fs = synthesize(topo, task, Sketch(max_hops=4))
        speedups[size] = round(t_ring / syn_fs.makespan, 2)
    best = max(speedups.values())
    return best, dict({f"{k>>10}KiB": v for k, v in speedups.items()},
                      paper="1.14-2.2x vs NCCL all-gather")


# ---------------------------------------------------------------------------
# Row: TACCL — sketch shrinks synthesis; 2.36x BERT (we report collective
# speedup of sketch-guided vs unguided greedy on heterogeneous topology)
# ---------------------------------------------------------------------------


def bench_taccl_sketch() -> Tuple[float, Dict]:
    topo = dgx_cluster(2)
    group = tuple(topo.accelerators)
    task = CommTask("ag", "all_gather", 2 ** 20, group)
    t_free = synthesize(topo, task, Sketch(max_hops=8)).makespan
    # sketch: prefer NVLink, single NIC hop (enter host via its NIC only)
    allowed = {(u, v) for u, v, d in topo.links()}
    t_sketch = synthesize(
        topo, task, Sketch(allowed_links=allowed, max_hops=3)).makespan
    return t_free / t_sketch, {
        "unguided_ms": round(t_free * 1e3, 3),
        "sketch_ms": round(t_sketch * 1e3, 3),
        "paper": "sketch guidance improves quality AND search time"}


# ---------------------------------------------------------------------------
# Row: SYNDICATE — overlap/schedule co-optimization, 1.21-1.74x
# ---------------------------------------------------------------------------


def bench_syndicate_overlap() -> Tuple[float, Dict]:
    """Best scheduling policy vs no-overlap across three archs (the
    'jointly optimize schedule+execution' effect)."""
    shape = SHAPES_BY_NAME["train_4k"]
    cost = _cost_fn(CostParams(alpha=5e-6, link_bw=10e9))
    out = {}
    for arch in ("granite-3-8b", "dbrx-132b", "jamba-1.5-large-398b"):
        dem = build_demand(get_config(arch), shape, SINGLE_POD_MESH,
                           DemandParams(grad_chunks=4))
        serial = simulate_iteration(dem, cost, "serial").jct
        best = min(simulate_iteration(dem, cost, p).jct
                   for p in ("fifo", "priority", "slack"))
        out[arch] = round(serial / best, 3)
    return max(out.values()), dict(out, paper="1.21x-1.74x")


# ---------------------------------------------------------------------------
# Rows: TPUv4 / TopoOpt — topology matched to traffic
# ---------------------------------------------------------------------------


def bench_topology_match() -> Tuple[float, Dict]:
    """Ring All-Reduce on matched (torus) vs mismatched (oversubscribed
    fat-tree) topologies at 256 accelerators (TPUv4/TopoOpt rows)."""
    n, size = 256, 256 * 2 ** 20
    task = CommTask("ar", "all_reduce", size, tuple(range(n)))
    fs = generate_flows(task, "ring")
    t_torus = simulate_flowset(torus2d(16, 16), fs)
    ft = fat_tree(num_hosts=n // 8, gpus_per_host=8, oversub=8.0)
    t_ft = simulate_flowset(ft, fs)
    return t_ft / t_torus, {
        "torus_ms": round(t_torus * 1e3, 2),
        "fattree4x_ms": round(t_ft * 1e3, 2),
        "paper": "TopoOpt up to 3.4x; TPUv4 torus suits ring collectives"}


# ---------------------------------------------------------------------------
# Row: CASSINI — multi-job staggering
# ---------------------------------------------------------------------------


def bench_cassini_stagger() -> Tuple[float, Dict]:
    jobs = [JobProfile("jobA", 0.012, 0.008),
            JobProfile("jobB", 0.010, 0.010)]
    phases, base, best = stagger_jobs(jobs, grid=6)
    worst_base = max(base[j.name] / j.period for j in jobs)
    worst_best = max(best[j.name] / j.period for j in jobs)
    return worst_base / worst_best, {
        "unstaggered_slowdown": round(worst_base, 3),
        "staggered_slowdown": round(worst_best, 3),
        "phases_s": [round(p, 4) for p in phases],
        "paper": "staggering peaks recovers contended JCT"}


# ---------------------------------------------------------------------------
# Row: ATP — in-network aggregation
# ---------------------------------------------------------------------------


def bench_atp_aggregation() -> Tuple[float, Dict]:
    topo = fat_tree(8)
    task = CommTask("grad", "all_reduce", 64 * 2 ** 20,
                    tuple(topo.accelerators[:32]))
    ps = topo.accelerators[-1]
    res = atp_traffic(topo, task, ps)
    degraded = atp_traffic(topo, task, ps, switch_capacity=4)
    return res["traffic_reduction"], {
        "traffic_reduction": round(res["traffic_reduction"], 2),
        "speedup": round(res["speedup"], 2),
        "degraded_reduction": round(degraded["traffic_reduction"], 2),
        "paper": "ATP reduces in-network traffic; degrades gracefully"}


# ---------------------------------------------------------------------------
# Sec. II-E / IV-A: vertical co-design (the codesign engine end-to-end)
# ---------------------------------------------------------------------------


def bench_codesign_hierarchical() -> Tuple[float, Dict]:
    """Topology-aware selection (FlowSim pricing on a 2-host DGX) picks the
    hierarchical Intra-Inter all-reduce for large gradient syncs and beats
    topology-blind flat-ring selection — the survey's co-design claim,
    measured end-to-end through demand -> placement -> selection -> JCT."""
    from repro.net.topology import dgx_cluster
    cfg = get_config("granite-3-8b")
    shape = SHAPES_BY_NAME["train_4k"]
    mesh = MeshConfig(shape=(16,), axis_names=("data",),
                      data_axes=("data",), model_axes=())
    topo = dgx_cluster(2)
    dpp = DemandParams(zero1=False)  # gradient sync as all-reduce
    auto = plan_iteration(cfg, shape, mesh, topo, policy="serial",
                          dp_params=dpp)
    ring = plan_iteration(cfg, shape, mesh, topo, policy="serial",
                          dp_params=dpp, force={"all_reduce": "ring"})
    hist = auto.algorithms_by_primitive().get("all_reduce", {})
    return ring.comm_time / auto.comm_time, {
        "selected": hist,
        "auto_comm_s": round(auto.comm_time, 3),
        "ring_comm_s": round(ring.comm_time, 3),
        "auto_jct_s": round(auto.jct, 3),
        "ring_jct_s": round(ring.jct, 3),
        "paper": "Intra-Inter co-design; algorithm choice flips with "
                 "hierarchy (Sec. II-E)"}


def bench_codesign_placement() -> Tuple[float, Dict]:
    """Physical placement of the logical mesh is a co-design knob of its
    own: packed placement keeps TP groups on NVLink, strided round-robin
    scatters them across the NIC tier.  (Written against the declarative
    API: one CodesignProblem, two pinned placements.)"""
    from repro.net.topology import dgx_cluster
    cfg = get_config("granite-3-8b")
    shape = SHAPES_BY_NAME["train_4k"]
    mesh = MeshConfig(shape=(2, 8), axis_names=("data", "model"))
    problem = CodesignProblem(cfg, shape, mesh, dgx_cluster(2),
                              space=PlanSpace().pinned(policy="serial"))
    packed = plan(problem.pinned(placement="packed"))
    strided = plan(problem.pinned(placement="strided"))
    return strided.comm_time / packed.comm_time, {
        "packed_comm_s": round(packed.comm_time, 3),
        "strided_comm_s": round(strided.comm_time, 3),
        "packed_jct_s": round(packed.jct, 3),
        "strided_jct_s": round(strided.jct, 3),
        "paper": "placement is the Para.->Net. arrow of Fig. 5a"}


# ---------------------------------------------------------------------------
# ROADMAP "Placement search" (TopoOpt row, revisited as an optimizer):
# search() over the placement knob of a declarative CodesignProblem
# ---------------------------------------------------------------------------


def _placement_search_problem() -> CodesignProblem:
    """TP-12 over 8-GPU hosts on a GPU-dense oversubscribed fat-tree.
    ``packed`` lands the second TP communicator 8+4 across a host
    boundary — an uneven partition the hierarchical decomposition cannot
    use — so its large activation all-reduces fall back to flat rings
    over the oversubscribed uplinks.  The host-balanced 6+6 split (one
    of ``placement_search``'s generated candidates) restores eligibility
    and search finds it."""
    topo = fat_tree(num_hosts=4, gpus_per_host=8, hosts_per_rack=1,
                    oversub=8.0, pcie_bw=128e9)
    mesh = MeshConfig(shape=(2, 12), axis_names=("data", "model"))
    return CodesignProblem(get_config("qwen2-0.5b"),
                           SHAPES_BY_NAME["train_4k"], mesh, topo,
                           space=PlanSpace(placement=Search()))


def bench_placement_search() -> Tuple[float, Dict]:
    """search() walking the placement knob: derived = packed JCT over the
    searched-best JCT (strictly > 1 when the optimizer earns its keep).
    The winning plan round-trips through CodesignReport.to_dict() so the
    harness persists it in experiments/bench_results.json."""
    problem = _placement_search_problem()
    res = search(problem, budget=12)
    packed = plan(problem.pinned(placement="packed"))
    best = res.best.to_dict()  # JSON-able plan, persisted via run.py
    return packed.jct / res.best.jct, {
        "best_strategy": res.best.placement.strategy,
        "packed_jct_s": round(packed.jct, 3),
        "searched_jct_s": round(res.best.jct, 3),
        "evaluated": res.evaluated,
        "attribution_jct_s": {k: round(v, 4)
                              for k, v in res.attribution.items()},
        "best_algorithms": res.best.algorithms_by_primitive(),
        "best_plan": {"strategy": best["placement"]["strategy"],
                      "devices": best["placement"]["devices"],
                      "jct": best["jct"]},
        "paper": "TopoOpt: topology/placement matched to traffic (up to "
                 "3.4x); here the balanced split unlocks hierarchical"}


# ---------------------------------------------------------------------------
# Sec. IV-A Horizontal: the multi-job cluster planner (CASSINI on real
# CodesignReports, not toy pulse trains)
# ---------------------------------------------------------------------------


def _contended_cluster():
    """Two DP-4 tenants, each straddling both racks of a slow fat-tree, so
    their gradient bursts collide on the tor<->agg uplinks.  The tenants
    run ``policy="serial"`` (no compute/comm overlap): the horizontal
    layer models each job's *exposed* burst, and the CASSINI scenario
    needs that burst to be the full gradient exchange, as in the paper's
    pulse model."""
    topo = fat_tree(num_hosts=4, gpus_per_host=2, hosts_per_rack=2,
                    nic_bw=2e9, agg_bw=8e9, oversub=4.0, pcie_bw=4e9)
    mesh = MeshConfig(shape=(4,), axis_names=("data",), data_axes=("data",),
                      model_axes=())
    cfg = get_config("qwen2-0.5b")
    shape = SHAPES_BY_NAME["train_4k"]
    dpp = DemandParams(zero1=False)
    jobs = [JobSpec("jobA", cfg, shape, mesh, policy="serial",
                    devices=topo.hosts[0] + topo.hosts[2], dp_params=dpp),
            JobSpec("jobB", cfg, shape, mesh, policy="serial",
                    devices=topo.hosts[1] + topo.hosts[3], dp_params=dpp)]
    return jobs, topo


def bench_cluster_planner() -> Tuple[float, Dict]:
    """plan_cluster end-to-end: per-job vertical plans -> shared-link
    detection -> CASSINI phase staggering.  Derived: worst-case JCT
    recovery of staggered vs zero-phase naive."""
    jobs, topo = _contended_cluster()
    rep = plan_cluster(jobs, topo, grid=6)
    return rep.stagger_speedup, {
        "contended_links": len(rep.contended),
        "naive_worst_stretch": round(rep.naive_worst_stretch, 4),
        "staggered_worst_stretch": round(rep.staggered_worst_stretch, 4),
        "phases_s": {n: round(p, 4) for n, p in rep.phases.items()},
        "solo_jct_s": {n: round(v, 3) for n, v in rep.solo_jct.items()},
        "paper": "CASSINI: stagger bursts on shared links to recover JCT"}


# ---------------------------------------------------------------------------
# Sec. IV-A Horizontal: event-driven dynamics with incremental re-planning
# ---------------------------------------------------------------------------


def _dynamic_cluster():
    """Four resident DP-2 tenants on a 4-pod redundant fat-tree.  Each
    tenant pairs two hosts in *adjacent* pods, so the A/B pair lives on
    pods 0-1 and the C/D pair on pods 2-3: a link event in one pod pair
    dirties only the jobs routed through it, which is what makes
    incremental re-planning cheaper than the full search.
    ``agg_redundancy=2`` gives every rack two uplinks, so a single
    tor<->agg failure re-routes instead of partitioning a tenant."""
    topo = fat_tree(num_hosts=8, gpus_per_host=2, hosts_per_rack=2,
                    racks_per_pod=1, agg_redundancy=2, nic_bw=2e9,
                    agg_bw=8e9, oversub=4.0, pcie_bw=4e9)
    mesh = MeshConfig(shape=(2,), axis_names=("data",),
                      data_axes=("data",), model_axes=())
    cfg = get_config("qwen2-0.5b")
    shape = SHAPES_BY_NAME["train_4k"]
    dpp = DemandParams(zero1=False)

    def job(name, devices):
        return JobSpec(name, cfg, shape, mesh, policy="serial",
                       devices=devices, dp_params=dpp)

    jobs = [job("jobA", (0, 4)), job("jobB", (2, 6)),
            job("jobC", (8, 12)), job("jobD", (10, 14))]
    events = [
        Event("job_arrive", time=1.0, job=job("jobE", (1, 5))),
        Event("straggler", time=2.0, name="jobC", factor=1.4),
        Event("link_degrade", time=3.0, link=("tor0", "agg0.0"),
              factor=0.5),
        Event("straggler", time=4.0, name="jobA", factor=1.3),
        Event("link_fail", time=5.0, link=("tor2", "agg2.0")),
        Event("job_depart", time=6.0, name="jobB"),
        Event("straggler", time=7.0, name="jobD", factor=1.2),
        Event("host_fail", time=8.0, host=2),
    ]
    return jobs, topo, events


def bench_replan() -> Tuple[float, Dict]:
    """ClusterDynamics over an 8-event trace (arrival, stragglers, link
    degrade/fail, departure, host failure) with every incremental answer
    priced against a from-scratch ``plan_cluster``.  Derived: aggregate
    wall-clock speedup of incremental re-planning at bounded regret."""
    jobs, topo, events = _dynamic_cluster()
    dyn = ClusterDynamics(jobs, topo, grid=6, compare_full=True)
    rep = dyn.run(events)
    return rep.incremental_speedup, {
        "events": len(rep.records),
        "incremental_events": sum(1 for r in rep.records
                                  if r.mode == "incremental"),
        "incremental_speedup": round(rep.incremental_speedup, 2),
        "worst_regret": round(rep.worst_regret, 4),
        "mean_replan_ms": round(rep.mean_replan_s * 1e3, 2),
        "per_event": [{"kind": r.kind, "target": r.target, "mode": r.mode,
                       "dirty_jobs": r.dirty_jobs,
                       "replan_ms": round(r.replan_s * 1e3, 2),
                       "worst_stretch": round(r.worst_stretch, 4)}
                      for r in rep.records],
        "final_jct_s": {n: round(v, 3) for n, v in
                        rep.final.staggered_jct.items()},
        "paper": "fault tolerance / elasticity (Sec. V): re-plan around "
                 "events instead of re-searching the whole cluster"}


# ---------------------------------------------------------------------------
# Sec. IV-B Host-Net: ATP as a first-class selection candidate
# ---------------------------------------------------------------------------


def bench_atp_candidate() -> Tuple[float, Dict]:
    """In-network aggregation competing in selection on a switched
    fat-tree: derived = atp's speedup over the best host-level algorithm
    for a latency-regime gradient chunk; the switch-memory fallback must
    push selection back to a host algorithm."""
    topo = fat_tree(num_hosts=8, gpus_per_host=1, oversub=4.0)
    task = CommTask("grad", "all_reduce", 2 ** 20,
                    tuple(topo.accelerators))
    sel = select_for_task(task, FlowSim(topo))
    host_best = min(c for a, c in sel.costs.items() if a != "atp")
    capped = select_for_task(task, FlowSim(topo, switch_capacity=4))
    return host_best / sel.costs["atp"], {
        "selected": sel.algorithm,
        "atp_us": round(sel.costs["atp"] * 1e6, 1),
        "host_best_us": round(host_best * 1e6, 1),
        "capped_selected": capped.algorithm,
        "paper": "ATP speeds aggregation; degrades to host agg when "
                 "switch memory is exhausted"}


# ---------------------------------------------------------------------------
# Para. lever 3: gradient compression as a selection candidate
# ---------------------------------------------------------------------------


def _compression_setting():
    """One worker per host on a heavily oversubscribed fat-tree: gradient
    all-reduces are bandwidth-bound, the compression sweet spot."""
    topo = fat_tree(num_hosts=8, gpus_per_host=1, oversub=8.0)
    return topo, tuple(topo.accelerators)


def bench_compression_candidate() -> Tuple[float, Dict]:
    """Compressed candidates (repro.compress) competing in selection under
    a 1% error budget: derived = the chosen codec candidate's speedup over
    the best lossless algorithm for a bandwidth-regime gradient sync; the
    latency-regime chunk must reject compression (codec overhead
    dominates), and plan_iteration must turn the win into lower JCT."""
    topo, group = _compression_setting()
    model = FlowSim(topo)
    big = CommTask("grad", "all_reduce", 64 * 2 ** 20, group)
    lossless = select_for_task(big, model)
    comp = select_for_task(big, model, error_budget=0.01)
    small = CommTask("gchunk", "all_reduce", 2 ** 12, group)
    comp_small = select_for_task(small, model, error_budget=0.01)

    mesh = MeshConfig(shape=(8,), axis_names=("data",), data_axes=("data",),
                      model_axes=())
    dpp = DemandParams(zero1=False)
    cfg = get_config("qwen2-0.5b")
    shape = SHAPES_BY_NAME["train_4k"]
    base = plan_iteration(cfg, shape, mesh, topo, policy="serial",
                          dp_params=dpp)
    budget = plan_iteration(cfg, shape, mesh, topo, policy="serial",
                            dp_params=dpp, error_budget=0.01)
    return lossless.cost / comp.cost, {
        "selected_64MiB": comp.algorithm,
        "lossless_ms": round(lossless.cost * 1e3, 2),
        "compressed_ms": round(comp.cost * 1e3, 2),
        "latency_regime_pick": comp_small.algorithm,
        "e2e_jct_s": {"lossless": round(base.jct, 3),
                      "budget_1pct": round(budget.jct, 3)},
        "wire_GiB_saved": round(budget.wire_bytes_saved / 2 ** 30, 2),
        "paper": "quantization/sparsification shrink the exposed-comm "
                 "term (Shi/Tang quantitative surveys)"}


# ---------------------------------------------------------------------------
# ROADMAP "Overlap-aware co-design": searched gradient bucketing +
# decomposed TP collectives vs the naive overlap schedule
# ---------------------------------------------------------------------------


def _overlap_search_problem() -> CodesignProblem:
    """h2o-danube-1.8b, DP-2 x TP-8 across two PCIe-class 8-GPU hosts
    (64 GB/s intra-host links): bulk TP all-reduces expose real time on
    the slower fabric and gradient buckets compete with them for the
    wire — the regime where the two overlap rewrites (bucket-size
    search, collective-matmul decomposition) pay, not just policy."""
    mesh = MeshConfig(shape=(2, 8), axis_names=("data", "model"))
    space = PlanSpace(bucket_bytes=Search(), decompose=Search(),
                      policy=Choice("fifo", "priority"))
    return CodesignProblem(get_config("h2o-danube-1.8b"),
                           SHAPES_BY_NAME["train_4k"], mesh,
                           dgx_cluster(2, nvlink_bw=64e9), space=space)


def bench_overlap_search() -> Tuple[float, Dict]:
    """search() walking bucket-size x decompose x policy jointly, with
    per-knob JCT attribution, under BOTH cost models.  Naive = the
    overlap everyone ships by default (fifo, per-layer gradient syncs,
    bulk TP collectives); derived = the weaker of the two models'
    naive/searched JCT ratios.  Target: beat the policy-only
    ``syndicate_overlap`` row (1.16x), i.e. reshaping the DAG must buy
    more than reordering it."""
    import dataclasses
    base = _overlap_search_problem()
    details: Dict = {}
    derived = math.inf
    for cm in ("alphabeta", "flowsim"):
        problem = dataclasses.replace(base, cost_model=cm)
        naive = plan(problem.pinned(policy="fifo", bucket_bytes=None,
                                    decompose=False))
        res = search(problem, budget=40)
        derived = min(derived, naive.jct / res.best.jct)
        details[cm] = {
            "naive_jct_s": round(naive.jct, 3),
            "naive_exposed_s": round(naive.exposed_comm, 3),
            "searched_jct_s": round(res.best.jct, 3),
            "searched_exposed_s": round(res.best.exposed_comm, 3),
            "speedup": round(naive.jct / res.best.jct, 3),
            "best_assignment": {k: v for k, v in
                                res.best_assignment.items()},
            "attribution_jct_s": {k: round(v, 4)
                                  for k, v in res.attribution.items()},
            "evaluated": res.evaluated,
            "naive_top_exposed": [(t, round(s, 4)) for t, s in
                                  naive.top_exposed_tasks(3)],
        }
    details["paper"] = ("bucket-size tradeoff (MG-WFBP/ByteScheduler) + "
                        "collective-matmul decomposition (Wang et al. "
                        "ASPLOS'23); must beat policy-only 1.16x")
    return derived, details


# ---------------------------------------------------------------------------
# ROADMAP "Collective synthesis as a plan-space optimizer": the synthesize
# knob — searched schedules as priced candidates, end to end
# ---------------------------------------------------------------------------


def _synth_codesign_problem(cost_model: str = "alphabeta") -> CodesignProblem:
    """qwen2-0.5b TP-8 on a flat 8-GPU full mesh: ~112 KiB latency-regime
    TP all-reduces, where the registry's best (halving-doubling, 6
    serialized steps) pays 3x the synthesized mesh schedule's 2 alphas —
    the regime where a topology-specific schedule wins under the
    closed-form model too, not just under FlowSim's contention pricing."""
    mesh = MeshConfig(shape=(8,), axis_names=("model",), data_axes=(),
                      model_axes=("model",))
    return CodesignProblem(get_config("qwen2-0.5b"),
                           ShapeConfig("synth_tiny", 64, 1, "train"), mesh,
                           full_mesh(8), cost_model=cost_model,
                           space=PlanSpace(synthesize=Search()))


def bench_synth_codesign() -> Tuple[float, Dict]:
    """SCCL/TACCL as a plan-space lever, end to end: ``search()`` walking
    the ``synthesize`` knob must find that synthesized schedules beat the
    registered candidates where topology-specific routing pays (flat
    mesh latency regime, oversubscribed fat-tree broadcast) and never
    get selected where the registry already matches the fabric.

    Derived = the weaker of the two cost models' knob-off/knob-on JCT
    ratios on the locked full-mesh problem (schedule-level fat-tree
    speedups go to details)."""
    import dataclasses
    details: Dict = {}
    # schedule level: broadcast on the oversubscribed fat-tree, where a
    # synthesized schedule crosses the thin tier once and fans out over
    # idle local links, vs binomial paying the thin tier every log-step
    ft = fat_tree(2, 8, oversub=8.0, hosts_per_rack=1)
    group = tuple(ft.accelerators)
    sched_rows: Dict[str, Dict] = {}
    for size in (2 ** 16, 2 ** 20, 2 ** 22):
        task = CommTask("b", "broadcast", size, group)
        fs = synthesize_schedule(ft, task).to_flowset(job_id=task.job_id)
        row = {}
        for model in (AlphaBeta.from_topology(ft), FlowSim(ft)):
            sel = select_for_task(task, model,
                                  extra_flowsets={"synthesized": fs})
            reg = min(v for k, v in sel.costs.items() if k != "synthesized")
            row[type(model).__name__.lower()] = {
                "picked": sel.algorithm,
                "speedup": round(reg / sel.costs["synthesized"], 2)}
        sched_rows[f"{size >> 10}KiB"] = row
    details["fat_tree_broadcast"] = sched_rows
    # plan level: the knob inside search(), per-knob JCT attribution
    derived = math.inf
    for cm in ("alphabeta", "flowsim"):
        prob = _synth_codesign_problem(cm)
        off = plan(prob.pinned(synthesize=False))
        res = search(prob, budget=8)
        derived = min(derived, off.jct / res.best.jct)
        details[cm] = {
            "off_jct_s": round(off.jct, 6),
            "searched_jct_s": round(res.best.jct, 6),
            "speedup": round(off.jct / res.best.jct, 3),
            "best_assignment": dict(res.best_assignment),
            "attribution_jct_s": {k: round(v, 6)
                                  for k, v in res.attribution.items()},
            "n_synthesized_tasks": len(res.best.synthesized_choices),
            "synth_cache": {k: v for k, v in res.telemetry.items()
                            if "synth" in k},
        }
    # the knob declines gracefully: on a plain ring the registry's
    # ring-shaped algorithms already match the fabric
    rprob = dataclasses.replace(_synth_codesign_problem("flowsim"),
                                topo=ring(8))
    rrep = plan(rprob.pinned(synthesize=True))
    details["ring_never_selected"] = {
        "n_synthesized_tasks": len(rrep.synthesized_choices),
        "algorithms": rrep.algorithms_by_primitive()}
    details["paper"] = ("SCCL 1.14-2.2x / TACCL 2.36x: synthesized "
                        "topology-specific schedules as first-class "
                        "priced candidates, lowered to shard_map")
    return derived, details


# ---------------------------------------------------------------------------
# Motivation: exposed communication fraction (up to 60% at Meta)
# ---------------------------------------------------------------------------


def bench_exposed_comm_fraction() -> Tuple[float, Dict]:
    shape = SHAPES_BY_NAME["train_4k"]
    cost = _cost_fn(CP_IB)
    out = {}
    for arch in ("granite-3-8b", "qwen2-0.5b", "dbrx-132b",
                 "deepseek-v2-236b", "jamba-1.5-large-398b"):
        dem = build_demand(get_config(arch), shape, SINGLE_POD_MESH)
        r = simulate_iteration(dem, cost, "serial")
        out[arch] = round(r.exposed_comm / r.jct, 3)
    return max(out.values()), dict(out, paper="up to 60% of iteration time")


# ---------------------------------------------------------------------------
# Serving co-design: SLO-constrained stagger search + training/serving
# co-tenancy on shared fabric (ROADMAP "serving co-design")
# ---------------------------------------------------------------------------


def _serving_cotenant_problem(cost_model: str = "alphabeta"):
    """One serving tenant whose requests arrive in lockstep with a
    training tenant's gradient pulse on an 8x-oversubscribed fat-tree.
    The naive zero-stagger phase collides every prefill batch with the
    training burst; shifting the pulse phase (the ``stagger`` knob)
    dodges it.  Canonical scenario shared with tests/test_serving.py so
    CI assertions and recorded numbers cannot drift."""
    cfg = ModelConfig(name="m", family="dense", source="[bench]",
                      num_layers=8, d_model=1024, num_heads=16,
                      num_kv_heads=8, d_ff=4096, vocab_size=32000)
    topo = fat_tree(4, gpus_per_host=4, oversub=8.0)
    period = 0.01
    arr = TraceArrivals(tuple(Arrival(f"r{k:02d}", k * period, 1024, 32)
                              for k in range(20)))
    pulse = CotenantPulse("train0", period_s=period, comm_s=0.004,
                          demand={(u, v): 1.0
                                  for u, v in topo.graph.edges})
    spec = ServingSpec(name="svc", cfg=cfg, prefill_devices=4,
                       decode_devices=4, arrivals=arr,
                       slo=ServingSLO(ttft_s=0.01, tpot_s=0.002),
                       prefill_batch=1, decode_slots=8, horizon_s=0.25,
                       cotenants=(pulse,))
    return serving_problem(spec, topo, cost_model=cost_model)


def _mixed_serving_cluster():
    """plan_cluster input: a DP-4 training tenant straddling both racks
    next to a disaggregated serving tenant, contending on the tor<->agg
    uplinks.  Requests span the training period, so the naive phase hits
    some prefill bursts with the gradient pulse."""
    topo = fat_tree(num_hosts=4, gpus_per_host=2, hosts_per_rack=2,
                    nic_bw=2e9, agg_bw=8e9, oversub=4.0, pcie_bw=4e9)
    mesh = MeshConfig(shape=(4,), axis_names=("data",),
                      data_axes=("data",), model_axes=())
    cfg = get_config("qwen2-0.5b")
    shape = SHAPES_BY_NAME["train_4k"]
    train = JobSpec("train", cfg, shape, mesh, policy="serial",
                    devices=topo.hosts[0] + topo.hosts[2],
                    dp_params=DemandParams(zero1=False))
    arr = TraceArrivals(tuple(Arrival(f"r{k:02d}", k * 0.4, 1024, 32)
                              for k in range(20)))
    svc = ServingSpec(name="svc", cfg=cfg, prefill_devices=2,
                      decode_devices=2, arrivals=arr,
                      slo=ServingSLO(ttft_s=0.05, tpot_s=0.01),
                      prefill_batch=1, decode_slots=8, horizon_s=8.0)
    serve = JobSpec("svc", serving=svc,
                    devices=topo.hosts[1] + topo.hosts[3])
    return [train, serve], topo


def bench_serving_codesign() -> Tuple[float, Dict]:
    """Serving co-design end-to-end: search() over the stagger knob under
    SLO constraints, plus training/serving co-tenancy through
    plan_cluster.  Derived = the weaker cost model's naive/staggered p99
    TTFT ratio (>1 means dodging the training pulse strictly improved
    tail latency while staying SLO-feasible)."""
    import dataclasses
    details: Dict = {}
    derived = math.inf
    for cm in ("alphabeta", "flowsim"):
        prob = _serving_cotenant_problem(cm)
        naive = plan(prob)
        sp = dataclasses.replace(prob.space, stagger=Search())
        res = search(dataclasses.replace(prob, space=sp), budget=16)
        derived = min(derived, naive.ttft_p99 / res.best.ttft_p99)
        details[cm] = {
            "naive_ttft_p99_ms": round(naive.ttft_p99 * 1e3, 3),
            "staggered_ttft_p99_ms": round(res.best.ttft_p99 * 1e3, 3),
            "ttft_recovery": round(naive.ttft_p99 / res.best.ttft_p99, 3),
            "stagger_ms": round(res.best.stagger_s * 1e3, 2),
            "slo_attainment": round(res.best.slo_attainment, 3),
            "goodput_rps": round(res.best.goodput, 2),
            "feasible": prob.objective.feasible(res.best),
        }
    jobs, topo = _mixed_serving_cluster()
    rep = plan_cluster(jobs, topo, grid=6)
    sm = rep.serving["svc"]
    details["cluster_cotenancy"] = {
        "contended_links": len(rep.contended),
        "naive_burst_stretch": round(sm["naive_burst_stretch"], 4),
        "staggered_burst_stretch":
            round(sm["staggered_burst_stretch"], 4),
        "ttft_p99_ms": {"naive": round(sm["naive_ttft_p99"] * 1e3, 3),
                        "staggered":
                            round(sm["staggered_ttft_p99"] * 1e3, 3)},
        "slo_attainment": round(sm["staggered_slo_attainment"], 3),
        "train_jct_regression": round(
            rep.staggered_jct["train"] / rep.solo_jct["train"], 4),
        "phases_s": {n: round(p, 4) for n, p in rep.phases.items()},
    }
    details["paper"] = ("co-tenancy on shared fabric (Sec. V "
                        "opportunities): phase serving bursts around "
                        "training pulses to recover tail latency at "
                        "bounded training cost")
    return derived, details


ALL_BENCHMARKS = {
    "megatron_tp_scaling": bench_megatron_tp_scaling,
    "ptdp_interleaved": bench_ptdp_interleaved,
    "lina_priority": bench_lina_priority,
    "janus_data_centric": bench_janus_data_centric,
    "nccl_selection": bench_nccl_selection,
    "sccl_synthesis": bench_sccl_synthesis,
    "taccl_sketch": bench_taccl_sketch,
    "syndicate_overlap": bench_syndicate_overlap,
    "topology_match": bench_topology_match,
    "cassini_stagger": bench_cassini_stagger,
    "atp_aggregation": bench_atp_aggregation,
    "codesign_hierarchical": bench_codesign_hierarchical,
    "codesign_placement": bench_codesign_placement,
    "placement_search": bench_placement_search,
    "cluster_planner": bench_cluster_planner,
    "replan": bench_replan,
    "atp_candidate": bench_atp_candidate,
    "compression_candidate": bench_compression_candidate,
    "overlap_search": bench_overlap_search,
    "synth_codesign": bench_synth_codesign,
    "exposed_comm_fraction": bench_exposed_comm_fraction,
    "serving_codesign": bench_serving_codesign,
}


# ---------------------------------------------------------------------------
# --smoke: tiny-shape assertions of the key orderings, for CI
# ---------------------------------------------------------------------------

# The executable ground truth behind the decomposed-TP pricing: the
# p-step collective-matmul kernels must equal the bulk matmul on 8
# forced host devices (the same step structure decompose_demand prices
# as p-1 "permute" tasks riding under split partials).
_COLLECTIVE_MATMUL_NUMERICS = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.parallel.collective_matmul import ag_matmul, matmul_rs

P_ = 8
mesh = make_mesh((P_,), ("x",))
key = jax.random.PRNGKey(0)
M, K, N = 8 * P_, 16, 12 * P_
x = jax.random.normal(key, (M, K))
w = jax.random.normal(jax.random.fold_in(key, 1), (K, N)) * 0.3
y = jax.jit(jax.shard_map(lambda xl, wl: ag_matmul(xl, wl, "x", P_),
                          mesh=mesh, in_specs=(P("x", None), P(None, "x")),
                          out_specs=P(None, "x")))(x, w)
np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), atol=1e-4)

K2 = 16 * P_
x2 = jax.random.normal(jax.random.fold_in(key, 2), (M, K2))
w2 = jax.random.normal(jax.random.fold_in(key, 3), (K2, N)) * 0.3
y2 = jax.jit(jax.shard_map(lambda xl, wl: matmul_rs(xl, wl, "x", P_),
                           mesh=mesh, in_specs=(P(None, "x"), P("x", None)),
                           out_specs=P("x", None)))(x2, w2)
np.testing.assert_allclose(np.asarray(y2), np.asarray(x2 @ w2), atol=1e-4)
print("OK")
"""

# Measured-vs-modeled collective probes on 8 forced host devices
# (repro.obs.probe): the subprocess serializes its probes back over
# stdout so the smoke run can lay measured tracks into the same trace.
_PROBE_SUITE = """
import json
from repro.obs.probe import probe_suite
probes = probe_suite(impls=("ring", "bidir_ring"), sizes=(1 << 14, 1 << 16),
                     repeats=2, warmup=1)
print("PROBES=" + json.dumps([p.to_dict() for p in probes]))
print("OK")
"""


def run_smoke(trace_out: Optional[str] = None) -> None:
    """Assert the headline claim *orderings* on tiny inputs — fast enough
    for a CI step, so paper-claim regressions fail PRs, not just the
    nightly benchmark run."""
    checks = []

    def check(name, ok, detail=""):
        checks.append((name, bool(ok), detail))
        status = "ok" if ok else "FAIL"
        print(f"  [{status}] {name}{' — ' + detail if detail else ''}")

    # 1. Intra-Inter: hierarchical beats flat ring on dgx, both models
    topo = dgx_cluster(2)
    task = CommTask("g", "all_reduce", 64 * 2 ** 20,
                    tuple(topo.accelerators))
    for model in (AlphaBeta.from_topology(topo), FlowSim(topo)):
        sel = select_for_task(task, model)
        check(f"hierarchical wins large grad AR ({type(model).__name__})",
              sel.algorithm == "hierarchical"
              and sel.costs["hierarchical"] < sel.costs["ring"],
              f"ring/hier = {sel.costs['ring'] / sel.costs['hierarchical']:.2f}x")

    # 2. Host-Net: atp wins on a switched fat-tree, capacity degrades it
    ft = fat_tree(num_hosts=8, gpus_per_host=1, oversub=4.0)
    gtask = CommTask("g", "all_reduce", 2 ** 20, tuple(ft.accelerators))
    for model in (AlphaBeta.from_topology(ft), FlowSim(ft)):
        sel = select_for_task(gtask, model)
        check(f"atp wins 1MiB grad chunk ({type(model).__name__})",
              sel.algorithm == "atp")
    capped = select_for_task(gtask, FlowSim(ft, switch_capacity=4))
    check("switch-memory fallback demotes atp", capped.algorithm != "atp",
          f"-> {capped.algorithm}")

    # 3. Placement: packed beats strided for TP on dgx
    mesh = MeshConfig(shape=(2, 8), axis_names=("data", "model"))
    cfg = get_config("qwen2-0.5b")
    shape = SHAPES_BY_NAME["train_4k"]
    packed = plan_iteration(cfg, shape, mesh, topo, policy="serial")
    strided = plan_iteration(cfg, shape, mesh, topo, policy="serial",
                             placement="strided")
    check("packed placement beats strided",
          packed.comm_time < strided.comm_time,
          f"{strided.comm_time / packed.comm_time:.2f}x")

    # 4. Compression: a 1% error budget wins bandwidth-regime gradient
    # syncs on the oversubscribed fat-tree, is rejected in the latency
    # regime, and strictly lowers end-to-end JCT
    ctopo, cgroup = _compression_setting()
    big = CommTask("g", "all_reduce", 64 * 2 ** 20, cgroup)
    small = CommTask("g", "all_reduce", 2 ** 12, cgroup)
    for model in (AlphaBeta.from_topology(ctopo), FlowSim(ctopo)):
        mn = type(model).__name__
        sel = select_for_task(big, model, error_budget=0.01)
        lossless = select_for_task(big, model)
        check(f"compression wins bandwidth-regime grad AR ({mn})",
              sel.algorithm.endswith("+q8") and sel.cost < lossless.cost,
              f"{sel.algorithm}, {lossless.cost / sel.cost:.2f}x")
        ssel = select_for_task(small, model, error_budget=0.01)
        check(f"codec overhead rejected in latency regime ({mn})",
              "+" not in ssel.algorithm, f"-> {ssel.algorithm}")
    cmesh = MeshConfig(shape=(8,), axis_names=("data",),
                       data_axes=("data",), model_axes=())
    cdpp = DemandParams(zero1=False)
    cbase = plan_iteration(cfg, shape, cmesh, ctopo, policy="serial",
                           dp_params=cdpp)
    cbudget = plan_iteration(cfg, shape, cmesh, ctopo, policy="serial",
                             dp_params=cdpp, error_budget=0.01)
    check("error budget strictly lowers JCT end-to-end",
          cbudget.jct < cbase.jct and cbudget.wire_bytes_saved > 0,
          f"{cbase.jct:.3f}s -> {cbudget.jct:.3f}s, "
          f"{cbudget.wire_bytes_saved / 2 ** 30:.1f} GiB saved")

    # 5. Placement search: search() over the placement knob never loses
    # to packed, and strictly wins on the oversubscribed fat-tree where
    # packed straddles a host boundary
    sproblem = _placement_search_problem()
    sres = search(sproblem, budget=12)
    spacked = plan(sproblem.pinned(placement="packed"))
    check("searched placement strictly beats packed (oversub fat-tree)",
          sres.best.jct < spacked.jct - 1e-9,
          f"{spacked.jct:.3f}s -> {sres.best.jct:.3f}s "
          f"({sres.best.placement.strategy}, "
          f"{spacked.jct / sres.best.jct:.2f}x)")
    dmesh = MeshConfig(shape=(2, 8), axis_names=("data", "model"))
    dproblem = CodesignProblem(cfg, shape, dmesh, topo,
                               space=PlanSpace(placement=Search()))
    dres = search(dproblem, budget=8)
    dpacked = plan(dproblem.pinned(placement="packed"))
    check("searched placement never loses to packed (dgx)",
          dres.best.jct <= dpacked.jct + 1e-9,
          f"{dres.best.placement.strategy} vs packed "
          f"{dpacked.jct:.3f}s")

    # 6. Overlap: searched bucket-size + decompose strictly beats the
    # naive overlap schedule (fifo, per-layer grads, bulk TP
    # collectives) under BOTH cost models, and the decomposed pricing
    # mirrors the executable collective-matmul kernels — structurally
    # (p-1 permute steps of S/p per half, wire bytes conserved) and
    # numerically (ag_matmul / matmul_rs on 8 forced host devices)
    import dataclasses
    obase = _overlap_search_problem()
    for cm in ("alphabeta", "flowsim"):
        oprob = dataclasses.replace(obase, cost_model=cm)
        onaive = plan(oprob.pinned(policy="fifo", bucket_bytes=None,
                                   decompose=False))
        ores = search(oprob, budget=40)
        check(f"searched overlap beats naive schedule ({cm})",
              ores.best.jct < onaive.jct - 1e-9,
              f"{onaive.jct:.3f}s -> {ores.best.jct:.3f}s "
              f"({onaive.jct / ores.best.jct:.2f}x, "
              f"{ores.best_assignment})")

    from repro.core.demand_builder import decompose_demand
    odem = build_demand(obase.cfg, obase.shape, obase.mesh)
    oddem = decompose_demand(odem)
    bulk_ar = next(t for t in odem.comm_tasks if t.axis == "model"
                   and t.primitive == "all_reduce")
    p = len(bulk_ar.group)
    steps = [t for t in oddem.comm_tasks
             if t.task_id.startswith(bulk_ar.task_id + ".")]
    wire_bulk = 2 * (p - 1) * (bulk_ar.size_bytes // p)
    check("decomposed AR = 2(p-1) permutes of S/p, wire bytes conserved",
          len(steps) == 2 * (p - 1)
          and all(t.primitive == "permute" for t in steps)
          and sum(t.size_bytes for t in steps) == wire_bulk,
          f"{len(steps)} steps x {steps[0].size_bytes >> 10} KiB")
    check("decomposition conserves total compute",
          math.isclose(sum(c.duration for c in oddem.compute_tasks),
                       sum(c.duration for c in odem.compute_tasks),
                       rel_tol=1e-9))

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tests"))
    from helpers import run_multidevice
    try:
        run_multidevice(_COLLECTIVE_MATMUL_NUMERICS, num_devices=8)
        ok, why = True, "ag_matmul + matmul_rs vs bulk matmul"
    except AssertionError as e:  # numerics mismatch or crash
        ok, why = False, str(e).splitlines()[0]
    check("decomposed kernels numerically exact on 8 forced devices",
          ok, why)

    # 7. Horizontal: plan_cluster staggering recovers worst-case JCT
    jobs, ctopo = _contended_cluster()
    rep = plan_cluster(jobs, ctopo, grid=6)
    check("two tenants contend on shared uplinks", len(rep.contended) >= 1,
          f"{len(rep.contended)} links")
    check("staggered worst JCT beats naive",
          rep.staggered_worst_stretch < rep.naive_worst_stretch,
          f"{rep.naive_worst_stretch:.4f} -> "
          f"{rep.staggered_worst_stretch:.4f}")

    # 8. Dynamics: incremental re-planning is much cheaper than the full
    #    search and barely worse, and a failed uplink re-routes (finite
    #    JCTs) on the redundant tree
    djobs, dtopo, devents = _dynamic_cluster()
    dyn = ClusterDynamics(djobs, dtopo, grid=6, compare_full=True)
    drep = dyn.run(devents)
    check("incremental re-plan >= 5x faster than full search",
          drep.incremental_speedup is not None
          and drep.incremental_speedup >= 5.0,
          f"{drep.incremental_speedup:.1f}x over "
          f"{len(drep.records)} events")
    check("incremental regret vs full re-search <= 5%",
          drep.worst_regret is not None and drep.worst_regret <= 0.05,
          f"worst {drep.worst_regret:.4f}")
    fail_rec = next(r for r in drep.records if r.kind == "link_fail")
    check("link_fail re-routes over redundant uplink (finite JCTs)",
          all(math.isfinite(v) for v in fail_rec.jct.values()),
          f"dirty={fail_rec.dirty_jobs} "
          f"worst_stretch={fail_rec.worst_stretch:.3f}")

    # 9. Observability: search telemetry accounts for every candidate,
    # FlowSim memoization carries the overlap search (fixed placement ->
    # repeated task keys), and one smoke trace — the searched overlap
    # plan + per-link counters + measured-collective probe tracks —
    # exports as valid Chrome Trace Event JSON (ores/obase are the
    # flowsim leg of check 6)
    from repro.obs.trace import validate_chrome
    tel = ores.telemetry
    check("search telemetry accounts for every candidate",
          tel.get("plan_evals", 0) >= 10
          and tel.get("plan_evals") == len(ores.frontier),
          f"{tel.get('plan_evals')} candidates, "
          f"{tel.get('memo_hits')} memo hits")
    check("FlowSim memoization carries the overlap search (hit rate >= 0.5)",
          tel.get("flowsim_cost_hit_rate", 0.0) >= 0.5,
          f"hit rate {tel.get('flowsim_cost_hit_rate', 0.0):.2f} over "
          f"{tel.get('charged_evals')} plans")
    trace = ores.to_trace(topo=obase.topo)
    try:
        probe_out = run_multidevice(_PROBE_SUITE, num_devices=8)
    except AssertionError as e:
        probe_out = None
        check("measured-collective probes on 8 forced devices", False,
              str(e).splitlines()[0])
    if probe_out is not None:
        from repro.obs.probe import (CollectiveProbe, model_vs_measured,
                                     probes_to_trace)
        probes = [CollectiveProbe.from_dict(d) for d in json.loads(
            next(l for l in probe_out.splitlines()
                 if l.startswith("PROBES="))[len("PROBES="):])]
        probes_to_trace(probes, trace=trace)
        mm = model_vs_measured(probes)
        check("measured-collective probes on 8 forced devices",
              mm["count"] >= 4
          and all(r["measured_s"] > 0 for r in mm["rows"]),
              f"{mm['count']} probes, geomean measured/modeled "
              f"{mm.get('geomean_ratio', 0.0):.3g}x")
    problems = validate_chrome(trace.to_chrome())
    check("smoke trace is valid Chrome Trace Event JSON", not problems,
          f"{len(trace.to_chrome()['traceEvents'])} events"
          if not problems else "; ".join(problems[:2]))

    # 10. Serving co-design: the stagger search strictly improves p99
    # TTFT over the naive co-tenant phase under BOTH cost models while
    # staying SLO-feasible, and in the mixed cluster the training JCT
    # regresses by <= 1% against its solo plan
    for cm in ("alphabeta", "flowsim"):
        svprob = _serving_cotenant_problem(cm)
        svnaive = plan(svprob)
        svres = search(dataclasses.replace(
            svprob, space=dataclasses.replace(svprob.space,
                                              stagger=Search())),
            budget=16)
        check(f"stagger search beats naive co-tenant p99 TTFT ({cm})",
              svres.best.ttft_p99 < svnaive.ttft_p99 - 1e-9,
              f"{svnaive.ttft_p99 * 1e3:.2f}ms -> "
              f"{svres.best.ttft_p99 * 1e3:.2f}ms "
              f"(stagger {svres.best.stagger_s * 1e3:.1f}ms)")
        check(f"staggered serving plan is SLO-feasible ({cm})",
              svprob.objective.feasible(svres.best)
              and svres.best.slo_attainment == 1.0,
              f"attainment {svres.best.slo_attainment:.2f}")
    mjobs, mtopo = _mixed_serving_cluster()
    mrep = plan_cluster(mjobs, mtopo, grid=6)
    msm = mrep.serving["svc"]
    check("mixed cluster staggering recovers serving burst stretch",
          msm["staggered_burst_stretch"]
          <= msm["naive_burst_stretch"] + 1e-12
          and msm["staggered_slo_attainment"]
          >= msm["naive_slo_attainment"] - 1e-12,
          f"stretch {msm['naive_burst_stretch']:.4f} -> "
          f"{msm['staggered_burst_stretch']:.4f}")
    check("co-tenant training JCT regresses <= 1% vs solo",
          mrep.staggered_jct["train"]
          <= 1.01 * mrep.solo_jct["train"],
          f"{mrep.solo_jct['train']:.3f}s -> "
          f"{mrep.staggered_jct['train']:.3f}s")
    # 11. Synthesis: synthesized schedules strictly beat the registry at
    # small sizes on the oversubscribed fat-tree under BOTH cost models,
    # are never selected where they lose, and search() walking the
    # synthesize knob attributes the end-to-end JCT win to it
    sft = fat_tree(2, 8, oversub=8.0, hosts_per_rack=1)
    sgroup = tuple(sft.accelerators)
    stask = CommTask("b", "broadcast", 2 ** 20, sgroup)
    sfs = synthesize_schedule(sft, stask).to_flowset(job_id=stask.job_id)
    for model in (AlphaBeta.from_topology(sft), FlowSim(sft)):
        mn = type(model).__name__
        ssel = select_for_task(stask, model,
                               extra_flowsets={"synthesized": sfs})
        sreg = min(v for k, v in ssel.costs.items() if k != "synthesized")
        check(f"synthesized broadcast beats registry on oversub "
              f"fat-tree ({mn})",
              ssel.algorithm == "synthesized"
              and ssel.costs["synthesized"] < sreg,
              f"{sreg / ssel.costs['synthesized']:.2f}x vs best registered")
    sttiny = CommTask("b", "broadcast", 2 ** 16, sgroup)
    stfs = synthesize_schedule(sft, sttiny).to_flowset(job_id=sttiny.job_id)
    stsel = select_for_task(sttiny, AlphaBeta.from_topology(sft),
                            extra_flowsets={"synthesized": stfs})
    check("synthesized never selected where it loses (64KiB AlphaBeta)",
          stsel.algorithm != "synthesized", f"-> {stsel.algorithm}")
    yring = plan(dataclasses.replace(
        _synth_codesign_problem("flowsim"),
        topo=ring(8)).pinned(synthesize=True))
    check("synthesized never selected on the matching ring fabric",
          not yring.synthesized_choices,
          str(yring.algorithms_by_primitive().get("all_reduce")))
    for cm in ("alphabeta", "flowsim"):
        yprob = _synth_codesign_problem(cm)
        yoff = plan(yprob.pinned(synthesize=False))
        yres = search(yprob, budget=8)
        check(f"synthesize knob wins end to end ({cm})",
              yres.best_assignment.get("synthesize") is True
              and yres.best.jct < yoff.jct - 1e-9
              and len(yres.best.synthesized_choices) > 0
              and yres.attribution.get("synthesize", 0.0) > 0,
              f"{yoff.jct * 1e3:.3f}ms -> {yres.best.jct * 1e3:.3f}ms "
              f"({len(yres.best.synthesized_choices)} tasks, "
              f"attr {yres.attribution.get('synthesize', 0.0) * 1e3:.3f}ms)")

    if trace_out:
        os.makedirs(os.path.dirname(trace_out) or ".", exist_ok=True)
        print(f"  trace -> {trace.write(trace_out)}")

    failed = [c for c in checks if not c[1]]
    print(f"smoke: {len(checks) - len(failed)}/{len(checks)} orderings hold")
    if failed:
        raise SystemExit(f"paper-claim smoke FAILED: "
                         f"{[name for name, _, _ in failed]}")


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="assert key claim orderings on tiny shapes (CI)")
    ap.add_argument("--trace-out", default=os.path.join(
        os.path.dirname(__file__), "..", "experiments",
        "smoke.trace.json"),
        help="where --smoke writes its Perfetto trace "
             "(empty string disables)")
    args = ap.parse_args()
    if args.smoke:
        run_smoke(trace_out=args.trace_out or None)
        return
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.run import main as run_all
    run_all()


if __name__ == "__main__":
    main()
