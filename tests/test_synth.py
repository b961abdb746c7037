"""Collective synthesis as a plan-space lever (ccl.synth + the
``synthesize`` knob): schedule invariants as properties, solver
memoization, persisted warm-start seeds, selection pricing under both
cost models, and the executable shard_map lowering on 8 forced host
devices."""
import dataclasses
import json
import os

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.ccl.select import AlphaBeta, FlowSim, select_for_task
from repro.ccl.synth import (DEFAULT_SYNTH_CACHE, Sketch, SynthCache,
                             atp_schedule, sketch_from_hotspots,
                             synthesize_schedule, topology_fingerprint)
from repro.core.demand import CommTask
from repro.core.knobs import Fixed, Search
from repro.core.types import MeshConfig, ShapeConfig
from repro.net.topology import dgx_cluster, fat_tree, full_mesh, ring

from helpers import run_multidevice

TOPOS = {
    "ring8": lambda: ring(8),
    "mesh8": lambda: full_mesh(8),
    "fattree": lambda: fat_tree(2, 8, oversub=8.0, hosts_per_rack=1),
    "dgx2": lambda: dgx_cluster(2),
}


def _task(topo, primitive, size):
    return CommTask("t", primitive, size, tuple(topo.accelerators))


# ---------------------------------------------------------------------------
# schedule invariants (property tests)
# ---------------------------------------------------------------------------


@given(st.sampled_from(sorted(TOPOS)), st.integers(10, 24))
@settings(max_examples=16, deadline=None)
def test_all_reduce_wire_bytes_are_ring_equal(topo_name, log_size):
    """Wire-byte conservation: the mirrored-tree all-reduce moves exactly
    the ring algorithm's bytes — 2(p-1) chunks per rank, every
    contribution crossing every tree edge once — at any payload size."""
    topo = TOPOS[topo_name]()
    task = _task(topo, "all_reduce", 1 << log_size)
    p = len(task.group)
    s = synthesize_schedule(topo, task)
    assert s.chunk_bytes == max(task.size_bytes // p, 1)
    assert len(s.moves) == 2 * p * (p - 1)
    assert s.wire_bytes() == 2 * p * (p - 1) * s.chunk_bytes


@given(st.sampled_from(sorted(TOPOS)),
       st.sampled_from(["broadcast", "all_gather"]), st.integers(10, 24))
@settings(max_examples=16, deadline=None)
def test_gather_like_wire_bytes_match_bulk(topo_name, primitive, log_size):
    """Broadcast moves its full payload to p-1 receivers; all-gather moves
    each of the p shards to p-1 receivers — the bulk collectives' wire
    bytes, no duplicated or dropped chunks."""
    topo = TOPOS[topo_name]()
    task = _task(topo, primitive, 1 << log_size)
    p = len(task.group)
    s = synthesize_schedule(topo, task)
    n_demands = (p - 1) if primitive == "broadcast" else p * (p - 1)
    assert len(s.moves) == n_demands
    assert s.wire_bytes() == n_demands * s.chunk_bytes


def _replay(schedule):
    """Replay the move list with strict step semantics: every step reads
    the *previous* step's state (same-step forwarding would be a
    causality bug), reduce moves union contribution sets, gather moves
    overwrite.  Returns rank -> chunk -> frozenset of contributions."""
    group = schedule.group
    state = {r: {} for r in group}
    if schedule.primitive == "all_reduce":
        # every rank holds a partial contribution to every chunk slot
        for r in group:
            for c in range(schedule.num_chunks):
                state[r][c] = frozenset([r])
    elif schedule.primitive == "broadcast":
        state[group[0]][0] = frozenset([group[0]])
    else:  # all_gather: chunk c starts at rank group[c]
        for c, r in enumerate(group):
            state[r][c] = frozenset([r])
    by_step = {}
    for m in schedule.moves:
        by_step.setdefault(m.step, []).append(m)
    for step in sorted(by_step):
        pre = {r: dict(cs) for r, cs in state.items()}
        for m in by_step[step]:
            src_val = pre[m.src].get(m.chunk)
            assert src_val is not None, \
                f"step {step}: {m.src} forwards chunk {m.chunk} it does " \
                f"not hold (same-step forwarding?)"
            if m.reduce:
                state[m.dst][m.chunk] = \
                    state[m.dst].get(m.chunk, frozenset()) | src_val
            else:
                state[m.dst][m.chunk] = src_val
    return state


@given(st.sampled_from(sorted(TOPOS)),
       st.sampled_from(["all_reduce", "broadcast", "all_gather"]))
@settings(max_examples=12, deadline=None)
def test_replay_delivers_everything(topo_name, primitive):
    """Full delivery: after replaying the schedule, every rank holds every
    chunk, and all-reduce chunks carry every rank's contribution exactly
    (no double counting — contribution sets, not sums, so a chunk
    crossing an edge twice would still pass; the wire-byte test pins
    that side)."""
    topo = TOPOS[topo_name]()
    task = _task(topo, primitive, 1 << 18)
    s = synthesize_schedule(topo, task)
    state = _replay(s)
    group = s.group
    everyone = frozenset(group)
    for r in group:
        for c in range(s.num_chunks):
            assert c in state[r], f"rank {r} missing chunk {c}"
            if primitive == "all_reduce":
                assert state[r][c] == everyone, \
                    f"rank {r} chunk {c} reduced only {sorted(state[r][c])}"


@given(st.sampled_from(sorted(TOPOS)),
       st.sampled_from(["all_reduce", "broadcast", "all_gather"]))
@settings(max_examples=12, deadline=None)
def test_per_step_moves_use_disjoint_directed_links(topo_name, primitive):
    """Link concurrency: no two moves of one step share a directed link.
    Reduce-phase moves are mirrored fan-out edges, so their paths are
    taken in fan-out orientation and reversed — ``path_links(dst, src)``
    itself may break antipodal shortest-path ties the other way round a
    ring, which is a pricing artifact, not a schedule collision."""
    topo = TOPOS[topo_name]()
    task = _task(topo, primitive, 1 << 18)
    s = synthesize_schedule(topo, task)
    by_step = {}
    for m in s.moves:
        by_step.setdefault(m.step, []).append(m)
    for step, moves in by_step.items():
        seen = set()
        for m in moves:
            if m.reduce:
                path = [(b, a) for a, b in
                        reversed(list(topo.path_links(m.dst, m.src)))]
            else:
                path = list(topo.path_links(m.src, m.dst))
            for link in path:
                assert link not in seen, \
                    f"step {step}: directed link {link} carries two moves"
                seen.add(link)


def test_all_reduce_reduce_phase_mirrors_fanout():
    """The reduce phase is exactly the fan-out trees reversed, and every
    reduce move lands strictly before its mirrored fan-out move (a
    contribution must reach the owner before the sum fans out)."""
    topo = TOPOS["fattree"]()
    s = synthesize_schedule(topo, _task(topo, "all_reduce", 1 << 18))
    span = s.num_steps // 2
    fanout = {(m.chunk, m.src, m.dst, m.step - span)
              for m in s.moves if not m.reduce}
    mirrored = {(m.chunk, m.dst, m.src, span - 1 - m.step)
                for m in s.moves if m.reduce}
    assert fanout == mirrored
    for m in s.moves:
        if m.reduce:
            assert m.step < span


def test_atp_schedule_replays_exactly():
    """The executable analogue of the priced ``atp`` candidate: all
    contributions converge on the aggregation point at step 0, the sum
    multicasts at step 1."""
    topo = full_mesh(8)
    task = _task(topo, "all_reduce", 1 << 16)
    s = atp_schedule(task)
    assert s.num_steps == 2 and s.num_chunks == 1
    assert s.wire_bytes() == 2 * (len(task.group) - 1) * task.size_bytes
    state = _replay(s)
    everyone = frozenset(task.group)
    assert all(state[r][0] == everyone for r in task.group)


# ---------------------------------------------------------------------------
# memoization (SynthCache) + topology fingerprints
# ---------------------------------------------------------------------------


def test_synth_cache_hits_within_size_bucket_and_rescales():
    cache = SynthCache()
    topo = full_mesh(8)
    s1 = cache.schedule(topo, _task(topo, "all_reduce", 1 << 20))
    stats = cache.cache_stats()
    assert stats["synth.miss"] == 1 and "synth.hit" not in stats
    assert stats["synth.entries"] == 1

    # same power-of-two bucket, different exact size: hit + exact rescale
    t2 = CommTask("t2", "all_reduce", (1 << 20) + (1 << 19),
                  tuple(topo.accelerators))
    s2 = cache.schedule(topo, t2)
    stats = cache.cache_stats()
    assert stats["synth.hit"] == 1 and stats["synth.entries"] == 1
    assert stats["synth.hit_rate"] == 0.5
    assert s2.task_id == "t2" and s2.size_bytes == t2.size_bytes
    assert [(m.chunk, m.src, m.dst, m.step) for m in s2.moves] == \
        [(m.chunk, m.src, m.dst, m.step) for m in s1.moves]
    assert s2.wire_bytes() == len(s2.moves) * s2.chunk_bytes

    # a different sketch is a different solver problem
    cache.schedule(topo, _task(topo, "all_reduce", 1 << 20),
                   Sketch(max_hops=2))
    assert cache.cache_stats()["synth.entries"] == 2


def test_topology_fingerprint_is_wiring_identity():
    assert topology_fingerprint(ring(8)) == topology_fingerprint(ring(8))
    assert topology_fingerprint(ring(8)) != topology_fingerprint(ring(6))
    topo = fat_tree(2, 8, oversub=8.0, hosts_per_rack=1)
    u, v, _ = next(iter(topo.links()))
    assert topology_fingerprint(topo.without_link(u, v)) != \
        topology_fingerprint(topo)
    # cross-instance: a second identical build hits the first's entry
    cache = SynthCache()
    cache.schedule(ring(8), _task(ring(8), "broadcast", 1 << 16))
    cache.schedule(ring(8), _task(ring(8), "broadcast", 1 << 16))
    assert cache.cache_stats()["synth.hit"] == 1


# ---------------------------------------------------------------------------
# selection pricing: extras under both models, budget gate, whitelists
# ---------------------------------------------------------------------------


def _extras(topo, task, wire_ratio=None):
    s = synthesize_schedule(topo, task)
    out = {"synthesized": s.to_flowset(job_id=task.job_id)}
    if wire_ratio is not None:
        out["synthesized+q8"] = s.to_flowset(
            job_id=task.job_id, wire_ratio=wire_ratio,
            algorithm="synthesized+q8")
    return out


def test_synthesized_priced_under_both_models_and_wins_latency_regime():
    topo = full_mesh(8)
    task = _task(topo, "all_reduce", 112 << 10)
    for model in (AlphaBeta.from_topology(topo), FlowSim(topo)):
        sel = select_for_task(task, model, extra_flowsets=_extras(topo, task))
        assert sel.algorithm == "synthesized", type(model).__name__
        reg = min(v for k, v in sel.costs.items() if k != "synthesized")
        assert sel.costs["synthesized"] < reg


def test_synthesized_never_selected_where_registry_matches_fabric():
    """On a plain ring at bandwidth-regime sizes the registered ring
    algorithms already match the fabric — the synthesized candidate is
    priced but loses."""
    topo = ring(8)
    task = _task(topo, "all_reduce", 8 << 20)
    for model in (AlphaBeta.from_topology(topo), FlowSim(topo)):
        sel = select_for_task(task, model, extra_flowsets=_extras(topo, task))
        assert sel.algorithm != "synthesized", type(model).__name__
        assert "synthesized" in sel.costs  # competed, lost


def test_synthesized_q8_faces_error_budget_and_whitelists():
    topo = fat_tree(2, 8, oversub=8.0, hosts_per_rack=1)
    task = _task(topo, "all_reduce", 8 << 20)
    model = FlowSim(topo)
    extras = _extras(topo, task, wire_ratio=0.25)
    zero = select_for_task(task, model, extra_flowsets=extras)
    assert "synthesized+q8" in zero.excluded  # default budget is exact
    budget = select_for_task(task, model, error_budget=0.01,
                             extra_flowsets=extras)
    assert "synthesized+q8" in budget.costs
    assert budget.costs["synthesized+q8"] < budget.costs["synthesized"]
    forced = select_for_task(task, model, constraint=Fixed("synthesized"),
                             extra_flowsets=extras)
    assert forced.algorithm == "synthesized"
    assert list(forced.costs) == ["synthesized"]


# ---------------------------------------------------------------------------
# the synthesize knob end to end: plan(), search(), warm-start seeds
# ---------------------------------------------------------------------------


def _knob_problem(cost_model="alphabeta", synthesize=Fixed(True)):
    from repro.codesign.api import CodesignProblem, PlanSpace
    from repro.configs import get_config
    mesh = MeshConfig(shape=(8,), axis_names=("model",), data_axes=(),
                      model_axes=("model",))
    return CodesignProblem(
        get_config("qwen2-0.5b"), ShapeConfig("synth_tiny", 64, 1, "train"),
        mesh, full_mesh(8), cost_model=cost_model,
        space=PlanSpace(synthesize=synthesize))


@pytest.mark.parametrize("cost_model", ["alphabeta", "flowsim"])
def test_plan_flips_latency_regime_tp_all_reduce(cost_model):
    from repro.codesign.api import plan
    rep = plan(_knob_problem(cost_model))
    base = plan(_knob_problem(cost_model, synthesize=Fixed(False)))
    synth = rep.synthesized_choices
    assert synth and len(synth) == len(rep.choices)
    assert rep.jct < base.jct
    for c in synth:
        reg = min(v for k, v in c.costs.items()
                  if not k.startswith("synthesized"))
        assert c.cost_s < reg
    # the report round-trips with the synthesized choices intact
    from repro.codesign.report import CodesignReport
    loaded = CodesignReport.from_dict(
        json.loads(json.dumps(rep.to_dict())))
    assert len(loaded.synthesized_choices) == len(synth)


@pytest.mark.parametrize("cost_model", ["alphabeta", "flowsim"])
def test_search_walks_synthesize_knob_with_attribution(cost_model):
    from repro.codesign.api import search
    res = search(_knob_problem(cost_model, synthesize=Search()), budget=8)
    assert res.best_assignment == {"synthesize": True}
    assert res.attribution["synthesize"] > 0
    assert res.best.synthesized_choices
    # solver cache telemetry rides along like FlowSim's cache stats
    assert res.telemetry["counters"]["synth.miss"] >= 0
    assert res.telemetry["counters"]["synth.hit"] >= 1
    assert res.telemetry["synth_hit_rate"] > 0


def test_search_persists_and_warm_starts_from_seed(tmp_path):
    from repro.codesign.api import search
    from repro.codesign.seeds import load_seed, seed_path
    prob = _knob_problem(synthesize=Search())
    res1 = search(prob, budget=8, seeds_dir=str(tmp_path))
    path = seed_path(str(tmp_path), prob)
    assert os.path.exists(path)
    assert load_seed(str(tmp_path), prob) == res1.best_assignment

    res2 = search(prob, budget=8, seeds_dir=str(tmp_path))
    warm = [c for c in res2.frontier if c.phase == "warm_start"]
    assert len(warm) == 1
    assert warm[0].assignment == res1.best_assignment
    assert res2.best_assignment == res1.best_assignment

    # a corrupt seed is treated as absent, never breaks the search
    with open(path, "w") as f:
        f.write("{not json")
    assert load_seed(str(tmp_path), prob) is None
    res3 = search(prob, budget=8, seeds_dir=str(tmp_path))
    assert res3.best_assignment == res1.best_assignment
    assert not [c for c in res3.frontier if c.phase == "warm_start"]

    # another topology's seed never leaks in: the key mismatches
    other = dataclasses.replace(prob, topo=ring(8))
    assert load_seed(str(tmp_path), other) is None


# ---------------------------------------------------------------------------
# executable lowering: synthesized schedules vs psum on 8 forced devices
# ---------------------------------------------------------------------------

_LOWERING = """
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.ccl.primitives import make_synthesized, synthesized_collective
from repro.ccl.synth import atp_schedule, synthesize_schedule
from repro.core.demand import CommTask
from repro.launch.mesh import make_mesh
from repro.net.topology import fat_tree, full_mesh, ring

mesh = make_mesh((8,), ("x",))
# integer-valued floats: float32 sums are exact, so lossless synthesized
# all-reduce must BIT-match psum (not just be close)
x = jnp.arange(8 * 48, dtype=jnp.float32).reshape(8, 48) - 150.0

def psum_ref(y):
    return jax.jit(jax.shard_map(lambda yl: jax.lax.psum(yl, "x"),
                                 mesh=mesh, in_specs=P("x", None),
                                 out_specs=P("x", None)))(y)

want = np.asarray(psum_ref(x))
topos = {"ring8": ring(8), "mesh8": full_mesh(8),
         "fattree": fat_tree(2, 4, oversub=8.0, hosts_per_rack=1)}
for name, topo in topos.items():
    task = CommTask("t", "all_reduce", x.nbytes, tuple(topo.accelerators))
    sched = synthesize_schedule(topo, task)
    got = np.asarray(make_synthesized(sched, mesh, "x")(x))
    np.testing.assert_array_equal(got, want, err_msg=name)
    print(name, "lossless exact")

# codec riding inside the send loop: within quantization tolerance
sched = synthesize_schedule(topos["fattree"],
                            CommTask("t", "all_reduce", x.nbytes,
                                     tuple(topos["fattree"].accelerators)))
got8 = np.asarray(make_synthesized(sched, mesh, "x", bits=8)(x))
# each of the 8 contributions quantizes to <= scale/2 = max|.|/(2^7-1)/2
# absolute error, and partial sums re-quantize along the reduce tree:
# bound by 2 * world * per-pass error on the largest partial magnitude
tol = 2 * 8 * float(np.max(np.abs(want))) / (2 ** 7 - 1)
assert np.max(np.abs(got8 - want)) <= tol, (np.max(np.abs(got8 - want)), tol)
print("q8 within tolerance")

# the executable analogue of the priced atp candidate: exact
atp = atp_schedule(CommTask("t", "all_reduce", x.nbytes,
                            tuple(range(8))))
gota = np.asarray(make_synthesized(atp, mesh, "x")(x))
np.testing.assert_array_equal(gota, want)
print("atp exact")

# broadcast: every rank ends with the root's shard
btask = CommTask("b", "broadcast", 48 * 4, tuple(range(8)))
bsched = synthesize_schedule(full_mesh(8), btask)
gotb = np.asarray(make_synthesized(bsched, mesh, "x")(x))
np.testing.assert_array_equal(gotb, np.tile(np.asarray(x)[:1], (8, 1)))
print("broadcast exact")

# all-gather inside an explicit shard_map: every rank stacks all shards
gtask = CommTask("g", "all_gather", x.nbytes, tuple(range(8)))
gsched = synthesize_schedule(full_mesh(8), gtask)
def gather_body(xl):
    return synthesized_collective(xl[0], "x", 8, gsched)[None]
gotg = np.asarray(jax.jit(jax.shard_map(
    gather_body, mesh=mesh, in_specs=P("x", None),
    out_specs=P("x", None, None)))(x))
np.testing.assert_array_equal(gotg, np.tile(np.asarray(x)[None], (8, 1, 1)))
print("all_gather exact")
print("OK")
"""


def test_synthesized_lowering_matches_psum_on_8_forced_devices():
    out = run_multidevice(_LOWERING, num_devices=8)
    for line in ("lossless exact", "q8 within tolerance", "atp exact",
                 "broadcast exact", "all_gather exact"):
        assert line in out, out
