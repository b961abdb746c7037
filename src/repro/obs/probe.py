"""Wall-clock probes for the executable collectives — measured vs modeled.

The ROADMAP's calibration item needs one thing the engine never had:
*measured* collective times to hold against the ``AlphaBeta``/``FlowSim``
predictions.  :func:`probe_all_reduce` runs one executable implementation
from ``ccl.primitives`` on a device mesh (a forced-host-device mesh via
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` in CI, real
accelerators when available), bracketing each run with
``block_until_ready`` so the span is the collective's wall-clock, not
dispatch time.  Each :class:`CollectiveProbe` carries the measurement
next to the closed-form prediction for the same
(algorithm, size, world); :func:`probes_to_trace` lays both out
side-by-side in a Perfetto trace, and :func:`model_vs_measured`
summarizes the drift — the regression target a calibration fit would
minimize.

This module imports ``jax`` lazily inside the probe functions so
``repro.obs`` stays importable (and the export CLI usable) without
touching an accelerator runtime.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.obs.trace import Trace


@dataclass
class CollectiveProbe:
    """One (implementation, size) measurement next to its prediction."""

    impl: str                 # executable name (ccl.primitives)
    algorithm: str            # the priced equivalent (MODEL_EQUIVALENTS)
    size_bytes: int
    world: int                # devices in the mesh axis
    measured_s: float         # min over timed runs (the standard estimator)
    modeled_s: float          # algo_cost prediction under the CostParams
    runs_s: List[float] = field(default_factory=list)
    model_terms: Dict[str, float] = field(default_factory=dict)
    primitive: str = "all_reduce"
    device_kind: str = "cpu"

    @property
    def ratio(self) -> Optional[float]:
        """measured / modeled (None when the model predicts 0)."""
        return self.measured_s / self.modeled_s if self.modeled_s > 0 \
            else None

    def to_dict(self) -> Dict:
        return {"impl": self.impl, "algorithm": self.algorithm,
                "size_bytes": self.size_bytes, "world": self.world,
                "measured_s": self.measured_s, "modeled_s": self.modeled_s,
                "runs_s": list(self.runs_s),
                "model_terms": dict(self.model_terms),
                "primitive": self.primitive,
                "device_kind": self.device_kind}

    @classmethod
    def from_dict(cls, d: Dict) -> "CollectiveProbe":
        return cls(impl=d["impl"], algorithm=d["algorithm"],
                   size_bytes=d["size_bytes"], world=d["world"],
                   measured_s=d["measured_s"], modeled_s=d["modeled_s"],
                   runs_s=list(d.get("runs_s", [])),
                   model_terms=dict(d.get("model_terms", {})),
                   primitive=d.get("primitive", "all_reduce"),
                   device_kind=d.get("device_kind", "cpu"))


def _default_mesh():
    import jax

    from repro.launch.mesh import make_mesh
    return make_mesh((len(jax.devices()),), ("x",))


def probe_all_reduce(impl: str, size_bytes: int, mesh=None,
                     params=None, repeats: int = 3, warmup: int = 1,
                     clock: Callable[[], float] = time.perf_counter
                     ) -> CollectiveProbe:
    """Measure one executable all-reduce and pair it with its prediction.

    ``impl`` names a ``ccl.primitives.IMPLEMENTATIONS`` entry; the mesh
    defaults to all visible devices on one axis.  Every timed run is
    ``block_until_ready``-bracketed; ``warmup`` runs absorb compilation.
    The prediction prices the ``MODEL_EQUIVALENTS`` algorithm with
    ``algo_cost`` under ``params`` (default :class:`CostParams`) — drift
    between the two is the calibration signal, not an error."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.ccl.cost import CostParams, algo_cost, cost_terms
    from repro.ccl.primitives import MODEL_EQUIVALENTS, make_all_reduce

    if impl not in MODEL_EQUIVALENTS:
        raise ValueError(f"unknown implementation {impl!r} "
                         f"(one of {sorted(MODEL_EQUIVALENTS)})")
    mesh = mesh if mesh is not None else _default_mesh()
    axis = mesh.axis_names[0]
    p = mesh.shape[axis]
    cp = params if params is not None else CostParams()

    elems = max(size_bytes // 4, p)
    elems += (-elems) % p  # shardable along the mesh axis
    # deterministic payload, no PRNG (probe results must be reproducible
    # modulo the clock)
    x = (jnp.arange(elems, dtype=jnp.float32) % 13.0) / 16.0 - 0.4
    x = jax.device_put(x, NamedSharding(mesh, P(axis)))
    fn = make_all_reduce(impl, mesh, axis)

    for _ in range(max(warmup, 0)):
        jax.block_until_ready(fn(x))
    runs: List[float] = []
    for _ in range(max(repeats, 1)):
        t0 = clock()
        jax.block_until_ready(fn(x))
        runs.append(clock() - t0)

    algorithm = MODEL_EQUIVALENTS[impl]
    return CollectiveProbe(
        impl=impl, algorithm=algorithm, size_bytes=size_bytes, world=p,
        measured_s=min(runs),
        modeled_s=algo_cost("all_reduce", algorithm, size_bytes, p, cp),
        runs_s=runs,
        model_terms=cost_terms("all_reduce", algorithm, size_bytes, p, cp),
        device_kind=jax.devices()[0].device_kind)


def probe_suite(impls: Sequence[str] = ("ring", "bidir_ring"),
                sizes: Sequence[int] = (1 << 16, 1 << 20), mesh=None,
                params=None, repeats: int = 3, warmup: int = 1,
                clock: Callable[[], float] = time.perf_counter
                ) -> List[CollectiveProbe]:
    """Probe an implementation x size grid (deterministic order)."""
    mesh = mesh if mesh is not None else _default_mesh()
    return [probe_all_reduce(impl, size, mesh=mesh, params=params,
                             repeats=repeats, warmup=warmup, clock=clock)
            for impl in impls for size in sizes]


def probes_to_trace(probes: Sequence[CollectiveProbe],
                    trace: Optional[Trace] = None, pid: int = 50,
                    t0: float = 0.0) -> Trace:
    """Measured and modeled spans side-by-side: one process, a
    *measured* thread and a *modeled* thread, each probe laid out on a
    shared cursor so the pair lines up vertically in Perfetto."""
    trace = trace if trace is not None else Trace()
    trace.process(pid, "collectives: measured vs modeled")
    trace.thread(pid, 0, "measured")
    trace.thread(pid, 1, "modeled")
    cursor = t0
    for pr in probes:
        name = f"{pr.impl} {pr.size_bytes}B"
        args = pr.to_dict()
        args.pop("model_terms", None)
        trace.span(name, cursor, pr.measured_s, pid=pid, tid=0,
                   cat="measured", args=args)
        trace.span(f"model:{pr.algorithm} {pr.size_bytes}B", cursor,
                   pr.modeled_s, pid=pid, tid=1, cat="modeled",
                   args=pr.model_terms or None)
        cursor += max(pr.measured_s, pr.modeled_s) * 1.05 + 1e-6
    return trace


def model_vs_measured(probes: Sequence[CollectiveProbe]) -> Dict:
    """Drift summary: per-probe rows plus aggregate measured/modeled
    ratio statistics (geometric mean and mean |log2 error| — the scale-
    free quantities a calibration regression would drive to 1 and 0)."""
    rows = []
    log2_errs = []
    for pr in probes:
        row = pr.to_dict()
        row.pop("runs_s", None)
        row["ratio"] = pr.ratio
        if pr.ratio is not None and pr.ratio > 0:
            err = math.log2(pr.ratio)
            row["log2_err"] = err
            log2_errs.append(err)
        rows.append(row)
    summary: Dict = {"count": len(rows), "rows": rows}
    if log2_errs:
        summary["geomean_ratio"] = 2.0 ** (sum(log2_errs) / len(log2_errs))
        summary["mean_abs_log2_err"] = (sum(abs(e) for e in log2_errs)
                                        / len(log2_errs))
        summary["max_ratio"] = 2.0 ** max(log2_errs)
        summary["min_ratio"] = 2.0 ** min(log2_errs)
    return summary
