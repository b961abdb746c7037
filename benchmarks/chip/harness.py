"""One run of a training cell: set-up, measured window, trace, check.

The timed path is the program's own entry: ``make_train_step`` jitted with
the planner's shardings (``param_specs``, ``batch_specs``) on a mesh from
``launch.mesh.make_mesh`` and a context from ``make_ctx``, fed by
``data.pipeline.make_batches``.  Every knob the traffic file does not fix
keeps the program's default (``TrainConfig()``).

Set-up makes the weights on the device from the seed, compiles the step (a
compiled object: nothing can compile inside the window), and drives the
same object through steps 1-3, reading what the check compares.  The window
then runs steps for ``--seconds``, one step in flight, each timed from the
completion of the one before it.  After the window the program's state is
freed and the plain reference runs steps 1-3 on the same rows.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import sys
import tempfile
import time
from functools import partial
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

import check
import hlo
import spec
import devtrace as tr

TRACE_SECONDS = 4.0     # longest traced window
TRACE_MIN_STEPS = 3


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number, beyond 32 bits too."""
    words = np.random.SeedSequence(seed % 2 ** 64).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def device_tag(devices) -> str:
    d = devices[0]
    return f"[{d.platform} {d.device_kind} x{len(devices)}]"


class Trainer:
    """The program's train step for one cell and seed, and its state."""

    def __init__(self, cell: spec.Cell, seed: int, devices, *,
                 dtype=jnp.float32, make_step: Optional[Callable] = None):
        from repro.core.types import MeshConfig, TrainConfig
        from repro.data.pipeline import make_batches
        from repro.launch.mesh import make_mesh
        from repro.models.transformer import init_params
        from repro.parallel.planner import batch_specs, make_ctx, param_specs
        from repro.train.step import make_train_step

        self.cell = cell
        self.cfg = cell.arch.program_config(cell.config)
        self.tcfg = TrainConfig(seed=seed)
        self.mcfg = MeshConfig(shape=cell.mesh_shape)
        n = self.mcfg.num_devices
        self.devices = list(devices[:n])
        self.mesh = make_mesh(self.mcfg.shape, self.mcfg.axis_names,
                              devices=self.devices)
        self.ctx = make_ctx(self.mesh, self.mcfg, remat=self.tcfg.remat)
        self.shapes = jax.eval_shape(lambda k: init_params(self.cfg, k),
                                     jax.random.PRNGKey(0))
        named = lambda sp: NamedSharding(self.mesh, sp)
        self.pshard = jax.tree.map(named, param_specs(self.cfg, self.mcfg),
                                   is_leaf=lambda x: isinstance(x, P))
        rep = named(P())
        self.oshard = {"m": self.pshard, "v": self.pshard, "step": rep}
        bs = batch_specs(self.mcfg)
        self.bshard = {k: named(bs[k]) for k in ("tokens", "labels")}
        self.key = seed_key(seed)
        init = partial(cell.arch.init_weights, self.shapes, dtype=dtype)
        self.init = jax.jit(init, out_shardings=self.pshard)
        self.norms = jax.jit(check.slice_norms)
        self.change_norms = jax.jit(
            lambda p, key: check.slice_norms(jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                p, init(key))))
        self.batches = make_batches(self.cfg, cell.global_batch, cell.seq,
                                    seed=seed % 2 ** 64)
        self.step_fn = (make_step or make_train_step)(self.cfg, self.tcfg,
                                                      self.ctx)
        self.data_wait: List[float] = []
        self.tokens_per_step = cell.global_batch * cell.seq

    def feed(self, labels_fault: Optional[Callable] = None):
        """Next batch from the pipeline, on the device: (device, host)."""
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("data"):
            host = next(self.batches)
            if labels_fault is not None:
                host = dict(host, labels=labels_fault(host["labels"]))
            dev = {k: jax.device_put(host[k], self.bshard[k])
                   for k in ("tokens", "labels")}
        self.data_wait.append(time.perf_counter() - t)
        return dev, host

    def start(self):
        """Weights from the seed, optimizer state, compiled step."""
        from repro.optim.adamw import init_opt_state
        self.params = self.init(self.key)
        self.opt = jax.device_put(init_opt_state(self.params), self.oshard)
        batch = {k: jax.ShapeDtypeStruct((self.cell.global_batch,
                                          self.cell.seq), jnp.int32,
                                         sharding=self.bshard[k])
                 for k in ("tokens", "labels")}
        self.compiled = jax.jit(
            self.step_fn, donate_argnums=(0, 1),
            in_shardings=(self.pshard, self.oshard, self.bshard),
            out_shardings=(self.pshard, self.oshard,
                           NamedSharding(self.mesh, P()))
        ).lower(self.params, self.opt, batch).compile()

    def first_steps(self, labels_fault=None) -> dict:
        """Steps 1-3 through the compiled step, with what the check reads:
        the losses, the first gradient as the optimizer got it, and the
        change of the weights."""
        losses, hosts = [], []
        beta1 = self.tcfg.beta1
        for i in range(3):
            dev, host = self.feed(labels_fault)
            hosts.append(host)
            self.params, self.opt, m = self.compiled(self.params, self.opt,
                                                     dev)
            losses.append(float(m["loss"]))
            if i == 0:
                grad = np.asarray(self.norms(self.opt["m"])) / (1 - beta1)
        change = np.asarray(self.change_norms(self.params, self.key))
        return {"losses": losses, "grad": grad, "change": change,
                "batches": hosts}

    def window(self, seconds: float, min_steps: int = 1) -> dict:
        """Steps for ``seconds`` with one in flight and the next batch on
        the device before it is needed; each step timed from the
        completion of the one before it to its own."""
        self.data_wait.clear()
        times, losses = [], []
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("window"):
            ready = self.feed()[0]
            with jax.profiler.TraceAnnotation("dispatch"):
                inflight = self.compiled(self.params, self.opt, ready)
            ready = self.feed()[0]
            t_prev = t0
            while True:
                with jax.profiler.TraceAnnotation("dispatch"):
                    nxt = self.compiled(inflight[0], inflight[1], ready)
                ready = self.feed()[0]
                with jax.profiler.TraceAnnotation("wait"):
                    inflight[2]["loss"].block_until_ready()
                t = time.perf_counter()
                times.append(t - t_prev)
                losses.append(inflight[2]["loss"])
                t_prev, inflight = t, nxt
                if t - t0 >= seconds and len(times) >= min_steps:
                    break
        self.params, self.opt, last = inflight
        jax.block_until_ready(last)   # the step after the window, uncounted
        return {"step_s": times, "wall_s": t_prev - t0,
                "losses": [float(x) for x in losses],
                "data_wait_s": list(self.data_wait[:len(times)])}

    def free(self):
        for x in jax.tree.leaves((self.params, self.opt)):
            x.delete()
        del self.params, self.opt, self.compiled


def reference_readings(cell: spec.Cell, shapes, seed: int, batches, devices
                       ) -> dict:
    """Steps 1-3 of the plain reference on the same rows, from the same
    seed, over the cell's chips (each leaf split over them on its largest
    divisible dimension)."""
    from repro.core.types import TrainConfig  # the stated hyperparameters
    h = {k: v for k, v in dataclasses.asdict(TrainConfig()).items()
         if isinstance(v, (int, float))}
    n = cell.chips
    mesh = Mesh(np.asarray(devices[:n]), ("ref",))

    def spread(x):
        dims = [i for i, d in enumerate(x.shape) if d % n == 0 and d >= n]
        if not dims:
            return NamedSharding(mesh, P())
        i = max(dims, key=lambda j: x.shape[j])
        return NamedSharding(mesh, P(*[("ref" if j == i else None)
                                       for j in range(len(x.shape))]))

    wshard = jax.tree.map(spread, shapes)
    rep = NamedSharding(mesh, P())
    bshard = (NamedSharding(mesh, P("ref", None))
              if cell.global_batch % n == 0 else rep)
    key = seed_key(seed)
    init = partial(cell.arch.init_weights, shapes, dtype=jnp.float32)
    w = jax.jit(init, out_shardings=wshard)(key)
    zeros = jax.jit(lambda w_: jax.tree.map(jnp.zeros_like, w_),
                    out_shardings=wshard)
    state = (w, zeros(w), zeros(w), jnp.zeros((), jnp.int32))
    sshard = (wshard, wshard, wshard, rep)
    step = jax.jit(partial(cell.reference.train_step, c=cell.config, h=h),
                   donate_argnums=(0,), in_shardings=(sshard, bshard, bshard),
                   out_shardings=(sshard, rep))
    losses = []
    for i, b in enumerate(batches):
        state, loss = step(state, jax.device_put(b["tokens"], bshard),
                           jax.device_put(b["labels"], bshard))
        losses.append(float(loss))
        if i == 0:
            grad = np.asarray(jax.jit(check.slice_norms)(state[1])) / (
                1 - h["beta1"])
    change = np.asarray(jax.jit(lambda p, k: check.slice_norms(jax.tree.map(
        jnp.subtract, p, init(k))))(state[0], key))
    return {"losses": losses, "grad": grad, "change": change}


def collector_timer(pauses: List[float]):
    """A ``gc.callbacks`` entry that appends each collection's pause."""
    start = [0.0]

    def timer(phase, info):
        if phase == "start":
            start[0] = time.perf_counter()
        else:
            pauses.append(time.perf_counter() - start[0])
    return timer


def memory_peak(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


def _number(x: float):
    """``x``, or its name where it is not finite (JSON has no NaN)."""
    return x if math.isfinite(x) else str(x)


def _p95(xs):
    return float(np.percentile(np.asarray(xs), 95, method="linear"))


def end_to_end(cell: spec.Cell, win: dict, setup_s: float, pk: dict,
               chips: int) -> Dict[str, dict]:
    tps = cell.global_batch * cell.seq * len(win["step_s"]) / win["wall_s"]
    flops = cell.arch.model_flops_per_token(cell.config, cell.seq)
    values = {
        "train_tokens_per_s": tps,
        "mfu": 100.0 * tps * flops / (chips * pk["bf16_flops_per_s"]),
        "step_ms_p95": 1e3 * _p95(win["step_s"]),
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader (``metrics/<name>.py``) may read."""
    events: dict               # trace.load_xplane form, ns
    classes: Dict[str, str]    # HLO instruction -> class (hlo.op_classes)
    hlo_text: str              # the compiled step, per device
    steps: int                 # steps completed in the traced window
    data_wait_s: List[float]   # host span per step of the window
    model_flops_per_step: float
    matmul_flops_per_step: float   # executed, all chips
    chips: int
    peak: dict


def traced_window(trainer: Trainer, seconds: float) -> tuple:
    """A short window under the profiler; returns (window result, events)."""
    d = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        with jax.profiler.trace(d, profiler_options=opts):
            win = trainer.window(min(seconds, TRACE_SECONDS), TRACE_MIN_STEPS)
        paths = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs
                 if f.endswith(".xplane.pb")]
        if len(paths) != 1:
            raise RuntimeError(f"expected one xplane file, found {paths}")
        events = tr.load_xplane(paths[0])
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return win, events


def per_layer(cell: spec.Cell, r: Reading) -> Dict[str, dict]:
    out = {}
    for m in cell.per_layer:
        v = cell.readers[m["name"]].read(r)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True,
        make_step: Optional[Callable] = None,
        labels_fault: Optional[Callable] = None) -> dict:
    """One run; returns the result object that ``run.py`` prints."""
    devices = jax.devices()
    tag = device_tag(devices)
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        raise SystemExit(f"{tag} {cell.name} needs {cell.chips} TPU chip(s); "
                         f"JAX found {len(devices)} {devices[0].platform} "
                         f"device(s)")
    if len(devices) < cell.chips:
        raise SystemExit(f"{tag} {cell.name} needs {cell.chips} devices")
    used = devices[:cell.chips]
    pk = spec.peaks(devices[0].device_kind, cell.bench_dir)

    trainer = Trainer(cell, seed, devices, make_step=make_step)
    trainer.start()
    ma = trainer.compiled.memory_analysis()
    log(f"{tag} compiled step memory_analysis: "
        f"argument={ma.argument_size_in_bytes} "
        f"output={ma.output_size_in_bytes} alias={ma.alias_size_in_bytes} "
        f"temp={ma.temp_size_in_bytes} "
        f"(bytes per device)")
    prog = trainer.first_steps(labels_fault)
    setup_s = time.time() - t_start
    log(f"{tag} set-up {setup_s:.3f} s; steps 1-3 loss {prog['losses']}")

    pauses = []
    gc.callbacks.append(collector_timer(pauses))
    if trace:
        win, events = traced_window(trainer, seconds)
    else:
        win = trainer.window(seconds)
    gc.callbacks.pop()
    med = float(np.median(win["step_s"]))
    slow = sorted(t for t in win["step_s"] if t > 1.05 * med)
    log(f"{tag} window: median step {med:.4f} s, {len(slow)} slower by 5%+ "
        f"(slowest {[round(t, 4) for t in slow[-5:]]}); "
        f"{len(pauses)} garbage collections, longest "
        f"{max(pauses, default=0.0):.4f} s")
    peak = memory_peak(used)
    log(f"{tag} window: {len(win['step_s'])} steps in {win['wall_s']:.3f} s; "
        f"memory_stats peak_bytes_in_use={peak} vs compiled argument+temp="
        f"{ma.argument_size_in_bytes + ma.temp_size_in_bytes}")
    hlo_text = trainer.compiled.as_text() if trace else ""
    shapes = trainer.shapes
    padded_vocab = trainer.cfg.padded_vocab
    trainer.free()

    t_ref = time.time()
    ref = reference_readings(cell, shapes, seed, prog["batches"], used)
    log(f"{tag} reference steps 1-3 loss {ref['losses']} in "
        f"{time.time() - t_ref:.3f} s; run {time.time() - t_start:.3f} s")
    nums = check.numbers(prog, ref, check.slice_names(shapes))
    failed = sum(1 for x in win["losses"] if not math.isfinite(x))
    correct = check.judge(nums, cell.limits) and failed == 0

    result = {"correct": correct, "attempted": len(win["step_s"]),
              "failed": failed}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": peak}
    if trace:
        r = Reading(
            events=events, classes=hlo.Program(hlo_text).op_classes(),
            hlo_text=hlo_text, steps=len(win["step_s"]),
            data_wait_s=win["data_wait_s"],
            model_flops_per_step=trainer.tokens_per_step
            * cell.arch.model_flops_per_token(cell.config, cell.seq),
            matmul_flops_per_step=cell.arch.executed_matmul_flops(
                cell.config, cell.global_batch, cell.seq,
                remat=trainer.tcfg.remat, padded_vocab=padded_vocab),
            chips=cell.chips, peak=pk)
        lo, hi = tr.window(events)
        busy = tr.busy_ns(events)
        device["busy_s"] = sum(busy.values()) / max(len(busy), 1) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        result["metrics"] = per_layer(cell, r)
        result["breakdown"] = {"device_ops": tr.top_ops(events, r.classes),
                               "idle_gaps": tr.idle_gaps(events)}
    else:
        result["metrics"] = end_to_end(cell, win, setup_s, pk, cell.chips)
    result["device"] = device
    result["check"] = {k: {"value": _number(v["value"]),
                           "limit": cell.limits[k]} for k, v in nums.items()}
    for k, v in nums.items():
        extra = f" left out: {v['left_out']}" if v.get("left_out") else ""
        log(f"{tag} check {k} = {v['value']!r} (limit {cell.limits[k]!r}, "
            f"worst at {v['at']}){extra}")
    for k, v in result["check"].items():
        log(f"{tag} {k} {v['value']!r} limit {v['limit']!r}")
    return result
