#!/usr/bin/env python3
"""Smoke run of the program on a TPU, through the entry points a user calls.

    python chip_smoke.py             # one chip: train, decode, kernels
    python chip_smoke.py --chips 4   # four chips: the (2, 2) train step and
                                     # the executable all-reduces only

One chip:
  * train   — qwen2-0.5b at full published width (24 layers, vocab 151936),
              batch 8 x seq 128, a few AdamW steps through ``make_train_step``
              built as ``repro.launch.train`` builds it.  Losses and grad norms
              must be finite, and step 0's loss must match a float32
              ("highest" matmul precision) evaluation of the same batch.
  * decode  — the trained params through ``init_cache``/``make_serve_step``:
              a 16-token prompt fed token by token, then 16 greedy tokens.
              Logits must be finite, tokens inside the vocabulary, and the
              prompt's decode logits must match a full ``forward``.
  * kernels — every Pallas kernel compiled on the chip at a published width
              and compared with its ``ref.py`` at the tolerance of its tests.
Four chips (``--chips 4``):
  * the qwen2-0.5b train step on a (data=2, model=2) mesh through
    ``make_ctx``/``param_specs``; its step-0 loss must match the same step on
    one chip of this process, with the same params and batch;
  * every ``ccl.primitives.IMPLEMENTATIONS`` all-reduce and a schedule
    synthesized for ``torus2d(2, 2)`` must match ``lax.psum`` over the four
    chips: exactly for the lossless ones, within the codec bound for
    ``ring_q8``/``ring_q4``.

Everything runs in this one process (a chip belongs to one process).  Any
failed check raises, so the exit code is non-zero.  The last line of
standard output is ``{"ok": true, "device": {...}}``.  Without a TPU the
script exits non-zero before doing anything.  Wall times printed here are
smoke readings (few steps, step 0 includes compilation), not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

DECODE_PROMPT = DECODE_NEW = 16


def log(msg: str) -> None:
    print(msg, flush=True)


def _finite(x) -> bool:
    import jax.numpy as jnp
    return bool(jnp.isfinite(x).all())


def _rel_err(got, want) -> float:
    """max |got - want| over max(max |want|, 1)."""
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1.0))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def train_phase(cfg, *, batch: int, seq: int, steps: int, seed: int = 0):
    """A few train steps; returns (params, losses)."""
    import jax
    import jax.numpy as jnp

    from repro.core.types import TrainConfig
    from repro.data.pipeline import make_batches
    from repro.models.transformer import init_params
    from repro.optim.adamw import init_opt_state
    from repro.train.step import make_eval_step, make_train_step

    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=10,
                       total_steps=steps, remat=False, seed=seed)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    opt = init_opt_state(params)
    batches = [{k: jnp.asarray(v) for k, v in b.items()}
               for b, _ in zip(make_batches(cfg, batch, seq, seed=seed),
                               range(steps))]
    with jax.default_matmul_precision("highest"):
        ref_loss = float(jax.jit(make_eval_step(cfg))(params, batches[0]))
    step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
    losses = []
    for i, b in enumerate(batches):
        t0 = time.perf_counter()
        params, opt, m = step_fn(params, opt, b)
        jax.block_until_ready((params, opt, m))
        wall = time.perf_counter() - t0
        loss, gnorm = float(m["loss"]), float(m["grad_norm"])
        log(f"train step {i}: loss={loss!r} grad_norm={gnorm!r} "
            f"wall_s={wall!r} (smoke reading"
            f"{', includes compile' if i == 0 else ''})")
        if not (math.isfinite(loss) and math.isfinite(gnorm)):
            raise AssertionError(f"train step {i}: non-finite loss {loss} "
                                 f"or grad norm {gnorm}")
        losses.append(loss)
    diff = abs(losses[0] - ref_loss)
    log(f"train step-0 loss vs float32 eval: {losses[0]!r} vs {ref_loss!r} "
        f"(|diff|={diff!r})")
    if diff > 1e-2 * abs(ref_loss):
        raise AssertionError(f"step-0 loss {losses[0]} differs from the "
                             f"float32 evaluation {ref_loss}")
    return params, losses


def decode_phase(cfg, params, *, batch: int, seed: int = 0):
    """Prompt through decode, then greedy tokens; returns generated tokens."""
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import make_batches
    from repro.models.transformer import forward, init_cache
    from repro.serve.step import make_serve_step

    prompt = jnp.asarray(next(make_batches(cfg, batch, DECODE_PROMPT,
                                           seed=seed + 1))["tokens"])
    cache = init_cache(cfg, params, batch, DECODE_PROMPT + DECODE_NEW)
    serve = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
    key = jax.random.PRNGKey(seed)
    prompt_logits = []
    t0 = time.perf_counter()
    for t in range(DECODE_PROMPT):
        tok, logits, cache = serve(params, cache, prompt[:, t:t + 1], t, key)
        prompt_logits.append(logits[:, 0])
    generated = [tok]
    for t in range(DECODE_PROMPT, DECODE_PROMPT + DECODE_NEW - 1):
        tok, logits, cache = serve(params, cache, tok, t, key)
        generated.append(tok)
        if not _finite(logits):
            raise AssertionError(f"decode position {t}: non-finite logits")
    tokens = jax.block_until_ready(jnp.concatenate(generated, axis=1))
    wall = time.perf_counter() - t0
    full, _ = jax.jit(lambda p, x: forward(cfg, p, x))(params, prompt)
    # real vocabulary only: the padded tail holds the -1e30 vocab bias
    v = cfg.vocab_size
    err = _rel_err(jnp.stack(prompt_logits, axis=1)[..., :v],
                   full[..., :v])
    log(f"decode: {DECODE_PROMPT} prompt + {DECODE_NEW} greedy tokens x "
        f"batch {batch}, wall_s={wall!r} (smoke reading, includes compile); "
        f"prompt logits vs forward rel err={err!r}")
    if not _finite(jnp.stack(prompt_logits)):
        raise AssertionError("decode: non-finite prompt logits")
    if not (0 <= int(tokens.min()) and int(tokens.max()) < cfg.vocab_size):
        raise AssertionError(f"decode: token outside the vocabulary "
                             f"[{int(tokens.min())}, {int(tokens.max())}]")
    if err > 2e-2:
        raise AssertionError(f"decode logits differ from forward: {err}")
    log(f"decode tokens[0]: {[int(t) for t in tokens[0]]}")
    return tokens


def kernel_shapes() -> dict:
    """Kernel operand widths from published configs: qwen2-0.5b attention,
    mamba2-130m SSD heads, dbrx-132b experts at 4 per chip, a qwen2-0.5b
    MLP gradient and a ragged payload (1012 rows, not a multiple of 8)."""
    from repro.configs import get_config
    qwen, mamba, dbrx = (get_config(a) for a in
                         ("qwen2-0.5b", "mamba2-130m", "dbrx-132b"))
    return {
        "flash": dict(b=8, h=qwen.num_heads, kv=qwen.num_kv_heads, s=1024,
                      d=qwen.resolved_head_dim),
        "ssd": dict(b=2, h=mamba.ssm_num_heads, l=1024, p=mamba.ssm_head_dim,
                    n=mamba.ssm_state, chunk=256),
        "gmm": dict(e=dbrx.num_experts // 4, c=512, d=dbrx.d_model,
                    f=dbrx.moe_d_ff),
        "quant": [(qwen.d_model, qwen.d_ff), (1000, 259)],
    }


def kernel_phase(shapes: dict) -> None:
    """Each Pallas kernel once against its reference (references run at
    "highest" matmul precision)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.compress.ops import dequantize, quantize
    from repro.kernels.compress.ref import dequantize_ref, quantize_ref
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.moe_gmm.ops import moe_gmm
    from repro.kernels.moe_gmm.ref import moe_gmm_ref
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_scan_ref

    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    bf16 = jnp.bfloat16
    hi = lambda: jax.default_matmul_precision("highest")

    def check(name, got, want, atol, rtol, scale=1.0):
        got = np.asarray(got, np.float32) / scale
        want = np.asarray(want, np.float32) / scale
        err = float(np.abs(got - want).max())
        log(f"kernel {name}: shape {got.shape} max abs err={err!r}")
        np.testing.assert_allclose(got, want, atol=atol, rtol=rtol,
                                   err_msg=name)

    f = shapes["flash"]
    q = jax.random.normal(ks[0], (f["b"], f["h"], f["s"], f["d"]), bf16)
    k = jax.random.normal(ks[1], (f["b"], f["kv"], f["s"], f["d"]), bf16)
    v = jax.random.normal(ks[2], (f["b"], f["kv"], f["s"], f["d"]), bf16)
    out = flash_attention(q, k, v, causal=True)
    with hi():
        ref = attention_ref(q, k, v, causal=True)
    check("flash_attention", out, ref, 2e-2, 2e-2)

    s = shapes["ssd"]
    x = (jax.random.normal(ks[3], (s["b"], s["l"], s["h"], s["p"])) * 0.5
         ).astype(bf16)
    dt = jax.nn.softplus(
        jax.random.normal(ks[4], (s["b"], s["l"], s["h"])) - 2.0)
    a = -jnp.exp(jax.random.normal(ks[5], (s["h"],)))
    bb = (jax.random.normal(ks[6], (s["b"], s["l"], s["n"])) * 0.3
          ).astype(bf16)
    cc = (jax.random.normal(ks[7], (s["b"], s["l"], s["n"])) * 0.3
          ).astype(bf16)

    def scan_and_grads(f):
        def loss(*v):
            y, last = f(*v, chunk=s["chunk"])
            return jnp.sum(jnp.sin(y)) + jnp.sum(last), y
        grads, y = jax.grad(loss, argnums=range(5), has_aux=True)(
            x, dt, a, bb, cc)
        return (y,) + grads

    got = jax.jit(lambda: scan_and_grads(ssd_scan))()
    with hi():
        want = jax.jit(lambda: scan_and_grads(ssd_scan_ref))()
    for name, g_, w_ in zip(("y", "dx", "ddt", "da", "db", "dc"), got, want):
        scale = max(float(jnp.abs(w_.astype(jnp.float32)).max()), 1.0)
        check(f"ssd_scan {name}", g_, w_, 3e-2, 3e-2, scale=scale)

    g = shapes["gmm"]
    xg = jax.random.normal(ks[0], (g["e"], g["c"], g["d"]), bf16)
    wg = (jax.random.normal(ks[1], (g["e"], g["d"], g["f"])) * 0.05
          ).astype(bf16)
    out = moe_gmm(xg, wg)
    with hi():
        ref = moe_gmm_ref(xg, wg)
    check("moe_gmm", out, ref, 2e-2, 2e-2)

    for shape in shapes["quant"]:
        xq = jax.random.normal(ks[2], shape)
        qv, scales, orig = quantize(xq)
        dec = dequantize(qv, scales, orig)
        rows = qv.shape[0]
        x_rows = jnp.pad(xq.reshape(-1), (0, qv.size - xq.size)).reshape(
            rows, -1)
        q_ref, s_ref = quantize_ref(x_rows, per_row=True)
        mismatched = int((np.asarray(qv) != np.asarray(q_ref)).sum())
        log(f"kernel quantize {shape}: {rows} rows, {mismatched} codes differ "
            f"from the reference")
        np.testing.assert_array_equal(np.asarray(qv), np.asarray(q_ref))
        np.testing.assert_allclose(np.asarray(scales), np.asarray(s_ref),
                                   rtol=1e-6)
        dec_ref = dequantize_ref(q_ref, s_ref).reshape(-1)[:xq.size]
        check(f"dequantize {shape}", dec, dec_ref.reshape(shape), 0.0, 1e-6)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def _spans(tree, devices, what: str) -> None:
    """Every array of ``tree`` lives on all of ``devices``."""
    import jax
    want = set(devices)
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        if set(leaf.sharding.device_set) != want:
            raise AssertionError(
                f"{what}{jax.tree_util.keystr(path)} is on "
                f"{sorted(d.id for d in leaf.sharding.device_set)}, not on "
                f"all {len(want)} devices")


def multichip_train_phase(cfg, devices, *, batch: int, seq: int,
                          seed: int = 0) -> None:
    """(data=2, model=2) train step vs the same step on one chip."""
    import jax
    from jax.sharding import NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.types import MeshConfig, TrainConfig
    from repro.data.pipeline import make_batches
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import init_params
    from repro.optim.adamw import init_opt_state
    from repro.parallel.planner import batch_specs, make_ctx, param_specs
    from repro.train.step import make_train_step

    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=10, total_steps=1,
                       remat=False, seed=seed)
    params_host = jax.device_get(init_params(cfg, jax.random.PRNGKey(seed)))
    batch_host = next(make_batches(cfg, batch, seq, seed=seed))

    one = SingleDeviceSharding(devices[0])
    p1 = jax.device_put(params_host, one)
    _, _, m1 = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))(
        p1, init_opt_state(p1), jax.device_put(batch_host, one))
    loss1, gnorm1 = float(m1["loss"]), float(m1["grad_norm"])
    del p1, m1

    mcfg = MeshConfig(shape=(2, 2))
    mesh = make_mesh(mcfg.shape, mcfg.axis_names, devices=devices)
    if sorted(d.id for d in mesh.devices.flat) != sorted(
            d.id for d in devices) or mesh.devices.size != 4:
        raise AssertionError(f"mesh {mesh} does not span {devices}")
    ctx = make_ctx(mesh, mcfg, remat=False)
    shard = jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                         param_specs(cfg, mcfg),
                         is_leaf=lambda x: isinstance(x, P))
    p4 = jax.device_put(params_host, shard)
    o4 = init_opt_state(p4)
    o4["step"] = jax.device_put(o4["step"], NamedSharding(mesh, P()))
    bspec = batch_specs(mcfg)
    b4 = {k: jax.device_put(v, NamedSharding(mesh, bspec[k]))
          for k, v in batch_host.items()}
    _spans((p4, o4, b4), devices, "input")
    if p4["embed"].addressable_shards[0].data.shape == p4["embed"].shape:
        raise AssertionError("embedding is not sharded over the mesh")
    step4 = jax.jit(make_train_step(cfg, tcfg, ctx), donate_argnums=(0, 1))
    new_p, new_o, m4 = step4(p4, o4, b4)
    _spans((new_p, new_o), devices, "output")
    loss4, gnorm4 = float(m4["loss"]), float(m4["grad_norm"])
    log(f"train (data=2, model=2) step-0 loss={loss4!r} "
        f"grad_norm={gnorm4!r}; one chip loss={loss1!r} "
        f"grad_norm={gnorm1!r}; |loss diff|={abs(loss4 - loss1)!r}")
    if abs(loss4 - loss1) > 2e-3 * abs(loss1):
        raise AssertionError(f"(2, 2) loss {loss4} != one-chip {loss1}")
    if abs(gnorm4 - gnorm1) > 1e-2 * abs(gnorm1):
        raise AssertionError(f"(2, 2) grad norm {gnorm4} != one-chip "
                             f"{gnorm1}")


def collectives_phase(devices, *, elems: int, seed: int = 0) -> None:
    """Executable all-reduces over a 4-device axis vs ``lax.psum``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.ccl.primitives import (IMPLEMENTATIONS, make_all_reduce,
                                      make_synthesized)
    from repro.ccl.synth import synthesize_schedule
    from repro.core.demand import CommTask
    from repro.launch.mesh import make_mesh
    from repro.net.topology import torus2d

    p = len(devices)
    mesh = make_mesh((p,), ("x",), devices=devices)
    spec = P("x", None)
    rng = np.random.default_rng(seed)
    # integer-valued floats: lossless float32 sums are exact in any order
    exact = jax.device_put(
        rng.integers(-1000, 1000, (p, elems)).astype(np.float32),
        NamedSharding(mesh, spec))
    lossy = jax.device_put(rng.standard_normal((p, elems), np.float32),
                           NamedSharding(mesh, spec))
    psum = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "x"), mesh=mesh,
                                 in_specs=spec, out_specs=spec))
    want = {"exact": np.asarray(psum(exact)), "lossy": np.asarray(psum(lossy))}
    codec_bits = {"ring_q8": 8, "ring_q4": 4}

    def compare(name, fn, x):
        got = fn(x)
        _spans(got, devices, name)
        got = np.asarray(got)
        bits = codec_bits.get(name)
        if bits is None:
            ok = np.array_equal(got, want["exact"])
            log(f"all_reduce {name}: {elems * 4} B per chip, equal to psum: "
                f"{ok}")
            np.testing.assert_array_equal(got, want["exact"], err_msg=name)
            return
        # each of the p-1 accumulate hops re-quantizes: p * absmax / qmax
        bound = p * float(np.abs(np.asarray(x)).max()) / (2 ** (bits - 1) - 1)
        err = float(np.abs(got - want["lossy"]).max())
        log(f"all_reduce {name}: {elems * 4} B per chip, max abs err vs "
            f"psum={err!r} (bound {bound!r})")
        if not err <= bound:
            raise AssertionError(f"{name}: error {err} > codec bound {bound}")
        if not all(np.array_equal(got[0], got[i]) for i in range(p)):
            raise AssertionError(f"{name}: ranks hold different results")

    for name in IMPLEMENTATIONS:
        compare(name, make_all_reduce(name, mesh, "x"),
                lossy if name in codec_bits else exact)
    topo = torus2d(2, 2)
    sched = synthesize_schedule(topo, CommTask(
        "t", "all_reduce", exact.nbytes, tuple(topo.accelerators)))
    compare("synthesized(torus2d 2x2)", make_synthesized(sched, mesh, "x"),
            exact)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip train step and "
                         "collectives")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform "
                 f"{devices[0].platform!r}); this script runs only on a TPU")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
                 f"devices, found {len(devices)}")

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: {devices[0].device_kind} x {len(devices)}; compile cache "
        f"{enable_compile_cache()}")
    cfg = get_config("qwen2-0.5b")
    t0 = time.perf_counter()
    if args.chips == 4:
        multichip_train_phase(cfg, devices[:4], batch=8, seq=128)
        collectives_phase(devices[:4], elems=1 << 22)
    else:
        params, _ = train_phase(cfg, batch=8, seq=128, steps=5)
        stats = devices[0].memory_stats() or {}
        log(f"peak_bytes_in_use after train: "
            f"{stats.get('peak_bytes_in_use')!r}")
        decode_phase(cfg, params, batch=8)
        del params
        kernel_phase(kernel_shapes())
    log(f"all phases passed in {time.perf_counter() - t0!r} s (smoke reading)")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
