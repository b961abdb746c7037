"""Mamba2 (state-space duality) block — chunked SSD form, TPU-adapted.

The GPU reference implementation relies on a fused selective-scan CUDA kernel
(warp shuffles, shared-memory staging).  That mechanism has no TPU analogue;
the TPU-idiomatic equivalent is the *chunked dual form* of SSD
[arXiv:2405.21060, Sec. 6]: intra-chunk work becomes dense (Q x Q) and
(Q x N) matmuls that map onto the MXU, and only the O(L/Q) inter-chunk state
recurrence is sequential.  Two implementations of the scan, one algorithm:

* on the TPU, ``repro.kernels.ssd_scan``: a Pallas forward and backward
  joined by a ``jax.custom_vjp``, each chunk's (Q x Q) decay matrices and
  their gradients in VMEM only;
* everywhere else, and for shapes the kernels do not tile,
  ``ssd_chunked_jnp`` (XLA's fusions; also the kernels' oracle).

``ssd_chunked`` picks one from the shapes and, when the program is lowered,
from the target platform (``lax.platform_dependent``); a program split over
a mesh of several devices keeps the jnp scan (``mamba_forward``).  The
short causal convolution (and its SiLU) of each stream is chosen the same
way by ``causal_conv``: the Pallas kernels of ``repro.kernels.causal_conv``
or the jnp ``_causal_conv``.  The x stream's conv also yields the D skip
term (``causal_conv_skip``), so that the kernels' backward sums the scan's
and the skip's cotangents of x on chip.

Projections are kept as separate tensors (z / x / B / C / dt and per-stream
convs) instead of one fused ``in_proj`` so the tensor-parallel planner can
shard the head-structured ones (z, x, dt, out) over the model axis while the
state projections (B, C — shared across heads, GQA-like) stay replicated.

In ``mamba_forward`` the scan runs under ``jax.named_scope("ssd")`` and each
convolution under ``jax.named_scope("causal_conv")``, inside the layer's
``ssm`` block scope (``models.transformer``): names in the HLO metadata
only, by which the benchmark reads their device time.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.types import ModelConfig
from repro.models.modules import dense_init, init_norm, rms_norm

DEFAULT_CHUNK = 256


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_mamba(key, cfg: ModelConfig, dtype) -> dict:
    d = cfg.d_model
    din = cfg.ssm_d_inner
    n = cfg.ssm_state
    h = cfg.ssm_num_heads
    k = cfg.ssm_conv_kernel
    ks = jax.random.split(key, 10)

    def conv_init(kk, ch):
        return (jax.random.normal(kk, (k, ch), jnp.float32) * 0.1).astype(dtype)

    return {
        "z_proj": dense_init(ks[0], d, (din,), dtype),
        "x_proj": dense_init(ks[1], d, (din,), dtype),
        "b_proj": dense_init(ks[2], d, (n,), dtype),
        "c_proj": dense_init(ks[3], d, (n,), dtype),
        "dt_proj": dense_init(ks[4], d, (h,), dtype),
        "conv_x": conv_init(ks[5], din),
        "conv_x_bias": jnp.zeros((din,), dtype),
        "conv_b": conv_init(ks[6], n),
        "conv_b_bias": jnp.zeros((n,), dtype),
        "conv_c": conv_init(ks[7], n),
        "conv_c_bias": jnp.zeros((n,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm": init_norm(din, dtype),
        "out_proj": dense_init(ks[8], din, (d,), dtype),
    }


# ---------------------------------------------------------------------------
# Pieces
# ---------------------------------------------------------------------------


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv via shifted adds, then SiLU. x: (B, L, C);
    w: (K, C); b: (C,).  XLA's fusions on any platform, and the Pallas
    kernels' oracle."""
    k = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(k):
        out = out + pad[:, i:i + x.shape[1], :] * w[i]
    return jax.nn.silu(out + b)


def _causal_conv_skip(x: jax.Array, w: jax.Array, b: jax.Array,
                      d: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``(y, y * d)``: ``_causal_conv``'s output y in float32 and the D skip
    term of a per-channel d (C,)."""
    y = _causal_conv(x, w, b).astype(jnp.float32)
    return y, y * d


def _conv_dispatch(kernel: str, oracle, x, w, *rest):
    """``oracle(x, w, *rest)``; where the Pallas kernels tile x
    (``causal_conv.fits``), a program lowered for the TPU runs
    ``kernels.causal_conv``'s ``kernel`` instead (forward and backward).
    The choice is made when the program is lowered."""
    from repro.kernels import causal_conv as kernels  # Pallas, where it runs
    if not kernels.fits(x.shape, w.shape[0], x.dtype.itemsize):
        return oracle(x, w, *rest)
    return jax.lax.platform_dependent(x, w, *rest,
                                      tpu=getattr(kernels, kernel),
                                      default=oracle)


def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """``_causal_conv``, through the kernels where they run."""
    return _conv_dispatch("causal_conv", _causal_conv, x, w, b)


def causal_conv_skip(x: jax.Array, w: jax.Array, b: jax.Array,
                     d: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``_causal_conv_skip``, through the kernels where they run: their
    backward sums the two outputs' cotangents on chip."""
    return _conv_dispatch("causal_conv_skip", _causal_conv_skip, x, w, b, d)


def _segsum(dac: jax.Array) -> jax.Array:
    """dac: (..., Q) log-decay per step. Returns (..., Q, Q) with
    out[i, j] = sum_{j < m <= i} dac[m]  (-inf above the diagonal)."""
    q = dac.shape[-1]
    cs = jnp.cumsum(dac, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]  # [i,j] = cs_i - cs_j
    mask = jnp.tril(jnp.ones((q, q), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x, dt, a, b, c, *, chunk: int = DEFAULT_CHUNK, h0=None):
    """Chunked SSD scan.

    x: (B, L, H, P) f32; dt: (B, L, H) f32 (post-softplus);
    a: (H,) negative decay rates; b, c: (B, L, N) (single group, broadcast
    over heads).  Returns (y (B,L,H,P), h_final (B,H,P,N)).

    Where the Pallas kernels tile the shapes (``ssd_scan.fits``), a program
    lowered for the TPU runs them (forward and backward, ``ssd_scan``); any
    other platform, and every shape they do not tile, runs
    ``ssd_chunked_jnp``.  The choice is made when the program is lowered."""
    from repro.kernels import ssd_scan   # Pallas, imported where it runs
    if not ssd_scan.fits(x.shape, b.shape[-1], chunk, x.dtype.itemsize):
        return ssd_chunked_jnp(x, dt, a, b, c, chunk=chunk, h0=h0)
    return jax.lax.platform_dependent(
        x, dt, a, b, c, h0,
        tpu=lambda *v: ssd_scan.ssd_scan(*v[:5], chunk=chunk, h0=v[5]),
        default=lambda *v: ssd_chunked_jnp(*v[:5], chunk=chunk, h0=v[5]))


def ssd_chunked_jnp(x, dt, a, b, c, *, chunk: int = DEFAULT_CHUNK, h0=None):
    """``ssd_chunked`` in plain jnp: XLA's fusions on any platform, and the
    Pallas kernels' oracle."""
    bsz, l, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, l)
    assert l % q == 0, (l, q)
    nc = l // q

    xs = x.reshape(bsz, nc, q, h, p)
    dts = dt.reshape(bsz, nc, q, h)
    bs = b.reshape(bsz, nc, q, n)
    cs_ = c.reshape(bsz, nc, q, n)

    da = dts * a  # (B,nc,Q,H) log-decay contributions
    da_cum = jnp.cumsum(da, axis=2)  # inclusive within chunk
    da_total = da_cum[:, :, -1]  # (B,nc,H)

    # --- intra-chunk (dual / attention-like) term ---
    lmat = jnp.exp(_segsum(jnp.moveaxis(da, 2, 3)))  # (B,nc,H,Q,Q)
    scores = jnp.einsum("bcin,bcjn->bcij", cs_, bs)  # (B,nc,Q,Q)
    w = scores[:, :, None] * lmat  # (B,nc,H,Q,Q)
    y_diag = jnp.einsum("bchij,bcjh,bcjhp->bcihp", w, dts, xs)

    # --- chunk -> state contributions ---
    decay_out = jnp.exp(da_total[:, :, None, :] - da_cum)  # (B,nc,Q,H)
    states = jnp.einsum("bcjn,bcjh,bcjh,bcjhp->bchpn",
                        bs, decay_out, dts, xs)  # (B,nc,H,P,N)

    # --- inter-chunk recurrence ---
    def step(hprev, inputs):
        st, dtot = inputs  # (B,H,P,N), (B,H)
        hnew = hprev * jnp.exp(dtot)[:, :, None, None] + st
        return hnew, hprev

    init = (jnp.zeros((bsz, h, p, n), jnp.float32)
            if h0 is None else h0.astype(jnp.float32))
    h_final, h_before = jax.lax.scan(
        step, init, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(da_total, 1, 0)))
    h_before = jnp.moveaxis(h_before, 0, 1)  # (B,nc,H,P,N) state at chunk start

    # --- inter-chunk output term ---
    decay_in = jnp.exp(da_cum)  # (B,nc,Q,H)
    y_off = jnp.einsum("bcin,bcih,bchpn->bcihp", cs_, decay_in, h_before)

    y = (y_diag + y_off).reshape(bsz, l, h, p)
    return y, h_final


# ---------------------------------------------------------------------------
# Block-level forward / decode
# ---------------------------------------------------------------------------


def mamba_forward(p: dict, cfg: ModelConfig, xin: jax.Array, *,
                  chunk: int = DEFAULT_CHUNK, ctx=None) -> jax.Array:
    """xin: (B, L, d) -> (B, L, d).  Under a ``ctx`` whose mesh holds more
    than one device the convolutions and the scan stay in jnp: GSPMD cannot
    split the kernels, and would gather their operands whole onto every
    device."""
    din, n, h = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_num_heads
    hd = cfg.ssm_head_dim
    mesh = getattr(ctx, "mesh", None)
    one = mesh is None or mesh.size == 1
    conv_fn, conv_skip_fn, scan = (
        (causal_conv, causal_conv_skip, ssd_chunked) if one
        else (_causal_conv, _causal_conv_skip, ssd_chunked_jnp))

    def conv(name, u, *d):
        with jax.named_scope("causal_conv"):
            return (conv_skip_fn if d else conv_fn)(
                u, p[name], p[name + "_bias"], *d)

    z = xin @ p["z_proj"]
    x, skip = conv("conv_x", xin @ p["x_proj"], jnp.repeat(p["D"], hd))
    b = conv("conv_b", xin @ p["b_proj"])
    c = conv("conv_c", xin @ p["c_proj"])
    dt = jax.nn.softplus(
        (xin @ p["dt_proj"]).astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    xh = x.reshape(*x.shape[:2], h, hd)
    with jax.named_scope("ssd"):
        y, _ = scan(xh, dt, a, b.astype(jnp.float32), c.astype(jnp.float32),
                    chunk=chunk)
    y = y + skip.reshape(xh.shape)
    y = y.reshape(*xin.shape[:2], din).astype(xin.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"]["scale"], cfg.norm_eps)
    return y @ p["out_proj"]


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype) -> dict:
    din, n = cfg.ssm_d_inner, cfg.ssm_state
    km1 = cfg.ssm_conv_kernel - 1
    return {
        "conv_x": jnp.zeros((batch, km1, din), dtype),
        "conv_b": jnp.zeros((batch, km1, n), dtype),
        "conv_c": jnp.zeros((batch, km1, n), dtype),
        "ssm": jnp.zeros((batch, cfg.ssm_num_heads, cfg.ssm_head_dim, n),
                         jnp.float32),
    }


def _conv_step(hist, new, w, b):
    """hist: (B, K-1, C) past inputs; new: (B, C). Returns (out, new_hist)."""
    full = jnp.concatenate([hist, new[:, None, :].astype(hist.dtype)], axis=1)
    out = jnp.einsum("bkc,kc->bc", full.astype(jnp.float32),
                     w.astype(jnp.float32)) + b
    return jax.nn.silu(out), full[:, 1:]


def mamba_decode(p: dict, cfg: ModelConfig, xin: jax.Array, cache: dict
                 ) -> Tuple[jax.Array, dict]:
    """Single-token recurrent step. xin: (B, 1, d)."""
    din, n, h = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_num_heads
    hd = cfg.ssm_head_dim
    x0 = xin[:, 0]
    z = x0 @ p["z_proj"]
    x, conv_x = _conv_step(cache["conv_x"], x0 @ p["x_proj"], p["conv_x"],
                           p["conv_x_bias"])
    b, conv_b = _conv_step(cache["conv_b"], x0 @ p["b_proj"], p["conv_b"],
                           p["conv_b_bias"])
    c, conv_c = _conv_step(cache["conv_c"], x0 @ p["c_proj"], p["conv_c"],
                           p["conv_c_bias"])
    dt1 = jax.nn.softplus(
        (x0 @ p["dt_proj"]).astype(jnp.float32) + p["dt_bias"])  # (B,H)
    a = -jnp.exp(p["A_log"])

    xh = x.astype(jnp.float32).reshape(-1, h, hd)
    decay = jnp.exp(dt1 * a)  # (B,H)
    hnew = (cache["ssm"] * decay[..., None, None]
            + jnp.einsum("bh,bhp,bn->bhpn", dt1, xh,
                         b.astype(jnp.float32)))
    y = jnp.einsum("bhpn,bn->bhp", hnew, c.astype(jnp.float32)) \
        + xh * p["D"][:, None]
    y = y.reshape(-1, din).astype(xin.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"]["scale"], cfg.norm_eps)
    out = (y @ p["out_proj"])[:, None, :]
    new_cache = {"conv_x": conv_x, "conv_b": conv_b, "conv_c": conv_c,
                 "ssm": hnew}
    return out, new_cache
