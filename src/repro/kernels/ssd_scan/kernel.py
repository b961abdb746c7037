"""Mamba2 SSD chunked-scan Pallas TPU kernel.

TPU adaptation of the GPU selective-scan: the chunked *dual form* turns the
recurrence into (Q x Q) and (Q x N)/(N x P) matmuls per chunk (MXU work),
with only the inter-chunk state carried sequentially.  The carry state
(P x N per head) lives in VMEM scratch and persists across the sequential
chunk grid dimension — the Pallas analogue of the fused CUDA scan's
register-resident state (DESIGN.md hardware-adaptation note).

Layouts: x (B, H, L, P), dt (B, H, L), a (H,), b/c (B, L, N) (group-
broadcast over heads).  Output y (B, H, L, P).
Grid: (B, H, L/Q) with the chunk dimension sequential.

Every block obeys the TPU (8, 128) tiling rule: ``dt`` enters twice, as a
(1, Q) row and a (Q, 1) column of free reshapes ``(B, H, 1, L)`` and
``(B, H, L, 1)``, so the kernel needs neither a transpose nor a 1-D vector;
the per-head scalar ``a`` is read from SMEM.  Cumulative sums are masked
reductions over the (Q, Q) triangle the kernel builds anyway.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call


def _ssd_kernel(x_ref, dt_row_ref, dt_col_ref, a_ref, b_ref, c_ref, y_ref,
                h_ref, *, q: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)              # (Q, P)
    a = a_ref[pl.program_id(1)]                      # scalar (SMEM)
    dt_row = dt_row_ref[0, 0].astype(jnp.float32)    # (1, Q)
    dt_col = dt_col_ref[0, 0].astype(jnp.float32)    # (Q, 1)
    da_row, da_col = dt_row * a, dt_col * a
    bmat = b_ref[0].astype(jnp.float32)              # (Q, N)
    cmat = c_ref[0].astype(jnp.float32)              # (Q, N)

    # cumulative sums of da as a column and a row: cs_i = sum_{j<=i} da_j
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tril = rows >= cols
    cs_col = jnp.sum(jnp.where(tril, da_row, 0.0), axis=1,
                     keepdims=True)                  # (Q, 1)
    cs_row = jnp.sum(jnp.where(rows <= cols, da_col, 0.0), axis=0,
                     keepdims=True)                  # (1, Q)
    total = jnp.sum(da_col, axis=0, keepdims=True)   # (1, 1)
    # intra-chunk decay matrix L[i,j] = exp(cs_i - cs_j) for j <= i
    lmat = jnp.where(tril, jnp.exp(cs_col - cs_row), 0.0)

    scores = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    w = scores * lmat * dt_row                       # (Q, Q)
    y_diag = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    # inter-chunk contribution from the carried state
    h_prev = h_ref[...]                              # (P, N)
    y_off = jax.lax.dot_general(cmat, h_prev, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, 0] = (y_diag + y_off * jnp.exp(cs_col)).astype(y_ref.dtype)

    # state update: h = h * exp(sum da) + x^T @ (b * decay_out * dt)
    bw = bmat * (jnp.exp(total - cs_col) * dt_col)   # (Q, N)
    state = jax.lax.dot_general(x, bw, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    h_ref[...] = h_prev * jnp.exp(total) + state


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan_kernel(x, dt, a, b, c, *, chunk: int = 128):
    """x: (B,H,L,P); dt: (B,H,L); a: (H,); b,c: (B,L,N) -> y (B,H,L,P)."""
    bsz, h, l, p = x.shape
    n = b.shape[-1]
    q = min(chunk, l)
    assert l % q == 0
    nc = l // q

    kernel = functools.partial(_ssd_kernel, q=q)
    return pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, q, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, 1, q), lambda ib, ih, ic: (ib, ih, 0, ic)),
            pl.BlockSpec((1, 1, q, 1), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, q, n), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((1, q, n), lambda ib, ih, ic: (ib, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q, p),
                               lambda ib, ih, ic: (ib, ih, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, l, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
    )(x, dt.reshape(bsz, h, 1, l), dt.reshape(bsz, h, l, 1),
      a.astype(jnp.float32), b, c)
