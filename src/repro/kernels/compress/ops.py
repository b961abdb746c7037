"""Jitted public wrappers for the compression kernels.

Payloads of any shape are flattened to a (rows, row_len) layout with
per-row scales/thresholds — the layout both the Pallas kernels and the
references share.  Row counts are zero-padded to a multiple of 8 so every
row block obeys the TPU (8, 128) tiling rule; the kernels run compiled on
TPU and interpreted on CPU (``repro.kernels.platform``).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.compress.kernel import (dequantize_kernel, matmul_kernel,
                                           quantize_kernel, sparsify_kernel)
from repro.kernels.compress.ref import (dequantize_ref, matmul_ref,
                                        quantize_ref, sparsify_ref)

_SUBLANES = 8  # rows per TPU vreg tile; every row block is a multiple


def _as_rows(x: jax.Array, row_len: int = 256) -> Tuple[jax.Array, int]:
    """Flatten + zero-pad to (rows, row_len) with rows a multiple of 8;
    returns (rows2d, orig_size)."""
    flat = x.reshape(-1)
    n = flat.size
    pad = (-n) % (row_len * _SUBLANES)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, row_len), n


def _tile(n: int, block: int) -> Tuple[int, int]:
    """(padded extent, block) for a dim of size ``n``: one whole block when
    ``n <= block`` (padded to a multiple of 8), otherwise ``block``-sized
    blocks over ``n`` rounded up to a multiple of ``block``."""
    if n <= block:
        whole = -(-n // _SUBLANES) * _SUBLANES
        return whole, whole
    return -(-n // block) * block, block


def quantize(x: jax.Array, *, bits: int = 8, stochastic: bool = False,
             key: Optional[jax.Array] = None, row_len: int = 256
             ) -> Tuple[jax.Array, jax.Array, Tuple[int, ...]]:
    """Quantize any-shape ``x`` -> (q int8 (rows, row_len), scales (rows, 1),
    original shape).  Stochastic rounding draws its bits from ``key``."""
    rows, _ = _as_rows(x, row_len)
    rand = None
    if stochastic:
        if key is None:
            raise ValueError("stochastic rounding needs a PRNG key")
        rand = jax.random.bits(key, rows.shape, jnp.uint32)
    q, scales = quantize_kernel(rows, rand, bits=bits, stochastic=stochastic,
                                bm=_SUBLANES)
    return q, scales, x.shape


def dequantize(q: jax.Array, scales: jax.Array, shape: Tuple[int, ...],
               dtype=jnp.float32) -> jax.Array:
    out = dequantize_kernel(q, scales, bm=_SUBLANES)
    n = math.prod(shape)
    return out.reshape(-1)[:n].reshape(shape).astype(dtype)


def sparsify(x: jax.Array, thresh: jax.Array, *,
             row_len: int = 256) -> jax.Array:
    """Zero entries of ``x`` below the (scalar) magnitude threshold."""
    rows, n = _as_rows(x, row_len)
    t = jnp.broadcast_to(jnp.asarray(thresh, jnp.float32),
                         (rows.shape[0], 1))
    out = sparsify_kernel(rows, t, bm=_SUBLANES)
    return out.reshape(-1)[:n].reshape(x.shape)


def lowrank_project(m: jax.Array, q: jax.Array) -> jax.Array:
    """PowerSGD projection P = M @ Q (and, transposed, Q' = M^T @ P) with
    fp32 accumulation; M's rows and Q's columns are zero-padded to whole
    tiles and the result is cut back to (rows of M, cols of Q)."""
    rows, cols = m.shape[0], q.shape[1]
    rows_pad, bm = _tile(rows, 128)
    cols_pad, bn = _tile(cols, 128)
    out = matmul_kernel(jnp.pad(m, ((0, rows_pad - rows), (0, 0))),
                        jnp.pad(q, ((0, 0), (0, cols_pad - cols))),
                        bm=bm, bn=bn)
    return out[:rows, :cols]


reference = {
    "quantize": quantize_ref,
    "dequantize": dequantize_ref,
    "sparsify": sparsify_ref,
    "matmul": matmul_ref,
}
