"""Pallas TPU kernels for gradient compression.

Three codec hot loops as fused VMEM kernels (per the survey's lever-3
compression arrow — the encode/decode passes sit on the critical path of
every compressed collective step, so they must run at VPU/MXU speed, not
as a chain of HBM-bound jnp ops):

  * ``quantize``   — per-row absmax scale + uniform int8/int4 rounding in
    one pass; stochastic rounding takes pre-generated uint32 bits (kept as
    an input so the kernel is reproducible and interpret-mode exact).
  * ``dequantize`` — scale-multiply back to fp32.
  * ``sparsify``   — magnitude thresholding against a per-row threshold
    (the top-k codec computes the k-th magnitude outside; the dense
    mask-apply is the bandwidth-bound pass).
  * ``matmul``     — fp32-accumulated blocked matmul, the PowerSGD
    projection primitive (M @ Q and M^T @ P).

Grids iterate over row blocks; the row length rides in whole (gradient
payloads are flattened to (rows, row_len) by ``ops.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_call

_TINY = 1e-30


def _quantize_kernel(x_ref, *refs, qmax: float, stochastic: bool):
    if stochastic:
        rand_ref, q_ref, scale_ref = refs
    else:
        q_ref, scale_ref = refs
    x = x_ref[...].astype(jnp.float32)                    # (bm, n)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)  # (bm, 1)
    scale = jnp.maximum(absmax, _TINY) / qmax
    scale_ref[...] = scale
    scaled = x / scale
    if stochastic:
        # uint32 -> uniform [0, 1): take the top 24 bits (exact in fp32)
        u = (rand_ref[...] >> 8).astype(jnp.float32) * (2.0 ** -24)
        q = jnp.floor(scaled + u)
    else:
        q = jnp.round(scaled)
    q_ref[...] = jnp.clip(q, -qmax, qmax).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bits", "stochastic", "bm"))
def quantize_kernel(x, rand_bits=None, *, bits: int = 8,
                    stochastic: bool = False, bm: int = 8):
    """x: (m, n) -> (q int8 (m, n), scale f32 (m, 1)), per-row scales.
    ``rand_bits`` (uint32, same shape) is only required — and only moved
    into VMEM — when ``stochastic=True``; the deterministic hot path stays
    a single-input bandwidth-bound pass."""
    m, n = x.shape
    bm = min(bm, m)
    assert m % bm == 0, (m, bm)
    qmax = float(2 ** (bits - 1) - 1)
    kernel = functools.partial(_quantize_kernel, qmax=qmax,
                               stochastic=stochastic)
    block = pl.BlockSpec((bm, n), lambda i: (i, 0))
    operands = (x,)
    in_specs = [block]
    if stochastic:
        if rand_bits is None:
            raise ValueError("stochastic quantize needs rand_bits")
        operands = (x, rand_bits)
        in_specs = [block, block]
    return pallas_call(
        kernel,
        grid=(m // bm,),
        in_specs=in_specs,
        out_specs=[block,
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((m, n), jnp.int8),
                   jax.ShapeDtypeStruct((m, 1), jnp.float32)],
    )(*operands)


def _dequantize_kernel(q_ref, scale_ref, out_ref):
    out_ref[...] = q_ref[...].astype(jnp.float32) * scale_ref[...]


@functools.partial(jax.jit, static_argnames=("bm",))
def dequantize_kernel(q, scale, *, bm: int = 8):
    """(q int8 (m, n), scale (m, 1)) -> f32 (m, n)."""
    m, n = q.shape
    bm = min(bm, m)
    assert m % bm == 0, (m, bm)
    return pallas_call(
        _dequantize_kernel,
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0)),
                  pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
    )(q, scale)


def _sparsify_kernel(x_ref, thresh_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)
    out_ref[...] = jnp.where(jnp.abs(x) >= thresh_ref[...], x, 0.0)


@functools.partial(jax.jit, static_argnames=("bm",))
def sparsify_kernel(x, thresh, *, bm: int = 8):
    """x: (m, n), thresh: (m, 1) -> masked f32 (m, n)."""
    m, n = x.shape
    bm = min(bm, m)
    assert m % bm == 0, (m, bm)
    return pallas_call(
        _sparsify_kernel,
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0)),
                  pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
    )(x, thresh)


def _matmul_kernel(a_ref, b_ref, out_ref):
    out_ref[...] = jax.lax.dot_general(
        a_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bn"))
def matmul_kernel(a, b, *, bm: int = 128, bn: int = 128):
    """Blocked (m, k) x (k, n) -> f32 (m, n); k rides whole (PowerSGD
    ranks are tiny, the k dimension is the payload one)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn = min(bm, m), min(bn, n)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    return pallas_call(
        _matmul_kernel,
        grid=(m // bm, n // bn),
        in_specs=[pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
                  pl.BlockSpec((k, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
    )(a, b)
