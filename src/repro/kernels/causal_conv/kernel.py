"""Mamba-2's causal depthwise convolution and SiLU as Pallas TPU kernels: a
forward and its backward.

    out[t] = silu(b + sum_{s < K} w[K-1-s] * x[t-s]),  x[t] = 0 for t < 0,

per channel, x (B, L, C), w (K, C), b (C).  One program holds one batch
row's whole sequence for a block of lanes (channels), so no program needs
rows of another.  It walks the sequence in slabs of rows
(``lax.fori_loop``).  A slab is read from VMEM together with the ``halo``
rows before it (one sublane tile, zeros before the sequence), and each tap's
time shift is a roll of that along the sublane axis (``pltpu.roll``): no
shifted or padded copy is ever written to HBM.

The backward recomputes the pre-activation from x, forms
``g = dy * silu'(pre)``, and writes ``dx[t] = sum_s w[K-1-s] * g[t+s]``.  It
walks the slabs from the last, carrying the first rows of g of the slab
after the current one.  The tap and bias gradients are summed over the
program's rows into (8, lanes) float32 partials, which ``ops.py`` sums over
batch rows and sublanes.

Where the output also feeds a per-channel skip term ``y * d`` (Mamba-2's
D skip), the backward takes that term's cotangent ds too: it uses
``dy + ds * d`` in place of dy, and sums dd's partials ``ds * y`` as it does
the bias's, so no sum of the two cotangents is written to HBM.

So the forward reads x once and writes the output once; the backward reads
x and dy (and ds) once and writes dx once.  Every product and sum is
float32, whatever the dtypes of x and the output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call

LANES = 128
SUBLANES = 8
_F32 = jnp.float32


def halo_rows(itemsize: int) -> int:
    """Rows of one sublane tile of x: 8 for 4-byte values, 16 for 2."""
    return SUBLANES * 4 // itemsize


def slab_rows(l: int, itemsize: int) -> int:
    """Rows a loop step of either kernel takes: the largest of 128, 64, ...
    down to one sublane tile that divides L."""
    r = 128
    while r > halo_rows(itemsize) and l % r:
        r //= 2
    return r


def block_bytes(l: int, itemsize: int) -> int:
    """VMEM of one backward program's double-buffered blocks (x and dx, and
    dy and ds, at most float32: the larger kernel) and its float32 slab
    temporaries."""
    return 2 * l * LANES * (2 * itemsize + 2 * 4) + 24 * 128 * LANES * 4


def _window(x_ref, j, rows, halo):
    """Slab j's rows of ``x_ref[0]`` with the ``halo`` rows before it, in
    float32 (halo + rows, lanes); zeros before the sequence."""
    r0 = pl.multiple_of(j * rows, rows)
    past = x_ref[0, pl.ds(pl.multiple_of(jnp.maximum(r0 - halo, 0), halo),
                          halo), :].astype(_F32)
    past = jnp.where(j > 0, past, 0.0)
    return jnp.concatenate([past, x_ref[0, pl.ds(r0, rows), :].astype(_F32)],
                           axis=0)


def _taps(win, k, halo):
    """[x[t - s] for s < K] over the slab's rows, from its window."""
    return [win[halo:]] + [pltpu.roll(win, s, 0)[halo:] for s in range(1, k)]


def _pre(xs, w, bias):
    """b + sum_s w[K-1-s] x[t-s], summed from the oldest tap, as
    ``models/ssm.py`` ``_causal_conv`` sums it."""
    k = len(xs)
    acc = xs[k - 1] * w[0:1]
    for i in range(1, k):
        acc = acc + xs[k - 1 - i] * w[i:i + 1]
    return acc + bias


def _fold(v):
    """(R, lanes) -> (8, lanes): the sum of its sublane tiles."""
    r, t = v.shape
    return jnp.sum(v.reshape(r // SUBLANES, SUBLANES, t), axis=0)


def _fwd_kernel(x_ref, w_ref, b_ref, o_ref, *, rows, halo):
    k = w_ref.shape[0]
    w, bias = w_ref[...].astype(_F32), b_ref[...].astype(_F32)

    def body(j, carry):
        pre = _pre(_taps(_window(x_ref, j, rows, halo), k, halo), w, bias)
        o_ref[0, pl.ds(pl.multiple_of(j * rows, rows), rows), :] = (
            pre * jax.nn.sigmoid(pre)).astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, x_ref.shape[1] // rows, body, 0)


def _bwd_kernel(*refs, rows, halo, skip):
    if skip:
        (x_ref, dy_ref, ds_ref, w_ref, b_ref, d_ref,
         dx_ref, dw_ref, db_ref, dd_ref) = refs
    else:
        x_ref, dy_ref, w_ref, b_ref, dx_ref, dw_ref, db_ref = refs
    k, lanes = w_ref.shape
    n = x_ref.shape[1] // rows
    w, bias = w_ref[...].astype(_F32), b_ref[...].astype(_F32)
    zeros = jnp.zeros((SUBLANES, lanes), _F32)

    def body(i, carry):
        g_after, dws, db, dd = carry
        j = n - 1 - i
        r0 = pl.multiple_of(j * rows, rows)
        xs = _taps(_window(x_ref, j, rows, halo), k, halo)
        pre = _pre(xs, w, bias)
        sig = jax.nn.sigmoid(pre)
        dy = dy_ref[0, pl.ds(r0, rows), :].astype(_F32)
        if skip:
            ds = ds_ref[0, pl.ds(r0, rows), :].astype(_F32)
            dy = dy + ds * d_ref[...].astype(_F32)
            dd = dd + _fold(ds * (pre * sig))
        g = dy * (sig * (1.0 + pre * (1.0 - sig)))
        ext = jnp.concatenate([g, g_after], axis=0)     # g[t], t < r0+R+halo
        dx = g * w[k - 1:k]
        for s in range(1, k):                            # + w[K-1-s] g[t+s]
            dx = dx + (pltpu.roll(ext, rows + halo - s, 0)[:rows]
                       * w[k - 1 - s:k - s])
        dx_ref[0, pl.ds(r0, rows), :] = dx.astype(dx_ref.dtype)
        dws = tuple(dws[s] + _fold(g * xs[s]) for s in range(k))
        return g[:halo], dws, db + _fold(g), dd

    _, dws, db, dd = lax.fori_loop(
        0, n, body,
        (jnp.zeros((halo, lanes), _F32), (zeros,) * k, zeros, zeros))
    for s in range(k):
        dw_ref[0, k - 1 - s] = dws[s]
    db_ref[0] = db
    if skip:
        dd_ref[0] = dd


def _params(l, itemsize):
    need = block_bytes(l, itemsize)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=max(32 * 2 ** 20, int(1.25 * need)))


def _specs(l, k):
    """Blocks of x (or dy), of w and of the bias (1, C), grid (B, C/128)."""
    seq = pl.BlockSpec((1, l, LANES), lambda ib, ic: (ib, 0, ic))
    return seq, [pl.BlockSpec((k, LANES), lambda ib, ic: (0, ic)),
                 pl.BlockSpec((1, LANES), lambda ib, ic: (0, ic))]


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def conv_forward(x, w, b, *, out_dtype):
    """x: (B, L, C); w: (K, C); b: (1, C).  Returns silu(conv) (B, L, C) in
    ``out_dtype``."""
    bsz, l, c = x.shape
    k = w.shape[0]
    isz = x.dtype.itemsize
    seq, params = _specs(l, k)
    return pallas_call(
        functools.partial(_fwd_kernel, rows=slab_rows(l, isz),
                          halo=halo_rows(isz)),
        grid=(bsz, c // LANES),
        in_specs=[seq] + params,
        out_specs=seq,
        out_shape=jax.ShapeDtypeStruct(x.shape, out_dtype),
        compiler_params=_params(l, isz),
        cost_estimate=pl.CostEstimate(
            flops=(2 * k + 4) * x.size, transcendentals=x.size,
            bytes_accessed=x.size * (isz + jnp.dtype(out_dtype).itemsize)),
    )(x, w, b)


@jax.jit
def conv_backward(x, dy, w, b, ds=None, d=None):
    """The gradients of ``conv_forward`` at x for cotangent dy (B, L, C):
    dx (B, L, C) in x's dtype and the per-row partials of dw (B, K, 8, C)
    and db (B, 8, C) in float32.  Given the cotangent ds (B, L, C) of the
    skip term ``y * d`` (d (1, C)) as well, the output's cotangent is
    ``dy + ds * d``, and the per-row partials of dd (B, 8, C) come fourth."""
    bsz, l, c = x.shape
    k = w.shape[0]
    isz = x.dtype.itemsize
    skip = ds is not None
    seq, (w_spec, row_spec) = _specs(l, k)
    part = pl.BlockSpec((1, SUBLANES, LANES), lambda ib, ic: (ib, 0, ic))
    part_shape = jax.ShapeDtypeStruct((bsz, SUBLANES, c), _F32)
    args = (x, dy, ds, w, b, d) if skip else (x, dy, w, b)
    return pallas_call(
        functools.partial(_bwd_kernel, rows=slab_rows(l, isz),
                          halo=halo_rows(isz), skip=skip),
        grid=(bsz, c // LANES),
        in_specs=([seq] * (2 + skip) + [w_spec, row_spec]
                  + [row_spec] * skip),
        out_specs=[
            seq,
            pl.BlockSpec((1, k, SUBLANES, LANES),
                         lambda ib, ic: (ib, 0, 0, ic)),
        ] + [part] * (1 + skip),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, k, SUBLANES, c), _F32)]
                  + [part_shape] * (1 + skip),
        compiler_params=_params(l, isz),
        cost_estimate=pl.CostEstimate(
            flops=(6 * k + 10 + 4 * skip) * x.size, transcendentals=x.size,
            bytes_accessed=x.size * (2 * isz + sum(
                t.dtype.itemsize for t in args[1:2 + skip]))),
    )(*args)
