"""Mamba-2's causal depthwise convolution and SiLU through the Pallas
kernels, differentiable, in the model's layout: ``causal_conv(x, w, b)``
takes and returns what ``repro.models.ssm._causal_conv`` does, and
``causal_conv_skip(x, w, b, d)`` what ``_causal_conv_skip`` does: the
output in float32 and its D skip term ``y * d``.  A ``jax.custom_vjp`` joins
the forward kernel to the backward kernel; the residual is the inputs
alone, and the backward's per-row partials of the tap, bias (and d)
gradients are summed here in float32.

Which path runs where: ``models/ssm.py`` ``causal_conv`` calls this in a
program lowered for the TPU at shapes that ``fits``; on any other
platform, at other shapes and on a mesh of several devices the jnp conv
``_causal_conv`` runs.  Called directly, as the tests do, the kernels run
through the Pallas interpreter on the CPU."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.causal_conv.kernel import (LANES, block_bytes,
                                              conv_backward, conv_forward,
                                              halo_rows)

VMEM_BUDGET = 64 * 2 ** 20   # of a v5e core's 128 MiB


def fits(x_shape, k: int, itemsize: int = 4) -> bool:
    """Whether the kernels tile x (B, L, C) for K taps: L a whole number of
    sublane tiles, C of 128-lane blocks, the K - 1 past rows within one
    tile, one program's blocks in VMEM."""
    _, l, c = x_shape
    halo = halo_rows(itemsize)
    return (l % halo == 0 and c % LANES == 0 and 1 <= k <= halo + 1
            and block_bytes(l, itemsize) <= VMEM_BUDGET)


@jax.custom_vjp
def causal_conv(x, w, b):
    """silu(causal depthwise conv(x, w) + b).  x: (B, L, C); w: (K, C);
    b: (C,).  Returns (B, L, C) in the dtype of x, w and b together.  The
    shapes must satisfy ``fits``."""
    return _fwd(x, w, b)[0]


def _fwd(x, w, b):
    out = conv_forward(x, w, b.reshape(1, -1),
                       out_dtype=jnp.result_type(x, w, b))
    return out, (x, w, b)


def _bwd(res, dy, ds=None):
    x, w, b, *d = res
    dx, dw, db, *dd = conv_backward(x, dy, w, b.reshape(1, -1), ds,
                                    *(t.reshape(1, -1) for t in d))
    sums = [jnp.sum(dw, axis=(0, 2)), jnp.sum(db, axis=(0, 1))] + [
        jnp.sum(t, axis=(0, 1)) for t in dd]
    return (dx,) + tuple(t.astype(p.dtype) for t, p in zip(sums, (w, b, *d)))


causal_conv.defvjp(_fwd, _bwd)


@jax.custom_vjp
def causal_conv_skip(x, w, b, d):
    """``(y, y * d)`` for ``y = causal_conv(x, w, b)`` in float32 and a
    per-channel d (C,): the conv's output and a skip connection's term.
    The backward kernel takes both outputs' cotangents and sums them on
    chip.  The shapes must satisfy ``fits``."""
    return _skip_fwd(x, w, b, d)[0]


def _skip_fwd(x, w, b, d):
    y = _fwd(x, w, b)[0].astype(jnp.float32)
    return (y, y * d), (x, w, b, d)


causal_conv_skip.defvjp(_skip_fwd, lambda res, cts: _bwd(res, *cts))
