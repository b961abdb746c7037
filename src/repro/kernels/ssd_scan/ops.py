"""The SSD scan through the Pallas kernels, differentiable, in the model's
layout: ``ssd_scan(x, dt, a, b, c, chunk=..., h0=...)`` takes and returns
what ``repro.models.ssm.ssd_chunked`` does.  A ``jax.custom_vjp`` joins the
forward kernel (which saves the state entering each chunk) to the backward
kernel (which sweeps the chunks in reverse); the gradient of the per-head
decay ``a`` is reduced here in float32 from the kernel's per-position
gradient of the log-decay ``dt * a``.

Which path runs where: ``models/ssm.py`` ``ssd_chunked`` calls ``ssd_scan``
in a program lowered for the TPU at shapes that ``fits``; on any other
platform, at other shapes and on a mesh of several devices the jnp scan
``ssd_chunked_jnp`` runs.  Called directly, as the tests do, the kernels
run through the Pallas interpreter on the CPU."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import (LANES, _block_bytes, ssd_backward,
                                           ssd_forward)

VMEM_BUDGET = 96 * 2 ** 20   # of a v5e core's 128 MiB


def fits(x_shape, n: int, chunk: int, x_bytes: int = 4) -> bool:
    """Whether the kernels tile x (B, L, H, P) with state size ``n`` at
    ``chunk``: L a multiple of a 128-aligned chunk, whole heads in each
    128-lane group, a lane-aligned state, one program's blocks in VMEM."""
    _, l, h, p = x_shape
    q = min(chunk, l)
    return (l % q == 0 and q % LANES == 0 and LANES % p == 0
            and h % (LANES // p) == 0 and n % LANES == 0
            and _block_bytes(q, h, p, n, x_bytes) <= VMEM_BUDGET)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a, b, c, h0, chunk):
    return _scan_fwd(x, dt, a, b, c, h0, chunk)[0]


def _scan_fwd(x, dt, a, b, c, h0, chunk):
    bsz, l, h, p = x.shape
    out = jnp.result_type(x, dt, a, b, c)
    y, h_final, states = ssd_forward(
        x.reshape(bsz, l, h * p), dt.astype(jnp.float32), a, b, c,
        h0.reshape(bsz, h * p, -1).astype(jnp.float32), chunk=chunk, p=p,
        out_dtype=out)
    return ((y.reshape(x.shape), h_final.reshape(h0.shape)),
            (x, dt, a, b, c, h0, states))


def _scan_bwd(chunk, res, cts):
    x, dt, a, b, c, h0, states = res
    dy, dh_final = cts
    bsz, l, h, p = x.shape
    dtf = dt.astype(jnp.float32)
    dx, ddt, dda, dda_t, db, dc, dh0 = ssd_backward(
        x.reshape(bsz, l, h * p), dtf, a, b, c, states,
        dy.reshape(bsz, l, h * p),
        dh_final.reshape(bsz, h * p, -1).astype(jnp.float32),
        chunk=chunk, p=p)
    # d(dt * a) -> d dt and d a, the sum over every position in float32
    dda = dda + jnp.swapaxes(dda_t, 1, 2)
    ddt = ddt + dda * a.astype(jnp.float32)
    da = jnp.sum(dda * dtf, axis=(0, 1))
    return (dx.reshape(x.shape).astype(x.dtype), ddt.astype(dt.dtype),
            da.astype(a.dtype), db.astype(b.dtype), dc.astype(c.dtype),
            dh0.reshape(h0.shape).astype(h0.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, a, b, c, *, chunk: int, h0=None):
    """Chunked SSD scan.  x: (B, L, H, P); dt: (B, L, H) (post-softplus);
    a: (H,) negative decay rates; b, c: (B, L, N) (one group, broadcast over
    heads); h0: (B, H, P, N) or None.  Returns (y (B, L, H, P), h_final
    (B, H, P, N) f32).  The shapes must satisfy ``fits``."""
    bsz, l, h, p = x.shape
    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32)
    return _scan(x, dt, a, b, c, h0, min(chunk, l))
