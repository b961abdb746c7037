"""Grouped expert matmul Pallas TPU kernel.

The MoE EP path (repro.models.moe) computes each local expert over its
capacity-padded token buffer: (E, C, d) x (E, d, f) -> (E, C, f).  On GPU
this is megablocks-style grouped GEMM with dynamic tile indexing; the TPU
adaptation keeps the capacity-padded layout (static shapes — what the XLA
pipeline and the A2A buffers already use) and tiles each expert's matmul
over the MXU with an f32 VMEM accumulator across the K (d) grid dimension.

Grid: (E, C/bc, f/bf, d/bd), last dimension sequential (accumulation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]            # (bc, bd)
    w = w_ref[0]            # (bd, bf)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bc", "bf", "bd"))
def moe_gmm_kernel(x, w, *, bc: int = 128, bf: int = 128, bd: int = 256):
    """x: (E, C, d); w: (E, d, f) -> (E, C, f)."""
    e, c, d = x.shape
    _, _, f = w.shape
    bc = min(bc, c)
    bf = min(bf, f)
    bd = min(bd, d)
    assert c % bc == 0 and f % bf == 0 and d % bd == 0
    nk = d // bd

    kernel = functools.partial(_gmm_kernel, nk=nk)
    return pallas_call(
        kernel,
        grid=(e, c // bc, f // bf, nk),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda ie, ic, if_, ik: (ie, ic, ik)),
            pl.BlockSpec((1, bd, bf), lambda ie, ic, if_, ik: (ie, ik, if_)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf),
                               lambda ie, ic, if_, ik: (ie, ic, if_)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
    )(x, w)
