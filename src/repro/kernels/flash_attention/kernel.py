"""Flash-attention Pallas TPU kernel (GQA, causal, sliding-window).

TPU adaptation of the classic GPU flash attention: instead of warp-level
softmax reductions, the online-softmax state (m, l, acc) lives in VMEM
scratch that persists across the sequential KV-block grid dimension, and
the (bq x bk) score tile is a single MXU matmul.  Block sizes are multiples
of 128 to align with the MXU systolic array; K/V tiles stream HBM->VMEM via
the BlockSpec pipeline.

Layout: q (B, H, Sq, D), k/v (B, KV, Sk, D) -> out (B, H, Sq, D).
Grid: (B, H, Sq/bq, Sk/bk); the last dimension is 'arbitrary' (sequential)
so scratch carries across KV blocks.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window, bq: int, bk: int,
                 num_kblocks: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq
    k_start = ik * bk

    # skip fully-masked blocks (causal: K block entirely after the Q block;
    # SWA: K block entirely before the window)
    run = True
    if causal:
        run = k_start <= q_start + bq - 1
    # (window lower-bound skip handled via mask; pl.when below keeps the
    # pipeline structure static)

    @pl.when(run if isinstance(run, bool) else run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)      # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)      # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)      # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == num_kblocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk"))
def flash_attention_kernel(q, k, v, *, causal: bool = True, window=None,
                           bq: int = 128, bk: int = 128):
    """q: (B, H, Sq, D); k, v: (B, KV, Sk, D) with H % KV == 0."""
    b, h, sq, d = q.shape
    _, kv, sk, _ = k.shape
    assert h % kv == 0
    g = h // kv
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    nq, nk = sq // bq, sk // bk
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window, bq=bq,
        bk=bk, num_kblocks=nk)

    return pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, iq, ik, _g=g: (b_, h_ // _g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, iq, ik, _g=g: (b_, h_ // _g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # running max
            pltpu.VMEM((bq,), jnp.float32),      # running denom
            pltpu.VMEM((bq, d), jnp.float32),    # output acc
        ],
    )(q, k, v)
