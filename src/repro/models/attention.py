"""Attention flavours: GQA (+RoPE, QKV-bias, sliding-window), MLA, cross-attn.

Two compute paths, chosen from the sequence lengths alone:
  * plain einsum attention where Sq * Sk <= 2048^2 (short training
    sequences, smoke tests, examples);
  * flash-style chunked attention in pure jnp (two nested ``lax.scan``) for
    longer sequences, with its own FlashAttention-2 backward — O(S * chunk)
    live memory in both directions, small HLO.  It is the train path's
    long-sequence attention.

Decode attends one new token against a KV cache; sliding-window caches are
ring buffers of ``window`` slots.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.types import ModelConfig
from repro.models.modules import apply_rope, dense_init, init_norm, rms_norm

_PLAIN_ATTN_MAX_SEQ = 2048
_Q_CHUNK = 1024
_KV_CHUNK = 1024

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def init_gqa(key, cfg: ModelConfig, dtype, cross: bool = False) -> dict:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, (cfg.num_heads, hd), dtype),
        "wk": dense_init(ks[1], d, (cfg.num_kv_heads, hd), dtype),
        "wv": dense_init(ks[2], d, (cfg.num_kv_heads, hd), dtype),
        "wo": dense_init(ks[3], cfg.num_heads * hd, (d,), dtype).reshape(
            cfg.num_heads, hd, d),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads, hd), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads, hd), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads, hd), dtype)
    if cross:
        # query-norm on the hidden stream, gating as in Llama-3.2-Vision
        p["gate_attn"] = jnp.zeros((), dtype)
    return p


def init_mla(key, cfg: ModelConfig, dtype) -> dict:
    d = cfg.d_model
    hd = cfg.resolved_head_dim          # qk nope dim
    vhd = cfg.resolved_v_head_dim
    rhd = cfg.qk_rope_head_dim
    ks = jax.random.split(key, 7)
    p = {}
    if cfg.q_lora_rank:
        p["w_dq"] = dense_init(ks[0], d, (cfg.q_lora_rank,), dtype)
        p["norm_q"] = init_norm(cfg.q_lora_rank, dtype)
        q_in = cfg.q_lora_rank
    else:
        q_in = d
    p["w_uq"] = dense_init(ks[1], q_in, (cfg.num_heads, hd + rhd), dtype)
    p["w_dkv"] = dense_init(ks[2], d, (cfg.kv_lora_rank + rhd,), dtype)
    p["norm_kv"] = init_norm(cfg.kv_lora_rank, dtype)
    p["w_uk"] = dense_init(ks[3], cfg.kv_lora_rank, (cfg.num_heads, hd), dtype)
    p["w_uv"] = dense_init(ks[4], cfg.kv_lora_rank, (cfg.num_heads, vhd), dtype)
    p["wo"] = dense_init(ks[5], cfg.num_heads * vhd, (d,), dtype).reshape(
        cfg.num_heads, vhd, d)
    return p


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------


def _group_q(q: jax.Array, num_kv: int) -> jax.Array:
    """(B, S, H, hd) -> (B, S, KV, G, hd)."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, num_kv, h // num_kv, hd)


def _plain_attention(q, k, v, *, q_pos, k_pos, causal, window, logit_dtype):
    """q: (B,Sq,KV,G,hd); k,v: (B,Sk,KV,hd). Materializes (Sq,Sk) scores."""
    hd = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.array(hd, jnp.float32))
    scores = jnp.einsum("bqkgh,bskh->bqkgs", q.astype(logit_dtype),
                        k.astype(logit_dtype)) * scale
    mask = jnp.ones((q.shape[1], k.shape[1]), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    scores = jnp.where(mask[None, :, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bqkgs,bskh->bqkgh", probs.astype(v.dtype), v)
    return out


@dataclasses.dataclass(frozen=True)
class _Chunking:
    """Static shape of a chunked attention call: its mask and its loops."""
    causal: bool
    window: Optional[int]
    q_chunk: int
    kv_chunk: int
    causal_skip: bool   # per-q-chunk KV extents (causal only)

    def kv_extent(self, i: int, sk: int) -> Tuple[int, int]:
        """The keys ``[lo, hi)`` that q chunk ``i`` visits."""
        if not self.causal_skip:
            return 0, sk
        lo = 0
        if self.window is not None:
            lo = max(0, (i * self.q_chunk - int(self.window))
                     // self.kv_chunk * self.kv_chunk)
        hi = ((i + 1) * self.q_chunk + self.kv_chunk - 1) // self.kv_chunk
        return lo, min(hi * self.kv_chunk, sk)


def _kv_chunks(k, v, k_pos, k_valid, kv_chunk):
    """Keys, values, positions and validity with a leading chunk axis."""
    b, sk, nkv, hd = k.shape
    n = sk // kv_chunk
    return (jnp.moveaxis(k.reshape(b, n, kv_chunk, nkv, hd), 1, 0),
            jnp.moveaxis(v.reshape(b, n, kv_chunk, nkv, v.shape[-1]), 1, 0),
            k_pos.reshape(n, kv_chunk), k_valid.reshape(n, kv_chunk))


def _unchunk(x):
    """(n, B, chunk, ...) -> (B, n * chunk, ...)."""
    return jnp.moveaxis(x, 0, 1).reshape(x.shape[1], -1, *x.shape[3:])


def _chunk_scores(q_blk, k_blk, qpos_blk, kp_blk, kval_blk, c: _Chunking):
    """f32 scores of one chunk pair (B,qc,KV,G,kc) and where they count."""
    scale = 1.0 / jnp.sqrt(jnp.array(q_blk.shape[-1], jnp.float32))
    s = jnp.einsum("bqkgh,bskh->bqkgs", q_blk, k_blk,
                   preferred_element_type=jnp.float32) * scale
    mask = kval_blk[None, :]
    if c.causal:
        mask = mask & (qpos_blk[:, None] >= kp_blk[None, :])
    if c.window is not None:
        mask = mask & (qpos_blk[:, None] - kp_blk[None, :] < c.window)
    return s, mask[None, :, None, None, :]


def _flash_forward(q, k, v, q_pos, k_pos, k_valid, c: _Chunking):
    """Online-softmax forward over chunk pairs.  Returns the f32 output
    (B,Sq,KV,G,vd) and the log-sum-exp of each query row (B,Sq,KV,G)."""
    b, sq, nkv, g, _ = q.shape
    vd = v.shape[-1]
    # The positions are constants of a layer loop.  Tied to q, the masks
    # made from them stay in the chunk loops; untied, JAX hoists their
    # computation out of the layer loop and stores every chunk pair's mask.
    q, q_pos, k_pos, k_valid = jax.lax.optimization_barrier(
        (q, q_pos, k_pos, k_valid))

    def one_q_chunk(q_blk, qpos_blk, kv):
        def body(carry, xs):
            m, l, acc = carry
            k_blk, v_blk, kp_blk, kval_blk = xs
            s, mask = _chunk_scores(q_blk, k_blk, qpos_blk, kp_blk,
                                    kval_blk, c)
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqkgs,bskh->bqkgh", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        qc = q_blk.shape[1]
        init = (jnp.full((b, qc, nkv, g), NEG_INF, jnp.float32),
                jnp.zeros((b, qc, nkv, g), jnp.float32),
                jnp.zeros((b, qc, nkv, g, vd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(body, init, kv)
        l = jnp.maximum(l, 1e-30)
        return acc / l[..., None], m + jnp.log(l)

    nqc = sq // c.q_chunk
    q_c = q.reshape(b, nqc, c.q_chunk, nkv, g, q.shape[-1])
    qp_c = q_pos.reshape(nqc, c.q_chunk)
    if c.causal_skip:
        outs = []
        for i in range(nqc):
            lo, hi = c.kv_extent(i, k.shape[1])
            outs.append(one_q_chunk(q_c[:, i], qp_c[i], _kv_chunks(
                k[:, lo:hi], v[:, lo:hi], k_pos[lo:hi], k_valid[lo:hi],
                c.kv_chunk)))
        o, lse = (jnp.stack(a, axis=1) for a in zip(*outs))
    else:
        kv = _kv_chunks(k, v, k_pos, k_valid, c.kv_chunk)
        o, lse = jax.lax.map(lambda xs: one_q_chunk(xs[0], xs[1], kv),
                             (jnp.moveaxis(q_c, 1, 0), qp_c))
        o, lse = jnp.moveaxis(o, 0, 1), jnp.moveaxis(lse, 0, 1)
    return o.reshape(b, sq, nkv, g, vd), lse.reshape(b, sq, nkv, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _flash(q, k, v, q_pos, k_pos, k_valid, c: _Chunking):
    return _flash_forward(q, k, v, q_pos, k_pos, k_valid, c)[0].astype(
        q.dtype)


def _flash_fwd(q, k, v, q_pos, k_pos, k_valid, c: _Chunking):
    o, lse = _flash_forward(q, k, v, q_pos, k_pos, k_valid, c)
    return o.astype(q.dtype), (q, k, v, q_pos, k_pos, k_valid, o, lse)


def _flash_bwd(c: _Chunking, res, do):
    """FlashAttention-2 backward: each chunk pair's scores are recomputed
    from q, k and the saved log-sum-exp, never read back.  A query row that
    sees no key (only padding rows do) gets no gradient."""
    q, k, v, q_pos, k_pos, k_valid, o, lse = res
    b, sq, nkv, g, hd = q.shape
    scale = 1.0 / jnp.sqrt(jnp.array(hd, jnp.float32))
    delta = jnp.sum(do.astype(jnp.float32) * o, axis=-1)  # rowsum(dO * O)

    def one_q_chunk(q_blk, qpos_blk, do_blk, lse_blk, delta_blk, kv):
        def body(dq, xs):
            k_blk, v_blk, kp_blk, kval_blk = xs
            s, mask = _chunk_scores(q_blk, k_blk, qpos_blk, kp_blk,
                                    kval_blk, c)
            p = jnp.where(mask, jnp.exp(s - lse_blk[..., None]), 0.0)
            dv = jnp.einsum("bqkgs,bqkgh->bskh", p.astype(v_blk.dtype),
                            do_blk, preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqkgh,bskh->bqkgs", do_blk, v_blk,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - delta_blk[..., None]) * scale
            dq = dq + jnp.einsum("bqkgs,bskh->bqkgh", ds, k_blk,
                                 preferred_element_type=jnp.float32)
            dk = jnp.einsum("bqkgs,bqkgh->bskh", ds, q_blk,
                            preferred_element_type=jnp.float32)
            return dq, (dk, dv)

        dq0 = jnp.zeros(q_blk.shape, jnp.float32)
        return jax.lax.scan(body, dq0, kv)

    nqc = sq // c.q_chunk

    def chunked(a):  # (B, Sq, ...) -> (B, nqc, q_chunk, ...)
        return a.reshape(b, nqc, c.q_chunk, *a.shape[2:])

    q_c, do_c, lse_c, delta_c = map(chunked, (q, do, lse, delta))
    qp_c = q_pos.reshape(nqc, c.q_chunk)
    if c.causal_skip:
        dk = jnp.zeros(k.shape, jnp.float32)
        dv = jnp.zeros(v.shape, jnp.float32)
        dqs = []
        for i in range(nqc):
            lo, hi = c.kv_extent(i, k.shape[1])
            dq_i, (dk_i, dv_i) = one_q_chunk(
                q_c[:, i], qp_c[i], do_c[:, i], lse_c[:, i], delta_c[:, i],
                _kv_chunks(k[:, lo:hi], v[:, lo:hi], k_pos[lo:hi],
                           k_valid[lo:hi], c.kv_chunk))
            dk = dk.at[:, lo:hi].add(_unchunk(dk_i))
            dv = dv.at[:, lo:hi].add(_unchunk(dv_i))
            dqs.append(dq_i)
        dq = jnp.stack(dqs, axis=1)
    else:
        kv = _kv_chunks(k, v, k_pos, k_valid, c.kv_chunk)

        def q_body(acc, xs):
            dq_i, (dk_i, dv_i) = one_q_chunk(*xs, kv)
            return (acc[0] + dk_i, acc[1] + dv_i), dq_i

        acc0 = (jnp.zeros(kv[0].shape, jnp.float32),
                jnp.zeros(kv[1].shape, jnp.float32))
        (dk, dv), dq = jax.lax.scan(
            q_body, acc0, (jnp.moveaxis(q_c, 1, 0), qp_c,
                           *(jnp.moveaxis(a, 1, 0)
                             for a in (do_c, lse_c, delta_c))))
        dk, dv, dq = _unchunk(dk), _unchunk(dv), jnp.moveaxis(dq, 0, 1)
    return (dq.reshape(q.shape).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype), None, None, None)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _flash_attention_jnp(q, k, v, *, q_pos, k_pos, causal, window,
                         q_chunk=_Q_CHUNK, kv_chunk=_KV_CHUNK,
                         causal_skip: bool = False):
    """Flash-style online-softmax attention, pure jnp, with its own backward.

    q: (B,Sq,KV,G,hd); k,v: (B,Sk,KV,hd); q_pos: (Sq,), k_pos: (Sk,).
    This is the train path's long-sequence attention.  The forward keeps
    only the output and one log-sum-exp per query row; the backward
    (``_flash_bwd``) loops over the same chunk pairs and recomputes each
    pair's scores, so no per-chunk score tensor is saved for it.

    ``causal_skip``: unroll the q-chunk loop in python and slice the KV range
    each q chunk can actually see (exact-causal FLOPs; bigger HLO).  Default
    is a uniform double-scan (2x the causal FLOPs, tiny HLO).
    """
    sq, sk = q.shape[1], k.shape[1]
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, sk)
    # pad ragged tails (e.g. 1601 vision tokens) and mask them out
    sq_pad = (-sq) % q_chunk
    sk_pad = (-sk) % kv_chunk
    if sq_pad:
        q = jnp.pad(q, ((0, 0), (0, sq_pad), (0, 0), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, sq_pad))
    if sk_pad:
        k = jnp.pad(k, ((0, 0), (0, sk_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_pad), (0, 0), (0, 0)))
        # padded keys get position +inf-ish so the causal mask kills them;
        # the explicit validity mask handles the non-causal case
        q_pos_max = jnp.iinfo(jnp.int32).max
        k_pos = jnp.pad(k_pos, (0, sk_pad), constant_values=q_pos_max)
    k_valid = jnp.arange(sk + sk_pad) < sk
    c = _Chunking(causal=causal, window=window, q_chunk=q_chunk,
                  kv_chunk=kv_chunk, causal_skip=causal_skip and causal)
    return _flash(q, k, v, q_pos, k_pos, k_valid, c)[:, :sq]


def multihead_attention(q, k, v, *, q_pos, k_pos, causal, window=None,
                        causal_skip=False):
    """Plain attention where Sq * Sk <= 2048^2, chunked flash beyond.
    q: (B,Sq,H,hd) ungrouped."""
    nkv = k.shape[2]
    qg = _group_q(q, nkv)
    if q.shape[1] * k.shape[1] <= _PLAIN_ATTN_MAX_SEQ ** 2:
        out = _plain_attention(qg, k, v, q_pos=q_pos, k_pos=k_pos,
                               causal=causal, window=window,
                               logit_dtype=jnp.float32)
    else:
        out = _flash_attention_jnp(qg, k, v, q_pos=q_pos, k_pos=k_pos,
                                   causal=causal, window=window,
                                   causal_skip=causal_skip)
    b, s = q.shape[:2]
    return out.reshape(b, s, q.shape[2], v.shape[-1])  # out head dim = v's


# ---------------------------------------------------------------------------
# GQA self-attention (train / prefill)
# ---------------------------------------------------------------------------


def _project_qkv(p: dict, cfg: ModelConfig, x, kv_x=None):
    kv_x = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", kv_x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_x, p["wv"])
    if cfg.qkv_bias and "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    return q, k, v


def gqa_forward(p: dict, cfg: ModelConfig, x, positions, *,
                window=None, causal_skip=False):
    """x: (B,S,d); positions: (S,) absolute positions."""
    q, k, v = _project_qkv(p, cfg, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    win = window if window is not None else cfg.sliding_window
    out = multihead_attention(q, k, v, q_pos=positions, k_pos=positions,
                              causal=True, window=win,
                              causal_skip=causal_skip)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def cross_attention_forward(p: dict, cfg: ModelConfig, x, context):
    """Cross-attention: queries from x (B,S,d), keys/values from context
    (B,T,d).  No RoPE, no causal mask (Llama-3.2-Vision / enc-dec style)."""
    q, k, v = _project_qkv(p, cfg, x, kv_x=context)
    s_pos = jnp.arange(x.shape[1])
    t_pos = jnp.arange(context.shape[1])
    out = multihead_attention(q, k, v, q_pos=s_pos, k_pos=t_pos, causal=False)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if "gate_attn" in p:
        out = out * jnp.tanh(p["gate_attn"])
    return out


# ---------------------------------------------------------------------------
# GQA decode with KV cache (ring buffer for sliding-window)
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
                  window=None) -> dict:
    win = window if window is not None else cfg.sliding_window
    slots = min(max_len, win) if win else max_len
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, slots, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, slots, cfg.num_kv_heads, hd), dtype),
    }


def _pos_vec(pos, batch: int):
    """Normalize decode positions to a (B,) vector (per-sequence positions
    enable continuous batching: each slot decodes at its own offset)."""
    pos = jnp.asarray(pos, jnp.int32)
    return jnp.broadcast_to(pos, (batch,)) if pos.ndim == 0 else pos


def _ring_slot_positions(pos, slots: int):
    """Positions stored in each ring slot after the token at ``pos`` was
    inserted; -1 where the slot has never been written. pos: (B,)."""
    s = jnp.arange(slots)
    p = pos[:, None] - ((pos[:, None] - s[None, :]) % slots)
    return jnp.where(p >= 0, p, -1)  # (B, slots)


def gqa_decode(p: dict, cfg: ModelConfig, x, cache: dict, pos, *,
               window=None):
    """x: (B,1,d); pos: scalar or (B,) int32 position(s) of the new token.
    Returns (out (B,1,d), new_cache)."""
    b = x.shape[0]
    pos = _pos_vec(pos, b)
    q, k, v = _project_qkv(p, cfg, x)
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k = apply_rope(k, pos[:, None], cfg.rope_theta)

    slots = cache["k"].shape[1]
    slot = jnp.mod(pos, slots)  # (B,)
    bi = jnp.arange(b)
    new_k = cache["k"].at[bi, slot].set(k[:, 0].astype(cache["k"].dtype))
    new_v = cache["v"].at[bi, slot].set(v[:, 0].astype(cache["v"].dtype))

    slot_pos = _ring_slot_positions(pos, slots)  # (B, slots)
    win = window if window is not None else cfg.sliding_window
    valid = slot_pos >= 0
    valid &= slot_pos <= pos[:, None]
    if win:
        valid &= pos[:, None] - slot_pos < win

    nkv = new_k.shape[2]
    qg = _group_q(q, nkv)  # (B,1,KV,G,hd)
    scale = 1.0 / jnp.sqrt(jnp.array(q.shape[-1], jnp.float32))
    scores = jnp.einsum("bqkgh,bskh->bqkgs", qg, new_k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bqkgs,bskh->bqkgh", probs.astype(new_v.dtype), new_v)
    out = out.reshape(x.shape[0], 1, cfg.num_heads, -1)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return out, {"k": new_k, "v": new_v}


def init_cross_cache(p: dict, cfg: ModelConfig, context, dtype) -> dict:
    """Precompute cross-attention K/V once from the (encoder/vision) context."""
    k = jnp.einsum("btd,dhk->bthk", context, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", context, p["wv"])
    if cfg.qkv_bias and "bk" in p:
        k = k + p["bk"]
        v = v + p["bv"]
    return {"k": k.astype(dtype), "v": v.astype(dtype)}


def cross_attention_decode(p: dict, cfg: ModelConfig, x, cross_cache: dict):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qkv_bias and "bq" in p:
        q = q + p["bq"]
    k, v = cross_cache["k"], cross_cache["v"]
    qg = _group_q(q, k.shape[2])
    scale = 1.0 / jnp.sqrt(jnp.array(q.shape[-1], jnp.float32))
    scores = jnp.einsum("bqkgh,bskh->bqkgs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bqkgs,bskh->bqkgh", probs.astype(v.dtype), v)
    out = out.reshape(x.shape[0], x.shape[1], cfg.num_heads, -1)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if "gate_attn" in p:
        out = out * jnp.tanh(p["gate_attn"])
    return out


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------


def _mla_q(p: dict, cfg: ModelConfig, x, positions):
    hd = cfg.resolved_head_dim
    if cfg.q_lora_rank:
        cq = x @ p["w_dq"]
        cq = rms_norm(cq, p["norm_q"]["scale"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["w_uq"])
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(p: dict, cfg: ModelConfig, x, positions):
    ckv = x @ p["w_dkv"]  # (B,S,lora+rope)
    c, k_rope = ckv[..., :cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
    c = rms_norm(c, p["norm_kv"]["scale"], cfg.norm_eps)
    # k_rope is shared across heads: treat as a single head for RoPE
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]
    return c, k_rope


def mla_forward(p: dict, cfg: ModelConfig, x, positions, *,
                window=None, causal_skip=False):
    """Naive (decompressed) MLA for train/prefill: materialize per-head K/V."""
    hd = cfg.resolved_head_dim
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    c, k_rope = _mla_latent(p, cfg, x, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", c, p["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", c, p["w_uv"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (*k_nope.shape[:3], cfg.qk_rope_head_dim))],
        axis=-1)
    out = multihead_attention(q, k, v, q_pos=positions, k_pos=positions,
                              causal=True, window=window,
                              causal_skip=causal_skip)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    return {
        "c": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
    }


def mla_decode(p: dict, cfg: ModelConfig, x, cache: dict, pos):
    """Absorbed MLA decode: attend directly in the latent space.

    Cache holds the 512-dim latent + 64-dim shared rope key per token —
    DeepSeek-V2's actual deployment trick (93% KV-cache reduction).
    pos: scalar or (B,) per-sequence positions."""
    hd = cfg.resolved_head_dim
    b = x.shape[0]
    pos = _pos_vec(pos, b)
    q_nope, q_rope = _mla_q(p, cfg, x, pos[:, None])  # (B,1,H,*)
    c_new, k_rope_new = _mla_latent(p, cfg, x, pos[:, None])

    bi = jnp.arange(b)
    cache_c = cache["c"].at[bi, pos].set(
        c_new[:, 0].astype(cache["c"].dtype))
    cache_r = cache["k_rope"].at[bi, pos].set(
        k_rope_new[:, 0].astype(cache["k_rope"].dtype))

    # absorb W_uk into the query: q_lat (B,1,H,lora)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"])
    scale = 1.0 / jnp.sqrt(jnp.array(hd + cfg.qk_rope_head_dim, jnp.float32))
    scores = (jnp.einsum("bshr,blr->bshl", q_lat, cache_c,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshk,blk->bshl", q_rope, cache_r,
                           preferred_element_type=jnp.float32)) * scale
    valid = jnp.arange(cache_c.shape[1])[None, :] <= pos[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx_lat = jnp.einsum("bshl,blr->bshr", probs.astype(cache_c.dtype),
                         cache_c)
    v = jnp.einsum("bshr,rhk->bshk", ctx_lat, p["w_uv"])
    out = jnp.einsum("bshk,hkd->bsd", v, p["wo"])
    return out, {"c": cache_c, "k_rope": cache_r}
