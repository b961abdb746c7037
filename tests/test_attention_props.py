"""Attention-path properties: flash==plain (outputs and gradients),
causal-skip==uniform scan (the chunked attention alone, the model forward and
the train step), RoPE norm preservation & relative-position property, MLA
absorption."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import smoke_config
from repro.core.types import TrainConfig
from repro.models import forward, init_params
from repro.models.attention import (_PLAIN_ATTN_MAX_SEQ, _Chunking,
                                    _flash_attention_jnp, _flash_forward,
                                    _group_q, _plain_attention, mla_forward,
                                    multihead_attention)
from repro.models.modules import apply_rope
from repro.optim.adamw import init_opt_state
from repro.parallel.planner import ParallelCtx
from repro.train.step import make_train_step


def _qkv(key, b, sq, sk, h, kv, d, vd=None):
    vd = vd or d
    q = jax.random.normal(key, (b, sq, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, sk, kv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, sk, kv, vd))
    return q, k, v


@pytest.mark.parametrize("sq,sk,window", [
    (256, 256, None), (128, 384, None), (256, 256, 100), (100, 300, 77),
])
def test_flash_equals_plain(sq, sk, window):
    key = jax.random.PRNGKey(0)
    q, k, v = _qkv(key, 2, sq, sk, 4, 2, 32)
    qg = _group_q(q, 2)
    qp = jnp.arange(sk - sq, sk)  # q positions aligned to the kv suffix
    kp = jnp.arange(sk)
    plain = _plain_attention(qg, k, v, q_pos=qp, k_pos=kp, causal=True,
                             window=window, logit_dtype=jnp.float32)
    flash = _flash_attention_jnp(qg, k, v, q_pos=qp, k_pos=kp, causal=True,
                                 window=window, q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(plain),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("seq,chunks", [
    (512, {"q_chunk": 128, "kv_chunk": 128}),
    (4096, {}),  # the default 1024-token chunks
], ids=["s512_chunk128", "s4096_default_chunks"])
def test_causal_skip_equals_uniform(seq, chunks):
    key = jax.random.PRNGKey(1)
    q, k, v = _qkv(key, 1, seq, seq, 2, 2, 32)
    qg = _group_q(q, 2)
    pos = jnp.arange(seq)
    a = _flash_attention_jnp(qg, k, v, q_pos=pos, k_pos=pos, causal=True,
                             window=None, causal_skip=False, **chunks)
    b = _flash_attention_jnp(qg, k, v, q_pos=pos, k_pos=pos, causal=True,
                             window=None, causal_skip=True, **chunks)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_mla_head_dims():
    """MLA with distinct qk (192) and v (128) head dims runs through both
    the plain and flash paths."""
    cfg = smoke_config("deepseek-v2-236b")
    from repro.models.attention import init_mla
    key = jax.random.PRNGKey(0)
    p = init_mla(key, cfg, jnp.float32)
    for s in (16, 4096):  # plain path, then flash path
        x = jax.random.normal(key, (1, s, cfg.d_model)) * 0.02
        out = mla_forward(p, cfg, x, jnp.arange(s))
        assert out.shape == (1, s, cfg.d_model)
        assert bool(jnp.isfinite(out).all())
        if s == 4096:
            break  # one flash-path pass is enough (CPU time)


@given(st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_rope_preserves_norm(pos):
    key = jax.random.PRNGKey(pos)
    x = jax.random.normal(key, (1, 1, 2, 64))
    y = apply_rope(x, jnp.asarray([pos]), 10_000.0)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x)),
                               np.linalg.norm(np.asarray(y)), rtol=1e-5)


def test_rope_relative_property():
    """<rope(q,m), rope(k,n)> depends only on m-n."""
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 1, 1, 64))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 1, 64))

    def dot_at(m, n):
        qm = apply_rope(q, jnp.asarray([m]), 10_000.0)
        kn = apply_rope(k, jnp.asarray([n]), 10_000.0)
        return float(jnp.sum(qm * kn))

    assert dot_at(5, 3) == pytest.approx(dot_at(105, 103), rel=1e-4)
    # f32 cos/sin at position ~1000 carries ~2e-4 relative rounding error
    assert dot_at(17, 0) == pytest.approx(dot_at(1017, 1000), rel=1e-3)


def test_softmax_rows_sum_to_one_under_padding():
    """Ragged KV (vision tokens) padding must not leak probability mass:
    attention output for valid tokens is unchanged by padding amount."""
    key = jax.random.PRNGKey(4)
    q, k, v = _qkv(key, 1, 128, 1601, 4, 4, 32)
    pos_q = jnp.arange(128)
    pos_k = jnp.arange(1601)
    out = multihead_attention(q, k, v, q_pos=pos_q, k_pos=pos_k,
                              causal=False)
    # same computation with KV padded manually to 2048 + masked
    k2 = jnp.pad(k, ((0, 0), (0, 447), (0, 0), (0, 0)))
    v2 = jnp.pad(v, ((0, 0), (0, 447), (0, 0), (0, 0)))
    out2 = multihead_attention(q, k2[:, :1601], v2[:, :1601], q_pos=pos_q,
                               k_pos=pos_k, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-5)


def _grads(attn, q, k, v, w):
    """dq, dk, dv of sum(attn(q, k, v) * w)."""
    return jax.grad(lambda *a: jnp.sum(attn(*a) * w), argnums=(0, 1, 2))(
        q, k, v)


def _assert_grads_close(got, want):
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4 * scale, err_msg=name)


@pytest.mark.parametrize("sq,sk,h,kv,d,vd,causal,window,opts", [
    (256, 256, 4, 2, 32, 32, True, None, {}),
    (256, 256, 4, 2, 32, 32, True, 100, {}),
    (256, 256, 4, 2, 32, 32, True, 100, {"causal_skip": True}),
    (2100, 2100, 2, 1, 16, 16, True, 700, {"causal_skip": True}),
    (128, 300, 4, 4, 32, 32, False, None, {}),
    (200, 200, 4, 2, 32, 32, True, None, {}),
    (256, 256, 8, 2, 32, 32, True, None, {}),
    (256, 256, 4, 2, 48, 32, True, None, {}),
], ids=["causal", "causal_window", "causal_skip",
        "causal_skip_ragged_window", "noncausal_ragged_sk", "ragged_sq", "gqa_g4", "mla_vd_ne_hd"])
def test_flash_gradients_equal_plain(sq, sk, h, kv, d, vd, causal, window,
                                     opts):
    """The chunked path's own backward gives the plain path's gradients."""
    key = jax.random.PRNGKey(5)
    q, k, v = _qkv(key, 2 if sq < 2048 else 1, sq, sk, h, kv, d, vd)
    qg = _group_q(q, kv)
    qp = jnp.arange(sk - sq, sk) if causal else jnp.arange(sq)
    kp = jnp.arange(sk)
    w = jax.random.normal(jax.random.fold_in(key, 3), qg.shape[:-1] + (vd,))
    plain = functools.partial(_plain_attention, q_pos=qp, k_pos=kp,
                              causal=causal, window=window,
                              logit_dtype=jnp.float32)
    flash = functools.partial(_flash_attention_jnp, q_pos=qp, k_pos=kp,
                              causal=causal, window=window, q_chunk=64,
                              kv_chunk=64, **opts)
    _assert_grads_close(jax.jit(lambda *a: _grads(flash, *a))(qg, k, v, w),
                        _grads(plain, qg, k, v, w))


def test_flash_gradients_equal_autodiff_through_chunks():
    """The custom backward and JAX's autodiff through the same chunked
    forward loops agree."""
    key = jax.random.PRNGKey(6)
    q, k, v = _qkv(key, 2, 256, 256, 6, 2, 32)
    qg = _group_q(q, 2)
    pos = jnp.arange(256)
    w = jax.random.normal(jax.random.fold_in(key, 3), qg.shape)
    c = _Chunking(causal=True, window=100, q_chunk=64, kv_chunk=64,
                  causal_skip=False)
    autodiff = lambda q_, k_, v_: _flash_forward(
        q_, k_, v_, pos, pos, jnp.ones(256, bool), c)[0]
    flash = functools.partial(_flash_attention_jnp, q_pos=pos, k_pos=pos,
                              causal=True, window=100, q_chunk=64,
                              kv_chunk=64)
    _assert_grads_close(jax.jit(lambda *a: _grads(flash, *a))(qg, k, v, w),
                        jax.jit(lambda *a: _grads(autodiff, *a))(qg, k, v, w))


# Causal skipping from the model down: ``ParallelCtx.causal_skip`` reaches
# the chunked attention through ``_apply_layer`` at a sequence above
# ``_PLAIN_ATTN_MAX_SEQ`` (2304 tokens: 3 q chunks, the last one ragged).
SKIP_SEQ = _PLAIN_ATTN_MAX_SEQ + 256


def _skip_setup():
    cfg = smoke_config("qwen2-0.5b")
    key = jax.random.PRNGKey(8)
    params = init_params(cfg, key)
    tokens = jax.random.randint(key, (1, SKIP_SEQ), 0, cfg.vocab_size)
    return cfg, params, tokens


def test_model_forward_causal_skip_equals_default():
    cfg, params, tokens = _skip_setup()
    want, got = (jax.jit(lambda p, t, c=ctx: forward(cfg, p, t, ctx=c)[0])(
        params, tokens) for ctx in (None, ParallelCtx(causal_skip=True)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_train_step_causal_skip_equals_default():
    """Loss and gradients (the first moment AdamW keeps, over 1 - beta1)
    of one step under causal skipping equal the default context's."""
    cfg, params, tokens = _skip_setup()
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}
    tcfg = TrainConfig()
    opt = init_opt_state(params)
    outs = [jax.jit(make_train_step(cfg, tcfg, ctx))(params, opt, batch)
            for ctx in (ParallelCtx(remat=True),
                        ParallelCtx(remat=True, causal_skip=True))]
    (_, want_opt, want_m), (_, got_opt, got_m) = outs
    assert float(got_m["loss"]) == pytest.approx(float(want_m["loss"]),
                                                 rel=1e-6)
    for a, b in zip(jax.tree.leaves(got_opt["m"]),
                    jax.tree.leaves(want_opt["m"])):
        scale = float(jnp.max(jnp.abs(b))) / (1 - tcfg.beta1)
        np.testing.assert_allclose(np.asarray(a) / (1 - tcfg.beta1),
                                   np.asarray(b) / (1 - tcfg.beta1),
                                   rtol=1e-4, atol=1e-4 * scale)
