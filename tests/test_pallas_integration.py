"""The Pallas flash-attention kernel at the model's attention layout: on the
same q, k and v (B, S, H, D), the kernel (interpreted on CPU, inputs swapped
to (B, H, S, D)) must give ``models.attention.multihead_attention``'s output.
The kernel has no backward, so no train path calls it; these tests hold it to
the model's attention at smoke widths."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.kernels.flash_attention.ops import flash_attention
from repro.models.attention import multihead_attention

SEQ = 256


def _assert_kernel_matches_model(cfg, batch: int, seed: int):
    key = jax.random.PRNGKey(seed)
    hd = cfg.resolved_head_dim
    q = jax.random.normal(key, (batch, SEQ, cfg.num_heads, hd))
    k, v = (jax.random.normal(jax.random.fold_in(key, i),
                              (batch, SEQ, cfg.num_kv_heads, hd))
            for i in (1, 2))
    pos = jnp.arange(SEQ)
    want = multihead_attention(q, k, v, q_pos=pos, k_pos=pos, causal=True,
                               window=cfg.sliding_window)
    got = flash_attention(*(jnp.swapaxes(a, 1, 2) for a in (q, k, v)),
                          causal=True, window=cfg.sliding_window)
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(got, 1, 2)),
                               np.asarray(want), atol=2e-5, rtol=2e-5)


def test_forward_with_pallas_attention_matches_jnp():
    cfg = dataclasses.replace(smoke_config("granite-3-8b"),
                              sliding_window=None)
    _assert_kernel_matches_model(cfg, batch=2, seed=0)


def test_pallas_sliding_window_model():
    cfg = dataclasses.replace(smoke_config("h2o-danube-1.8b"),
                              sliding_window=128)
    _assert_kernel_matches_model(cfg, batch=1, seed=1)


def test_pallas_mqa_matches_jnp():
    """One KV head shared by every query head (multi-query attention)."""
    cfg = dataclasses.replace(smoke_config("granite-3-8b"),
                              sliding_window=None, num_kv_heads=1)
    _assert_kernel_matches_model(cfg, batch=2, seed=2)
