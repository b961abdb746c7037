"""Full-model integration of the Pallas flash-attention kernel: a GQA
model's forward with ``use_pallas=True`` (interpret mode on CPU) must match
the jnp attention path, and shapes the kernel does not cover must raise."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import forward, init_params
from repro.parallel.planner import ParallelCtx


def test_forward_with_pallas_attention_matches_jnp():
    cfg = dataclasses.replace(smoke_config("granite-3-8b"),
                              sliding_window=None, max_seq_len=256)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    tokens = jax.random.randint(key, (2, 256), 0, cfg.vocab_size)
    ref_logits, _ = forward(cfg, params, tokens)
    ctx = ParallelCtx(use_pallas=True)
    pal_logits, _ = forward(cfg, params, tokens, ctx=ctx)
    np.testing.assert_allclose(np.asarray(pal_logits),
                               np.asarray(ref_logits), atol=5e-4, rtol=1e-3)


def test_pallas_sliding_window_model():
    cfg = dataclasses.replace(smoke_config("h2o-danube-1.8b"),
                              sliding_window=128)
    key = jax.random.PRNGKey(1)
    params = init_params(cfg, key)
    tokens = jax.random.randint(key, (1, 256), 0, cfg.vocab_size)
    ref_logits, _ = forward(cfg, params, tokens)
    pal_logits, _ = forward(cfg, params, tokens,
                            ctx=ParallelCtx(use_pallas=True))
    np.testing.assert_allclose(np.asarray(pal_logits),
                               np.asarray(ref_logits), atol=5e-4, rtol=1e-3)


def test_pallas_attention_rejects_unsupported_shape():
    """A sequence that is not a multiple of the 128-row kernel block raises
    instead of silently taking the jnp path."""
    cfg = smoke_config("granite-3-8b")
    key = jax.random.PRNGKey(2)
    params = init_params(cfg, key)
    tokens = jax.random.randint(key, (1, 100), 0, cfg.vocab_size)
    with pytest.raises(ValueError, match="Pallas flash attention"):
        forward(cfg, params, tokens, ctx=ParallelCtx(use_pallas=True))
