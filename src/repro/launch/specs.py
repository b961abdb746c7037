"""Decode-cache shapes (ShapeDtypeStructs, no device allocation) and the
long-context policy: which architectures decode 500k tokens natively and
which via the sliding-window variant (``decode_window``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.types import ModelConfig, ShapeConfig

SWA_VARIANT_WINDOW = 8192


def decode_window(cfg: ModelConfig, shape: ShapeConfig) -> Optional[int]:
    """Window override for decode shapes.  None = model's own policy.

    long_500k policy (DESIGN.md §Arch-applicability):
      native   — SSM (no KV), hybrid (9 attn layers, seq-sharded cache),
                 MLA (compact latent cache), archs with built-in SWA;
      variant  — full-attention dense/MoE/VLM archs run the sliding-window
                 variant (window 8192).
    """
    if shape.name != "long_500k":
        return None
    if cfg.sliding_window or cfg.attention == "none" or cfg.attn_period:
        return None
    if cfg.attention == "mla":
        return None
    return SWA_VARIANT_WINDOW


def uses_swa_variant(cfg: ModelConfig, shape: ShapeConfig) -> bool:
    return decode_window(cfg, shape) is not None


def cache_shapes(cfg: ModelConfig, shape: ShapeConfig, params_shapes,
                 dtype=jnp.bfloat16):
    """eval_shape of the decode cache for this workload."""
    from repro.models.transformer import init_cache
    win = decode_window(cfg, shape)
    ctx_s = None  # the encoder's frames or the vision tokens
    if cfg.is_encoder_decoder:
        ctx_s = jax.ShapeDtypeStruct(
            (shape.global_batch, cfg.num_audio_frames, cfg.d_model), dtype)
    elif cfg.cross_attn_period:
        ctx_s = jax.ShapeDtypeStruct(
            (shape.global_batch, cfg.num_vision_tokens, cfg.d_model), dtype)

    def build(p, c):
        return init_cache(cfg, p, shape.global_batch, shape.seq_len,
                          dtype, context=c, window=win)

    if ctx_s is not None:
        return jax.eval_shape(build, params_shapes, ctx_s)
    return jax.eval_shape(lambda p: build(p, None), params_shapes)
