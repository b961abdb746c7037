"""Full-model assembly: embeddings -> scanned layer groups -> LM head.

Layer stacks are built as ``lax.scan`` over parameter-stacked *layer groups*
(``ModelConfig.layer_groups``): HLO size stays O(period), so 100-layer
configs lower and compile quickly.  Heterogeneous patterns (Jamba's 1 attn :
7 mamba, Llama-Vision's 4 self : 1 cross) become a short unrolled period
inside the scan body.

Each block runs under a ``jax.named_scope`` named by its kind: ``attention``,
``ssm`` or ``cross_attention`` (norm, mixer, residual), ``ffn`` or ``moe``
(norm, FFN, residual), and ``vocab`` (the embedding gather, the final norm and
the logits; the train step's loss too).  The names go into the HLO metadata
only, so the compiled program is the same instruction for instruction; the
benchmark charges each device op to its block by them.

Every function takes an optional ``ctx`` (repro.parallel.planner.ParallelCtx)
that carries the mesh + axis names for the expert-parallel shard_map path and
activation sharding constraints; with ``ctx=None`` everything runs on a
single device (smoke tests, examples).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.types import LayerSpec, ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm
from repro.models.modules import (dense_init, embed_init, ffn_apply,
                                  init_ffn, init_norm, rms_norm)


def _constrain(x, ctx, spec_name: str):
    if ctx is not None and getattr(ctx, spec_name, None) is not None:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(ctx.mesh, getattr(ctx, spec_name)))
    return x


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_layer(key, cfg: ModelConfig, spec: LayerSpec, dtype) -> dict:
    ks = jax.random.split(key, 4)
    p = {"norm1": init_norm(cfg.d_model, dtype)}
    if spec.mixer == "attn":
        if cfg.attention == "mla":
            p["mixer"] = attn.init_mla(ks[0], cfg, dtype)
        else:
            p["mixer"] = attn.init_gqa(ks[0], cfg, dtype)
    elif spec.mixer == "cross_attn":
        p["mixer"] = attn.init_gqa(ks[0], cfg, dtype, cross=True)
    else:
        p["mixer"] = ssm.init_mamba(ks[0], cfg, dtype)
    if spec.ffn != "none":
        p["norm2"] = init_norm(cfg.d_model, dtype)
        if spec.ffn == "moe":
            p["ffn"] = moe_mod.init_moe(ks[1], cfg, dtype)
        else:
            p["ffn"] = init_ffn(ks[1], cfg, cfg.d_ff, dtype)
    return p


def _init_group(key, cfg: ModelConfig, period, repeats: int, dtype) -> dict:
    """Params for one layer group: each leaf stacked over ``repeats``."""
    def init_one(k):
        ks = jax.random.split(k, len(period))
        return {f"pos{i}": _init_layer(ks[i], cfg, spec, dtype)
                for i, spec in enumerate(period)}
    return jax.vmap(init_one)(jax.random.split(key, repeats))


def init_params(cfg: ModelConfig, key, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 8)
    params = {
        "embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": init_norm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[1], cfg.d_model,
                                       (cfg.padded_vocab,), dtype)
    for gi, (period, repeats) in enumerate(cfg.layer_groups()):
        params[f"group{gi}"] = _init_group(ks[2 + gi], cfg, period, repeats,
                                           dtype)
    if cfg.is_encoder_decoder:
        enc_spec = LayerSpec(mixer="attn", ffn="dense")
        params["encoder"] = {
            "group0": _init_group(ks[6], cfg, (enc_spec,), cfg.encoder_layers,
                                  dtype),
            "final_norm": init_norm(cfg.d_model, dtype),
        }
        # decoder cross-attention: one per decoder layer (stacked)
        params["cross"] = jax.vmap(
            lambda k: attn.init_gqa(k, cfg, dtype, cross=True))(
            jax.random.split(ks[7], cfg.num_layers))
    return params


# ---------------------------------------------------------------------------
# Layer application (train / prefill)
# ---------------------------------------------------------------------------


def _mixer_scope(spec: LayerSpec) -> str:
    return {"attn": "attention", "cross_attn": "cross_attention"}.get(
        spec.mixer, "ssm")


def _ffn_scope(spec: LayerSpec) -> str:
    return "moe" if spec.ffn == "moe" else "ffn"


def _apply_layer(lp: dict, spec: LayerSpec, cfg: ModelConfig, x, positions,
                 context, ctx, window, cross_lp=None):
    with jax.named_scope(_mixer_scope(spec)):
        h = rms_norm(x, lp["norm1"]["scale"], cfg.norm_eps)
        if spec.mixer == "attn":
            if cfg.attention == "mla":
                h = attn.mla_forward(lp["mixer"], cfg, h, positions,
                                     window=window,
                                     causal_skip=_flag(ctx, "causal_skip"))
            else:
                h = attn.gqa_forward(lp["mixer"], cfg, h, positions,
                                     window=window,
                                     causal_skip=_flag(ctx, "causal_skip"))
        elif spec.mixer == "cross_attn":
            h = attn.cross_attention_forward(lp["mixer"], cfg, h, context)
        else:
            h = ssm.mamba_forward(lp["mixer"], cfg, h, ctx=ctx)
        x = x + h
        x = _constrain(x, ctx, "act_spec")
    aux = jnp.zeros((), jnp.float32)

    # encoder-decoder: interleave a cross-attention block after self-attn
    if cross_lp is not None:
        with jax.named_scope("cross_attention"):
            h = rms_norm(x, lp["norm1"]["scale"], cfg.norm_eps)
            x = x + attn.cross_attention_forward(cross_lp, cfg, h, context)
            x = _constrain(x, ctx, "act_spec")

    if spec.ffn != "none":
        with jax.named_scope(_ffn_scope(spec)):
            h2 = rms_norm(x, lp["norm2"]["scale"], cfg.norm_eps)
            if spec.ffn == "moe":
                y, aux = moe_mod.moe_apply(lp["ffn"], cfg, h2, ctx=ctx)
            else:
                y = ffn_apply(lp["ffn"], h2, cfg.ffn_act)
            x = x + y
            x = _constrain(x, ctx, "act_spec")
    return x, aux


def _flag(ctx, name: str) -> bool:
    return bool(getattr(ctx, name, False)) if ctx is not None else False


def _run_groups(params, cfg: ModelConfig, x, positions, context, ctx,
                window, cross_stack=None):
    """Apply all layer groups via scan; returns (x, aux_total)."""
    aux_total = jnp.zeros((), jnp.float32)
    layer_offset = 0
    for gi, (period, repeats) in enumerate(cfg.layer_groups()):
        gp = params[f"group{gi}"]

        def body(carry, xs, _period=period, _off=layer_offset):
            h, aux = carry
            lp_stack = xs["lp"]
            for i, spec in enumerate(_period):
                cross_lp = None
                if xs.get("cross") is not None and spec.mixer == "attn" \
                        and cfg.is_encoder_decoder:
                    cross_lp = jax.tree.map(lambda a, _i=i: a[_i],
                                            xs["cross"])
                h, a = _apply_layer(lp_stack[f"pos{i}"], spec, cfg, h,
                                    positions, context, ctx, window,
                                    cross_lp=cross_lp)
                aux = aux + a
            return (h, aux), None

        if _flag(ctx, "remat"):
            body = jax.checkpoint(body)

        xs = {"lp": gp, "cross": None}
        if cross_stack is not None:
            per = len(period)
            sl = jax.tree.map(
                lambda a: a[layer_offset:layer_offset + repeats * per]
                .reshape(repeats, per, *a.shape[1:]), cross_stack)
            xs["cross"] = sl
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), xs)
        layer_offset += repeats * len(period)
    return x, aux_total


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def _vocab_bias(cfg: ModelConfig, dtype):
    v = jnp.arange(cfg.padded_vocab)
    return jnp.where(v < cfg.vocab_size, 0.0, attn.NEG_INF).astype(dtype)


def _head(cfg: ModelConfig, params: dict, x):
    """Final norm, logits and the padded vocabulary's mask."""
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = x @ params["lm_head"]
    return logits + _vocab_bias(cfg, logits.dtype)


def encode(cfg: ModelConfig, params: dict, frames: jax.Array, ctx=None
           ) -> jax.Array:
    """Encoder stack over stub frame embeddings (B, T, d) -> context."""
    enc = params["encoder"]
    positions = jnp.arange(frames.shape[1])
    gp = enc["group0"]
    spec = LayerSpec(mixer="attn", ffn="dense")

    def body(carry, lp):
        h = carry
        with jax.named_scope("attention"):
            hh = rms_norm(h, lp["pos0"]["norm1"]["scale"], cfg.norm_eps)
            hh = attn.gqa_forward(lp["pos0"]["mixer"], cfg, hh, positions)
            h = h + hh
        with jax.named_scope("ffn"):
            h2 = rms_norm(h, lp["pos0"]["norm2"]["scale"], cfg.norm_eps)
            h = h + ffn_apply(lp["pos0"]["ffn"], h2, cfg.ffn_act)
            h = _constrain(h, ctx, "act_spec")
        return h, None

    if _flag(ctx, "remat"):
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, frames, gp)
    return rms_norm(x, enc["final_norm"]["scale"], cfg.norm_eps)


def forward(cfg: ModelConfig, params: dict, tokens: jax.Array, *,
            context: Optional[jax.Array] = None, ctx=None,
            window: Optional[int] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """tokens: (B, S) int32. Returns (logits (B,S,V_pad), aux_loss).

    ``context``: encoder output (audio), vision patch embeddings (vlm), or
    None.  ``window``: overrides cfg.sliding_window (long-context variant).
    """
    with jax.named_scope("vocab"):
        x = jnp.take(params["embed"], tokens, axis=0)
        x = _constrain(x, ctx, "act_spec")
    positions = jnp.arange(tokens.shape[1])
    if cfg.is_encoder_decoder and context is None:
        raise ValueError("encoder-decoder model requires context")
    win = window if window is not None else cfg.sliding_window
    cross_stack = params.get("cross")
    x, aux = _run_groups(params, cfg, x, positions, context, ctx, win,
                         cross_stack=cross_stack)
    with jax.named_scope("vocab"):
        logits = _head(cfg, params, x)
        logits = _constrain(logits, ctx, "logit_spec")
    return logits, aux


# ---------------------------------------------------------------------------
# Decode path
# ---------------------------------------------------------------------------


def _init_layer_cache(cfg: ModelConfig, spec: LayerSpec, lp: dict,
                      batch: int, max_len: int, dtype, context, window):
    if spec.mixer == "attn":
        if cfg.attention == "mla":
            return attn.init_mla_cache(cfg, batch, max_len, dtype)
        return attn.init_kv_cache(cfg, batch, max_len, dtype, window=window)
    if spec.mixer == "cross_attn":
        return attn.init_cross_cache(lp["mixer"], cfg, context, dtype)
    return ssm.init_mamba_cache(cfg, batch, dtype)


def init_cache(cfg: ModelConfig, params: dict, batch: int, max_len: int,
               dtype=jnp.float32, *, context=None,
               window: Optional[int] = None) -> dict:
    """Build the decode cache pytree (stacked per layer group)."""
    win = window if window is not None else cfg.sliding_window
    cache = {}
    for gi, (period, repeats) in enumerate(cfg.layer_groups()):
        gp = params[f"group{gi}"]

        def one(lp_r):
            return {f"pos{i}": _init_layer_cache(
                cfg, spec, lp_r[f"pos{i}"], batch, max_len, dtype, context,
                win) for i, spec in enumerate(period)}

        cache[f"group{gi}"] = jax.vmap(one)(gp)
    if cfg.is_encoder_decoder:
        cache["cross"] = jax.vmap(
            lambda lp: attn.init_cross_cache(lp, cfg, context, dtype))(
            params["cross"])
    return cache


def _decode_layer(lp: dict, spec: LayerSpec, cfg: ModelConfig, x, lcache,
                  pos, ctx, window, cross_lp=None, cross_cache=None):
    new_cache = lcache
    with jax.named_scope(_mixer_scope(spec)):
        h = rms_norm(x, lp["norm1"]["scale"], cfg.norm_eps)
        if spec.mixer == "attn":
            if cfg.attention == "mla":
                h, new_cache = attn.mla_decode(lp["mixer"], cfg, h, lcache,
                                               pos)
            else:
                h, new_cache = attn.gqa_decode(lp["mixer"], cfg, h, lcache,
                                               pos, window=window)
        elif spec.mixer == "cross_attn":
            h = attn.cross_attention_decode(lp["mixer"], cfg, h, lcache)
        else:
            h, new_cache = ssm.mamba_decode(lp["mixer"], cfg, h, lcache)
        x = x + h
    if cross_lp is not None:
        with jax.named_scope("cross_attention"):
            h = rms_norm(x, lp["norm1"]["scale"], cfg.norm_eps)
            x = x + attn.cross_attention_decode(cross_lp, cfg, h, cross_cache)
    if spec.ffn != "none":
        with jax.named_scope(_ffn_scope(spec)):
            h2 = rms_norm(x, lp["norm2"]["scale"], cfg.norm_eps)
            if spec.ffn == "moe":
                y, _ = moe_mod.moe_apply(lp["ffn"], cfg, h2, ctx=ctx,
                                         decode=True)
            else:
                y = ffn_apply(lp["ffn"], h2, cfg.ffn_act)
            x = x + y
    x = _constrain(x, ctx, "act_spec")
    return x, new_cache


def decode_step(cfg: ModelConfig, params: dict, cache: dict,
                tokens: jax.Array, pos, *, ctx=None,
                window: Optional[int] = None) -> Tuple[jax.Array, dict]:
    """tokens: (B, 1) int32; pos: scalar int32 (position of the new token).
    Returns (logits (B,1,V_pad), new_cache)."""
    win = window if window is not None else cfg.sliding_window
    with jax.named_scope("vocab"):
        x = jnp.take(params["embed"], tokens, axis=0)
        x = _constrain(x, ctx, "act_spec")
    new_cache = {}
    layer_offset = 0
    for gi, (period, repeats) in enumerate(cfg.layer_groups()):
        gp = params[f"group{gi}"]
        gc = cache[f"group{gi}"]
        cross_all = cache.get("cross")

        def body(carry, xs, _period=period, _off=layer_offset):
            h = carry
            lp_stack, c_stack, cross_lp_s, cross_c_s = xs
            new_c = {}
            for i, spec in enumerate(_period):
                clp = cc = None
                if cross_lp_s is not None and spec.mixer == "attn" \
                        and cfg.is_encoder_decoder:
                    clp = jax.tree.map(lambda a, _i=i: a[_i], cross_lp_s)
                    cc = jax.tree.map(lambda a, _i=i: a[_i], cross_c_s)
                h, nc = _decode_layer(lp_stack[f"pos{i}"], spec, cfg, h,
                                      c_stack[f"pos{i}"], pos, ctx, win,
                                      cross_lp=clp, cross_cache=cc)
                new_c[f"pos{i}"] = nc
            return h, new_c

        cross_lp_stack = cross_c_stack = None
        if cfg.is_encoder_decoder:
            per = len(period)
            cross_lp_stack = jax.tree.map(
                lambda a: a[layer_offset:layer_offset + repeats * per]
                .reshape(repeats, per, *a.shape[1:]), params["cross"])
            cross_c_stack = jax.tree.map(
                lambda a: a[layer_offset:layer_offset + repeats * per]
                .reshape(repeats, per, *a.shape[1:]), cross_all)
        x, nc = jax.lax.scan(
            body, x, (gp, gc, cross_lp_stack, cross_c_stack))
        new_cache[f"group{gi}"] = nc
        layer_offset += repeats * len(period)
    if cfg.is_encoder_decoder:
        new_cache["cross"] = cache["cross"]
    with jax.named_scope("vocab"):
        logits = _head(cfg, params, x)
    return logits, new_cache
