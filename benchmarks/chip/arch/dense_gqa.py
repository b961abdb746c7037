"""Dense decoders with grouped-query attention (Qwen2, H2O-Danube).

How the benchmark turns a published ``config.json`` into the program's
``ModelConfig``, how it makes the weights from a seed in the program's
parameter layout, and how many FLOPs a step needs and executes.  The weights
are the benchmark's own: the plain reference in ``reference/dense_gqa.py``
makes the same ones from the same seed with this module, and neither takes
anything that the program has made.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

REFERENCE = "dense_gqa"


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def window(c: dict) -> Optional[int]:
    """Sliding window in tokens, or None for full causal attention."""
    if not c.get("use_sliding_window", True):
        return None
    return c.get("sliding_window")


def program_config(c: dict):
    """The program's ``ModelConfig`` for published config ``c``."""
    from repro.core.types import ModelConfig
    if c["hidden_act"] != "silu":
        raise ValueError(f"{c['name']}: only SwiGLU (silu) MLPs are built")
    return ModelConfig(
        name=c["name"], family="dense", source=c["source_url"],
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=head_dim(c),
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        attention="gqa", rope_theta=float(c["rope_theta"]),
        qkv_bias=bool(c["architecture"]["qkv_bias"]),
        sliding_window=window(c), ffn_act="swiglu",
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]))


def _path(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)


def init_weights(shapes, key, dtype=jnp.float32):
    """Weights for the parameter tree ``shapes`` (the program's layout, from
    ``jax.eval_shape``), drawn from ``key``.

    Matrices are normal with std 1/sqrt(fan-in), the embedding and the
    q/k/v biases normal with std 0.02, norm scales 1 + 0.05 * normal.  The
    biases and scales are not left at 0 and 1 so that a path which dropped
    them would show.  Generated in float32 and cast, so a lower ``dtype``
    holds the same weights rounded."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (kp, leaf) in enumerate(leaves):
        path, shape = _path(kp), leaf.shape
        name = path.rsplit("/", 1)[-1]
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        stacked = path.startswith("group")
        if name == "scale":
            w = 1.0 + 0.05 * z
        elif name in ("embed", "bq", "bk", "bv"):
            w = 0.02 * z
        elif name == "wo":  # (L, heads, head_dim, d)
            w = z / math.sqrt(shape[1] * shape[2])
        elif name in ("wq", "wk", "wv", "w_gate", "w_up", "w_down"):
            w = z / math.sqrt(shape[1] if stacked else shape[0])
        elif name == "lm_head":  # (d, vocab)
            w = z / math.sqrt(shape[0])
        else:
            raise ValueError(f"no initializer for parameter {path}")
        out.append(w.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _layer_matmul_params(c: dict) -> int:
    d, hd = c["hidden_size"], head_dim(c)
    h, kv, f = (c["num_attention_heads"], c["num_key_value_heads"],
                c["intermediate_size"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def mean_context(seq: int, win: Optional[int]) -> float:
    """Mean number of keys a causal query attends to, window-capped."""
    w = win or seq
    if seq <= w:
        return (seq + 1) / 2
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def model_flops_per_token(c: dict, seq: int) -> float:
    """Training FLOPs a token needs: 6 x (matmul params of the layers +
    head) + 12 x L x H x hd x mean causal context.  The embedding gather and
    recomputation do not count."""
    L, h, hd = c["num_hidden_layers"], c["num_attention_heads"], head_dim(c)
    mm = L * _layer_matmul_params(c) + c["hidden_size"] * c["vocab_size"]
    return 6 * mm + 12 * L * h * hd * mean_context(seq, window(c))


def executed_matmul_flops(c: dict, batch: int, seq: int, *, remat: bool,
                          padded_vocab: int) -> float:
    """Matmul FLOPs one step of the program executes, over all chips.

    The program's attention (plain einsum, or the chunked jnp path without
    causal skipping) multiplies every query with every key and masks after,
    so it executes the full S x S products.  The head runs over the padded
    vocabulary.  Backward is twice the forward; with remat the layer stack's
    forward runs once more."""
    t = batch * seq
    L, h, hd = c["num_hidden_layers"], c["num_attention_heads"], head_dim(c)
    layers = 2 * t * L * _layer_matmul_params(c) + 4 * t * seq * L * h * hd
    head = 2 * t * c["hidden_size"] * padded_vocab
    return layers * (4 if remat else 3) + 3 * head
