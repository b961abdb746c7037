"""Mamba-2 language models (state-spaces/mamba2-*): attention-free SSD blocks.

How the benchmark turns a published ``config.json`` (with the mixer's
defaults that it does not state, listed under ``assumed``) into the
program's ``ModelConfig``, how it makes the weights from a seed in the
program's parameter layout, and how many FLOPs a step needs and executes.
The plain reference in ``reference/mamba2.py`` makes the same weights from
the same seed with this module; neither takes anything the program made.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

REFERENCE = "mamba2"


def vocab_rows(c: dict) -> int:
    """Rows of the embedding and of the logits: ``vocab_size`` padded to
    ``pad_vocab_size_multiple``, as the published model holds them."""
    m = c["pad_vocab_size_multiple"]
    return -(-c["vocab_size"] // m) * m


def dims(c: dict) -> dict:
    """d (model), din (inner), n (state), h (heads), p (head size), g
    (groups of B and C), q (published chunk)."""
    a = c["assumed"]
    din = a["expand"] * c["d_model"]
    return {"d": c["d_model"], "din": din, "n": a["d_state"],
            "h": din // a["headdim"], "p": a["headdim"], "g": a["ngroups"],
            "q": a["chunk_size"]}


def program_config(c: dict):
    """The program's ``ModelConfig`` for published config ``c``."""
    from repro.core.types import ModelConfig
    a = c["assumed"]
    if c["ssm_cfg"].get("layer") != "Mamba2" or c["attn_layer_idx"]:
        raise ValueError(f"{c['name']}: only attention-free Mamba2 stacks")
    if c["d_intermediate"] or a["ngroups"] != 1 or a["D_has_hdim"] or \
            a["bias"] or not a["conv_bias"] or a["norm_before_gate"]:
        raise ValueError(f"{c['name']}: the program builds the Mamba2 block "
                         "with no MLP, one group of B and C, a per-head D, "
                         "conv biases, no projection bias, gate before norm")
    return ModelConfig(
        name=c["name"], family="ssm", source=c["source_url"],
        num_layers=c["n_layer"], d_model=c["d_model"], num_heads=0,
        num_kv_heads=0, d_ff=0, vocab_size=vocab_rows(c), attention="none",
        ssm_state=a["d_state"], ssm_expand=a["expand"],
        ssm_head_dim=a["headdim"], ssm_conv_kernel=a["d_conv"],
        norm_eps=float(a["norm_eps"]),
        tie_embeddings=bool(c["tie_embeddings"]))


def _path(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)


def init_weights(shapes, key, dtype=jnp.float32):
    """Weights for the parameter tree ``shapes`` (the program's layout, from
    ``jax.eval_shape``), drawn from ``key``.

    As published (``mamba_ssm``'s ``Mamba2`` and ``_init_weights``): ``A_log``
    is log U(1, 16) per head; ``dt_bias`` is the inverse softplus of a dt
    drawn log-uniform in [0.001, 0.1], floored at 1e-4; the out-projection
    has the std of a Kaiming-uniform fan-in init over sqrt(layers).  Else
    normal: matrices with std 1/sqrt(fan-in) (a depthwise conv's fan-in is
    its width), the embedding and the conv biases std 0.02, norm scales and
    ``D`` 1 + 0.05 * normal, so that a path which dropped a bias, a scale or
    ``D`` would show.  Generated in float32 and cast, so a lower ``dtype``
    holds the same weights rounded."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (kp, leaf) in enumerate(leaves):
        path, shape = _path(kp), leaf.shape
        name = path.rsplit("/", 1)[-1]
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        if name in ("scale", "D"):
            w = 1.0 + 0.05 * z
        elif name in ("embed", "conv_x_bias", "conv_b_bias", "conv_c_bias"):
            w = 0.02 * z
        elif name in ("z_proj", "x_proj", "b_proj", "c_proj", "dt_proj"):
            w = z / math.sqrt(shape[1])           # (L, d, out)
        elif name in ("conv_x", "conv_b", "conv_c"):
            w = z / math.sqrt(shape[1])           # (L, width, channels)
        elif name == "out_proj":                  # (L, din, d)
            w = z / math.sqrt(3 * shape[1] * shape[0])
        elif name == "A_log":
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            u = jax.random.uniform(k, shape, jnp.float32)
            dt = jnp.maximum(jnp.exp(u * (math.log(0.1) - math.log(0.001))
                                     + math.log(0.001)), 1e-4)
            w = dt + jnp.log(-jnp.expm1(-dt))     # softplus(w) = dt
        else:
            raise ValueError(f"no initializer for parameter {path}")
        out.append(w.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def layer_matmul_params(c: dict) -> int:
    """One layer's projection weights: in (z, x, B, C, dt) and out."""
    m = dims(c)
    return m["d"] * (2 * m["din"] + 2 * m["g"] * m["n"] + m["h"]) + \
        m["din"] * m["d"]


def ssd_flops_per_token(c: dict, seq: int) -> float:
    """Forward FLOPs a token of one layer's SSD needs at the published chunk
    Q: C.B^T over the mean causal context in its chunk, (Q + 1)/2 x 2N; the
    intra-chunk output, (Q + 1)/2 x 2HP; the chunk's state and the output
    from the state carried in, 2HPN each."""
    m = dims(c)
    q = min(m["q"], seq)
    ctx = (q + 1) / 2
    return ctx * 2 * m["n"] + ctx * 2 * m["h"] * m["p"] + \
        4 * m["h"] * m["p"] * m["n"]


def model_flops_per_token(c: dict, seq: int) -> float:
    """Training FLOPs a token needs: 6 x (projection weights of the layers +
    the tied head over the published rows) + 3 x layers x the SSD's forward.
    The embedding gather, the convolutions, the norms and recomputation do
    not count."""
    mm = c["n_layer"] * layer_matmul_params(c) + c["d_model"] * vocab_rows(c)
    return 6 * mm + 3 * c["n_layer"] * ssd_flops_per_token(c, seq)


def executed_matmul_flops(c: dict, batch: int, seq: int, *, remat: bool,
                          padded_vocab: int) -> float:
    """Matmul FLOPs one step of the program executes, over all chips.

    A layer's forward: the projections, and ``models/ssm.py``'s
    ``ssd_chunked`` at its chunk Q (the full Q x Q C.B^T and intra-chunk
    products, masked after; the chunk states; the output from the carried
    state).  Backward is twice the forward, plus the four small products by
    which the multi-operand einsums send gradients to dt and the decays
    (2HN, 2HN, 2HP, 2HP a token).  With remat the layer's forward runs once
    more, all but the out-projection, whose output the backward does not
    use.  The head runs over the padded vocabulary, forward and twice in the
    backward.  Checked against the dots of the compiled step."""
    from repro.models.ssm import DEFAULT_CHUNK
    m = dims(c)
    q = min(DEFAULT_CHUNK, seq)
    t = batch * seq
    fwd = 2 * layer_matmul_params(c) + 2 * q * m["n"] + \
        2 * q * m["h"] * m["p"] + 4 * m["h"] * m["p"] * m["n"]
    bwd = 2 * fwd + 4 * m["h"] * (m["n"] + m["p"])
    again = fwd - 2 * m["din"] * m["d"] if remat else 0
    head = 2 * t * c["d_model"] * padded_vocab
    return t * c["n_layer"] * (fwd + bwd + again) + 3 * head
