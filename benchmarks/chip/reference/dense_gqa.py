"""Plain reference of one training step of a dense GQA decoder.

Written from the published descriptions (Qwen2, arXiv:2407.10671; H2O-Danube,
arXiv:2401.16818; both follow the Llama/Mistral block): token embedding;
per layer RMSNorm, q/k/v projections (with q/k/v biases where the config
says so), rotary embeddings in the rotate-half form, causal grouped-query
attention with an optional sliding window, output projection and residual,
RMSNorm and a SwiGLU MLP and residual; final RMSNorm; the head (the
embedding, transposed, where tied); mean token cross-entropy.  Then the
gradients, global-norm clipping, and AdamW with decoupled weight decay
(lr x wd x w) and bias correction, under a linear-warmup cosine schedule
that decays to a tenth of the base rate.

Float32 throughout, every product at ``Precision.HIGHEST``.  Nothing of the
program is imported.  The weights arrive in the program's parameter layout
(``embed``, ``final_norm``, ``lm_head``, and one stacked group ``group0/pos0``
of ``norm1``, ``mixer``, ``norm2``, ``ffn``), made by the benchmark from the
seed.  Each layer, each block of query rows and each block of tokens of the
head is rematerialised, so that the step fits beside the optimizer state.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
TOKEN_BLOCK = 1024


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HI)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (B, S, heads, hd); position i rotates pair (j, j + hd/2) by
    i * theta^(-2j/hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., : hd // 2]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def attention(q, k, v, window):
    """Causal attention; query head h reads key/value head h // (H / KV)."""
    b, s, h, hd = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    blk = math.gcd(s, Q_BLOCK)
    pos = jnp.arange(s)

    @jax.checkpoint
    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        sc = _mm("bqhd,bkhd->bhqk", qb, k) / math.sqrt(hd)
        qp = i * blk + jnp.arange(blk)
        mask = qp[:, None] >= pos[None, :]
        if window:
            mask &= qp[:, None] - pos[None, :] < window
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return _mm("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(rows, jnp.arange(s // blk))  # (blocks, B, blk, H, hd)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd)


def layer(x, p, c):
    eps = c["rms_norm_eps"]
    a = p["mixer"]
    h = rms_norm(x, p["norm1"]["scale"], eps)
    q = _mm("bsd,dhk->bshk", h, a["wq"])
    k = _mm("bsd,dhk->bshk", h, a["wk"])
    v = _mm("bsd,dhk->bshk", h, a["wv"])
    if c["architecture"]["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k = rope(q, c["rope_theta"]), rope(k, c["rope_theta"])
    window = (c.get("sliding_window") if c.get("use_sliding_window", True)
              else None)
    o = attention(q, k, v, window)
    x = x + _mm("bshk,hkd->bsd", o, a["wo"])
    f = p["ffn"]
    h = rms_norm(x, p["norm2"]["scale"], eps)
    g = jax.nn.silu(_mm("bsd,df->bsf", h, f["w_gate"]))
    return x + _mm("bsf,fd->bsd", g * _mm("bsd,df->bsf", h, f["w_up"]),
                   f["w_down"])


def loss_fn(w, tokens, labels, c):
    """Mean next-token cross-entropy over all B x S positions."""
    vocab = c["vocab_size"]
    x = w["embed"][tokens]
    x, _ = jax.lax.scan(jax.checkpoint(lambda x, p: (layer(x, p, c), None)),
                        x, w["group0"]["pos0"])
    x = rms_norm(x, w["final_norm"]["scale"], c["rms_norm_eps"])
    head = (w["embed"][:vocab].T if c["tie_word_embeddings"]
            else w["lm_head"][:, :vocab])
    t = tokens.size
    blk = math.gcd(t, TOKEN_BLOCK)
    xs = x.reshape(t // blk, blk, -1)
    ls = labels.reshape(t // blk, blk)

    @jax.checkpoint
    def block(total, xl):
        logits = _mm("td,dv->tv", xl[0], head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        true = jnp.take_along_axis(logits, xl[1][:, None], axis=-1)[:, 0]
        return total + jnp.sum(lse - true), None

    total, _ = jax.lax.scan(block, jnp.zeros((), jnp.float32), (xs, ls))
    return total / t


def learning_rate(step, h):
    """Rate of the step that ``step`` steps precede."""
    step = step.astype(jnp.float32)
    warm = jnp.minimum(1.0, (step + 1) / max(h["warmup_steps"], 1))
    prog = jnp.clip((step - h["warmup_steps"])
                    / max(h["total_steps"] - h["warmup_steps"], 1), 0.0, 1.0)
    cosine = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return h["learning_rate"] * warm * (0.1 + 0.9 * cosine)


def train_step(state, tokens, labels, c, h):
    """state = (weights, first moments, second moments, steps done)."""
    w, m, v, t = state
    loss, g = jax.value_and_grad(loss_fn)(w, tokens, labels, c)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    clip = jnp.minimum(1.0, h["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    g = jax.tree.map(lambda x: x * clip, g)
    lr = learning_rate(t, h)
    b1, b2 = h["beta1"], h["beta2"]
    t = t + 1
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    c1 = 1 - b1 ** t.astype(jnp.float32)
    c2 = 1 - b2 ** t.astype(jnp.float32)
    w = jax.tree.map(
        lambda p, a, s: p - lr * ((a / c1) / (jnp.sqrt(s / c2) + h["eps"])
                                  + h["weight_decay"] * p), w, m, v)
    return (w, m, v, t), loss
