"""Plain reference of one training step of a Mamba-2 language model.

Written from the published description (Dao and Gu, "Transformers are
SSMs", arXiv:2405.21060; ``mamba_ssm``'s ``Mamba2`` block in a
``MixerModel``): token embedding; per layer a pre-RMSNorm residual block
whose mixer projects the normed input to z, x, B, C (one group, shared by
every head) and dt; runs a causal depthwise convolution with bias and then
SiLU over x, B and C; sets dt = softplus(dt + dt_bias) and A = -exp(A_log);
runs the state-space recurrence over time, per head,

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,    y_t = h_t C_t + D x_t,

from a zero state; then RMSNorm(y * SiLU(z)) with its scale, and the out
projection.  Final RMSNorm; the head (the embedding, transposed) over the
published vocabulary rows; mean token cross-entropy.  Then the gradients,
global-norm clipping, and AdamW with decoupled weight decay (lr x wd x w)
and bias correction, under a linear-warmup cosine schedule that decays to a
tenth of the base rate.

One departure in form, none in value: the published block has one input
projection and one convolution over x, B and C side by side; the weights
arrive as separate matrices and convolutions, the program's layout, which
compute the same channels.  A convolution's weight is (width, channels): tap
i multiplies the input width - 1 - i steps back, as torch's ``conv1d`` with
left padding width - 1 does with its weight ``[c, 0, i]``.

Float32 throughout, every product at ``Precision.HIGHEST``.  Nothing of the
program is imported.  The weights arrive in the program's parameter layout
(``embed``, ``final_norm``, and one stacked group ``group0/pos0`` of
``norm1`` and ``mixer``), made by the benchmark from the seed.  Each layer,
each segment of the time recurrence and each block of tokens of the head is
rematerialised, so that the step fits beside the optimizer state.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
SEGMENT = 128        # time steps between saved states of the recurrence
TOKEN_BLOCK = 1024


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HI)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def causal_conv(x, w, bias):
    """x: (B, S, C); w: (width, C).  SiLU of the convolution plus bias."""
    k, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(w[i] * xp[:, i:i + s] for i in range(k))
    return jax.nn.silu(out + bias)


def recurrence(x, dt, a, b, c):
    """x: (B, S, H, P); dt: (B, S, H); a: (H,); b, c: (B, S, N).  The
    state h: (B, H, P, N), stepped once per time step; returns y without the
    D x term, (B, S, H, P)."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    seg = math.gcd(s, SEGMENT)

    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = (jnp.exp(dtt * a)[:, :, None, None] * state
                 + (dtt[:, :, None] * xt)[..., None] * bt[:, None, None, :])
        return state, jnp.sum(state * ct[:, None, None, :], axis=-1)

    @jax.checkpoint
    def segment(state, inp):
        return jax.lax.scan(step, state, inp)

    def by_time(v):   # (B, S, ...) -> (S / seg, seg, B, ...)
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape(s // seg, seg, *v.shape[1:])

    _, y = jax.lax.scan(segment, jnp.zeros((bsz, h, p, n), jnp.float32),
                        tuple(by_time(v) for v in (x, dt, b, c)))
    return jnp.moveaxis(y.reshape(s, bsz, h, p), 0, 1)


def mixer(u, m, c):
    """u: the normed input (B, S, d); m: the layer's mixer weights."""
    eps = c["assumed"]["norm_eps"]
    bsz, s, _ = u.shape
    h = m["A_log"].shape[-1]
    z = _mm("bsd,de->bse", u, m["z_proj"])
    x = causal_conv(_mm("bsd,de->bse", u, m["x_proj"]), m["conv_x"],
                    m["conv_x_bias"])
    b = causal_conv(_mm("bsd,dn->bsn", u, m["b_proj"]), m["conv_b"],
                    m["conv_b_bias"])
    cc = causal_conv(_mm("bsd,dn->bsn", u, m["c_proj"]), m["conv_c"],
                     m["conv_c_bias"])
    dt = jax.nn.softplus(_mm("bsd,dh->bsh", u, m["dt_proj"]) + m["dt_bias"])
    xh = x.reshape(bsz, s, h, -1)
    y = recurrence(xh, dt, -jnp.exp(m["A_log"]), b, cc)
    y = (y + m["D"][:, None] * xh).reshape(bsz, s, -1)
    y = rms_norm(y * jax.nn.silu(z), m["norm"]["scale"], eps)
    return _mm("bse,ed->bsd", y, m["out_proj"])


def layer(x, p, c):
    u = rms_norm(x, p["norm1"]["scale"], c["assumed"]["norm_eps"])
    return x + mixer(u, p["mixer"], c)


def loss_fn(w, tokens, labels, c):
    """Mean next-token cross-entropy over all B x S positions, the logits
    over the published rows (``vocab_size`` padded to
    ``pad_vocab_size_multiple``)."""
    m = c["pad_vocab_size_multiple"]
    rows = -(-c["vocab_size"] // m) * m
    x = w["embed"][tokens]
    x, _ = jax.lax.scan(jax.checkpoint(lambda x, p: (layer(x, p, c), None)),
                        x, w["group0"]["pos0"])
    x = rms_norm(x, w["final_norm"]["scale"], c["assumed"]["norm_eps"])
    head = w["embed"][:rows].T
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
