"""The chunked attention's own backward, as the compiler sees it.

A layer (q/k/v projections, the chunked attention, the output projection,
under ``jax.named_scope("attention")`` as ``_apply_layer`` has it) runs in a
``jax.checkpoint``ed scan over two layers, as the trainer's layer loop does
with remat on; its gradient is compiled for the CPU.  The custom backward
stores no chunk pair's scores or mask, where JAX's autodiff through the same
chunked forward loops stores every pair's; and the benchmark's block and
phase rule (``benchmarks/chip/scopes.py``) charges the backward's ops to
``attention`` / ``backward`` and the recomputed forward to ``recompute``.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.models.attention import (_Chunking, _flash_attention_jnp,
                                    _flash_forward)

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks", "chip")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
import scopes  # noqa: E402

B, S, D, KV, G, HD, LAYERS = 1, 384, 32, 2, 3, 16, 2
Q_CHUNK, KV_CHUNK = 128, 96  # 3 x 4 chunk pairs; sizes no other dim has


def _custom(q, k, v):
    pos = jnp.arange(S)
    return _flash_attention_jnp(q, k, v, q_pos=pos, k_pos=pos, causal=True,
                                window=None, q_chunk=Q_CHUNK,
                                kv_chunk=KV_CHUNK)


def _autodiff(q, k, v):
    pos = jnp.arange(S)
    c = _Chunking(causal=True, window=None, q_chunk=Q_CHUNK,
                  kv_chunk=KV_CHUNK, causal_skip=False)
    return _flash_forward(q, k, v, pos, pos, jnp.ones(S, bool), c)[0]


def _grad_hlo(attn) -> str:
    def layer(x, w):
        with jax.named_scope("attention"):
            q = jnp.einsum("bsd,dkgh->bskgh", x, w["wq"])
            k = jnp.einsum("bsd,dkh->bskh", x, w["wk"])
            v = jnp.einsum("bsd,dkh->bskh", x, w["wv"])
            x = x + jnp.einsum("bskgh,kghd->bsd", attn(q, k, v), w["wo"])
        return x, None

    def loss(w, x):
        y, _ = jax.lax.scan(jax.checkpoint(layer), x, w)
        return jnp.sum(y * y)

    shapes = {"wq": (LAYERS, D, KV, G, HD), "wk": (LAYERS, D, KV, HD),
              "wv": (LAYERS, D, KV, HD), "wo": (LAYERS, KV, G, HD, D)}
    w = {n: jax.ShapeDtypeStruct(s, jnp.float32) for n, s in shapes.items()}
    x = jax.ShapeDtypeStruct((B, S, D), jnp.float32)
    return jax.jit(jax.grad(loss)).lower(w, x).compile().as_text()


@pytest.fixture(scope="module")
def hlo():
    return {"custom": _grad_hlo(_custom), "autodiff": _grad_hlo(_autodiff)}


def _stacked_pairs(text: str):
    """Buffers written by a dynamic-update-slice whose shape holds a chunk
    pair (both chunk sizes), with their dtypes."""
    out = set()
    for dtype, dims in re.findall(
            r"= (\w+)\[([\d,]*)\]\S* dynamic-update-slice\(", text):
        shape = [int(n) for n in dims.split(",") if n]
        if Q_CHUNK in shape and KV_CHUNK in shape:
            out.add(f"{dtype}[{dims}]")
    return out


def test_backward_stores_no_chunk_pair(hlo):
    assert _stacked_pairs(hlo["custom"]) == set()
    # autodiff through the chunk loops stacks every pair's f32 scores
    assert any(s.startswith("f32[3,4,") for s in _stacked_pairs(
        hlo["autodiff"])), _stacked_pairs(hlo["autodiff"])


def _phases(names, part):
    """(block, phase) of every instruction made inside the attention's chunk
    loops whose op name holds ``part``."""
    return {(scopes.block(o), scopes.phase(o)) for o in names.values()
            if re.search(r"attention/.*while/body/.*" + re.escape(part), o)}


def test_backward_ops_read_attention_backward(hlo):
    """The custom backward's products read ``attention`` / ``backward``;
    the chunk probabilities, taken by the forward, again by the
    rematerialized forward and again by the backward, read ``forward``,
    ``recompute`` and ``backward``, all in ``attention``."""
    names = scopes.op_names(hlo["custom"])
    # dk and dv: only the backward contracts over the query rows
    assert _phases(names, "/bqkgs,bqkgh->bskh/") == {
        ("attention", "backward")}
    assert _phases(names, "/exp") == {
        ("attention", "forward"), ("attention", "recompute"),
        ("attention", "backward")}
