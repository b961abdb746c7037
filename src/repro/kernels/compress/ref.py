"""Pure-JAX references for the compression kernels (the oracles the Pallas
kernels are validated against, and the implementations the codecs and the
compressed collectives in ``ccl.primitives`` run on any backend)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_TINY = 1e-30  # guards scale against all-zero payloads


def quantize_ref(x: jax.Array, bits: int = 8, stochastic: bool = False,
                 key: Optional[jax.Array] = None, per_row: bool = False
                 ) -> Tuple[jax.Array, jax.Array]:
    """Uniform symmetric quantization to ``bits`` (stored as int8).

    ``per_row=True`` scales each row of a 2D input independently (the
    kernel's layout); otherwise one scale covers the whole tensor.
    ``stochastic=True`` rounds stochastically with ``key`` (unbiased —
    E[dequant] = x); default is round-to-nearest."""
    qmax = float(2 ** (bits - 1) - 1)
    x32 = x.astype(jnp.float32)
    if per_row:
        absmax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    else:
        absmax = jnp.max(jnp.abs(x32))
    scale = jnp.maximum(absmax, _TINY) / qmax
    scaled = x32 / scale
    if stochastic:
        if key is None:
            raise ValueError("stochastic rounding needs a PRNG key")
        u = jax.random.uniform(key, x.shape)
        q = jnp.floor(scaled + u)
    else:
        q = jnp.round(scaled)
    q = jnp.clip(q, -qmax, qmax).astype(jnp.int8)
    return q, jnp.asarray(scale, jnp.float32)


def dequantize_ref(q: jax.Array, scale: jax.Array,
                   dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def pack_int4(q: jax.Array) -> jax.Array:
    """Pack a 1D int8 array of 4-bit values (range [-7, 7]) into uint8
    nibble pairs — the transform that makes a q4 payload genuinely half
    the q8 wire bytes.  Byte i holds value i (low nibble) and value
    i + len/2 (high nibble): contiguous halves, not interleaved pairs,
    because the TPU compiler handles strided byte slices so slowly that
    pack + unpack of 2^20 interleaved values took 208 s to compile for a
    v5e (0.6 s as halves).  Odd lengths get a zero nibble of padding."""
    flat = q.reshape(-1)
    if flat.size % 2:
        flat = jnp.pad(flat, (0, 1))
    u = (flat.astype(jnp.int32) + 8).astype(jnp.uint8)  # [-7,7] -> [1,15]
    half = u.size // 2
    return (u[:half] | (u[half:] << 4)).astype(jnp.uint8)


def unpack_int4(packed: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`pack_int4`; ``n`` is the unpacked length."""
    lo = (packed & 0xF).astype(jnp.int32) - 8
    hi = ((packed >> 4) & 0xF).astype(jnp.int32) - 8
    return jnp.concatenate([lo, hi])[:n].astype(jnp.int8)


def wire_codec(bits: int, length: int):
    """(encode, decode) pair for quantize-on-the-wire collectives: encode
    maps a length-``length`` fp chunk to (int payload, 1-element fp32
    scale) — nibble-packed for ``bits=4`` so the wire saving is real —
    and decode inverts it.  Shared by the compressed ring and the
    synthesized move-list interpreter in ``ccl.primitives`` so every
    send-loop compresses identically (and swaps to the Pallas kernels
    together)."""

    def encode(v: jax.Array) -> Tuple[jax.Array, jax.Array]:
        q, scale = quantize_ref(v, bits=bits)
        if bits == 4:
            q = pack_int4(q)
        return q, scale.reshape(1)

    def decode(q: jax.Array, scale: jax.Array) -> jax.Array:
        if bits == 4:
            q = unpack_int4(q, length)
        return dequantize_ref(q, scale[0])

    return encode, decode


def sparsify_ref(x: jax.Array, thresh: jax.Array) -> jax.Array:
    """Magnitude thresholding: keep entries with |x| >= thresh (thresh
    broadcasts; per-row for 2D inputs), zero the rest."""
    x32 = x.astype(jnp.float32)
    return jnp.where(jnp.abs(x32) >= thresh, x32, 0.0)


def matmul_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """fp32-accumulated matmul — the PowerSGD projection primitive."""
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
