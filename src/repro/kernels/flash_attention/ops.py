"""Jitted public wrapper for the flash-attention kernel (compiled on TPU,
interpreted on CPU: see ``repro.kernels.platform``)."""
from __future__ import annotations

from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import attention_ref


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    bq: int = 128, bk: int = 128):
    """Flash attention with GQA/causal/sliding-window support.

    q: (B, H, Sq, D); k, v: (B, KV, Sk, D); returns (B, H, Sq, D)."""
    return flash_attention_kernel(q, k, v, causal=causal, window=window,
                                  bq=bq, bk=bk)


reference = attention_ref
