"""Jitted public wrapper for the grouped expert matmul kernel."""
from __future__ import annotations

from repro.kernels.moe_gmm.kernel import moe_gmm_kernel
from repro.kernels.moe_gmm.ref import moe_gmm_ref


def moe_gmm(x, w, *, bc: int = 128, bf: int = 128, bd: int = 256):
    """Capacity-padded grouped expert matmul: (E,C,d) x (E,d,f) -> (E,C,f)."""
    return moe_gmm_kernel(x, w, bc=bc, bf=bf, bd=bd)


reference = moe_gmm_ref
