"""Jitted public wrapper for the SSD scan kernel."""
from __future__ import annotations

from repro.kernels.ssd_scan.kernel import ssd_scan_kernel
from repro.kernels.ssd_scan.ref import ssd_scan_ref


def ssd_scan(x, dt, a, b, c, *, chunk: int = 128):
    """Chunked SSD scan. x: (B,H,L,P); dt: (B,H,L); a: (H,); b,c: (B,L,N)."""
    return ssd_scan_kernel(x, dt, a, b, c, chunk=chunk)


reference = ssd_scan_ref
