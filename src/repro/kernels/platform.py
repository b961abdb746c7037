"""How every Pallas kernel in this package runs, decided in one place.

A kernel is compiled by Mosaic when the program is lowered for a TPU and
runs through the Pallas interpreter when it is lowered for the CPU backend
(the tests and CPU rehearsals).  The choice is made at lowering time from
the target platform (``lax.platform_dependent``), not from a flag and not
from the host's default backend: a program compiled from a CPU host for a
described TPU topology gets the compiled kernel, and no path runs the
interpreter on a TPU.  Any other platform fails to lower.
"""
from __future__ import annotations

from typing import Callable

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel: Callable, **kwargs) -> Callable:
    """``pl.pallas_call(kernel, **kwargs)``, compiled on TPU and interpreted
    on CPU."""
    compiled = pl.pallas_call(kernel, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, tpu=compiled,
                                          cpu=interpreted)

    return call
