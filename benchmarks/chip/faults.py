"""Faults planted under a run, to show that the check catches them.

Each is a way the timed path of a training cell could go wrong.  The step
faults wrap the program's step builder (the ``make_step`` of
``harness.Trainer``); ``half_batch`` alters the labels the step is fed.
``calibrate.py`` reads them on the chip at a cell's size, and
``tests/bench`` sees each turn ``correct`` false at a small size.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import PartitionSpec as P


def half_batch(labels: np.ndarray) -> np.ndarray:
    """Half of the batch left out, the mean taken over the rest: the second
    half of the rows (of the positions, for one row) get the ignored label."""
    out = labels.copy()
    b, s = labels.shape
    if b >= 2:
        out[b // 2:] = -1
    else:
        out[:, s // 2:] = -1
    return out


def unchanged(make_step):
    """A step that returns its state unchanged."""
    def build(cfg, tcfg, ctx):
        step = make_step(cfg, tcfg, ctx)

        def f(params, opt, batch):
            return params, opt, step(params, opt, batch)[2]
        return f
    return build


def layer_lost(make_step):
    """The update of the first layer's slice of every stacked leaf lost."""
    def build(cfg, tcfg, ctx):
        step = make_step(cfg, tcfg, ctx)

        def f(params, opt, batch):
            new, opt_new, m = step(params, opt, batch)
            keep = {k: (jax.tree.map(lambda a, b: a.at[0].set(b[0]),
                                     new[k], params[k])
                        if k.startswith("group") else new[k]) for k in new}
            return keep, opt_new, m
        return f
    return build


def no_exchange(make_step):
    """No collective between chips: each device steps on its own data shard
    with the whole model, and the state of the first is what comes out."""
    def build(cfg, tcfg, ctx):
        step = make_step(cfg, tcfg, None)
        data = ctx.data_axes if len(ctx.data_axes) > 1 else ctx.data_axes[0]
        return jax.shard_map(step, mesh=ctx.mesh,
                             in_specs=(P(), P(), P(data)),
                             out_specs=(P(), P(), P()), check_vma=False)
    return build
