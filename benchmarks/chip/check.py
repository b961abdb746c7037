"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against a limit of its own (``checks/<cell>.json``):

* ``loss_gap``    the largest relative gap of the loss of steps 1-3;
* ``grad_gap``    the first gradient as the optimizer got it (the first
                  moment after one step, over 1 - beta1): by the worst slice,
                  the gap between the program's norm and the reference's,
                  over the larger of the reference's norm of that slice and
                  of the median slice;
* ``change_gap``  the same for the change of the weights over steps 1-3,
                  leaving out slices whose reference gradient is under a
                  thousandth of the median slice's (they move by round-off
                  alone, as the key bias does under softmax).

A slice is one leaf, or one layer of a leaf stacked over layers, so that a
layer left unmoved shows as a gap of 1.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

EXCLUDE_BELOW = 1e-3


def _path(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)


def _stacked(path: str) -> bool:
    return path.startswith("group")


def slice_names(tree) -> List[str]:
    names = []
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        path = _path(kp)
        if _stacked(path):
            names += [f"{path}[{i}]" for i in range(leaf.shape[0])]
        else:
            names.append(path)
    return names


def slice_norms(tree) -> jax.Array:
    """Float32 norm of every slice, in ``slice_names`` order."""
    out = []
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = leaf.astype(jnp.float32)
        if _stacked(_path(kp)):
            out.append(jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim)))))
        else:
            out.append(jnp.sqrt(jnp.sum(x * x))[None])
    return jnp.concatenate(out)


def _worst(got: np.ndarray, want: np.ndarray, keep: np.ndarray):
    floor = float(np.median(want[keep]))
    gaps = np.abs(got - want) / np.maximum(want, floor)
    gaps = np.where(keep, gaps, 0.0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def numbers(prog: dict, ref: dict, names: List[str]) -> Dict[str, dict]:
    """``prog``/``ref``: {"losses": [3], "grad": slice norms, "change": slice
    norms}.  Returns each number with the slice that set it."""
    lp, lr = np.asarray(prog["losses"], np.float64), np.asarray(ref["losses"],
                                                                np.float64)
    loss = np.abs(lp - lr) / np.abs(lr)
    gp, gr = (np.asarray(prog["grad"], np.float64),
              np.asarray(ref["grad"], np.float64))
    cp, cr = (np.asarray(prog["change"], np.float64),
              np.asarray(ref["change"], np.float64))
    moved = gr >= EXCLUDE_BELOW * np.median(gr)
    grad, gi = _worst(gp, gr, np.ones_like(moved))
    change, ci = _worst(cp, cr, moved)
    return {
        "loss_gap": {"value": float(np.max(loss)),
                     "at": f"step {int(np.argmax(loss)) + 1}"},
        "grad_gap": {"value": grad, "at": names[gi]},
        "change_gap": {"value": change, "at": names[ci],
                       "left_out": [n for n, k in zip(names, moved) if not k]},
    }


def judge(nums: Dict[str, dict], limits: Dict[str, float]) -> bool:
    """Every number finite and at or under its limit."""
    return all(math.isfinite(nums[k]["value"]) and nums[k]["value"] <= lim
               for k, lim in limits.items())
