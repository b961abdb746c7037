"""Charging the step's device time to the program's blocks and phases.

The program runs each block of the step under a ``jax.named_scope`` named by
its kind (``models/transformer.py``, ``train/step.py``).  JAX writes the scope
into each HLO instruction's ``metadata={op_name="..."}``, wrapped in what the
transformations did to it:

* forward:    ``jit(train_step)/jvp()/while/body/closed_call/attention/dot_general``
* backward:   ``jit(train_step)/transpose(jvp())/while/body/.../ffn/mul``,
              or ``jit(train_step)/transpose(jvp(vocab))/mul`` outside the scan
* recomputed: ``.../transpose(jvp())/.../checkpoint/rematted_computation/attention/...``
* optimizer:  ``jit(train_step)/optimizer/sub``

The rules live here as literals, as ``hlo.py`` keeps its own copy of the
program's arithmetic, so that no change to the program moves them:

* block: the one program scope among the op name's ``/``-separated parts, the
  wrappers ``jvp(`` / ``transpose(`` / ``)`` stripped; ``unscoped`` where there
  is none (the scan's own bookkeeping, copies the compiler made);
* phase: ``optimizer`` for the optimizer block, else ``backward`` where a part
  starts with ``transpose(``, else ``forward``; ``recompute`` is the part of
  ``backward`` whose name holds ``rematted_computation``.

Where an instruction carries several names joined by ``;`` the first counts.
Each subset's time is ``devtrace.class_ns``'s reduction: the union of its op
intervals on each core, clipped to the ``window`` span, loops and calls
(``control``: their events span their bodies) left out; the mean over cores,
per step, in ms.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, Optional

import devtrace as tr

BLOCKS = ("attention", "ssm", "cross_attention", "ffn", "moe", "vocab",
          "optimizer")
UNSCOPED = "unscoped"
RECOMPUTE_MARK = "rematted_computation"

_OP_NAME_RE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*\bmetadata=\{[^}]*?\bop_name="([^"]*)"')
_WRAPPER_RE = re.compile(r"^(?:jvp\(|transpose\()+")


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction -> the ``op_name`` of its metadata (the first of several
    joined by ``;``); instructions without one are left out."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_NAME_RE.match(line)
        if m:
            out[m.group(1)] = m.group(2).split(";")[0]
    return out


def block(op_name: str) -> str:
    for part in op_name.split("/"):
        name = _WRAPPER_RE.sub("", part).rstrip(")")
        if name in BLOCKS:
            return name
    return UNSCOPED


def phase(op_name: str) -> str:
    """``optimizer``, ``recompute`` (a part of backward), ``backward`` or
    ``forward``."""
    if block(op_name) == "optimizer":
        return "optimizer"
    if any(p.startswith("transpose(") for p in op_name.split("/")):
        return "recompute" if RECOMPUTE_MARK in op_name else "backward"
    return "forward"


def _labels(r, of: Callable[[str], str]) -> Optional[Dict[str, str]]:
    """Event name -> ``of(op_name)`` for every op event of the trace
    (``control`` for loops and calls); None where no instruction of the
    program carries a block scope, as in a program built before the
    scopes were."""
    names = op_names(r.hlo_text)
    if not any(block(n) != UNSCOPED for n in names.values()):
        return None
    out = {}
    for evs in r.events["devices"].values():
        for name, _, _ in evs:
            if name not in out:
                out[name] = ("control" if r.classes.get(name) == "control"
                             else of(names.get(name, "")))
    return out


def ms(r, of: Callable[[str], str], *labels: str) -> Optional[float]:
    """Device time a step of the ops whose ``of(op_name)`` is one of
    ``labels``, mean over cores, in ms; None with nothing to read."""
    marks = _labels(r, of)
    if marks is None or r.steps <= 0 or not r.events["devices"]:
        return None
    total = sum(sum(tr.class_ns(r.events, marks, lab).values())
                for lab in labels)
    return total / len(r.events["devices"]) / r.steps / 1e6
