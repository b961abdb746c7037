"""Model: device time a step of the causal convolutions inside the ``ssm``
blocks.

Ops with an op-name part ``causal_conv`` once the wrappers ``jvp(`` /
``transpose(`` / ``)`` are stripped (``models/ssm.py`` ``mamba_forward``
runs each stream's conv and its SiLU under that scope: x, B and C; forward,
backward and recomputed), through ``scopes.ms``, mean over cores, in ms.
The rule is this file's own literal, as ``scopes.py`` keeps its own.
Nothing is read from a program whose instructions carry no such part.
"""
import re

import scopes

LAYER = "model"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"

_WRAPPER_RE = re.compile(r"^(?:jvp\(|transpose\()+")


def _is_conv(op_name: str) -> bool:
    return any(_WRAPPER_RE.sub("", part).rstrip(")") == "causal_conv"
               for part in op_name.split("/"))


def _mark(op_name: str) -> str:
    return "causal_conv" if _is_conv(op_name) else "other"


def read(r):
    if not any(_is_conv(n) for n in scopes.op_names(r.hlo_text).values()):
        return None
    return scopes.ms(r, _mark, "causal_conv")
