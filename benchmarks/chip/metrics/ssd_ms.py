"""Model: device time a step of the SSD scan inside the ``ssm`` blocks.

Ops with an op-name part ``ssd`` once the wrappers ``jvp(`` / ``transpose(``
/ ``)`` are stripped (``models/ssm.py`` ``mamba_forward`` runs
``ssd_chunked`` under that scope: the decay matrices, the intra-chunk and
state products, the scan over chunks; forward, backward and recomputed),
through ``scopes.ms``, mean over cores, in ms.  The rule is this file's own
literal, as ``scopes.py`` keeps its own.  Nothing is read from a program
whose instructions carry no such part.
"""
import re

import scopes

LAYER = "model"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"

_WRAPPER_RE = re.compile(r"^(?:jvp\(|transpose\()+")


def _is_ssd(op_name: str) -> bool:
    return any(_WRAPPER_RE.sub("", part).rstrip(")") == "ssd"
               for part in op_name.split("/"))


def _mark(op_name: str) -> str:
    return "ssd" if _is_ssd(op_name) else "other"


def read(r):
    if not any(_is_ssd(n) for n in scopes.op_names(r.hlo_text).values()):
        return None
    return scopes.ms(r, _mark, "ssd")
