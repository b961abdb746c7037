"""Model: device time a step of the ``attention`` blocks.

Ops whose HLO metadata names the ``attention`` scope (``models/transformer.py``
``_apply_layer``: ``norm1``, the attention mixer, its residual add; forward,
backward and recomputed), by ``scopes.py``'s rule, mean over cores, in ms.
Collectives the compiler put in the block (Megatron all-reduces) count here
too, as in ``collective_ms``.
"""
import scopes

LAYER = "model"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(r):
    return scopes.ms(r, scopes.block, "attention")
