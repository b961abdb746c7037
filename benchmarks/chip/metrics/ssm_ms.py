"""Model: device time a step of the ``ssm`` blocks.

Ops whose HLO metadata names the ``ssm`` scope (``models/transformer.py``
``_apply_layer``: ``norm1``, the Mamba-2 mixer of ``models/ssm.py`` with its
projections, convolutions, SSD scan and gated norm, its residual add;
forward, backward and recomputed), by ``scopes.py``'s rule, mean over
cores, in ms.
"""
import scopes

LAYER = "model"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(r):
    return scopes.ms(r, scopes.block, "ssm")
