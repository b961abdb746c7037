"""Model: device time a step of the ``optimizer`` block.

Ops whose HLO metadata names the ``optimizer`` scope (``train/step.py``
``train_step``: gradient cast, learning-rate schedule, global norm, clip and
AdamW), by ``scopes.py``'s rule, mean over cores, in ms.  It belongs to both
partitions of the step's busy time: the blocks' and the phases'.
"""
import scopes

LAYER = "model"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(r):
    return scopes.ms(r, scopes.block, "optimizer")
