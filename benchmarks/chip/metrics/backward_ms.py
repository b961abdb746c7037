"""Step: device time a step of the backward pass, recomputation included.

Ops outside the optimizer whose HLO op name has a ``transpose(`` part, by
``scopes.py``'s rule, mean over cores, in ms.
"""
import scopes

LAYER = "step"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(r):
    return scopes.ms(r, scopes.phase, "backward", "recompute")
