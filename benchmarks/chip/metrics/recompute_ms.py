"""Step: device time a step of the forward pass recomputed in the backward.

The part of ``backward_ms`` whose HLO op name holds ``rematted_computation``
(JAX's mark of what ``jax.checkpoint`` recomputes; ``TrainConfig.remat``), by
``scopes.py``'s rule, mean over cores, in ms.
"""
import scopes

LAYER = "step"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(r):
    return scopes.ms(r, scopes.phase, "recompute")
