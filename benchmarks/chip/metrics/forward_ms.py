"""Step: device time a step of the forward pass.

Ops outside the optimizer whose HLO op name has no ``transpose(`` part (JAX's
own mark of the backward pass), by ``scopes.py``'s rule, mean over cores, in
ms.  With ``optimizer_ms`` and ``backward_ms`` it partitions the busy time.
"""
import scopes

LAYER = "step"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(r):
    return scopes.ms(r, scopes.phase, "forward")
