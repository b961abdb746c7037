"""Model: device time a step of ops in no block scope.

Ops whose HLO metadata names none of the program's block scopes (the layer
scan's own slicing and stacking, copies the compiler made), by ``scopes.py``'s
rule, mean over cores, in ms: what the block metrics leave out, so the guard
on their coverage.
"""
import scopes

LAYER = "model"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(r):
    return scopes.ms(r, scopes.block, scopes.UNSCOPED)
