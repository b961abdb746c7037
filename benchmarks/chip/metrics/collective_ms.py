"""Collectives (XLA's, as placed by ``parallel.planner``): exposed time.

Device time per step of the ops that the compiled step's HLO classes as
collectives (all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all, or a fusion holding one), on each TPU core's op line, as a mean
over cores, in ms.  Transfer hidden behind compute is not on that line, so
this is the exposed part.  No collective op in the window: nothing to read.
"""
import devtrace as tr

LAYER = "collectives"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(r):
    per_dev = tr.class_ns(r.events, r.classes, "collective")
    total = sum(per_dev.values())
    if total <= 0 or r.steps <= 0:
        return None
    return total / len(per_dev) / r.steps / 1e6
