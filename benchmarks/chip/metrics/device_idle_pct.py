"""Device: the share of the traced window in which no op runs.

1 - (union of the op intervals on each TPU core's op line / window), mean
over cores, in %.
"""
import devtrace as tr

LAYER = "device"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(r):
    lo, hi = tr.window(r.events)
    busy = tr.busy_ns(r.events)
    if not busy or hi <= lo:
        return None
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / (hi - lo))
