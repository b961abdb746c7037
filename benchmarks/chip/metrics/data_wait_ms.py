"""Input layer (``data.pipeline``): host time a step spends getting its batch.

The harness's host span around ``next(batches)`` and the transfer of the
batch to the device, mean per step of the traced window, in ms.  It runs
while the step before is in flight, so it costs the device only where it
outlasts that step.
"""
LAYER = "input"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(r):
    if not r.data_wait_s:
        return None
    return 1e3 * sum(r.data_wait_s) / len(r.data_wait_s)
