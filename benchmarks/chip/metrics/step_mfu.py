"""Device: the whole step's share of the chips' peak in the traced window.

Model FLOPs of the steps completed in the traced window
(``arch.model_flops_per_token``: recomputation and the embedding gather do
not count) over the window's length times chips times the bf16 peak, in %.
It bounds every kernel's share from above: a kernel taken off the path
leaves its roofline silent, and this still counts.
"""
import devtrace as tr

LAYER = "device"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(r):
    lo, hi = tr.window(r.events)
    if hi <= lo or r.steps <= 0:
        return None
    return 100.0 * r.model_flops_per_step * r.steps / (
        (hi - lo) / 1e9 * r.chips * r.peak["bf16_flops_per_s"])
