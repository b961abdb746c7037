"""Kernels (XLA's fusions of ``models.*``, ``train.step``, ``optim.adamw``):
the matmuls' share of their roofline.

The matmul FLOPs the step executes (``arch.executed_matmul_flops``:
recomputation included, full S x S attention products as the program
computes them), per chip, over the device time of the ops that the compiled
HLO classes as matmuls (a convolution or dot, or a fusion holding one) times
the chip's bf16 peak, in %.  The classes come from the HLO, never from op
names.
"""
import devtrace as tr

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(r):
    per_dev = tr.class_ns(r.events, r.classes, "matmul")
    t = sum(per_dev.values()) / max(len(per_dev), 1) / 1e9
    if t <= 0 or r.steps <= 0:
        return None
    flops = r.matmul_flops_per_step * r.steps / r.chips
    return 100.0 * flops / (t * r.peak["bf16_flops_per_s"])
