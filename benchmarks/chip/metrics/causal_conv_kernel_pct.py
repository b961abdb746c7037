"""Model: the share of the causal convolutions' device time that Mosaic
kernels take.

Of the ops that ``causal_conv_ms`` charges (an op-name part
``causal_conv`` once the wrappers ``jvp(`` / ``transpose(`` / ``)`` are
stripped; this file keeps the rule as its own literal), the device time of
those whose compiled HLO instruction is a ``custom-call`` with
``custom_call_target="tpu_custom_call"`` (``models/ssm.py`` runs the convs
through the Pallas kernels of ``kernels/causal_conv`` on the TPU), over the
time of them all; each a union of op intervals as ``devtrace.class_ns``
takes it, the share per core, mean over cores, in %.  A program whose convs
have no such kernel reads 0; one with no ``causal_conv`` scope, nothing.
"""
import re

import devtrace as tr
import scopes

LAYER = "model"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"

_WRAPPER_RE = re.compile(r"^(?:jvp\(|transpose\()+")
_KERNEL_RE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*\bcustom-call\(.*'
    r'\bcustom_call_target="tpu_custom_call"', re.M)


def _is_conv(op_name: str) -> bool:
    return any(_WRAPPER_RE.sub("", part).rstrip(")") == "causal_conv"
               for part in op_name.split("/"))


def read(r):
    names = scopes.op_names(r.hlo_text)
    conv = {n for n, op in names.items() if _is_conv(op)}
    if not conv or r.steps <= 0 or not r.events["devices"]:
        return None
    kernels = set(_KERNEL_RE.findall(r.hlo_text))
    every = {n: "conv" for n in conv if r.classes.get(n) != "control"}
    kern = {n: "conv" for n in every if n in kernels}
    total = tr.class_ns(r.events, every, "conv")
    part = tr.class_ns(r.events, kern, "conv")
    shares = [part[d] / t for d, t in total.items() if t > 0]
    return 100.0 * sum(shares) / len(shares) if shares else 0.0
