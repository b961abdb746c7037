"""Model: device time a step of the ``vocab`` blocks.

Ops whose HLO metadata names the ``vocab`` scope: the embedding gather and the
final norm, logits and vocabulary mask (``models/transformer.py`` ``forward``)
and the cross-entropy (``train/step.py`` ``loss_fn``), forward and backward,
by ``scopes.py``'s rule, mean over cores, in ms.  With tied embeddings both
ends share the table and its gradient.
"""
import scopes

LAYER = "model"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(r):
    return scopes.ms(r, scopes.block, "vocab")
