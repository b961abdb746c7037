"""Collectives: operand bytes a step moves, per device.

The result-shape bytes of every collective op of the compiled step's HLO
(``hlo.collective_bytes``: ``launch/analysis.py``'s arithmetic, each op
weighted by its loop trip count), in MB (1e6 bytes).  A count fixed by the
program, not a time.
"""
import hlo

LAYER = "collectives"
UNIT = "MB"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(r):
    total = sum(hlo.collective_bytes(r.hlo_text).values())
    return total / 1e6 if total > 0 else None
