"""Model operations of every token processed in the traced window over the
window times the chip's bf16 peak, in %."""
from bench import readers

LAYER = "model step (models/transformer.py)"
UNIT = "%"
MOVES = "output_tok_s"


def read(run):
    return readers.mfu(run)
