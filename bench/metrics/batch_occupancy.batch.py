"""Decoding slots per decode micro-step over the slots, from the engine's
step counter and the token events of the window."""
from bench import readers

LAYER = "scheduler (serve/scheduler.py)"
UNIT = "share"
MOVES = "output_tok_s"


def read(run):
    return readers.batch_occupancy(run)
