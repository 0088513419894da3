"""Device time of the decode programs (plain and run-ahead) per decode
micro-step, from the device trace."""
from bench import readers

LAYER = "engine step (serve/core.py, models/transformer.py)"
UNIT = "ms"
MOVES = "output_tok_s"


def read(run):
    return readers.decode_step_ms(run)
