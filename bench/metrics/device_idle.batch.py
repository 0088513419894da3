"""Share of the traced window in which no operation ran on the device."""
from bench import readers

LAYER = "device"
UNIT = "share"
MOVES = "output_tok_s"


def read(run):
    return readers.device_idle(run)
