"""Paged LUT decode kernel (kernels/paged_decode.py): least time of its
work over its summed event time, in %."""
from bench import readers

LAYER = "kernels (kernels/paged_decode.py, kernels/paged_prefill.py)"
UNIT = "%"
MOVES = "output_tok_s"


def read(run):
    return readers.decode_roofline(run)
