"""Readings of the engine's spans and named programs in one traced run of
a cell (not part of a benchmark run): the run is the benchmark's own
``--trace 1`` run, and the trace it writes is also reduced by
:mod:`bench.trace.engine` before the run removes it.

    python3 bench/engine_readings.py --workload yi-9b.decode-long \
        --seed 7 --seconds 20

Prints one JSON line: the run's result (``correct``, the per-layer
metrics, the breakdown), the engine reduction (self time and count per
span, idle time per span with ``engine.none``, device time per named
program) and its :func:`bench.trace.engine.readings`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import run  # noqa: E402
from bench.trace import engine  # noqa: E402
from bench.trace import reduce as treduce  # noqa: E402


def traced(args, root: str = ROOT, require_tpu: bool = True) -> dict:
    """One traced run of ``args.workload``; its result, the engine
    reduction of its trace and the readings of that reduction."""
    got = {}
    device_reduce = treduce.reduce_dir

    def reduce_dir(trace_dir, layers, expect=()):
        # expect: the kernels whose tokens the window delivered
        got["engine"] = engine.reduce_dir(
            trace_dir, steps=bool(expect), chunks="paged_prefill" in expect)
        return device_reduce(trace_dir, layers, expect)

    def window(sess, rec):
        got["decode_steps"] = rec["decode_steps"]

    treduce.reduce_dir = reduce_dir
    try:
        res = run.run(argparse.Namespace(**{**vars(args), "trace": 1}),
                      root, require_tpu, hooks={"window": window})
    finally:
        treduce.reduce_dir = device_reduce
    red = got["engine"]
    return {"result": res, "engine": red,
            "readings": engine.readings(red, got["decode_steps"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    print(json.dumps(traced(args)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
