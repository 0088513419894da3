"""Readings that set the limit of ``correct`` for a cell (not part of a
benchmark run): on each seed, one run of the cell as the benchmark makes
it, with the widest gap of the served tokens (the program's reading) and
the control's widest gap on the same prompts and served tokens (the
reference in float8 put in the program's place).

    python3 bench/readings.py --workload yi-9b.decode-long \
        --seconds 30 --seeds 101,102,103 [--control-seeds 101,102,103]

Prints one JSON line per seed and a summary line: the lower reading (the
largest program gap) and the upper reading (the smallest control gap).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import check, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rate", type=float, default=None)
    args = ap.parse_args(argv)
    ctrl_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    prog, ctrl = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        got = {}

        def checked(ref, params, conf, reqs, pad_to, seed=seed, got=got):
            if seed in ctrl_seeds:
                got["control"] = check.control_gap(ref, params, conf,
                                                   reqs, pad_to)[0]

        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0,
                                rate=args.rate)
        res = run.run(ns, hooks={"checked": checked})
        gap = res["checks"]["widest_logit_gap"]["value"]
        prog.append(gap)
        if "control" in got:
            ctrl.append(got["control"])
        print(json.dumps({"seed": seed, "program_gap": gap,
                          "control_gap": got.get("control"),
                          "metrics": res["metrics"],
                          "checks": res["checks"]}), flush=True)
    print(json.dumps({"workload": args.workload, "lower": max(prog),
                      "program_gaps": prog, "upper": min(ctrl, default=None),
                      "control_gaps": ctrl}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
