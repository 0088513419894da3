"""The knee of an open-loop cell (not part of a benchmark run): runs the
cell at each offered rate and prints what says whether the system kept up,
so the highest rate with no backlog growing over the window can be read.

    python3 bench/sweep.py --workload granite-8b.chat-open --seconds 20 \
        --rates 4,8,12,16 --seed 5

A backlog grows when the queue wait of the requests due in the last third
of the window exceeds that of the first third, and requests finish slower
than they arrive.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import driver, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    for rate in (float(r) for r in args.rates.split(",")):
        got = {}

        def window(s, rec, got=got):
            t0, t1 = s.t_open, s.t_close
            third = (t1 - t0) / 3
            due = [r for r in s.recs.values() if t0 <= r.due < t1]

            def wait(lo, hi):
                return driver.pct([(r.admit if r.admit is not None and
                                    r.admit < t1 else t1) - r.due
                                   for r in due if lo <= r.due < hi], 50)

            got.update(
                first_wait_s=wait(t0, t0 + third),
                last_wait_s=wait(t1 - third, t1),
                due=rec["requests_due"], done=rec["requests_done"],
                ttft_p50_s=driver.pct(rec["ttft_s"], 50),
                ttft_p95_s=driver.pct(rec["ttft_s"], 95),
                itl_p95_s=driver.pct(rec["itl_s"], 95),
                tok_s=rec["tokens"] / rec["window_s"],
                decode_steps=rec["decode_steps"])

        ns = argparse.Namespace(workload=args.workload, seed=args.seed,
                                seconds=args.seconds, trace=0, rate=rate)
        res = run.run(ns, hooks={"window": window})
        print(json.dumps({"rate": rate, **got,
                          "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
