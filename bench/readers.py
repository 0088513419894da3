"""Arithmetic the per-layer metric files share: each file under
``bench/metrics/`` names its layer, unit and the end-to-end metric it
moves, and calls one of these on the run (``run["record"]``, the host
clock's record of the window; ``run["trace"]``, the reduced device trace
or None; ``run["conf"]``, ``run["peaks"]``). A reader that finds nothing to
read returns None, and the metric is left out of the result line."""
from __future__ import annotations

from bench.yardstick import work


def batch_occupancy(run):
    """Decoding slots per decode micro-step over the slots, in the window:
    decode tokens delivered over micro-steps run times slots."""
    r = run["record"]
    if not r["decode_steps"]:
        return None
    return r["decode_tokens"] / (r["decode_steps"] * r["slots"])


def decode_step_ms(run):
    """Device time of the decode programs (plain and run-ahead) over the
    decode micro-steps they ran, in the traced window."""
    tr = run["trace"]
    if tr is None:
        return None
    p = tr["programs"]
    steps = p["decode"]["n"] + p["runahead"]["micro_steps"]
    if not steps:
        return None
    return 1e3 * (p["decode"]["s"] + p["runahead"]["s"]) / steps


def device_idle(run):
    tr = run["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]


def decode_roofline(run):
    """Least time of the paged decode kernel's work (from the live length
    of every decode token delivered in the window) over its summed event
    time, in %."""
    tr = run["trace"]
    k = tr and tr["kernels"].get("paged_decode")
    ctx = run["record"]["decode_contexts"]
    if not k or not k["s"] or not len(ctx):
        return None
    f, b = work.decode_attention(run["conf"]["model"],
                                 run["conf"]["serving"]["keys"], ctx)
    share, bound = work.roofline_share(f, b, k["s"], run["peaks"])
    run.setdefault("bounds", {})["paged_decode"] = bound
    return share


def mfu(run):
    """Model operations of the tokens processed in the traced window
    (decode tokens and prefill chunks) over the window's length times the
    chip's peak, in %."""
    tr = run["trace"]
    r = run["record"]
    if tr is None or tr["window_s"] <= 0 or r["chunks"] is None:
        return None
    if not len(r["decode_contexts"]) and not r["chunks"]:
        return None
    f = work.model_flops(run["conf"]["model"], r["decode_contexts"],
                         r["chunks"])
    return 100.0 * f / (tr["window_s"] * run["peaks"]["bf16_flops"])
