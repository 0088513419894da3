"""Reduction of a profiler trace (``.xplane.pb``) to what the engine's own
spans and named programs say: the device's idle time put down to the
engine code the host was running, the engine's host time, and the device
time of each named program.

The engine (``serve/core.py``) runs its work inside
``jax.profiler.TraceAnnotation`` spans named ``engine.*``, on the host
thread that holds ``bench.window`` and on the profiler's clock: each
``step()`` is an ``engine.step``, and inside it ``engine.admit``,
``engine.prefill_chunk``, ``engine.decode``, ``engine.runahead.land``,
``engine.dispatch`` (one call of a jitted program, its arguments
included) and ``engine.sync`` (the host blocked on the device) nest by
containment. A span of ``engine.dispatch`` or ``engine.sync`` is labelled
with its argument, ``engine.dispatch:prefill_chunk``,
``engine.sync:decode_tokens``. The device's ``XLA Modules`` line names
each program run ``jit_<name>(<hash>)``.

Events are flattened to ``(plane, line, label, start_ns, duration_ns)``
as in :mod:`bench.trace.reduce`, whose idle intervals this reduction
takes, so the tests lay events out by hand. :func:`readings` turns a
reduction into three numbers per window, and ``bench/engine_readings.py``
prints them for one traced run of a cell.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

from bench.trace.reduce import (
    MODULES_LINE, OPS_LINE, WINDOW_SPAN, _inside, _union,
)

PREFIX = "engine."
SYNC = "engine.sync"
NONE = "engine.none"
#: the argument that tells two spans of one name apart
LABEL_ARGS = ("program", "what")
#: the engine's device programs, as the modules line names them
PROGRAMS = ("jit_decode_step", "jit_runahead_horizon", "jit_prefill_chunk",
            "jit_prefill", "jit_verify", "jit_copy_pages")
CHUNK_PROGRAM = "jit_prefill_chunk"


def label(name: str, stats) -> str:
    """A span's name, with the argument of :data:`LABEL_ARGS` it carries."""
    for k, v in stats:
        if k in LABEL_ARGS:
            return f"{name}:{v}"
    return name


def program(name: str) -> str:
    """A modules-line event's program, without its hash."""
    return name.split("(", 1)[0]


def events_from_xplane(path: str) -> list[tuple]:
    """Every event of the device planes' ops and modules lines (programs
    by :func:`program`), and the window and ``engine.*`` spans of the host
    planes (spans by :func:`label`)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        dev = plane.name.startswith("/device:")
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if dev and line.name == MODULES_LINE:
                    name = program(name)
                elif not dev:
                    if name.startswith(PREFIX):
                        name = label(name, ev.stats)
                    elif name != WINDOW_SPAN:
                        continue
                out.append((plane.name, line.name, name,
                            int(ev.start_ns), int(ev.duration_ns)))
    return out


def _segments(spans) -> list[tuple]:
    """(start, end, label) of the innermost span at each instant covered
    by one: spans nest by containment (a span that outlives the one around
    it is cut at its end)."""
    out, stack, t = [], [], 0
    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            if end > t:
                out.append((t, end, top))
            t = max(t, end)
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        t = s
        stack.append((min(e, stack[-1][0]) if stack else e, n))
    while stack:
        end, top = stack.pop()
        if end > t:
            out.append((t, end, top))
        t = max(t, end)
    return out


def reduce(events: list, steps: bool = False, chunks: bool = False):
    """Self time and count of each engine span, the device's idle time
    booked to the innermost engine span then open (``engine.none`` when
    none is), and the device time and count of each program, over the
    window. ``steps``: the window ran engine steps; ``chunks``: it ran
    prefill chunks. Then a trace with no engine span, or no
    ``jit_prefill_chunk`` program, is an error. A trace with neither an
    engine span nor any of :data:`PROGRAMS` comes from a program that
    has neither yet: None."""
    win = [(p, l, s, s + d) for p, l, n, s, d in events if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    wplane, wline, lo, hi = win[0]
    spans = []
    for p, l, n, s, d in events:
        iv = (_inside(s, d, lo, hi) if (p, l) == (wplane, wline)
              and n.startswith(PREFIX) else None)
        if iv is not None:
            spans.append((iv[0], iv[1], n))
    progs = defaultdict(lambda: {"s": 0.0, "n": 0})
    ops_by_dev = defaultdict(list)
    for p, l, n, s, d in events:
        iv = _inside(s, d, lo, hi) if p.startswith("/device:") else None
        if iv is None:
            continue
        if l == MODULES_LINE:
            progs[n]["s"] += (iv[1] - iv[0]) * 1e-9
            progs[n]["n"] += 1
        elif l == OPS_LINE:
            ops_by_dev[p].append(iv)
    if not spans and not any(n in progs for n in PROGRAMS):
        return None
    if steps and not spans:
        raise ValueError(f"the window ran engine steps but no {PREFIX}* "
                         f"span is on the thread of {WINDOW_SPAN}")
    if chunks and CHUNK_PROGRAM not in progs:
        raise ValueError(f"the window ran prefill chunks but no program "
                         f"is named {CHUNK_PROGRAM}; programs: "
                         f"{sorted(progs)}")
    segs = _segments(spans)
    own = defaultdict(lambda: {"s": 0.0, "n": 0})
    for s, e, n in segs:
        own[n]["s"] += (e - s) * 1e-9
    for s, e, n in spans:
        own[n]["n"] += 1
    idle = defaultdict(float)
    devices = sorted(ops_by_dev)
    for p in devices:
        busy = [x for iv in _union(ops_by_dev[p]) for x in iv]
        edges = [lo] + busy + [hi]
        _book(list(zip(edges[::2], edges[1::2])), segs, idle)
    return {
        "window_s": (hi - lo) * 1e-9,
        "devices": len(devices),
        "spans": dict(own),
        "idle": sorted(([n, t] for n, t in idle.items()),
                       key=lambda x: -x[1]),
        "programs": dict(progs),
    }


def _book(gaps, segs, idle) -> None:
    """Book each idle interval ``[a, b)`` to the innermost spans that
    cover it (``segs``, sorted and disjoint); what none covers is
    :data:`NONE`."""
    j = 0
    for a, b in gaps:
        if b <= a:
            continue
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        covered, k = 0, j
        while k < len(segs) and segs[k][0] < b:
            o = min(segs[k][1], b) - max(segs[k][0], a)
            if o > 0:
                idle[segs[k][2]] += o * 1e-9
                covered += o
            k += 1
        if b - a > covered:
            idle[NONE] += (b - a - covered) * 1e-9


def reduce_dir(trace_dir: str, steps: bool = False, chunks: bool = False):
    pbs = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                    recursive=True)
    if not pbs:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(events_from_xplane(pbs[0]), steps, chunks)


def _not_sync(label: str) -> bool:
    return label.split(":", 1)[0] != SYNC


def readings(red, decode_steps: int) -> dict:
    """Of a reduction (None reads nothing): ``engine_idle``, the share of
    the window in which the device ran nothing while the host was in an
    engine span other than ``engine.sync`` (the device waiting on the
    engine's own host work); ``engine_host_ms``, the self time of the
    engine's spans other than ``engine.sync`` over the window's decode
    micro-steps; ``prefill_chunk_ms``, the device time of the
    ``jit_prefill_chunk`` programs over their number. A number with
    nothing to read is None."""
    out = dict.fromkeys(("engine_idle", "engine_host_ms",
                         "prefill_chunk_ms"))
    if red is None:
        return out
    if red["devices"] and red["window_s"] > 0:
        t = sum(s for n, s in red["idle"] if n != NONE and _not_sync(n))
        out["engine_idle"] = t / (red["window_s"] * red["devices"])
    if decode_steps:
        t = sum(v["s"] for n, v in red["spans"].items() if _not_sync(n))
        out["engine_host_ms"] = 1e3 * t / decode_steps
    p = red["programs"].get(CHUNK_PROGRAM)
    if p and p["n"]:
        out["prefill_chunk_ms"] = 1e3 * p["s"] / p["n"]
    return out
