"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

A trace is first flattened to events, ``(plane, line, name, start_ns,
duration_ns)``, by :func:`events_from_xplane`; everything else works on
that list, so the tests check the arithmetic on events laid out by hand.

On a TPU v5e the device plane is ``/device:TPU:<n>``: its ``XLA Ops`` line
holds one event per operation run, named by the operation's HLO text
(``%polar_paged_decode_grouped.7 = (f32[64,4,8,128]...) custom-call(...)``),
and its ``XLA Modules`` line one event per program run
(``jit__lambda(<hash>)``). An operation that holds others, such as the
``while`` loop of a scan over layers, is an event of its own around theirs.
A kernel's custom call is found by the names :data:`KERNELS` gives (see
:func:`_kernel_label`) and renamed ``<kernel>:<instruction name>``. The
benchmark's own host spans (``bench.*``, from
``jax.profiler.TraceAnnotation``) are on the host plane ``/host:CPU``;
``bench.window`` brackets the measured window.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

#: kernel -> (its jitted wrapper, its kernel function). The compiler names
#: the Mosaic custom call after the wrapper (``polar_paged_decode_grouped.7``)
#: and gives it no op_name; the trace's events carry no other name of it
KERNELS = {"paged_decode": ("polar_paged_decode_grouped",
                            "_paged_attn_kernel"),
           "paged_prefill": ("polar_paged_prefill_grouped",
                             "_paged_prefill_kernel")}
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


def events_from_xplane(path: str) -> list[tuple]:
    """Every event of the device planes' ops and modules lines, and every
    ``bench.*`` span of the host plane. An operation is named by its HLO
    instruction, a kernel's as :func:`_kernel_label` says."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        dev = plane.name.startswith("/device:")
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not dev and not ev.name.startswith("bench."):
                    continue
                name = ev.name
                if dev and line.name == OPS_LINE:
                    name = _kernel_label(name)
                out.append((plane.name, line.name, name,
                            int(ev.start_ns), int(ev.duration_ns)))
    return out


def op_name(text: str) -> str:
    """The HLO instruction name of an ops-line event: the trace names an
    operation by its HLO text, ``%<name> = <shape> <opcode>(...)``."""
    return text[1:].split(" ", 1)[0] if text.startswith("%") else text


def _kernel_label(text: str) -> str:
    """``<kernel>:<instruction name>`` for the custom call of one of
    :data:`KERNELS`, else the instruction name. It is the kernel's when
    its instruction is named after the wrapper, or its text holds the
    kernel's function. (The other instructions that the wrapper leaves
    are named ``pallas_call.<n>`` and ``jit_<wrapper>_.<n>``.)"""
    name = op_name(text)
    for k, (wrapper, fn) in KERNELS.items():
        if name.startswith(wrapper + ".") or fn in text:
            return f"{k}:{name}"
    return name


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _inside(start, dur, lo, hi):
    s, e = max(start, lo), min(start + dur, hi)
    return (s, e) if e > s else None


def reduce(events: list, layers: int, kernels: dict = KERNELS,
           expect=()) -> dict:
    """Busy and idle time of the device over the window, kernel time, the
    time of each kind of program, the operations that took most time and
    the idle gaps by what the host was doing. ``layers``: the model's
    layer count, the number of kernel calls in one step. A window with no
    device operation, or with no event of a kernel in ``expect`` (one the
    window is known to have run), is an error: the trace's names are not
    the ones this reduction reads."""
    win = [(s, s + d) for p, l, n, s, d in events if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    lo, hi = win[0]
    devices = sorted({p for p, *_ in events if p.startswith("/device:")})
    host = sorted((s, s + d, n) for p, l, n, s, d in events
                  if not p.startswith("/device:") and n != WINDOW_SPAN)
    starts = [h[0] for h in host]
    busy_total, gaps = 0.0, defaultdict(float)
    op_time = defaultdict(float)
    kern = {k: {"s": 0.0, "n": 0} for k in kernels}
    ops_by_dev = defaultdict(list)
    for p, l, n, s, d in events:
        iv = _inside(s, d, lo, hi) if l == OPS_LINE else None
        if iv is not None:
            ops_by_dev[p].append(iv)
    for p, n, s, d in _leaves(events):
        iv = _inside(s, d, lo, hi)
        if iv is None:
            continue
        op_time[n] += (iv[1] - iv[0]) * 1e-9
        for k in kernels:
            if n.startswith(k + ":"):
                kern[k]["s"] += (iv[1] - iv[0]) * 1e-9
                kern[k]["n"] += 1
    if not op_time:
        raise ValueError(f"no event on a device's {OPS_LINE!r} line in the "
                         f"window; device planes: {devices}")
    missing = [k for k in expect if not kern[k]["n"]]
    if missing:
        raise ValueError(f"the window ran {missing} but no operation in it "
                         f"carries a name of {[kernels[k] for k in missing]}")
    for p in devices:
        merged = _union(ops_by_dev[p])
        busy_total += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                _attribute(host, starts, a, b, gaps)
    n_dev = max(len(devices), 1)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total / n_dev,
        "kernels": kern,
        "programs": _programs(events, lo, hi, kernels, layers),
        "top_ops": sorted(([n, t] for n, t in op_time.items()),
                          key=lambda x: -x[1])[:10],
        "idle_gaps": sorted(([n, t] for n, t in gaps.items()),
                            key=lambda x: -x[1])[:10],
    }


def _leaves(events) -> list[tuple]:
    """(device, name, start, duration) of every ops-line event that holds
    no other: a loop's event spans the operations run inside it, which
    are events of their own, and counting both would count their time
    twice."""
    ops = sorted(((p, s, -d, n) for p, l, n, s, d in events
                  if l == OPS_LINE))
    out = []
    for i, (p, s, nd, n) in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt[0] == p and nxt[1] < s - nd:
            continue
        out.append((p, n, s, -nd))
    return out


def _attribute(host, starts, a, b, gaps) -> None:
    """Split the idle interval [a, b) over the benchmark spans running on
    the host meanwhile (the driver's spans do not nest, bar the window's);
    time in none of them is ``bench.none``."""
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    covered = 0
    while i < len(host) and host[i][0] < b:
        s, e, n = host[i]
        o = min(e, b) - max(s, a)
        if o > 0:
            gaps[n] += o * 1e-9
            covered += o
        i += 1
    gaps["bench.none"] += (b - a - covered) * 1e-9


def _programs(events, lo, hi, kernels, layers) -> dict:
    """Device time of each kind of program run in the window, told apart
    by what runs inside it: a decode step runs the decode kernel once per
    layer, a run-ahead scan several times that, a prefill chunk the
    prefill kernel."""
    mods = sorted((s, s + d) for p, l, n, s, d in events
                  if l == MODULES_LINE and _inside(s, d, lo, hi))
    kev = sorted((s, k) for p, l, n, s, d in events if l == OPS_LINE
                 for k in kernels if n.startswith(k + ":"))
    per = []
    j = 0
    for s, e in mods:
        while j < len(kev) and kev[j][0] < s:
            j += 1
        c = defaultdict(int)
        i = j
        while i < len(kev) and kev[i][0] < e:
            c[kev[i][1]] += 1
            i += 1
        per.append((s, e, c["paged_decode"], c["paged_prefill"]))
    out = {"decode": {"s": 0.0, "n": 0},
           "runahead": {"s": 0.0, "n": 0, "micro_steps": 0},
           "prefill": {"s": 0.0, "n": 0}, "other": {"s": 0.0, "n": 0}}
    for s, e, nd, npf in per:
        iv = _inside(s, e - s, lo, hi)
        t = (iv[1] - iv[0]) * 1e-9
        if npf:
            kind = "prefill"
        elif nd == layers:
            kind = "decode"
        elif nd:
            kind = "runahead"
            out[kind]["micro_steps"] += nd // layers
        else:
            kind = "other"
        out[kind]["s"] += t
        out[kind]["n"] += 1
    return out


def reduce_dir(trace_dir: str, layers: int, expect=()) -> dict:
    pbs = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                    recursive=True)
    if not pbs:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(events_from_xplane(pbs[0]), layers, expect=expect)
