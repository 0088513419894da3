"""The benchmark's real-time driver: sends the generator's requests into the
engine when they fall due on the host's clock, steps the engine, and times
every request on the client's side.

``EngineCore`` keeps a simulated clock that jumps to the next arrival when
idle. Here every request is handed over with ``arrival_time`` equal to the
engine's clock at the moment it falls due, so the engine never jumps, and
no latency is read from the engine's clock: each is taken on the host's
monotonic clock from the request's due time to the moment ``step()``
returned the event.

Every call into a layer runs inside a ``jax.profiler.TraceAnnotation``
named ``bench.<what>``, so a device trace can say what the host was doing
in each idle gap.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import numpy as np

clock = time.monotonic


@dataclasses.dataclass
class Rec:
    """What the client saw of one request (host seconds)."""
    rid: int
    prompt_len: int
    max_new: int
    client: int
    due: float
    sent: Optional[float] = None
    admit: Optional[float] = None
    toks: list = dataclasses.field(default_factory=list)
    done: Optional[float] = None
    failed: Optional[str] = None


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


class Session:
    """One serving session of the engine under one traffic mix.

    ``closed_streams``: per client the requests it sends in order (closed
    loop); ``open_specs``: requests with due times relative to
    :meth:`open_window` (open loop). Exactly one of the two is given."""

    def __init__(self, core, *, closed_streams=None, open_specs=None):
        from repro.serve.scheduler import Request

        self._Request = Request
        self.core = core
        self.recs: dict[int, Rec] = {}
        self.reqs: dict[int, object] = {}
        self._streams = [list(s) for s in closed_streams or []]
        self._open = list(open_specs or [])
        self._due: list = []            # (due host time, spec), due order
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.preempts = 0
        if closed_streams is not None:
            now = clock()
            for s in self._streams:
                self._due.append((now, s.pop(0)))

    # --- the window -------------------------------------------------------

    def open_window(self) -> None:
        """Open the measured window: open-loop due times count from here;
        the engine's counters are read now and at the close."""
        self.t_open = clock()
        self.counters_open = self.counters()
        for spec in self._open:
            self._due.append((self.t_open + spec.due, spec))
        self._open = []

    def close_window(self) -> None:
        self.t_close = clock()
        self.counters_close = self.counters()

    def counters(self) -> dict:
        c = self.core
        return {"decode_steps": c.decode_steps,
                "prefill_tokens": c.prefill_computed,
                "horizons": c.runahead_horizons}

    # --- the loop ---------------------------------------------------------

    def _send_due(self, now: float) -> None:
        while self._due and self._due[0][0] <= now:
            due, spec = self._due.pop(0)
            req = self._Request(rid=spec.rid, prompt=spec.prompt,
                                max_new_tokens=spec.max_new_tokens,
                                arrival_time=self.core.clock)
            self.recs[spec.rid] = Rec(rid=spec.rid,
                                      prompt_len=len(spec.prompt),
                                      max_new=spec.max_new_tokens,
                                      client=spec.client, due=due)
            self.reqs[spec.rid] = req
            with _span("submit"):
                self.core.add_request(req)
            self.recs[spec.rid].sent = clock()

    def _observe(self, events, t: float) -> None:
        for ev in events:
            rec = self.recs.get(ev.rid)
            if rec is None:
                continue
            if ev.kind == "admit":
                if rec.admit is None:
                    rec.admit = t
            elif ev.kind in ("first_token", "token"):
                rec.toks.append(t)
            elif ev.kind == "finish":
                rec.done = t
                if rec.client >= 0 and self._streams[rec.client]:
                    self._due.append((t, self._streams[rec.client].pop(0)))
                elif rec.client >= 0:
                    raise RuntimeError(
                        f"client {rec.client} ran out of requests: raise "
                        "the mix's requests_per_client")
            elif ev.kind == "preempt":
                self.preempts += 1
                if ev.token is not None and rec.toks:
                    rec.toks.pop()      # the retracted token
            else:                       # reject, shed, cancel
                rec.failed = ev.kind
                rec.done = t

    def run(self, until: Callable[[float], bool]) -> None:
        """Step until ``until(now)`` holds (checked between steps); with
        no work and nothing due, wait for the next due time."""
        core = self.core
        while True:
            now = clock()
            if until(now):
                return
            self._send_due(now)
            if core.has_work:
                with _span("step"):
                    events = core.step()
                self._observe(events, clock())
            else:
                # idle: nothing admitted, nothing due yet
                nxt = self._due[0][0] if self._due else now + 0.005
                with _span("wait_arrival"):
                    time.sleep(max(0.0, min(nxt - clock(), 0.005)))

    def finished(self) -> list:
        """Requests the engine finished, with their served tokens."""
        return [self.reqs[r.rid] for r in self.recs.values()
                if r.done is not None and r.failed is None]

    def served(self) -> list:
        """Requests the engine served tokens for, finished or not, with
        the tokens served so far."""
        return [self.reqs[r.rid] for r in self.recs.values()
                if r.failed is None and self.reqs[r.rid].out_tokens]


# ---------------------------------------------------------------------------
# What the client saw, reduced to numbers (host clock)
# ---------------------------------------------------------------------------


def pct(values, p: float) -> Optional[float]:
    """Nearest-rank percentile: the value at 1-based rank ceil(p/100 * n);
    None for no values."""
    vals = sorted(values)
    if not vals:
        return None
    rank = min(max(int(np.ceil(p / 100 * len(vals))), 1), len(vals))
    return float(vals[rank - 1])


def window_record(s: Session) -> dict:
    """The run record the end-to-end metrics and the host-clock per-layer
    readers read."""
    t0, t1 = s.t_open, s.t_close
    win = t1 - t0
    recs = list(s.recs.values())
    due_in = [r for r in recs if t0 <= r.due < t1]
    toks_in = sum(1 for r in recs for t in r.toks if t0 <= t < t1)
    decode_toks = sum(1 for r in recs for i, t in enumerate(r.toks)
                      if i > 0 and t0 <= t < t1)

    def age(t):
        return (t if t is not None and t < t1 else t1)

    ttft = [age(r.toks[0] if r.toks else None) - r.due for r in due_in]
    qwait = [age(r.admit) - r.due for r in due_in]
    late = [r.sent - r.due for r in due_in if r.sent is not None]
    itl = [b - a for r in recs for a, b in zip(r.toks, r.toks[1:])
           if t0 <= b < t1]
    c0, c1 = s.counters_open, s.counters_close
    decode_ctx = np.array([r.prompt_len + i for r in recs
                           for i, t in enumerate(r.toks)
                           if i > 0 and t0 <= t < t1], np.int64)
    return {
        "window_s": win,
        "tokens": toks_in,
        "decode_tokens": decode_toks,
        "requests_due": len(due_in),
        "requests_sent": sum(1 for r in recs if r.sent is not None
                             and t0 <= r.sent < t1),
        "requests_done": sum(1 for r in recs if r.done is not None
                             and t0 <= r.done < t1 and r.failed is None),
        "failed": sum(1 for r in recs if r.failed is not None),
        "preempts": s.preempts,
        "ttft_s": ttft, "queue_wait_s": qwait, "gen_late_s": late,
        "itl_s": itl,
        "decode_steps": c1["decode_steps"] - c0["decode_steps"],
        "prefill_tokens": c1["prefill_tokens"] - c0["prefill_tokens"],
        "horizons": c1["horizons"] - c0["horizons"],
        "slots": s.core.layout.slots,
        "decode_contexts": decode_ctx,
        "chunks": (None if s.preempts else
                   _chunks(recs, s.core.prefill_chunk,
                           c0["prefill_tokens"], c1["prefill_tokens"])),
    }


def _chunks(recs, chunk: int, lo: int, hi: int) -> list:
    """(start, length) of every prefill chunk the engine ran while its
    prefill counter went from ``lo`` to ``hi``. The engine prefills
    admissions one at a time, oldest first, front to back in chunks of
    ``chunk`` tokens, so the sequence of chunks follows from the order of
    the admit events (with no preemption, which re-prefills)."""
    out, cum = [], 0
    for r in sorted((r for r in recs if r.admit is not None),
                    key=lambda r: r.admit):
        for off in range(0, r.prompt_len, chunk):
            n = min(chunk, r.prompt_len - off)
            if lo <= cum < hi:
                out.append((off, n))
            cum += n
    return out
