"""The engine-span reduction (``bench/trace/engine.py``): device idle time
booked to the innermost engine span, self times, named programs and the
three readings of them, on events laid out by hand as a TPU trace lays
them out, the labels read back from a real profiler trace, and
``bench/engine_readings.py``, which reduces a run's trace."""
import argparse
import glob
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import engine_readings  # noqa: E402
from bench.trace import engine as eng  # noqa: E402
from bench.trace import reduce as tr  # noqa: E402

DEV, HOST, THREAD = "/device:TPU:0", "/host:CPU", "python3"
OPS, MODS = tr.OPS_LINE, tr.MODULES_LINE
MS = 1_000_000


def _span(name, start, end, line=THREAD):
    return (HOST, line, name, start * MS, (end - start) * MS)


def _events():
    """Window 0-100 ms; the device busy 10-30 (a decode step), 40-60 (a
    prefill chunk) and 70-95 (a run-ahead horizon). The first engine step
    (5-35) dispatches the decode step and waits on its tokens; the second
    (38-98) runs a prefill chunk, then decodes and dispatches a horizon."""
    return [
        _span("bench.window", 0, 100),
        _span("engine.step", 5, 35),
        _span("engine.dispatch:decode_step", 6, 12),
        _span("engine.sync:decode_tokens", 12, 31),
        _span("engine.step", 38, 98),
        _span("engine.prefill_chunk", 38, 65),
        _span("engine.dispatch:prefill_chunk", 38, 41),
        _span("engine.sync:chunk_barrier", 41, 61),
        _span("engine.decode", 66, 72),
        _span("engine.dispatch:runahead_horizon", 67, 71),
        # another thread's span and a span after the window: not read
        _span("engine.step", 0, 100, line="worker"),
        _span("engine.step", 101, 104),
        (DEV, MODS, "jit_decode_step", 10 * MS, 20 * MS),
        (DEV, OPS, "fusion.1", 10 * MS, 20 * MS),
        (DEV, MODS, "jit_prefill_chunk", 40 * MS, 20 * MS),
        (DEV, OPS, "fusion.2", 40 * MS, 20 * MS),
        (DEV, MODS, "jit_runahead_horizon", 70 * MS, 25 * MS),
        (DEV, OPS, "while.1", 70 * MS, 25 * MS),
        (DEV, OPS, "fusion.3", 71 * MS, 20 * MS),
    ]


def test_idle_is_booked_to_the_innermost_span():
    got = eng.reduce(_events(), steps=True, chunks=True)
    idle = dict(got["idle"])
    # idle 0-10: 0-5 no span, 5-6 the step, 6-10 the decode dispatch;
    # 30-40: 30-31 the sync, 31-35 the step, 35-38 none, 38-40 the chunk's
    # dispatch; 60-70: 60-61 the barrier, 61-65 the chunk, 65-66 the step,
    # 66-67 the decode, 67-70 the horizon's dispatch; 95-100: 95-98 the
    # step, 98-100 none
    want = {"engine.none": 10, "engine.step": 9,
            "engine.dispatch:decode_step": 4, "engine.sync:decode_tokens": 1,
            "engine.dispatch:prefill_chunk": 2,
            "engine.sync:chunk_barrier": 1, "engine.prefill_chunk": 4,
            "engine.decode": 1, "engine.dispatch:runahead_horizon": 3}
    assert idle == {k: pytest.approx(v * 1e-3) for k, v in want.items()}
    # every idle second is booked once: the idle of the window
    assert sum(idle.values()) == pytest.approx(0.035)
    assert got["idle"][0] == ["engine.none", pytest.approx(0.010)]
    assert got["window_s"] == pytest.approx(0.1) and got["devices"] == 1


def test_self_times_and_programs():
    got = eng.reduce(_events(), steps=True, chunks=True)
    own = {k: (round(v["s"] * 1e3, 6), v["n"])
           for k, v in got["spans"].items()}
    # a span's self time: its length less what its children cover
    assert own == {"engine.step": (5 + 27, 2),
                   "engine.dispatch:decode_step": (6, 1),
                   "engine.sync:decode_tokens": (19, 1),
                   "engine.prefill_chunk": (27 - 3 - 20, 1),
                   "engine.dispatch:prefill_chunk": (3, 1),
                   "engine.sync:chunk_barrier": (20, 1),
                   "engine.decode": (6 - 4, 1),
                   "engine.dispatch:runahead_horizon": (4, 1)}
    progs = {k: (round(v["s"] * 1e3, 6), v["n"])
             for k, v in got["programs"].items()}
    assert progs == {"jit_decode_step": (20, 1),
                     "jit_prefill_chunk": (20, 1),
                     "jit_runahead_horizon": (25, 1)}


def test_the_metrics_read_the_reduction():
    got = eng.readings(eng.reduce(_events(), steps=True, chunks=True), 5)
    # idle under engine spans, sync and no span left out: 23 of 100 ms;
    # 90 ms of spans less 39 of sync, over 5 micro-steps
    assert got == {"engine_idle": pytest.approx(0.23),
                   "engine_host_ms": pytest.approx(51 / 5),
                   "prefill_chunk_ms": pytest.approx(20.0)}


@pytest.mark.parametrize("drop,steps,unread", [
    ("jit_prefill_chunk", 5, "prefill_chunk_ms"),
    (OPS, 5, "engine_idle"),
    ("", 0, "engine_host_ms")])
def test_a_reading_with_nothing_to_read_is_none(drop, steps, unread):
    """No chunk program, no device operation, no decode micro-step: that
    reading alone is None."""
    ev = [e for e in _events() if drop not in (e[1], e[2])]
    got = eng.readings(eng.reduce(ev, steps=True), steps)
    assert got[unread] is None
    assert all(v is not None for k, v in got.items() if k != unread)


def test_engine_readings_reduces_the_runs_trace(monkeypatch):
    """The tool reduces the trace the run writes, before the run removes
    it, with the flags the window's kernels give; the run is traced, and
    the device reduction is put back after."""
    calls = []

    def device(d, layers, expect=()):
        calls.append(("device", d, layers, tuple(expect)))
        return {"device": True}

    def engine(d, steps=False, chunks=False):
        calls.append(("engine", d, steps, chunks))
        return eng.reduce(_events(), steps, chunks)

    def run(args, root, require_tpu, hooks):
        assert args.trace == 1 and args.workload == "w"
        hooks["window"](None, {"decode_steps": 5})
        assert tr.reduce_dir("d", 12, ["paged_decode", "paged_prefill"]) \
            == {"device": True}
        return {"correct": True}

    monkeypatch.setattr(tr, "reduce_dir", device)
    monkeypatch.setattr(eng, "reduce_dir", engine)
    monkeypatch.setattr(engine_readings.run, "run", run)
    got = engine_readings.traced(argparse.Namespace(
        workload="w", seed=1, seconds=1.0), require_tpu=False)
    assert calls == [("engine", "d", True, True),
                     ("device", "d", 12, ("paged_decode", "paged_prefill"))]
    assert tr.reduce_dir is device
    assert got["result"] == {"correct": True}
    assert got["engine"]["programs"]["jit_prefill_chunk"]["n"] == 1
    assert got["readings"]["engine_host_ms"] == pytest.approx(51 / 5)


def test_a_span_that_outlives_its_parent_is_cut():
    ev = [_span("bench.window", 0, 10), _span("engine.step", 1, 5),
          _span("engine.sync:horizon", 4, 7),
          (DEV, OPS, "fusion.1", 9 * MS, 1 * MS)]
    got = eng.reduce(ev, steps=True)
    # idle 0-9: 0-1 and 5-9 in no span, 1-4 the step, 4-5 the sync
    assert dict(got["idle"]) == {"engine.none": pytest.approx(0.005),
                                 "engine.step": pytest.approx(0.003),
                                 "engine.sync:horizon": pytest.approx(0.001)}
    assert got["spans"]["engine.sync:horizon"]["s"] == pytest.approx(0.001)


def test_missing_names_are_an_error():
    ev = _events()
    no_spans = [e for e in ev if not e[2].startswith("engine.")]
    with pytest.raises(ValueError, match="no engine"):
        eng.reduce(no_spans, steps=True)
    assert eng.reduce(no_spans)["spans"] == {}
    no_chunk = [e for e in ev if e[2] != "jit_prefill_chunk"]
    with pytest.raises(ValueError, match="jit_prefill_chunk"):
        eng.reduce(no_chunk, steps=True, chunks=True)
    with pytest.raises(ValueError, match="bench.window"):
        eng.reduce([e for e in ev if e[2] != "bench.window"])


def test_a_program_without_spans_or_names_reduces_to_nothing():
    """A program older than the engine's spans and program names (every
    program ``jit__lambda``): nothing to read, so its traced run leaves
    the three readings out instead of failing."""
    ev = [(p, l, "jit__lambda" if l == MODS else n, s, d)
          for p, l, n, s, d in _events() if not n.startswith("engine.")]
    got = eng.reduce(ev, steps=True, chunks=True)
    assert got is None
    assert eng.readings(got, 5) == {"engine_idle": None,
                                    "engine_host_ms": None,
                                    "prefill_chunk_ms": None}


def test_labels_read_from_a_profiler_trace(tmp_path):
    """A span's argument labels it in what :func:`events_from_xplane`
    reads from a real trace (on the CPU: host spans only)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    x = jnp.ones((8,))
    f(x).block_until_ready()
    T = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(str(tmp_path))
    with T("bench.window"):
        with T("engine.step", cycle=1):
            with T("engine.dispatch", program="decode_step"):
                y = f(x)
            with T("engine.sync", what="decode_tokens"):
                y.block_until_ready()
        with T("bench.step"):
            pass
    jax.profiler.stop_trace()
    pb = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    ev = eng.events_from_xplane(pb)
    assert {n for p, l, n, s, d in ev if p.startswith("/host")} == {
        "bench.window", "engine.step", "engine.dispatch:decode_step",
        "engine.sync:decode_tokens"}
    got = eng.reduce(ev, steps=True)
    assert {k: v["n"] for k, v in got["spans"].items()} == {
        "engine.step": 1, "engine.dispatch:decode_step": 1,
        "engine.sync:decode_tokens": 1}


def test_trace_recorded_on_the_chip():
    """300 ms of a traced yi-9b.decode-long window on a TPU v5e, with the
    engine's spans: a run-ahead horizon lands, a replacement request is
    admitted and its prefill chunk runs, then a decode step. The idle
    time, read off the events by hand: the horizon's program ends at
    111.99 ms and its sync returns at 114.47 (2.48 ms), the landing's
    bookkeeping runs to 116.51 (2.04 ms), the chunk's dispatch prepares
    its arguments as six eager programs until its program starts at
    120.00 (3.12 ms), and the chunk's barrier returns 1.85 ms after its
    program ends."""
    import gzip
    import json

    path = os.path.join(ROOT, "bench", "trace", "v5e_engine_spans.json.gz")
    with gzip.open(path) as f:
        raw = json.load(f)["events"]
    ev = [(p, l, eng.program(n) if l == MODS else n, s, d)
          for p, l, n, s, d in raw]
    got = eng.reduce(ev, steps=True, chunks=True)
    idle = dict(got["idle"])
    assert idle["engine.dispatch:prefill_chunk"] == pytest.approx(
        0.003118538)
    assert idle["engine.sync:horizon"] == pytest.approx(0.002481994)
    assert idle["engine.runahead.land"] == pytest.approx(0.0020423)
    assert idle["engine.sync:chunk_barrier"] == pytest.approx(0.001851992)
    # booked once each: the window's idle time as the device reduction
    # reads it
    dev = tr.reduce([e for e in ev if not e[2].startswith("engine.")],
                    layers=12)
    assert sum(idle.values()) == pytest.approx(
        dev["window_s"] - dev["busy_s"])
    progs = got["programs"]
    assert progs["jit_prefill_chunk"] == {"s": pytest.approx(0.058035648),
                                          "n": 1}
    assert progs["jit_decode_step"]["n"] == 1
    assert progs["jit_runahead_horizon"]["n"] == 1
    assert not any(n.startswith("jit__lambda") for n in progs)
    assert got["spans"]["engine.dispatch:prefill_chunk"] == {
        "s": pytest.approx(0.00435742), "n": 1}
