"""The trace reduction: known busy, idle, kernel and program numbers on a
small hand-made trace laid out as a TPU trace is, and on 300 ms of a trace
recorded on a TPU v5e (``bench/trace/v5e_decode_long.json.gz``)."""
import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.trace import reduce as tr  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"
OPS, MODS = tr.OPS_LINE, tr.MODULES_LINE
DEC = "paged_decode:polar_paged_decode_grouped.1"
PRE = "paged_prefill:polar_paged_prefill_grouped.3"
MS = 1_000_000


def _events():
    """Window 0-100 ms. A decode step (2 layers: 2 decode-kernel calls)
    10-30 ms, a prefill chunk 40-60 ms, a run-ahead scan of 3 micro-steps
    70-95 ms; the host steps 5-35 and 38-98 and submits 35-38."""
    ev = [(HOST, "python", "bench.window", 0, 100 * MS),
          (HOST, "python", "bench.step", 5 * MS, 30 * MS),
          (HOST, "python", "bench.submit", 35 * MS, 3 * MS),
          (HOST, "python", "bench.step", 38 * MS, 60 * MS),
          (DEV, MODS, "jit__lambda_", 10 * MS, 20 * MS),
          (DEV, OPS, "fusion.1", 10 * MS, 5 * MS),
          (DEV, OPS, DEC, 15 * MS, 4 * MS),
          (DEV, OPS, DEC, 19 * MS, 4 * MS),
          (DEV, OPS, "fusion.2", 23 * MS, 7 * MS),
          (DEV, MODS, "jit__lambda_", 40 * MS, 20 * MS),
          (DEV, OPS, PRE, 40 * MS, 6 * MS),
          (DEV, OPS, PRE, 46 * MS, 6 * MS),
          (DEV, OPS, "fusion.1", 52 * MS, 8 * MS),
          (DEV, MODS, "jit__lambda_", 70 * MS, 25 * MS)]
    ev += [(DEV, OPS, DEC, (70 + 4 * i) * MS, 4 * MS) for i in range(6)]
    ev.append((DEV, OPS, "fusion.3", 94 * MS, 1 * MS))
    # the scan's loop: an operation around the run-ahead's, not counted
    # as an operation of its own
    ev.append((DEV, OPS, "while.1", 70 * MS, 25 * MS))
    # outside the window: cut away
    ev.append((DEV, OPS, "fusion.1", 100 * MS, 5 * MS))
    return ev


def test_hand_made_trace():
    got = tr.reduce(_events(), layers=2)
    assert got["window_s"] == pytest.approx(0.100)
    # busy: 10-30, 40-60, 70-95
    assert got["busy_s"] == pytest.approx(0.065)
    assert got["kernels"]["paged_decode"] == {"s": pytest.approx(0.032),
                                              "n": 8}
    assert got["kernels"]["paged_prefill"] == {"s": pytest.approx(0.012),
                                               "n": 2}
    p = got["programs"]
    assert p["decode"]["n"] == 1 and p["decode"]["s"] == pytest.approx(0.02)
    assert p["prefill"]["n"] == 1
    assert p["runahead"] == {"s": pytest.approx(0.025), "n": 1,
                             "micro_steps": 3}
    gaps = dict(got["idle_gaps"])
    # idle 0-10 (0-5 in no span, 5-10 in a step), 30-40 (30-35 step,
    # 35-38 submit, 38-40 step), 60-70 (step), 95-100 (95-98 step, 98-100
    # in no span)
    assert gaps == {"bench.none": pytest.approx(0.007),
                    "bench.submit": pytest.approx(0.003),
                    "bench.step": pytest.approx(0.025)}
    assert got["top_ops"][0] == [DEC, pytest.approx(0.032)]


@pytest.mark.parametrize("text,label", [
    # as a TPU v5e trace names them: the operation's HLO text
    ("%polar_paged_decode_grouped.7 = (f32[64,4,8,128]{3,2,1,0:T(8,128)S(1)}"
     ", f32[64,4,8,1]{3,2,1,0:T(8,128)S(1)}) custom-call(%copy-done.1)",
     "paged_decode:polar_paged_decode_grouped.7"),
    ("%polar_paged_prefill_grouped.8 = f32[4,4096,128]{2,1,0:T(8,128)S(1)} "
     "custom-call(s32[32]{0:T(128)} %get-tuple-element.1734)",
     "paged_prefill:polar_paged_prefill_grouped.8"),
    ("polar_paged_decode_grouped.1", DEC),
    ("custom-call.2 kernel_name=_paged_attn_kernel",
     "paged_decode:custom-call.2 kernel_name=_paged_attn_kernel"),
    # what the wrapper leaves around the kernel is not the kernel
    ("%jit_polar_paged_decode_grouped_.12 = f32[4,1,8,128]{3,2,1,0} "
     "get-tuple-element(%polar_paged_decode_grouped.7), index=0",
     "jit_polar_paged_decode_grouped_.12"),
    ("%pallas_call.23 = f32[4,1,8,1]{3,2,1,0} get-tuple-element("
     "%polar_paged_decode_grouped.7), index=2", "pallas_call.23"),
    ("%while.3 = (s32[]{:T(128)}, bf16[64,1,4096]) while(%tuple.1)",
     "while.3"),
    ("fusion.4", "fusion.4"),
])
def test_kernel_events_are_labelled(text, label):
    assert tr._kernel_label(text) == label


def test_trace_recorded_on_the_chip():
    """300 ms from the middle of a traced yi-9b.decode-long window on a
    TPU v5e: the tail of one decode step, two prefill chunks and the head
    of the next decode step. The numbers were read off the events by
    hand: the decode kernel's events at -8.65 (17.52 ms), 13.83, 36.31,
    58.78, 81.26, 272.54 and 295.02 ms (17.52 ms each), cut to the slice;
    12 prefill-kernel events in each chunk; device busy 285.923 ms by a
    count of 1 us bins."""
    path = os.path.join(ROOT, "bench", "trace", "v5e_decode_long.json.gz")
    with gzip.open(path) as f:
        raw = json.load(f)["events"]
    ev = [(p, l, tr._kernel_label(n) if l == OPS else n, s, d)
          for p, l, n, s, d in raw]
    got = tr.reduce(ev, layers=12, expect=("paged_decode", "paged_prefill"))
    assert got["window_s"] == pytest.approx(0.300)
    assert got["busy_s"] == pytest.approx(0.285923, abs=2e-6)
    assert got["kernels"]["paged_decode"] == {
        "s": pytest.approx(0.101462096), "n": 7}
    assert got["kernels"]["paged_prefill"]["n"] == 24
    assert got["kernels"]["paged_prefill"]["s"] == pytest.approx(0.03402237)
    # the two prefill chunks' programs, 83.03 and 58.05 ms
    assert got["programs"]["prefill"] == {
        "s": pytest.approx(0.141072651), "n": 2}
    # the loops of the scans over layers are not operations of their own
    assert not any(n.startswith("while.") for n, _ in got["top_ops"])
    assert got["top_ops"][0][0] == "paged_decode:polar_paged_decode_grouped.7"


def test_names_the_reduction_cannot_read_are_an_error():
    ev = _events()
    with pytest.raises(ValueError, match="paged_prefill"):
        tr.reduce([e for e in ev if e[2] != PRE], layers=2,
                  expect=("paged_decode", "paged_prefill"))
    with pytest.raises(ValueError, match="no event"):
        tr.reduce([e for e in ev if e[1] != OPS], layers=2)
