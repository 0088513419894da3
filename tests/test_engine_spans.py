"""EngineCore's host spans and named device programs, read back from a
profiler trace: a device idle gap in a chip trace can only be put down to
the engine code that caused it if every step, admission, prefill chunk,
decode, horizon landing, dispatch and host sync runs inside its own
``engine.*`` span, on the profiler's clock, and every sync nests in the
step that made it."""
import glob

import numpy as np
import pytest

import jax

from repro.configs import get_config, reduce_for_smoke
from repro.models import get_model
from repro.serve import EngineCore, GenerationConfig, Request

SPANS = {"engine.step", "engine.admit", "engine.prefill_chunk",
         "engine.decode", "engine.runahead.land", "engine.dispatch",
         "engine.sync"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two requests through chunked prefill (two chunks each), the H=1
    decode while the second prefills, then run-ahead horizons of 4."""
    cfg = reduce_for_smoke(get_config("tinyllama-1.1b"))
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    core = EngineCore(m, params, max_slots=2, max_len=128,
                      prefill_chunk=32, runahead=4)
    core.reset(GenerationConfig())
    rng = np.random.RandomState(3)
    for i in range(2):
        core.add_request(Request(
            rid=i, prompt=rng.randint(0, cfg.vocab_size, (40,)).astype(
                np.int32), max_new_tokens=16, arrival_time=i * 1e-3))
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    list(core.events())
    jax.profiler.stop_trace()
    pb = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    host = []
    for plane in jax.profiler.ProfileData.from_file(pb).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(ev.name, int(ev.start_ns),
                          int(ev.start_ns + ev.duration_ns), dict(ev.stats))
                         for ev in line.events]
    return core.result(), host


def test_every_engine_span_is_traced(traced):
    res, host = traced
    names = {n for n, *_ in host if n.startswith("engine.")}
    assert names == SPANS
    whats = {st["what"] for n, _, _, st in host if n == "engine.sync"}
    assert whats == {"first_token", "chunk_barrier", "decode_tokens",
                     "horizon"}
    progs = {st["program"] for n, _, _, st in host if n == "engine.dispatch"}
    assert progs == {"prefill_chunk", "sample", "decode_step",
                     "runahead_horizon"}
    chunks = [st for n, _, _, st in host if n == "engine.prefill_chunk"]
    assert sorted(st["tokens"] for st in chunks) == [8, 8, 32, 32]
    assert res["prefill_chunks"] == 4
    assert res["runahead"]["horizons"] > 0


def test_syncs_nest_in_their_step(traced):
    res, host = traced
    steps = [(s, e) for n, s, e, _ in host if n == "engine.step"]
    syncs = [(s, e) for n, s, e, _ in host if n == "engine.sync"]
    assert syncs and all(any(a <= s and e <= b for a, b in steps)
                         for s, e in syncs)
    # the host's blocked time is what the sync spans cover, and no more
    covered = sum(e - s for s, e in syncs) * 1e-9
    assert 0 < res["host_sync_s"]
    assert covered <= res["host_sync_s"] + 1e-3


def test_device_programs_are_named(traced):
    """The jitted calls carry the programs' names (``jit_<name>`` on the
    device's modules line); none is an anonymous lambda."""
    _, host = traced
    calls = {n for n, *_ in host if n.startswith("PjitFunction(")}
    assert {"PjitFunction(decode_step)", "PjitFunction(prefill_chunk)",
            "PjitFunction(runahead_horizon)"} <= calls
    assert not any("lambda" in n for n in calls)
