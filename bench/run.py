"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload yi-9b.decode-long --seed 7 \
        --seconds 30 --trace 0

Set-up, in order: the weights are made on the device from ``--seed`` in the
serving dtype; the engine is built with the configuration's settings; the
programs the cell's traffic can reach are compiled (from JAX's persistent
compilation cache in ``.jax_cache/`` of the checkout, or where
``JAX_COMPILATION_CACHE_DIR`` points); a closed-loop mix then sends every
client's first request and prefills them all, back to back. The window then runs for
``--seconds`` on the host's clock, with the profiler off (``--trace 0``,
the end-to-end metrics) or on (``--trace 1``, the per-layer metrics). Then
the served tokens are checked against the configuration's reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced, a
``breakdown``; its last key, ``checks``, repeats each number compared
beside its limit, as do the last lines of standard error. It exits non-zero
with no result when JAX finds no TPU, or fewer chips than the cell asks
for, or a device kind missing from the benchmark's table of peaks.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import manifest  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax

    from bench.yardstick.peaks import peaks

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s); JAX sees "
                     f"{len(devs)}")
    kind = devs[0].device_kind
    # off the chip (tests only) the arithmetic runs against the v5e's peaks
    return {"platform": devs[0].platform, "kind": kind, "count": chips,
            "peaks": peaks(kind if require_tpu else "TPU v5 lite")}


def compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCount:
    """Counts programs made ready to run: XLA backend compilations and
    programs read from the persistent compilation cache. Inside the
    window there must be none."""

    def __init__(self):
        import jax

        self.n = 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def run(args, root: str = ROOT, require_tpu: bool = True,
        hooks=None) -> dict:
    """Set up, measure and check one run; returns the result object.

    ``hooks`` (tests and ``bench/readings.py`` only) may hold
    ``engine(core)``, called on the engine before its programs are warmed
    up, to break the timed path underneath; ``window(session, record)``, called when the
    window has closed; ``gap(ref, params, conf, reqs, pad_to)``, which
    takes the place of :func:`check.served_gap` (so the control is judged
    by this same ``correct``); and ``checked(ref, params, conf, reqs,
    pad_to)``, called after the check with what it compared."""
    import jax

    man = manifest.load(root)
    cell = manifest.cell(man, args.workload)
    dev = device_info(cell["chips"], require_tpu)
    conf = manifest.config(root, man, cell["config"])
    mix = manifest.mix(root, cell["traffic"])
    sys.path.insert(0, os.path.join(root, "src"))
    cache = compile_cache() if require_tpu else None
    compiles = CompileCount()

    from bench import check, driver, generate
    from bench import model as bmodel
    from bench.trace import reduce as treduce
    from repro.models import get_model
    from repro.serve.core import GenerationConfig

    log(f"{cell['name']}: {dev['kind']} x{dev['count']}, seed {args.seed}, "
        f"{args.seconds} s, trace {args.trace}, compile cache {cache}")
    cfg = bmodel.program_config(conf)
    model = get_model(cfg)
    params = bmodel.make_weights(model, args.seed)
    t_weights = time.monotonic()
    core = bmodel.build_engine(model, params, conf, mix)
    if hooks and "engine" in hooks:
        hooks["engine"](core)
    gen = GenerationConfig(temperature=0.0, eos_id=-1)
    prompt_r, out_r = generate.length_ranges(mix)
    n_prog = bmodel.warm_shapes(core, gen, prompt_r, out_r)
    t_warm = time.monotonic()
    compiles_warm = compiles.n

    vocab = conf["model"]["vocab_size"]
    if mix["kind"] == "closed":
        streams = generate.closed(mix, args.seed, vocab)
        sess = driver.Session(core, closed_streams=streams)
        firsts = [s[0].rid for s in streams]
        # the fill: every first prompt prefilled back to back in one cycle
        # of the engine (one decode step between two chunks would make it
        # several times longer), the window then at the configured budget
        budget = core.prefill_budget
        core.prefill_budget = sum(len(s[0].prompt) for s in streams)
        sess.run(lambda now: all(sess.recs[r].toks for r in firsts
                                 if r in sess.recs)
                 and all(r in sess.recs for r in firsts))
        core.prefill_budget = budget
    elif mix["kind"] == "poisson":
        specs = generate.poisson(mix, args.seed, vocab, args.seconds,
                                 rate=getattr(args, "rate", None))
        sess = driver.Session(core, open_specs=specs)
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    setup_s = time.monotonic() - T_START
    log(f"set-up {setup_s:.3f} s: to weights made {t_weights - T_START:.3f}"
        f" s, {n_prog} programs warmed in {t_warm - t_weights:.3f} s, fill "
        f"{time.monotonic() - t_warm:.3f} s")

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles_open = compiles.n
    with jax.profiler.TraceAnnotation("bench.window"):
        sess.open_window()
        t_end = sess.t_open + args.seconds
        sess.run(lambda now: now >= t_end)
        sess.close_window()
    window_compiles = compiles.n - compiles_open
    if args.trace:
        jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    rec = driver.window_record(sess)
    if hooks and "window" in hooks:
        hooks["window"](sess, rec)
    log(f"window {rec['window_s']:.3f} s: {rec['tokens']} tokens, "
        f"{rec['requests_due']} due, {rec['requests_done']} done, "
        f"{rec['decode_steps']} decode steps, {rec['prefill_tokens']} "
        f"prefill tokens, {window_compiles} compiles in the window "
        f"({compiles_warm} at set-up), peak {peak} bytes")

    result = {"correct": False, "attempted": rec["requests_sent"],
              "failed": rec["failed"], "metrics": {},
              "device": {"platform": dev["platform"], "kind": dev["kind"],
                         "count": dev["count"], "memory_peak_bytes": peak}}
    run_info = {"record": rec, "session": sess, "conf": conf, "mix": mix,
                "peaks": dev["peaks"], "trace": None}
    if args.trace:
        ran = [k for k, n in (("paged_decode", rec["decode_tokens"]),
                              ("paged_prefill", rec["prefill_tokens"])) if n]
        tr = treduce.reduce_dir(trace_dir,
                                conf["model"]["num_hidden_layers"], ran)
        shutil.rmtree(trace_dir, ignore_errors=True)
        run_info["trace"] = tr
        result["device"]["busy_s"] = tr["busy_s"]
        result["device"]["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["top_ops"][:10],
                               "idle_gaps": tr["idle_gaps"][:10]}
        for m in manifest.per_layer(man, cell["name"]):
            v = manifest.metric(root, m["name"]).read(run_info)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        for k, b in run_info.get("bounds", {}).items():
            log(f"{k}: the least time of its work is bound by {b}")
        log(f"trace: {tr['kernels']} {tr['programs']}")
    else:
        e2e = {"output_tok_s": rec["tokens"] / rec["window_s"],
               "ttft_p95_ms": _ms(driver.pct(rec["ttft_s"], 95)),
               "itl_p95_ms": _ms(driver.pct(rec["itl_s"], 95)),
               "setup_s": setup_s}
        for m in manifest.end_to_end(man, cell["name"]):
            if e2e.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    for k, v in result["metrics"].items():
        log(f"metric {k} = {v['value']!r} {v['unit']}")

    # the check: after the window, with memory_peak_bytes read and the
    # engine's pool freed; the reference runs on the weights alone
    # every token served is an answer: the sample is drawn among all the
    # requests served, in flight or finished (a finished one must have
    # all its tokens)
    reqs = check.sample(sess.served(), int(mix["check_requests"]),
                        args.seed)
    done = {r.rid for r in sess.finished()}
    short = [r.rid for r in reqs
             if r.rid in done and len(r.out_tokens) != r.max_new_tokens]
    core.state = None
    del core, sess
    run_info.pop("session")
    gc.collect()
    t_ref = time.monotonic()
    ref = manifest.reference(root, conf)
    gap_of = (hooks or {}).get("gap", check.served_gap)
    gap, n_cmp = gap_of(ref, params, conf, reqs, int(mix["max_len"]))
    limit = conf["check"]["max_logit_gap"]
    log(f"reference over {len(reqs)} requests, {n_cmp} served tokens, in "
        f"{time.monotonic() - t_ref:.3f} s")
    checks = {
        "widest_logit_gap": {"value": gap, "limit": limit},
        "requests_compared": {"value": len(reqs), "limit": 1},
        "requests_short": {"value": len(short), "limit": 0},
        "compiles_in_window": {"value": window_compiles, "limit": 0},
    }
    if hooks and "checked" in hooks:
        hooks["checked"](ref, params, conf, reqs, int(mix["max_len"]))
    result["correct"] = bool(
        limit is not None and gap <= limit and len(reqs) >= 1
        and not short and rec["tokens"] > 0 and window_compiles == 0)
    result["window"] = {"preempts": rec["preempts"]}
    result["checks"] = checks
    for k, v in checks.items():
        log(f"check {k} = {v['value']!r} (limit {v['limit']!r})")
    log(f"correct = {result['correct']}")
    return result


def _ms(s):
    return None if s is None else 1e3 * s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except NoChip as e:
        log(f"no result: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
