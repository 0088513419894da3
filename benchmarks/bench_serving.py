"""Serving benchmark: continuous batching (paged cache) vs static batching.

Drives a Poisson-arrival workload with mixed prompt/output lengths through
both engines and reports aggregate *useful* tokens/s (padding and
over-generation excluded), p50/p99 per-request latency, and cache-page
utilization.

The continuous-batching arms run through the **streaming front door**
(`repro.serve.api.StreamingEngine` over `EngineCore.step()`): requests are
submitted to the open loop and tokens consumed as `TokenEvent`s, which is
what unlocks the honest per-token numbers — **TTFT** (arrival -> first
token, queueing + admission + the whole prefill) and **inter-token
latency** p50/p95/p99 — instead of end-of-run aggregates.

Both engines run against a simulated arrival clock: device time is
measured (block_until_ready) and added to the clock, while idle gaps jump
to the next arrival — so latencies compose queueing + compute without
having to sleep through the gaps.

The static baseline is the pre-refactor serving model: FCFS batches of up
to ``--slots`` requests, prompts right-padded to a shared bucket, one
shared prefill, and lock-step decode for the *batch max* output length —
every request holds its slot until the slowest one finishes.

Run (CPU):  PYTHONPATH=src python benchmarks/bench_serving.py
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduce_for_smoke
from repro.models import get_model
from repro.serve import (
    ChaosConfig, ChaosInjector, ContinuousBatchingEngine, GenerationConfig,
    QosConfig, Request, ServeEngine, StreamingEngine, check_event_stream,
    goodput_under_sla, stream_latency_stats,
)
from repro.utils import nearest_rank_pct as _pct, pow2_bucket as _bucket


def make_workload(n: int, rate: float, seed: int, prompt_lo: int,
                  prompt_hi: int, out_lo: int, out_hi: int) -> list[Request]:
    """Poisson arrivals (exponential gaps at ``rate`` req/s), uniform
    prompt and output lengths — output lengths deliberately heterogeneous:
    the static baseline pays for the batch max, continuous batching
    doesn't."""
    rng = np.random.default_rng(seed)
    t = 0.0
    reqs = []
    for i in range(n):
        t += rng.exponential(1.0 / rate)
        reqs.append(Request(
            rid=i,
            prompt=rng.integers(0, 512, (int(rng.integers(
                prompt_lo, prompt_hi + 1)),)).astype(np.int32),
            max_new_tokens=int(rng.integers(out_lo, out_hi + 1)),
            arrival_time=t))
    return reqs


def make_shared_prefix_workload(n: int, rate: float, seed: int,
                                prefix_len: int, suffix_lo: int,
                                suffix_hi: int, out_lo: int,
                                out_hi: int) -> list[Request]:
    """The system-prompt workload: every request shares a ``prefix_len``
    token prefix (one system prompt for the whole fleet) followed by a
    short random user suffix — the regime where shared-prefix page reuse
    converts the prompt-heavy part of prefill into free page adoption."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, 512, (prefix_len,)).astype(np.int32)
    t = 0.0
    reqs = []
    for i in range(n):
        t += rng.exponential(1.0 / rate)
        suffix = rng.integers(0, 512, (int(rng.integers(
            suffix_lo, suffix_hi + 1)),)).astype(np.int32)
        reqs.append(Request(
            rid=i, prompt=np.concatenate([shared, suffix]),
            max_new_tokens=int(rng.integers(out_lo, out_hi + 1)),
            arrival_time=t))
    return reqs


def run_static(model, params, requests: list[Request], slots: int,
               max_len: int) -> dict:
    """FCFS static batching on the dense-cache ServeEngine under the same
    simulated clock. Batches are padded to (slots, bucket) so the engine
    compiles once per prompt bucket."""
    eng = ServeEngine(model, params, max_len=max_len)
    # dense caches allow mixed per-layer group sizes; bucket to the largest
    g = model.cfg.policy.max_group_size()
    queue = sorted(requests, key=lambda r: r.arrival_time)
    buckets = sorted({_bucket(r.prompt_len, g) for r in queue})

    for b in buckets:  # warmup: compile prefill per bucket + decode
        eng.generate({"tokens": np.zeros((slots, b), np.int32)},
                     GenerationConfig(max_new_tokens=2))

    clock, i, useful = 0.0, 0, 0
    done: list[Request] = []
    while i < len(queue):
        if queue[i].arrival_time > clock:
            clock = queue[i].arrival_time
        batch = []
        while (i < len(queue) and len(batch) < slots
               and queue[i].arrival_time <= clock):
            batch.append(queue[i])
            i += 1
        b = _bucket(max(r.prompt_len for r in batch), g)
        toks = np.zeros((slots, b), np.int32)
        for j, r in enumerate(batch):
            toks[j, : r.prompt_len] = r.prompt
        horizon = max(r.max_new_tokens for r in batch)
        t0 = time.monotonic()
        out = eng.generate({"tokens": toks},
                           GenerationConfig(max_new_tokens=horizon))
        clock += time.monotonic() - t0
        for j, r in enumerate(batch):
            r.t_done = clock
            r.out_tokens = out["tokens"][j, : r.max_new_tokens].tolist()
            useful += r.max_new_tokens
        done.extend(batch)

    lats = sorted(r.latency() for r in done)
    pct = lambda p: _pct(lats, p)
    return {"requests": done, "total_tokens": useful, "wall_s": clock,
            "tokens_per_s": useful / max(clock, 1e-9),
            "p50_latency_s": pct(50), "p99_latency_s": pct(99)}


def _strip_requests(r: dict) -> dict:
    """JSON-serializable copy of an engine result dict (drops the Request
    and TokenEvent objects; everything else is plain numbers/lists)."""
    return {k: v for k, v in r.items()
            if k not in ("requests", "events", "cancelled_requests",
                         "shed_requests", "rejected_requests")}


def run_cb(cfg, params, args, *, backend: str, max_len: int,
           table_slicing: bool = True, mesh=None) -> dict:
    """One continuous-batching arm at a decode backend + pool capacity,
    driven open-loop through the streaming API: the Poisson workload is
    submitted to ``StreamingEngine`` and consumed as TokenEvents, from
    which per-request TTFT and inter-token-latency percentiles are
    computed. ``mesh`` threads a device mesh through the engine
    (head-sharded KV page pools, DESIGN.md §17)."""
    model = get_model(dataclasses.replace(cfg, decode_backend=backend))
    eng = ContinuousBatchingEngine(
        model, params, max_slots=args.slots, max_len=max_len,
        num_pages=args.num_pages or None, table_slicing=table_slicing,
        mesh=mesh)
    wl = make_workload(args.requests, args.rate, args.seed,
                       args.prompt_lo, args.prompt_hi,
                       args.out_lo, args.out_hi)
    # include the capacity bucket: preemption-resume prefills the full
    # context, which can land above any prompt bucket
    eng.warmup([r.prompt_len for r in wl] + [max_len])
    stream = StreamingEngine(eng)
    for r in sorted(wl, key=lambda q: q.arrival_time):
        stream.submit(r)
    events = list(stream.events())
    res = stream.result()
    res.update(stream_latency_stats(events, wl))
    res["max_len"] = max_len
    res["table_slicing"] = table_slicing
    return res


def run_shared_prefix(cfg, params, args) -> dict:
    """Shared-system-prompt A/B: prefix-cache reuse vs the no-reuse chunked
    baseline on the same workload.

    Both arms run identical chunked prefill (same chunk size), so reuse
    must produce *bit-identical* greedy outputs — adopted pages hold the
    same encoded bytes the baseline recomputes — while skipping the shared
    prompt's prefill work and pool pages (the acceptance check for
    DESIGN.md §12)."""
    model = get_model(dataclasses.replace(cfg, decode_backend=args.backend))
    wl = lambda: make_shared_prefix_workload(
        args.requests, args.rate, args.seed, args.shared_prefix,
        args.suffix_lo, args.suffix_hi, args.out_lo, args.out_hi)
    arms = {}
    for name, reuse in (("baseline", False), ("reuse", True)):
        eng = ContinuousBatchingEngine(
            model, params, max_slots=args.slots, max_len=args.max_len,
            num_pages=args.num_pages or None, prefix_cache=reuse,
            prefill_chunk=args.prefill_chunk)
        eng.warmup([args.max_len])
        workload = wl()
        arms[name] = eng.run(workload, GenerationConfig())
        arms[name].update(
            stream_latency_stats(arms[name]["events"], workload))
    base, reuse = arms["baseline"], arms["reuse"]
    out_of = lambda r: {q.rid: list(q.out_tokens) for q in r["requests"]}
    identical = out_of(base) == out_of(reuse)
    saved_pages = reuse["adopted_pages"]
    per_req_base = base["fresh_pages"] / max(args.requests, 1)
    per_req_reuse = reuse["fresh_pages"] / max(args.requests, 1)
    print(f"shared-prefix({args.shared_prefix} tok) "
          f"hit={reuse['prefix_hit_rate'] * 100:5.1f}% "
          f"skipped={reuse['prefill_tokens_skipped']:5d} tok "
          f"pages/req {per_req_base:.1f}->{per_req_reuse:.1f} "
          f"bytes-shared={reuse['prefix_pool_bytes_saved'] / 2**20:.2f}MiB "
          f"bit-identical={identical}")
    return {
        "prefix_len": args.shared_prefix,
        "prefill_chunk": reuse["prefill_chunk"],
        "baseline": _strip_requests(base),
        "reuse": _strip_requests(reuse),
        "outputs_bit_identical": identical,
        "prefill_tokens_skipped": reuse["prefill_tokens_skipped"],
        "adopted_pages": saved_pages,
        "prefix_pool_bytes_saved": reuse["prefix_pool_bytes_saved"],
        "fresh_pages_per_request_baseline": per_req_base,
        "fresh_pages_per_request_reuse": per_req_reuse,
    }


def run_prefill_sweep(cfg, params, args) -> list[dict]:
    """Long-prompt TTFT A/B: chunked prefill through the gathering jnp
    reference vs the page-native fused path (``prefill_backend``), same
    decode settings on both arms.

    TTFT on a long prompt is dominated by the per-chunk attention over the
    already-cached prefix: the jnp arm gathers the slot's *full-capacity*
    table row every chunk, the page-native arm walks only the pages the
    prefix actually occupies (width-sliced row). Greedy outputs must stay
    bit-identical — the kernel reorders no float ops relative to the
    reference."""
    arms = []
    g = cfg.quant.group_size
    for plen in args.prefill_sweep:
        max_len = -(-(plen + args.out_hi) // g) * g
        per_pb = {}
        for pb in ("jnp", "paged_fused"):
            model = get_model(dataclasses.replace(
                cfg, decode_backend=args.backend, prefill_backend=pb))
            eng = ContinuousBatchingEngine(
                model, params, max_slots=2, max_len=max_len,
                prefill_chunk=args.prefill_sweep_chunk)
            eng.warmup([plen], GenerationConfig(max_new_tokens=4))
            # one request: TTFT here is pure chunked-prefill latency, and
            # the jnp arm is O(prompt * capacity) on CPU — keep it lean
            rng = np.random.default_rng(args.seed)
            wl = [Request(rid=0,
                          prompt=rng.integers(0, 512, (plen,))
                          .astype(np.int32),
                          max_new_tokens=4, arrival_time=0.0)]
            r = eng.run(wl, GenerationConfig(max_new_tokens=4))
            r.update(stream_latency_stats(r["events"], wl))
            r["outputs"] = {q.rid: list(q.out_tokens)
                            for q in r["requests"]}
            per_pb[pb] = r
        identical = (per_pb["jnp"]["outputs"]
                     == per_pb["paged_fused"]["outputs"])
        ttft_jnp = per_pb["jnp"]["ttft_s"]["p50"]
        ttft_fused = per_pb["paged_fused"]["ttft_s"]["p50"]
        print(f"  prefill sweep plen={plen:5d} "
              f"ttft jnp={ttft_jnp * 1e3:8.1f}ms "
              f"paged_fused={ttft_fused * 1e3:8.1f}ms "
              f"speedup={ttft_jnp / max(ttft_fused, 1e-9):.2f}x "
              f"bit-identical={identical}")
        arms.append({
            "prompt_len": plen,
            "prefill_chunk": args.prefill_sweep_chunk,
            "max_len": max_len,
            "jnp": _strip_requests(per_pb["jnp"]),
            "paged_fused": _strip_requests(per_pb["paged_fused"]),
            "ttft_speedup_fused_over_jnp":
                ttft_jnp / max(ttft_fused, 1e-9),
            "outputs_bit_identical": identical,
        })
    return arms


def run_context_sweep(cfg, params, args) -> list[dict]:
    """Decode-step latency vs pool capacity: the gathered baseline
    (PR-2 formulation: full-width table + gather_view copy) against the
    page-native path. The workload's live context is fixed, so a flat
    paged-fused line across the sweep is the "no full-cache gather"
    signature; the gathered baseline grows with capacity."""
    arms = []
    for max_len in args.sweep:
        for backend, slicing in (("gathered", False), ("paged_fused", True)):
            r = run_cb(cfg, params, args, backend=backend, max_len=max_len,
                       table_slicing=slicing)
            arm = _strip_requests(r)
            arm["arm"] = ("gathered_baseline" if backend == "gathered"
                          else "paged_fused")
            arms.append(arm)
            print(f"  sweep max_len={max_len:5d} {arm['arm']:17s} "
                  f"decode_step={r['decode_step_s_mean'] * 1e3:8.2f}ms "
                  f"tok/s={r['tokens_per_s']:8.1f}")
    return arms


def _spec_workload(cfg, kind: str, n: int, seed: int,
                   max_new: int) -> list[Request]:
    """Spec-decode workloads. ``repetitive``: every prompt is one token
    repeated — greedy continuations tend to fall into short cycles that
    ngram self-speculation rides (the favorable regime). ``random``:
    i.i.d. prompts whose continuations rarely repeat — the adversarial
    regime where acceptance, and any speedup, should collapse."""
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        if kind == "repetitive":
            prompt = np.full((36,), rng.randint(0, cfg.vocab_size),
                             np.int32)
        else:
            prompt = rng.randint(0, cfg.vocab_size, (36,)).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=max_new,
                            arrival_time=i * 0.002))
    return reqs


def run_spec_sweep(cfg, params, args) -> dict:
    """Speculative-decode A/B: ngram self-speculation vs plain decode.

    Low-batch serving (2 slots) is the regime speculation targets: the
    per-dispatch overhead dominates per-token cost, so retiring several
    tokens per dispatch is a real win *when drafts get accepted*. Each
    arm must stay bit-identical to the baseline — a mismatch fails the
    whole benchmark (nonzero rc), speedups are only reported for correct
    runs. The sweep runs the span verifier's reference formulation
    (decode_backend="jnp", the backend the batched verifier reproduces
    bit-for-bit)."""
    from repro.spec import SpecConfig
    model = get_model(dataclasses.replace(cfg, decode_backend="jnp"))
    slots, max_len, max_new = 2, 512, args.spec_gen
    out: dict = {"slots": slots, "max_new": max_new,
                 "k_sweep": args.spec_sweep, "workloads": {}}
    rc_ok = True
    for kind in ("repetitive", "random"):
        wl = lambda: _spec_workload(cfg, kind, 6, args.seed + 3, max_new)
        arms = []
        base = None
        base_toks = None
        for k in [0] + args.spec_sweep:
            spec = SpecConfig(mode="ngram", k=k) if k else None
            eng = ContinuousBatchingEngine(
                model, params, max_slots=slots, max_len=max_len, spec=spec)
            reqs = wl()
            eng.warmup([r.prompt_len for r in reqs])
            r = eng.run(reqs, GenerationConfig())
            toks = {q.rid: list(q.out_tokens) for q in r["requests"]}
            if k == 0:
                base, base_toks = r, toks
                continue
            same = toks == base_toks
            rc_ok &= same
            sp = r["spec"]
            arms.append({
                "k": k,
                "tokens_per_s": r["tokens_per_s"],
                "speedup_vs_baseline": r["tokens_per_s"]
                / max(base["tokens_per_s"], 1e-9),
                "acceptance_rate": sp["acceptance_rate"],
                "mean_accepted_per_step": sp["mean_accepted_per_step"],
                "spec_steps": sp["steps"],
                "decode_steps": r["decode_steps"],
                "outputs_bit_identical": same,
            })
            print(f"spec/{kind:10s} k={k}: "
                  f"tok/s={r['tokens_per_s']:8.1f} "
                  f"({arms[-1]['speedup_vs_baseline']:.2f}x) "
                  f"acc={sp['acceptance_rate'] * 100:5.1f}% "
                  f"acc/step={sp['mean_accepted_per_step']:.2f} "
                  f"bit-identical={same}")
        out["workloads"][kind] = {
            "baseline_tokens_per_s": base["tokens_per_s"],
            "baseline_decode_steps": base["decode_steps"],
            "arms": arms,
        }
        print(f"spec/{kind:10s} base: tok/s={base['tokens_per_s']:8.1f}")
    rep = out["workloads"]["repetitive"]["arms"]
    out["best_speedup_repetitive"] = max(
        (a["speedup_vs_baseline"] for a in rep), default=0.0)
    out["outputs_bit_identical"] = rc_ok
    return out


def _runahead_workload(n: int, seed: int, prompt_len: int,
                       max_new: int) -> list[Request]:
    """Decode-bound workload for the run-ahead sweep: ``n`` equal-length
    short prompts all arriving ~t=0 (one per millisecond), so the queue
    drains immediately and the horizon planner sees the pure decode-bound
    stretch run-ahead targets."""
    rng = np.random.default_rng(seed)
    return [Request(
        rid=i,
        prompt=rng.integers(0, 512, (prompt_len,)).astype(np.int32),
        max_new_tokens=max_new, arrival_time=i * 1e-3)
        for i in range(n)]


def run_runahead_sweep(cfg, params, args) -> dict:
    """Run-ahead fused decode A/B (DESIGN.md §18): horizon H x slot-count
    grid against the H=1 per-token dispatch baseline, same decode-bound
    workload per slot arm.

    Low-slot decode is the regime the per-token host sync dominates: every
    step pays scheduling + event emission + a device round-trip for a
    handful of tokens. Run-ahead amortizes that host work over H fused
    micro-steps and overlaps it with device compute (async dispatch
    pipeline), so the win should be largest at 1-4 slots and taper as
    device compute grows with the batch. Greedy outputs must stay
    **bit-identical** to H=1 at every grid point — a digest mismatch fails
    the whole benchmark (nonzero rc); speedups are only reported for
    correct runs. The recorded dispatch-gap EWMA (host time between a
    block landing and the next horizon's dispatch) and sync-wait time are
    the per-step host-vs-device breakdown."""
    import hashlib
    import json

    model = get_model(dataclasses.replace(cfg, decode_backend=args.backend))
    g = cfg.quant.group_size
    max_new = args.runahead_gen
    plen = 2 * g
    max_len = -(-(plen + max_new) // g) * g + g
    out: dict = {"h_sweep": args.runahead_sweep,
                 "slots_sweep": args.runahead_slots,
                 "prompt_len": plen, "max_new": max_new,
                 "max_len": max_len, "arms": []}
    rc_ok = True
    for slots in args.runahead_slots:
        wl = lambda: _runahead_workload(slots, args.seed + 29, plen,
                                        max_new)
        base_tps, base_digest = None, None
        for h in args.runahead_sweep:
            eng = ContinuousBatchingEngine(
                model, params, max_slots=slots, max_len=max_len,
                runahead=0 if h <= 1 else h)
            reqs = wl()
            eng.warmup([plen])
            r = eng.run(reqs, GenerationConfig())
            outs = sorted((q.rid, list(q.out_tokens))
                          for q in r["requests"])
            digest = hashlib.sha256(
                json.dumps(outs).encode()).hexdigest()[:16]
            if base_tps is None:   # the first grid point is the baseline
                base_tps, base_digest = r["tokens_per_s"], digest
            same = digest == base_digest
            rc_ok &= same
            arm = {
                "h": h, "slots": slots,
                "tokens_per_s": r["tokens_per_s"],
                "total_tokens": r["total_tokens"],
                "decode_steps": r["decode_steps"],
                "speedup_vs_h1": r["tokens_per_s"] / max(base_tps, 1e-9),
                "outputs_digest": digest,
                "outputs_bit_identical": same,
            }
            arm["host_sync_s"] = r["host_sync_s"]
            if "runahead" in r:
                arm["horizons"] = r["runahead"]["horizons"]
                arm["horizon_tokens"] = r["runahead"]["tokens"]
            out["arms"].append(arm)
            extra = f" host-sync={arm['host_sync_s'] * 1e3:7.1f}ms"
            if "runahead" in r:
                extra = f" horizons={arm['horizons']:4d}" + extra
            print(f"runahead slots={slots:2d} h={h}: "
                  f"tok/s={r['tokens_per_s']:8.1f} "
                  f"({arm['speedup_vs_h1']:.2f}x) "
                  f"bit-identical={same}{extra}")
    out["best_speedup_low_slots"] = max(
        (a["speedup_vs_h1"] for a in out["arms"] if a["slots"] <= 4),
        default=0.0)
    out["outputs_bit_identical"] = rc_ok
    return out


# ---------------------------------------------------------------------------
# Adversarial arms (DESIGN.md §16): hostile workloads, goodput-under-SLA
# ---------------------------------------------------------------------------


def make_bursty_workload(n: int, bursts: int, gap: float, seed: int,
                         prompt_lo: int, prompt_hi: int, out_lo: int,
                         out_hi: int, deadline: float = 0.0,
                         tenant: str = "default") -> list[Request]:
    """Synchronized arrival storms: ``bursts`` groups of ~n/bursts
    requests landing within a millisecond of each other, ``gap`` seconds
    apart — the anti-Poisson workload where FCFS queueing delay spikes
    and deadline-aware shedding has to triage."""
    rng = np.random.default_rng(seed)
    reqs, rid = [], 0
    per = max(n // bursts, 1)
    for b in range(bursts):
        for _ in range(per):
            reqs.append(Request(
                rid=rid,
                prompt=rng.integers(0, 512, (int(rng.integers(
                    prompt_lo, prompt_hi + 1)),)).astype(np.int32),
                max_new_tokens=int(rng.integers(out_lo, out_hi + 1)),
                arrival_time=b * gap + rng.uniform(0, 1e-3),
                ttft_deadline=deadline, tenant=tenant))
            rid += 1
    return reqs


def make_tenant_workload(n: int, rate: float, seed: int, prefix_len: int,
                         suffix_lo: int, suffix_hi: int, out_lo: int,
                         out_hi: int, heavy_frac: float = 0.9,
                         deadline: float = 0.0) -> list[Request]:
    """90/10 multi-tenant mix: a ``heavy`` tenant floods ~90% of the
    arrivals, a ``light`` tenant trickles the rest; each tenant has its
    own shared system prompt (prefix-cache-friendly within a tenant,
    cross-tenant pollution between them). Under FCFS the light tenant
    queues behind the flood; WFQ's attained-service ordering is what
    should keep its latency flat."""
    rng = np.random.default_rng(seed)
    prefixes = {t: rng.integers(0, 512, (prefix_len,)).astype(np.int32)
                for t in ("heavy", "light")}
    t, reqs = 0.0, []
    for i in range(n):
        t += rng.exponential(1.0 / rate)
        tenant = "heavy" if rng.random() < heavy_frac else "light"
        suffix = rng.integers(0, 512, (int(rng.integers(
            suffix_lo, suffix_hi + 1)),)).astype(np.int32)
        reqs.append(Request(
            rid=i, prompt=np.concatenate([prefixes[tenant], suffix]),
            max_new_tokens=int(rng.integers(out_lo, out_hi + 1)),
            arrival_time=t, tenant=tenant, ttft_deadline=deadline))
    return reqs


def make_straggler_workload(n: int, rate: float, seed: int, long_len: int,
                            long_every: int, chat_lo: int, chat_hi: int,
                            out_lo: int, out_hi: int,
                            deadline: float = 0.0) -> list[Request]:
    """Long-context stragglers beside chat traffic: every
    ``long_every``-th request carries a ``long_len`` prompt (tenant
    ``batch``, no deadline) between short chat requests (tenant ``chat``,
    deadline-bound) — the head-of-line-blocking regime chunked prefill +
    QoS must keep interactive."""
    rng = np.random.default_rng(seed)
    t, reqs = 0.0, []
    for i in range(n):
        t += rng.exponential(1.0 / rate)
        straggler = long_every > 0 and i % long_every == long_every - 1
        plen = long_len if straggler else int(rng.integers(
            chat_lo, chat_hi + 1))
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, 512, (plen,)).astype(np.int32),
            max_new_tokens=int(rng.integers(out_lo, out_hi + 1)),
            arrival_time=t,
            tenant="batch" if straggler else "chat",
            ttft_deadline=0.0 if straggler else deadline))
    return reqs


def _adv_run(model, params, args, wl: list[Request], *, qos=None,
             chaos=None, slo: float, num_pages=None, prefill_chunk: int = 0,
             warm_caps: list[int] | None = None) -> dict:
    """One adversarial arm: run, assert the event-stream invariants and
    post-drain allocator conservation, return metrics + goodput-under-SLA
    (tokens/s from requests whose TTFT met the deadline)."""
    eng = ContinuousBatchingEngine(
        model, params, max_slots=args.slots, max_len=args.max_len,
        num_pages=num_pages if num_pages is not None
        else (args.num_pages or None),
        prefill_chunk=prefill_chunk, qos=qos, chaos=chaos)
    eng.warmup(sorted({r.prompt_len for r in wl})
               + (warm_caps or [args.max_len]))
    r = eng.run(wl, GenerationConfig())
    # invariants hold under every hostile workload, not just in tests
    check_event_stream(r["events"])
    alloc = eng.core.sched.alloc
    assert alloc.quarantined_pages == 0, "chaos quarantine leaked"
    assert alloc.free_pages == eng.core.layout.num_pages, \
        "pages leaked after drain"
    r.update(stream_latency_stats(r["events"], wl))
    r["goodput"] = goodput_under_sla(r["requests"], r["wall_s"], slo)
    return r


def run_adversarial(cfg, params, args) -> dict:
    """The hostile-workload scenario suite: each arm runs an SLA-aware
    QoS engine against the FCFS no-QoS baseline on the same workload,
    with **goodput-under-SLA** (tokens/s from requests meeting their
    TTFT deadline) as the headline. The soak arm's QoS-beats-FCFS margin
    is the benchmark's acceptance gate (nonzero rc on regression)."""
    model = get_model(dataclasses.replace(cfg, decode_backend=args.backend))

    # calibrate the SLO off the *unloaded* TTFT: a trickle workload the
    # pool absorbs instantly, p50 TTFT * 4 = the deadline a lightly
    # loaded engine comfortably meets and an overloaded queue blows
    calib = _adv_run(model, params, args,
                     make_workload(max(args.slots, 4), 2.0, args.seed,
                                   args.prompt_lo, args.prompt_hi,
                                   args.out_lo, args.out_hi),
                     slo=float("inf"))
    slo = max(4.0 * calib["ttft_s"]["p50"], 1e-3)
    qos_cfg = QosConfig(ttft_slo=slo)
    out: dict = {"slo_s": slo,
                 "calibration_ttft_p50_s": calib["ttft_s"]["p50"]}

    def ab(name, wl_fn, *, qos=qos_cfg, fcfs_kw=None, qos_kw=None,
           extra=None):
        kw = dict(slo=slo)
        a = _adv_run(model, params, args, wl_fn(),
                     **{**kw, **(fcfs_kw or {})})
        b = _adv_run(model, params, args, wl_fn(), qos=qos,
                     **{**kw, **(qos_kw or {})})
        arm = {
            "fcfs": _strip_requests(a), "qos": _strip_requests(b),
            "goodput_win": b["goodput"]["goodput_tokens_per_s"]
            / max(a["goodput"]["goodput_tokens_per_s"], 1e-9),
        }
        if extra:
            arm.update(extra(a, b))
        print(f"adversarial/{name:12s} goodput fcfs="
              f"{a['goodput']['goodput_tokens_per_s']:8.1f} qos="
              f"{b['goodput']['goodput_tokens_per_s']:8.1f} tok/s "
              f"({arm['goodput_win']:.2f}x)  met-rate "
              f"{a['goodput']['deadline_met_rate']:.2f}->"
              f"{b['goodput']['deadline_met_rate']:.2f}  "
              f"shed={b['n_shed']}")
        out[name] = arm
        return a, b

    # --- sustained-overload soak: a deadline-bound storm far over
    # service capacity, on an undersized pool (preemption churn); FCFS
    # serves everyone late, QoS sheds the doomed tail and keeps the
    # survivors inside the SLA ---
    soak_pages = max(args.slots * 2,
                     (args.prompt_hi + args.out_hi)
                     // cfg.quant.group_size + 2)
    soak_wl = lambda: make_bursty_workload(
        args.adversarial_requests, 2, 0.05, args.seed + 11,
        args.prompt_lo, args.prompt_hi, args.out_lo, args.out_hi,
        deadline=slo)
    ab("soak", soak_wl,
       qos=dataclasses.replace(qos_cfg, pressure_hi=0.85),
       fcfs_kw=dict(num_pages=soak_pages),
       qos_kw=dict(num_pages=soak_pages),
       extra=lambda a, b: {
           "num_pages": soak_pages,
           "preemptions_fcfs": sum(q.preemptions
                                   for q in a["requests"]),
           "preemptions_qos": sum(q.preemptions
                                  for q in b["requests"]),
           "degrade": b.get("qos", {}).get("degrade"),
       })

    # --- bursty Poisson storms: arrival clusters instead of a smooth
    # stream; same A/B, deadlines only meetable near the burst head ---
    burst_wl = lambda: make_bursty_workload(
        args.adversarial_requests, 4, 0.4, args.seed + 13,
        args.prompt_lo, args.prompt_hi, args.out_lo, args.out_hi,
        deadline=slo)
    ab("burst", burst_wl)

    # --- cancellation flood: deterministic chaos storms cancel half the
    # live requests twice mid-run; the stream invariants (no events
    # after cancel, dense ordinals) must survive, and the engine's
    # goodput comes only from the survivors ---
    flood_chaos = lambda: ChaosInjector(ChaosConfig(
        seed=args.seed, cancel_at=(8, 20), cancel_frac=0.5))
    flood_wl = lambda: make_workload(
        args.adversarial_requests, args.rate * 2, args.seed + 17,
        args.prompt_lo, args.prompt_hi, args.out_lo, args.out_hi)
    fa = _adv_run(model, params, args, flood_wl(), slo=slo,
                  chaos=flood_chaos())
    fb = _adv_run(model, params, args, flood_wl(), slo=slo)
    out["cancel_flood"] = {
        "chaos": _strip_requests(fa), "clean": _strip_requests(fb),
        "storm_cancels": fa["chaos"]["storm_cancels"],
    }
    print(f"adversarial/cancel_flood  cancelled="
          f"{fa['n_cancelled']} of {args.adversarial_requests}  "
          f"survivor tok/s={fa['tokens_per_s']:.1f} "
          f"(clean {fb['tokens_per_s']:.1f})")

    # --- 90/10 multi-tenant shared-prefix mix: WFQ must hold the light
    # tenant's TTFT under the heavy tenant's flood ---
    tenant_wl = lambda: make_tenant_workload(
        args.adversarial_requests, args.rate * 2, args.seed + 19,
        args.shared_prefix or 32, args.suffix_lo, args.suffix_hi,
        args.out_lo, args.out_hi, deadline=slo)

    def tenant_ttft(r, tenant):
        ts = [q.t_first_token - q.arrival_time for q in r["requests"]
              if q.tenant == tenant and q.t_first_token is not None]
        return _pct(sorted(ts), 50)

    ab("tenants", tenant_wl,
       qos=dataclasses.replace(qos_cfg, weights={"light": 4.0}),
       extra=lambda a, b: {
           "light_ttft_p50_fcfs_s": tenant_ttft(a, "light"),
           "light_ttft_p50_qos_s": tenant_ttft(b, "light"),
           "tenants_qos": b["qos"]["tenants"],
       })

    # --- long-context stragglers beside chat traffic: chunked prefill +
    # QoS keep the chat class inside its deadline while batch-class
    # stragglers (no deadline) grind through. The SLO recalibrates on
    # the chunked config — per-chunk dispatch overhead shifts the whole
    # unloaded TTFT scale ---
    chunk = max(cfg.quant.group_size * 2, 32)
    calib_chunked = _adv_run(model, params, args,
                             make_workload(max(args.slots, 4), 2.0,
                                           args.seed, args.prompt_lo,
                                           args.prompt_hi, args.out_lo,
                                           args.out_hi),
                             slo=float("inf"), prefill_chunk=chunk)
    slo_chunked = max(4.0 * calib_chunked["ttft_s"]["p50"], 1e-3)
    out["slo_chunked_s"] = slo_chunked
    strag_wl = lambda: make_straggler_workload(
        args.adversarial_requests, args.rate, args.seed + 23,
        long_len=min(args.max_len - args.out_hi, 4 * args.prompt_hi),
        long_every=5, chat_lo=args.prompt_lo, chat_hi=args.prompt_hi,
        out_lo=args.out_lo, out_hi=args.out_hi, deadline=slo_chunked)
    ab("stragglers", strag_wl,
       qos=dataclasses.replace(qos_cfg, ttft_slo=slo_chunked),
       fcfs_kw=dict(prefill_chunk=chunk, slo=slo_chunked),
       qos_kw=dict(prefill_chunk=chunk, slo=slo_chunked),
       extra=lambda a, b: {
           "prefill_chunk": chunk,
           "chat_ttft_p50_fcfs_s": tenant_ttft(a, "chat"),
           "chat_ttft_p50_qos_s": tenant_ttft(b, "chat"),
       })

    out["soak_gate_ok"] = out["soak"]["goodput_win"] > 1.0
    return out


def _parse_mesh_shape(spec: str) -> tuple[int, int]:
    try:
        d, m = (int(x) for x in spec.split("x"))
    except ValueError:
        raise SystemExit(f"bad --mesh-shape {spec!r}; "
                         "expected e.g. '1x2' (data x model)")
    return d, m


def mesh_arm_record(args, d: int, m: int, devices=None) -> dict:
    """ONE continuous-batching arm on a (data x model) mesh over
    ``devices`` (default: every device of the process) -> a minimal
    record, greedy-output digest included."""
    import hashlib
    import json

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((d, m), ("data", "model"), devices=devices)
    cfg = reduce_for_smoke(get_config(args.arch))
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    res = run_cb(cfg, params, args, backend=args.backend,
                 max_len=args.max_len, mesh=mesh)
    outs = sorted((r.rid, list(r.out_tokens))
                  for r in res.get("requests", []))
    return {
        "devices": d * m,
        "mesh": {"data": d, "model": m},
        "head_sharded": cfg.num_kv_heads % m == 0,
        "tokens_per_s": res["tokens_per_s"],
        "total_tokens": res["total_tokens"],
        "ttft_s": res["ttft_s"],
        "itl_s": res["itl_s"],
        "decode_step_s_mean": res.get("decode_step_s_mean"),
        # greedy-output fingerprint: the sweep driver asserts it is
        # identical across device counts (sharding must not change tokens)
        "outputs_digest": hashlib.sha256(
            json.dumps(outs).encode()).hexdigest()[:16],
    }


def run_mesh_arm(args) -> int:
    """Internal ``--mesh-arm`` mode: one mesh arm, its record to ``--json``.

    The CPU sweep runs it in its own process so the driver's
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` takes effect —
    device forcing is a process-level switch that must precede jax init.
    """
    import json

    d, m = _parse_mesh_shape(args.mesh_shape)
    rec = mesh_arm_record(args, d, m)
    with open(args.json, "w") as f:
        json.dump(rec, f, indent=2, sort_keys=True)
    return 0


def _forced_host_arm(args, n: int) -> dict:
    """Run one CPU mesh arm in a child process with ``n`` forced host
    devices; a failed child fails the sweep."""
    import json
    import os
    import subprocess
    import sys
    import tempfile

    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform")]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    fd, out_path = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    cmd = [sys.executable, "-m", "benchmarks.bench_serving",
           "--mesh-arm", "--mesh-shape", f"1x{n}",
           "--arch", args.arch,
           "--requests", str(args.requests), "--rate", str(args.rate),
           "--slots", str(args.slots), "--max-len", str(args.max_len),
           "--num-pages", str(args.num_pages),
           "--prompt-lo", str(args.prompt_lo),
           "--prompt-hi", str(args.prompt_hi),
           "--out-lo", str(args.out_lo), "--out-hi", str(args.out_hi),
           "--seed", str(args.seed), "--backend", args.backend,
           "--json", out_path]
    try:
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=1800)
        if proc.returncode != 0:
            raise RuntimeError(f"mesh-sweep arm devices={n} failed "
                               f"(exit {proc.returncode})\n"
                               f"{proc.stdout}\n{proc.stderr}")
        with open(out_path) as f:
            return json.load(f)
    finally:
        os.unlink(out_path)


def run_mesh_sweep(args) -> dict:
    """Multi-device serving sweep: tokens/s + TTFT p50/p95 vs device count
    at fixed total pool bytes (same slots/max_len/num_pages every arm; only
    the device count — and thus per-device pool bytes, where kv_heads
    divides the model axis — changes). Each count N runs one arm on a
    (1, N) (data x model) mesh, and a failed arm fails the sweep.

    On a TPU host each arm builds its mesh from the first N chips in this
    process: a chip belongs to one process at a time, and this one already
    holds them all. On CPU each arm runs :func:`run_mesh_arm` in a child
    process under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``;
    the forced "devices" are host threads, so those arms measure sharding
    *orchestration* overhead and correctness, not a speedup. Greedy-output
    digests must agree across arms.
    """
    on_tpu = jax.default_backend() == "tpu"
    arms = []
    for n in args.mesh_sweep:
        if on_tpu:
            if n > jax.device_count():
                raise ValueError(f"mesh-sweep arm devices={n} but only "
                                 f"{jax.device_count()} chips are present")
            arm = mesh_arm_record(args, 1, n, devices=jax.devices()[:n])
        else:
            arm = _forced_host_arm(args, n)
        arms.append(arm)
        print(f"mesh devices={n:2d} "
              f"head_sharded={str(arm['head_sharded']):5s} "
              f"tok/s={arm['tokens_per_s']:8.1f} "
              f"ttft_p50={arm['ttft_s']['p50'] * 1e3:7.1f}ms "
              f"p95={arm['ttft_s']['p95'] * 1e3:7.1f}ms "
              f"dstep={arm['decode_step_s_mean'] * 1e3:.2f}ms")
    digests = {a["outputs_digest"] for a in arms}
    identical = len(digests) <= 1
    if not identical:
        print("mesh-sweep: greedy outputs DIVERGED across device counts")
    return {"arms": arms, "outputs_identical_across_devices": identical}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--slots", type=int, default=6)
    ap.add_argument("--max-len", type=int, default=192)
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page pool size (0 = fully provisioned)")
    ap.add_argument("--prompt-lo", type=int, default=16)
    ap.add_argument("--prompt-hi", type=int, default=96)
    ap.add_argument("--out-lo", type=int, default=4)
    ap.add_argument("--out-hi", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="paged_fused",
                    help="decode backend for the paged path "
                         "(jnp|gathered|paged_fused|ref|interpret|pallas)")
    ap.add_argument("--sweep", default="",
                    help="comma-separated max_len sweep for the "
                         "decode-step-vs-context scaling arms (e.g. "
                         "'512,2048,4096'; empty = skip)")
    ap.add_argument("--prefill-sweep", default="",
                    help="comma-separated long-prompt lengths for the "
                         "chunked-prefill TTFT A/B arms (jnp vs "
                         "page-native prefill backend, e.g. "
                         "'2048,4096,8192'; empty = skip)")
    ap.add_argument("--prefill-sweep-chunk", type=int, default=256,
                    help="chunk size for the --prefill-sweep arms")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="shared system-prompt length for the prefix-cache "
                         "A/B arm (0 = skip)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="chunked-prefill size for the shared-prefix arms")
    ap.add_argument("--suffix-lo", type=int, default=8)
    ap.add_argument("--suffix-hi", type=int, default=32)
    ap.add_argument("--spec-sweep", default="",
                    help="comma-separated draft-length sweep for the "
                         "speculative-decode A/B arms (ngram proposer, "
                         "e.g. '2,4,8'; empty = skip)")
    ap.add_argument("--spec-gen", type=int, default=192,
                    help="output tokens per request in the spec-sweep "
                         "arms")
    ap.add_argument("--runahead-sweep", default="",
                    help="comma-separated horizon sweep for the run-ahead "
                         "fused-decode A/B arms (e.g. '1,2,4,8'; the "
                         "first entry is the baseline, empty = skip); "
                         "each horizon runs at every --runahead-slots "
                         "count on a decode-bound workload")
    ap.add_argument("--runahead-slots", default="1,4,16",
                    help="comma-separated slot counts for the run-ahead "
                         "sweep grid")
    ap.add_argument("--runahead-gen", type=int, default=64,
                    help="output tokens per request in the run-ahead "
                         "sweep arms")
    ap.add_argument("--adversarial", action="store_true",
                    help="run the hostile-workload scenario suite "
                         "(overload soak, burst storms, cancel floods, "
                         "multi-tenant mix, stragglers) with "
                         "goodput-under-SLA A/B vs the no-QoS FCFS "
                         "baseline")
    ap.add_argument("--adversarial-requests", type=int, default=16,
                    help="requests per adversarial arm")
    ap.add_argument("--mesh-sweep", default="",
                    help="comma-separated device counts for the "
                         "multi-device serving sweep (e.g. '1,2,4'; "
                         "empty = skip). Each count runs the cb arm in a "
                         "subprocess under XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N with a "
                         "1xN (data x model) mesh — fixed num_pages, so "
                         "total pool bytes stay constant while per-device "
                         "bytes shrink where kv_heads divides N")
    ap.add_argument("--mesh-shape", default="",
                    help="mesh for the cb arm, e.g. '1x2' (data x model); "
                         "used by the --mesh-arm subprocess mode")
    ap.add_argument("--mesh-arm", action="store_true",
                    help="internal: run ONLY the cb arm under --mesh-shape "
                         "and write a minimal JSON record to --json (the "
                         "--mesh-sweep driver invokes this per device "
                         "count so XLA device forcing precedes jax init)")
    ap.add_argument("--json", default="",
                    help="write machine-readable results to this path")
    args = ap.parse_args(argv)
    args.sweep = [int(x) for x in args.sweep.split(",") if x]
    args.prefill_sweep = [int(x) for x in args.prefill_sweep.split(",") if x]
    args.spec_sweep = [int(x) for x in args.spec_sweep.split(",") if x]
    args.mesh_sweep = [int(x) for x in args.mesh_sweep.split(",") if x]
    args.runahead_sweep = [int(x) for x in args.runahead_sweep.split(",")
                           if x]
    args.runahead_slots = [int(x) for x in args.runahead_slots.split(",")
                           if x]

    if args.mesh_arm:
        return run_mesh_arm(args)

    cfg = reduce_for_smoke(get_config(args.arch))
    # the static arm shares the requested backend (dense path normalizes
    # the paged dispatch names), keeping the cb-vs-static speedup apples
    # to apples
    model = get_model(dataclasses.replace(cfg, decode_backend=args.backend))
    params = model.init(jax.random.PRNGKey(0))

    print(f"# arch={cfg.name} quant={cfg.quant.method} "
          f"backend={args.backend} slots={args.slots} "
          f"requests={args.requests} rate={args.rate}/s")

    # --- continuous batching (requested backend + gathered baseline) ---
    res_cb = run_cb(cfg, params, args, backend=args.backend,
                    max_len=args.max_len)
    # the PR-2 formulation: gather_view copy + dense fused kernel over the
    # full-width table — isolates the structural gather-removal win
    res_base = run_cb(cfg, params, args, backend="gathered",
                      max_len=args.max_len, table_slicing=False)

    # --- static baseline ---
    res_st = run_static(model, params,
                        make_workload(args.requests, args.rate, args.seed,
                                      args.prompt_lo, args.prompt_hi,
                                      args.out_lo, args.out_hi),
                        args.slots, args.max_len)

    def row(name, r):
        extra = ""
        if "mean_page_utilization" in r:
            extra = (f" util={r['mean_page_utilization']:.2f}"
                     f" active={r['mean_active_slots']:.2f}"
                     f" dstep={r['decode_step_s_mean'] * 1e3:.2f}ms"
                     f" preempt={sum(q.preemptions for q in r['requests'])}")
        if "ttft_s" in r:
            extra += (f" ttft_p50={r['ttft_s']['p50'] * 1e3:.1f}ms"
                      f"/p99={r['ttft_s']['p99'] * 1e3:.1f}ms"
                      f" itl_p50={r['itl_s']['p50'] * 1e3:.1f}ms"
                      f"/p99={r['itl_s']['p99'] * 1e3:.1f}ms")
        print(f"{name:12s} tokens={r['total_tokens']:5d} "
              f"wall={r['wall_s']:7.3f}s "
              f"tok/s={r['tokens_per_s']:8.1f} "
              f"p50={r['p50_latency_s']:6.3f}s "
              f"p99={r['p99_latency_s']:6.3f}s{extra}")

    row(f"cb/{args.backend}", res_cb)
    row("cb/gathered", res_base)
    row("static", res_st)
    speedup = res_cb["tokens_per_s"] / max(res_st["tokens_per_s"], 1e-9)
    print(f"speedup(tokens/s cb vs static) = {speedup:.2f}x")
    fused_speedup = res_cb["tokens_per_s"] / max(res_base["tokens_per_s"],
                                                 1e-9)
    print(f"speedup(tokens/s {args.backend} vs gathered) = "
          f"{fused_speedup:.2f}x")

    sweep = run_context_sweep(cfg, params, args) if args.sweep else []
    prefill_sweep = (run_prefill_sweep(cfg, params, args)
                     if args.prefill_sweep else [])
    shared = (run_shared_prefix(cfg, params, args)
              if args.shared_prefix else None)
    spec_sweep = (run_spec_sweep(cfg, params, args)
                  if args.spec_sweep else None)
    runahead_sweep = (run_runahead_sweep(cfg, params, args)
                      if args.runahead_sweep else None)
    adversarial = (run_adversarial(cfg, params, args)
                   if args.adversarial else None)
    mesh_sweep = run_mesh_sweep(args) if args.mesh_sweep else None

    if args.json:
        import json
        payload = {
            "arch": cfg.name,
            "quant": cfg.quant.method,
            "backend": args.backend,
            "workload": {
                "requests": args.requests, "rate": args.rate,
                "slots": args.slots, "max_len": args.max_len,
                "prompt": [args.prompt_lo, args.prompt_hi],
                "out": [args.out_lo, args.out_hi], "seed": args.seed,
            },
            "continuous": _strip_requests(res_cb),
            "gathered_baseline": _strip_requests(res_base),
            "static": _strip_requests(res_st),
            "speedup_cb_vs_static": speedup,
            "speedup_fused_vs_gathered": fused_speedup,
            "context_sweep": sweep,
            "prefill_sweep": prefill_sweep,
            "shared_prefix": shared,
            "spec_sweep": spec_sweep,
            "runahead_sweep": runahead_sweep,
            "adversarial": adversarial,
            "mesh_sweep": mesh_sweep,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    if shared is not None and not shared["outputs_bit_identical"]:
        return 1   # prefix reuse must never change greedy outputs
    if any(not a["outputs_bit_identical"] for a in prefill_sweep):
        return 1   # the fused prefill must never change greedy outputs
    if spec_sweep is not None and not spec_sweep["outputs_bit_identical"]:
        return 1   # speculation must never change greedy outputs
    if runahead_sweep is not None and \
            not runahead_sweep["outputs_bit_identical"]:
        return 1   # run-ahead must never change greedy outputs
    if adversarial is not None and not adversarial["soak_gate_ok"]:
        return 1   # QoS must beat FCFS on deadline-met goodput under
        # sustained overload — the suite's acceptance gate
    if mesh_sweep is not None and \
            not mesh_sweep["outputs_identical_across_devices"]:
        return 1   # sharding must never change greedy outputs
    # when both engines keep up with the Poisson arrivals, tokens/s
    # converges to the offered load for everyone — the continuous-batching
    # win then shows up as per-request latency, not throughput
    ok = (speedup > 1.0
          or res_cb["p50_latency_s"] < res_st["p50_latency_s"])
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
