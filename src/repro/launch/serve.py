"""Serving launcher: batched generation over the PolarQuant cache.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --smoke --batch 4 --prompt-len 64 --gen 32 \
        --quant polar --rho-bits 4 --theta-bits 4 --value-bits 0

``--engine cb`` swaps in the continuous-batching engine over the paged
cache; ``--prefill-chunk`` enables interleaved chunked prefill and
``--prefix-cache`` shared-prefix page reuse (the launcher then gives every
request a common system-prompt prefix so the hit rate is visible).
``--stream`` drives the same workload open-loop through the streaming
front door (``repro.serve.api.StreamingEngine`` over ``EngineCore.step``):
tokens print the step they are sampled and the summary reports per-token
TTFT / inter-token-latency percentiles from the event stream.

QoS + chaos (DESIGN.md §16) quickstart::

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --smoke --engine cb --batch 4 --gen 16 \
        --tenant-budget 500 --ttft-slo 0.5 --max-pending 8 \
        --chaos "exhaust@8,cancel@12:0.5"

``--tenant-budget``/``--ttft-slo``/``--max-pending`` enable SLA-aware
admission (weighted-fair queueing, deadline shedding, bounded-queue
rejects); ``--chaos`` injects a deterministic fault schedule through the
production scheduler/allocator paths.

Run-ahead fused decode (DESIGN.md §18) quickstart::

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --smoke --engine cb --batch 2 --gen 64 --runahead 8

``--runahead H`` batches H decode micro-steps — paged append, LUT decode
attention, on-device sampling, EOS/budget masking — into one fused
device dispatch whenever the horizon planner sees a pure decode-bound
stretch, and pipelines the next horizon while a block is in flight.
Greedy outputs are bit-identical to ``--runahead 0``.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, reduce_for_smoke
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.serve import (
    ContinuousBatchingEngine, GenerationConfig, Request, ServeEngine,
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--quant", default="polar",
                    choices=["polar", "kivi", "int", "zipcache", "none"])
    ap.add_argument("--rho-bits", type=int, default=4)
    ap.add_argument("--theta-bits", type=int, default=4)
    ap.add_argument("--value-bits", type=int, default=0)
    ap.add_argument("--group-size", type=int, default=0,
                    help="0 = keep config default")
    ap.add_argument("--int8-layers", type=int, default=0,
                    help="mixed policy: run the first N layers at int8 "
                         "(KVTuner-style) and the rest at --quant")
    ap.add_argument("--decode-backend", default="paged_fused",
                    choices=["jnp", "gathered", "paged_fused", "ref",
                             "interpret", "pallas"],
                    help="decode-attention backend (paged_fused = "
                         "page-native fused kernel on the paged path: the "
                         "Pallas kernel on TPU, its jnp oracle elsewhere)")
    ap.add_argument("--prefill-backend", default="paged_fused",
                    choices=["jnp", "paged_fused", "ref", "interpret",
                             "pallas"],
                    help="chunked-prefill attention backend (cb engine "
                         "with --prefill-chunk; paged_fused = page-native "
                         "fused kernel over the quantized prefix pages)")
    ap.add_argument("--engine", default="static", choices=["static", "cb"],
                    help="static = one-shot batched ServeEngine; cb = "
                         "continuous batching over the paged cache")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="cb engine: chunked-prefill size in tokens "
                         "(0 = one-shot prefill; rounded up to the page "
                         "size)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="cb engine: shared-prefix page reuse (implies "
                         "chunked prefill)")
    ap.add_argument("--stream", action="store_true",
                    help="cb engine: serve through the streaming API, "
                         "printing tokens as they arrive and per-token "
                         "TTFT/ITL percentiles")
    ap.add_argument("--spec-mode", default="off",
                    choices=["off", "ngram", "draft"],
                    help="cb engine: speculative multi-token decode — "
                         "ngram self-speculation or a smaller draft model "
                         "(greedy outputs stay bit-identical to off)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens per speculative step")
    ap.add_argument("--shared-prefix-len", type=int, default=64,
                    help="cb engine: common system-prompt length prepended "
                         "to every request (demo workload for "
                         "--prefix-cache)")
    ap.add_argument("--tenant-budget", type=float, default=0.0,
                    help="cb engine: per-tenant token-bucket budget in "
                         "tokens/s of engine time (0 = unlimited); enables "
                         "QoS weighted-fair admission")
    ap.add_argument("--ttft-slo", type=float, default=0.0,
                    help="cb engine: session TTFT deadline in seconds — "
                         "requests whose deadline is blown or unmeetable "
                         "are shed with an explicit event (0 = off)")
    ap.add_argument("--max-pending", type=int, default=0,
                    help="cb engine: bounded admission queue — intake over "
                         "this depth rejects with an explicit event "
                         "(0 = unbounded)")
    ap.add_argument("--runahead", type=int, default=0,
                    help="cb engine: run-ahead fused decode horizon H — "
                         "batch H decode micro-steps with on-device "
                         "sampling into one dispatch in decode-bound "
                         "stretches (0/1 = off; greedy outputs stay "
                         "bit-identical)")
    ap.add_argument("--chaos", default="",
                    help="cb engine: deterministic fault injection spec, "
                         "e.g. 'exhaust@8,slow@5:0.05,cancel@12:0.5,"
                         "proposer@0.3' (see repro.serve.chaos)")
    ap.add_argument("--mesh-shape", default="",
                    help="thread a device mesh through the engine, e.g. "
                         "'1x2' = (data=1, model=2): KV page pools and "
                         "attention heads shard over the model axis where "
                         "divisible (DESIGN.md §17). Multi-device CPU runs "
                         "need XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N set before launch")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    devs = jax.devices()
    print(f"[serve] platform={devs[0].platform} "
          f"device_kind={devs[0].device_kind} devices={len(devs)}")
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    qkw = dict(method=args.quant, rho_bits=args.rho_bits,
               theta_bits=args.theta_bits, value_bits=args.value_bits)
    if args.group_size:
        qkw["group_size"] = args.group_size
    quant = dataclasses.replace(cfg.quant, **qkw)
    policy = None
    if args.int8_layers > 0:
        from repro.core import CachePolicy
        policy = CachePolicy.first_k(
            args.int8_layers,
            dataclasses.replace(quant, method="int", key_bits=8),
            quant)
    cfg = dataclasses.replace(cfg, quant=quant, cache_policy=policy,
                              decode_backend=args.decode_backend,
                              prefill_backend=args.prefill_backend)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))

    mesh = None
    if args.mesh_shape:
        from repro.launch.mesh import make_mesh
        try:
            shape = tuple(int(x) for x in args.mesh_shape.split("x"))
        except ValueError:
            raise SystemExit(f"bad --mesh-shape {args.mesh_shape!r}; "
                             "expected e.g. '1x2' (data x model)")
        if len(shape) != 2:
            raise SystemExit("--mesh-shape takes two axes: data x model")
        mesh = make_mesh(shape, ("data", "model"))
        print(f"[serve] mesh data={shape[0]} model={shape[1]} over "
              f"{jax.device_count()} devices "
              f"(kv_heads={cfg.num_kv_heads}: "
              f"{'head-sharded' if cfg.num_kv_heads % shape[1] == 0 else 'replicated fallback'})")

    rng = np.random.default_rng(args.seed)
    batch = {"tokens": rng.integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len)).astype(np.int32)}
    if cfg.family == "encdec":
        batch["frames"] = rng.standard_normal(
            (args.batch, cfg.frontend_tokens, cfg.frontend_dim)).astype(np.float32)
    if cfg.family == "vlm":
        batch["patches"] = rng.standard_normal(
            (args.batch, cfg.frontend_tokens, cfg.frontend_dim)).astype(np.float32)

    print(f"[serve] {cfg.name} quant={args.quant} bits/key-elem="
          f"{cfg.policy.avg_key_bits(cfg.num_layers, cfg.head_dim):.2f}")
    if args.engine == "cb":
        shared = rng.integers(0, cfg.vocab_size,
                              (args.shared_prefix_len,)).astype(np.int32)
        # the first request arrives alone so its prefill registers the
        # shared prefix's pages before the rest admit (simulated clock:
        # the idle gap is jumped, not slept)
        reqs = [Request(rid=i,
                        prompt=np.concatenate([shared, batch["tokens"][i]]),
                        max_new_tokens=args.gen,
                        arrival_time=0.0 if i == 0 else 100.0 + 0.01 * i)
                for i in range(args.batch)]
        spec = None
        if args.spec_mode != "off":
            from repro.spec import SpecConfig
            spec = SpecConfig(mode=args.spec_mode, k=args.spec_k)
        qos = None
        if args.tenant_budget > 0 or args.ttft_slo > 0 or \
                args.max_pending > 0:
            from repro.serve import QosConfig
            qos = QosConfig(tenant_budget=args.tenant_budget,
                            ttft_slo=args.ttft_slo,
                            max_pending=args.max_pending)
            print(f"[serve] qos: budget={args.tenant_budget} tok/s  "
                  f"ttft-slo={args.ttft_slo}s  "
                  f"max-pending={args.max_pending}")
        chaos = None
        if args.chaos:
            from repro.serve import ChaosConfig, ChaosInjector
            chaos = ChaosInjector(ChaosConfig.parse(args.chaos,
                                                    seed=args.seed))
            print(f"[serve] chaos: {chaos.cfg}")
        eng = ContinuousBatchingEngine(
            model, params, max_slots=args.batch, max_len=args.max_len,
            mesh=mesh, prefix_cache=args.prefix_cache,
            prefill_chunk=args.prefill_chunk, spec=spec,
            qos=qos, chaos=chaos, runahead=args.runahead)
        eng.warmup([r.prompt_len for r in reqs] + [args.max_len],
                   GenerationConfig(max_new_tokens=args.gen))
        gen = GenerationConfig(max_new_tokens=args.gen,
                               temperature=args.temperature, seed=args.seed)
        if args.stream:
            from repro.serve import StreamingEngine, stream_latency_stats
            stream = StreamingEngine(eng, gen)
            for r in reqs:
                stream.submit(r)
            texts: dict[int, list] = {r.rid: [] for r in reqs}
            events = []
            for ev in stream.events():
                events.append(ev)
                if ev.kind in ("first_token", "token"):
                    texts[ev.rid].append(ev.token)
                    print(f"[stream] t={ev.t * 1e3:8.1f}ms rid={ev.rid} "
                          f"slot={ev.slot} +{ev.token}")
                elif ev.kind == "preempt":
                    # the victim's last streamed token is retracted and
                    # re-sampled when it resumes
                    if texts[ev.rid]:
                        texts[ev.rid].pop()
                    print(f"[stream] t={ev.t * 1e3:8.1f}ms rid={ev.rid} "
                          f"preempt (-{ev.token})")
                else:
                    print(f"[stream] t={ev.t * 1e3:8.1f}ms rid={ev.rid} "
                          f"{ev.kind}")
            out = stream.result()
            lat = stream_latency_stats(events, reqs)
            print(f"[serve] streamed {out['total_tokens']} tokens  "
                  f"{out['tokens_per_s']:.1f} tok/s  "
                  f"ttft p50 {lat['ttft_s']['p50'] * 1e3:.1f}ms "
                  f"p99 {lat['ttft_s']['p99'] * 1e3:.1f}ms  "
                  f"itl p50 {lat['itl_s']['p50'] * 1e3:.1f}ms "
                  f"p99 {lat['itl_s']['p99'] * 1e3:.1f}ms")
            if "spec" in out:
                sp = out["spec"]
                print(f"[serve] spec mode={sp['mode']} k={sp['k']}  "
                      f"acceptance {sp['acceptance_rate'] * 100:.1f}%  "
                      f"({sp['accepted_tokens']}/{sp['drafted_tokens']} "
                      "drafts)")
                print(f"[serve] spec mean accepted/step "
                      f"{sp['mean_accepted_per_step']:.2f} over "
                      f"{sp['steps']} speculative steps")
            print(f"[serve] first sequence: {texts[reqs[0].rid]}")
            return 0
        out = eng.run(reqs, gen)
        print(f"[serve] cb decode {out['tokens_per_s']:.1f} tok/s  "
              f"p50 {out['p50_latency_s'] * 1e3:.1f}ms  "
              f"cache {out['cache_bytes'] / 2**20:.2f} MiB  "
              f"prefill-chunk {out['prefill_chunk']}")
        print(f"[serve] {out['decode_steps']} decode steps  "
              f"{out['prefill_chunks']} prefill chunks  "
              f"host sync {out['host_sync_s'] * 1e3:.1f}ms")
        if "qos" in out:
            print(f"[serve] qos: {out['n_shed']} shed  "
                  f"{out['n_rejected']} rejected  "
                  f"prefill-rate-est {out['qos']['prefill_rate_est']}")
        if "chaos" in out:
            print(f"[serve] chaos: {out['chaos']}")
        if "spec" in out:
            sp = out["spec"]
            print(f"[serve] spec mode={sp['mode']} k={sp['k']}  "
                  f"acceptance {sp['acceptance_rate'] * 100:.1f}%  "
                  f"mean accepted/step {sp['mean_accepted_per_step']:.2f}")
        if "runahead" in out:
            ra = out["runahead"]
            print(f"[serve] runahead h={ra['h']}  "
                  f"{ra['horizons']} horizons  "
                  f"{ra['tokens']} horizon tokens")
        if args.prefix_cache:
            print(f"[serve] prefix hit rate "
                  f"{out['prefix_hit_rate'] * 100:.1f}%  "
                  f"({out['prefill_tokens_skipped']} prompt tokens "
                  f"skipped, {out['adopted_pages']} pages adopted, "
                  f"{out['prefix_pool_bytes_saved'] / 2**20:.2f} MiB "
                  "pool bytes shared)")
        first = out["requests"][0].out_tokens
        print(f"[serve] first sequence: {first}")
        return 0
    skw = {}
    if mesh is not None:
        from repro.distributed.sharding import serving_rules
        skw = dict(mesh=mesh, rules=serving_rules(cfg, mesh, args.batch))
    eng = ServeEngine(model, params, max_len=args.max_len, **skw)
    out = eng.generate(batch, GenerationConfig(
        max_new_tokens=args.gen, temperature=args.temperature, seed=args.seed))
    print(f"[serve] prefill {out['prefill_s'] * 1e3:.1f}ms  "
          f"decode {out['tokens_per_s']:.1f} tok/s  "
          f"cache {out['cache_bytes'] / 2**20:.2f} MiB")
    print(f"[serve] first sequence: {out['tokens'][0].tolist()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
