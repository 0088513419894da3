"""Smoke run of the paged serving path on the TPU: tinyllama-1.1b at its
published widths, PolarQuant keys, the paged Pallas kernels.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # a (1, 4) mesh against one chip

This is a smoke run, not a benchmark: it proves that the system's main
path starts, serves and agrees with its reference on the chip, and prints
what it saw along the way (device, compile seconds, tokens/s, peak HBM).
The weights are random, made from ``--seed``.

One chip, in order:

1. Compile the decode step and the prefill-chunk step (``paged_fused``)
   and check that both programs hold the Pallas kernel (``tpu_custom_call``).
2. Teacher-forced logits: prefill two prompts chunk by chunk, then decode
   a few steps, through the Pallas kernels (``paged_fused``) and through
   their jnp oracles (``ref``), both on this chip, in float32 and in
   bfloat16 activations; see :data:`F32_REL_RMS_TOL` and
   :data:`BF16_NOISE_FACTOR` for what must agree and how closely.
3. Serve eight requests (512-1536 prompt tokens, 64 new tokens each) on
   four slots through ``ContinuousBatchingEngine`` with 256-token prefill
   chunks; every request must finish with all its tokens.

With ``--chips 4`` it runs only the sharded path and what it is compared
with, in one process: the same teacher-forced logits and the same
requests, meshless on one chip and on a (1, 4) mesh whose page pools are
split over the four chips' KV heads. The logits must agree within the
same tolerances; the greedy-output digests are compared and, where they
differ, the first divergence is printed.

It exits non-zero when JAX finds no TPU, and when any phase fails. The
last line of its output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys
import time

ARCH = "tinyllama-1.1b"

#: Logits are compared as relative RMS errors, ||got - ref|| / ||ref||,
#: per logit vector (one after each prefill chunk and each decode step),
#: and the largest one counts. A wrong page, mask or scale moves logits by
#: the order of their own RMS, far beyond either bound below.
#:
#: float32 activations at matmul precision "highest": both sides compute
#: the same attention to f32 roundoff (kernel vs oracle agree to ~1e-5
#: relative per call on v5e), and 22 layers of random weights amplify that
#: to about 7e-4 in the logits. 2**-7 leaves 10x above that.
F32_REL_RMS_TOL = 2.0 ** -7
#: bfloat16 activations, as served: every activation and weight is rounded
#: to bf16 (unit roundoff 2**-9), and the random layers spread those
#: roundings to a few 1e-2 in the logits, whichever attention code runs.
#: So the bf16 path under test is held to the float32 reference's answer
#: no worse than BF16_NOISE_FACTOR times the bf16 oracle's own distance
#: from it: the checked code may add no error the size of bf16 rounding.
BF16_NOISE_FACTOR = 2.0


@dataclasses.dataclass(frozen=True)
class Plan:
    """What one smoke run serves and compares."""
    slots: int = 4
    max_len: int = 2048
    chunk: int = 256                       # prefill chunk, tokens
    requests: int = 8
    prompt_range: tuple = (512, 1536)      # prompt tokens, inclusive
    new_tokens: int = 64
    forced_prompts: tuple = ((0, 700), (2, 300))   # (slot, prompt tokens)
    forced_steps: int = 8                  # teacher-forced decode steps


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def build(base, kernel_backend: str, dtype: str | None = None):
    """``base`` config with polar 4+4 keys (group == page) and both paged
    attention paths on ``kernel_backend``, activations in ``dtype``
    (default: the config's serving dtype) -> (config, model)."""
    from repro.models import get_model

    quant = dataclasses.replace(base.quant, method="polar", rho_bits=4,
                                theta_bits=4)
    cfg = dataclasses.replace(base, quant=quant, dtype=dtype or base.dtype,
                              decode_backend=kernel_backend,
                              prefill_backend=kernel_backend)
    return cfg, get_model(cfg)


def layout_for(cfg, plan: Plan):
    from repro.core.cache_layout import PagedLayout

    g = cfg.quant.group_size
    per_slot = -(-plan.max_len // g)
    return PagedLayout(page_size=g, num_pages=plan.slots * per_slot,
                       slots=plan.slots, pages_per_slot=per_slot)


def check_kernels_compiled(model, params, layout, chunk: int) -> dict:
    """Compile the decode step and the prefill-chunk step at full table
    width; each program must hold the Pallas kernel."""
    import jax
    import jax.numpy as jnp

    state = jax.eval_shape(lambda: model.init_paged_state(layout))
    s, n = layout.slots, layout.pages_per_slot
    out = {}
    t0 = time.monotonic()
    dec = jax.jit(model.decode_paged, donate_argnums=(1,)).lower(
        params, state, jnp.zeros((s,), jnp.int32),
        jnp.zeros((s, n), jnp.int32), jnp.zeros((s,), bool)).compile()
    out["decode_compile_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    pre = jax.jit(model.prefill_paged_chunk, donate_argnums=(2,)).lower(
        params, jnp.zeros((1, chunk), jnp.int32), state,
        jnp.zeros((), jnp.int32), jnp.zeros((n,), jnp.int32),
        jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)).compile()
    out["prefill_chunk_compile_s"] = time.monotonic() - t0
    for name, prog in (("decode", dec), ("prefill_chunk", pre)):
        if "tpu_custom_call" not in prog.as_text():
            raise RuntimeError(f"the compiled {name} step holds no Pallas "
                               "kernel (no tpu_custom_call)")
    return out


def forced_logits(model, params, layout, prompts, steps: int, chunk: int,
                  tokens=None, mesh=None):
    """Prefill ``prompts`` ({slot: token array}) chunk by chunk into
    fragmented page rows, then decode ``steps`` steps over those slots.

    Decode feeds ``tokens`` (one (S,) vector per step) when given, else
    this run's own greedy choices, so a second run can be forced onto the
    first one's tokens. Returns (list of (·, V) fp32 logit arrays, the
    decode token vectors used). With ``mesh`` the run is placed exactly as
    ``EngineCore`` places it: replicated params, KV-head-sharded pools.
    """
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed import ctx
    from repro.distributed import serving as dsrv
    from repro.distributed.sharding import serving_rules

    s, n, g = layout.slots, layout.pages_per_slot, layout.page_size
    state = model.init_paged_state(layout)
    scope = contextlib.nullcontext()
    if mesh is not None:
        rules = serving_rules(model.cfg, mesh, s)
        params = jax.device_put(params, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()))
        state = dsrv.shard_paged_state(state, mesh, rules["kv_heads"])
        scope = ctx.use_sharding(mesh, rules)
    # every slot's row is a scattered, non-monotonic run of pool pages;
    # unused entries hold the scratch page, as the allocator leaves them
    perm = np.random.default_rng(1).permutation(layout.num_pages)
    table = np.full((s, n), layout.scratch_page, np.int32)
    for i, (slot, prompt) in enumerate(sorted(prompts.items())):
        need = -(-(len(prompt) + steps) // g)
        table[slot, :need] = perm[i * n:i * n + need]
    prefill = jax.jit(model.prefill_paged_chunk, donate_argnums=(2,))
    decode = jax.jit(model.decode_paged, donate_argnums=(1,))
    logits, used = [], []
    nxt = np.zeros((s,), np.int32)
    active = np.zeros((s,), bool)
    with scope:
        for slot, prompt in sorted(prompts.items()):
            for off in range(0, len(prompt), chunk):
                clen = min(chunk, len(prompt) - off)
                toks = np.zeros((1, chunk), np.int32)
                toks[0, :clen] = prompt[off:off + clen]
                lg, state = prefill(params, jnp.asarray(toks), state,
                                    jnp.asarray(slot, jnp.int32),
                                    jnp.asarray(table[slot]),
                                    jnp.asarray(off, jnp.int32),
                                    jnp.asarray(clen, jnp.int32))
                lg = np.asarray(lg, np.float32)
                logits.append(lg)
            nxt[slot] = int(lg[0].argmax())
            active[slot] = True
        for i in range(steps):
            tok = tokens[i] if tokens is not None else nxt.copy()
            used.append(tok)
            lg, state = decode(params, state, jnp.asarray(tok),
                               jnp.asarray(table), jnp.asarray(active))
            lg = np.asarray(lg, np.float32)
            logits.append(lg[active])
            nxt = np.where(active, lg.argmax(-1), 0).astype(np.int32)
    return logits, used


def rel_rms_error(ref, got) -> float:
    """Largest relative RMS error over paired logit vectors (see
    :data:`F32_REL_RMS_TOL`)."""
    import numpy as np

    return max(float(np.sqrt(np.mean((b - a) ** 2)) / np.sqrt(np.mean(a * a)))
               for a, b in zip(ref, got, strict=True))


def argmax_agreement(ref, got) -> str:
    agree = sum(int((a.argmax(-1) == b.argmax(-1)).sum())
                for a, b in zip(ref, got, strict=True))
    return f"{agree}/{sum(a.shape[0] for a in ref)}"


def check_logits(base, plan: Plan, params, ref_backend: str,
                 got_backend: str, label: str, seed: int,
                 mesh=None) -> None:
    """Teacher-forced logits of ``got_backend`` (on ``mesh``, if given)
    against ``ref_backend`` without a mesh, in float32 and in bfloat16
    activations, every run forced onto the float32 reference's greedy
    tokens. Fails past :data:`F32_REL_RMS_TOL` or
    :data:`BF16_NOISE_FACTOR`."""
    import jax
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    prompts = {slot: rng.integers(0, base.vocab_size, (length,), np.int32)
               for slot, length in plan.forced_prompts}

    def run(backend, dtype, tokens=None, mesh=None):
        cfg, model = build(base, backend, dtype)
        return forced_logits(model, params, layout_for(cfg, plan), prompts,
                             plan.forced_steps, plan.chunk, tokens=tokens,
                             mesh=mesh)

    with jax.default_matmul_precision("highest"):
        exact, toks = run(ref_backend, "float32")
        got32, _ = run(got_backend, "float32", toks, mesh)
    ref16, _ = run(ref_backend, "bfloat16", toks)
    got16, _ = run(got_backend, "bfloat16", toks, mesh)
    e32 = rel_rms_error(exact, got32)
    floor16 = rel_rms_error(exact, ref16)
    e16 = rel_rms_error(exact, got16)
    log(f"{label}, float32: max rel-RMS logit error {e32:.3e} (tolerance "
        f"{F32_REL_RMS_TOL:.3e}), argmax agrees "
        f"{argmax_agreement(exact, got32)}")
    log(f"{label}, bfloat16: max rel-RMS logit error from the float32 "
        f"reference {e16:.3e}, the bfloat16 reference's own {floor16:.3e} "
        f"(tolerance {BF16_NOISE_FACTOR:g}x that = "
        f"{BF16_NOISE_FACTOR * floor16:.3e}); against the bfloat16 "
        f"reference {rel_rms_error(ref16, got16):.3e}, argmax agrees "
        f"{argmax_agreement(ref16, got16)}")
    if not e32 <= F32_REL_RMS_TOL:
        raise RuntimeError(f"{label}: float32 logits differ beyond "
                           "tolerance")
    if not e16 <= BF16_NOISE_FACTOR * floor16:
        raise RuntimeError(f"{label}: bfloat16 logits differ beyond "
                           "tolerance")


def make_requests(cfg, plan: Plan, seed: int):
    import numpy as np

    from repro.serve import Request

    rng = np.random.default_rng(seed)
    lo, hi = plan.prompt_range
    lens = rng.integers(lo, hi + 1, plan.requests)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, (int(t),),
                                               np.int32),
                    max_new_tokens=plan.new_tokens)
            for i, t in enumerate(lens)]


def serve(model, params, requests, plan: Plan, mesh=None,
          label: str = "serve") -> dict:
    """Serve ``requests`` to completion through the continuous-batching
    engine; every request must come back with all its tokens."""
    from repro.serve import ContinuousBatchingEngine, GenerationConfig

    gen = GenerationConfig(max_new_tokens=plan.new_tokens, eos_id=-1)
    eng = ContinuousBatchingEngine(model, params, max_slots=plan.slots,
                                   max_len=plan.max_len,
                                   prefill_chunk=plan.chunk, mesh=mesh)
    t0 = time.monotonic()
    eng.warmup([r.prompt_len for r in requests], gen)
    warm_s = time.monotonic() - t0
    fresh = [dataclasses.replace(r, out_tokens=[]) for r in requests]
    t0 = time.monotonic()
    res = eng.run(fresh, gen)
    wall = time.monotonic() - t0
    done = {r.rid: list(r.out_tokens) for r in res["requests"]}
    short = [r.rid for r in requests
             if len(done.get(r.rid, ())) != plan.new_tokens]
    if short:
        raise RuntimeError(f"{label}: requests {short} did not finish with "
                           f"{plan.new_tokens} tokens")
    outs = sorted(done.items())
    digest = hashlib.sha256(json.dumps(outs).encode()).hexdigest()[:16]
    toks = sum(len(v) for v in done.values())
    prompt_toks = sum(r.prompt_len for r in requests)
    log(f"{label} (smoke run, not a benchmark): {len(outs)} requests, "
        f"{prompt_toks} prompt + {toks} generated tokens in {wall:.3f} s "
        f"wall = {toks / wall:.1f} generated tok/s; warmup (compile) "
        f"{warm_s:.1f} s; engine clock {res['wall_s']:.3f} s; "
        f"mean decode step {res['decode_step_s_mean'] * 1e3:.2f} ms; "
        f"greedy digest {digest}")
    return {"engine": eng, "digest": digest, "outputs": outs}


def check_pools_split(state, mesh) -> None:
    """Every page-pool leaf must be split over the mesh's devices along its
    KV-head axis, not placed whole on one device."""
    import jax

    n_dev = mesh.devices.size
    checked = 0
    for leaf in jax.tree_util.tree_leaves(state):
        if leaf.ndim < 4:
            continue            # lengths: replicated by design
        devs = {sh.device for sh in leaf.addressable_shards}
        heads = leaf.shape[leaf.ndim - 3]
        if heads % n_dev:
            raise RuntimeError(f"{heads} KV heads do not split over "
                               f"{n_dev} devices")
        shard_heads = {sh.data.shape[leaf.ndim - 3]
                       for sh in leaf.addressable_shards}
        if len(devs) != n_dev or shard_heads != {heads // n_dev}:
            raise RuntimeError(
                f"pool leaf {leaf.shape} sits on {len(devs)} device(s) with "
                f"{sorted(shard_heads)} heads per shard; expected "
                f"{heads // n_dev} heads on each of {n_dev}")
        checked += 1
    if not checked:
        raise RuntimeError("no page-pool leaves found in the engine state")
    log(f"page pools split over {n_dev} devices: {checked} pool leaves, "
        f"each device holding 1/{n_dev} of the KV heads")


def first_divergence(a_outs, b_outs):
    for (rid, a), (_, b) in zip(a_outs, b_outs):
        for i, (x, y) in enumerate(zip(a, b)):
            if x != y:
                return {"rid": rid, "token_index": i}
    return None


def peak_hbm(devices) -> str:
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(f"{d.id}:{st.get('peak_bytes_in_use', 0) / 2**30:.2f}"
                     f"/{st.get('bytes_limit', 0) / 2**30:.2f} GiB")
    return ", ".join(parts)


def run_one_chip(base, params, plan: Plan, seed: int,
                 kernel: str = "paged_fused") -> None:
    cfg, model = build(base, kernel)
    if kernel == "paged_fused":
        t = check_kernels_compiled(model, params, layout_for(cfg, plan),
                                   plan.chunk)
        log(f"compile: decode step {t['decode_compile_s']:.1f} s, "
            f"prefill-chunk step {t['prefill_chunk_compile_s']:.1f} s; "
            "both hold tpu_custom_call")
    check_logits(base, plan, params, "ref", kernel, "pallas vs ref", seed)
    serve(model, params, make_requests(cfg, plan, seed), plan,
          label=f"serve {kernel}")


def run_mesh(base, params, plan: Plan, seed: int, n: int,
             kernel: str = "paged_fused") -> None:
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, n), ("data", "model"))
    log(f"mesh data=1 model={n} (kv_heads={base.num_kv_heads})")
    check_logits(base, plan, params, kernel, kernel, f"mesh 1x{n} vs one "
                 "chip", seed, mesh=mesh)
    cfg, model = build(base, kernel)
    reqs = make_requests(cfg, plan, seed)
    one = serve(model, params, reqs, plan, label="serve one chip")
    many = serve(model, params, reqs, plan, mesh=mesh,
                 label=f"serve mesh 1x{n}")
    check_pools_split(many["engine"].core.state, mesh)
    if one["digest"] == many["digest"]:
        log("greedy digests identical: mesh and one chip served the same "
            "tokens")
    else:
        log(f"greedy digests differ; first divergence "
            f"{first_divergence(one['outputs'], many['outputs'])} — the "
            "teacher-forced logits above are within tolerance")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip smoke run; 4: only the (1, 4) "
                         "mesh run and the one-chip run it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r}); this run needs the chip",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} chip(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    log(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
        f"devices={len(devs)} compile cache {cache_dir}")

    base = get_config(ARCH)
    cfg, model = build(base, "paged_fused")
    log(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.num_heads}/{cfg.num_kv_heads} heads, head_dim "
        f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{cfg.dtype} activations, keys polar {cfg.quant.rho_bits}+"
        f"{cfg.quant.theta_bits} group {cfg.quant.group_size}")
    t0 = time.monotonic()
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    log(f"random weights from seed {args.seed}: "
        f"{cfg.param_count() / 1e9:.3f} B params in "
        f"{time.monotonic() - t0:.1f} s")

    if args.chips == 1:
        run_one_chip(base, params, Plan(), args.seed)
    else:
        run_mesh(base, params, Plan(), args.seed, args.chips)
    log(f"peak HBM per device (memory_stats): {peak_hbm(devs[:args.chips])}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
