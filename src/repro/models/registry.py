"""Uniform Model interface over every architecture family.

``get_model(cfg)`` returns a :class:`Model` bundle of pure functions:
  init(key) -> params
  loss(params, batch) -> (loss, metrics)                 [train step core]
  init_decode_state(batch, max_len) -> state             [concrete zeros]
  prefill(params, batch, state) -> (last_logits, state)
  decode(params, state, token) -> (logits, state)
  input_specs(shape) -> dict[str, ShapeDtypeStruct]      [dry-run stand-ins]

The decode path for attention families runs over the quantized KV cache
(cfg.quant policy — PolarQuant by default); ssm/hybrid use their O(1)
recurrent states (+ ring cache for hybrid local attention).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import transformer as TF
from repro.models import encdec as ED
from repro.models import ssm_lm as SSM
from repro.models import hybrid as HY

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[[Array], dict]
    loss: Callable[[dict, dict], tuple[Array, dict]]
    init_decode_state: Callable[..., Any]
    prefill: Callable[[dict, dict, Any], tuple[Array, Any]]
    decode: Callable[[dict, Any, Array], tuple[Array, Any]]
    input_specs: Callable[[ShapeConfig], dict]
    # --- continuous batching over paged caches (None where unsupported) ---
    # init_paged_state(layout) -> per-segment stacked PagedKVCaches
    # prefill_paged(params, tokens (1,Tp), state, slot, page_row, true_len)
    # prefill_paged_chunk(params, tokens (1,Tc), state, slot, page_row,
    #                     start, chunk_len) — chunked prefill at an offset;
    #                     chunk attention dispatches per cfg.prefill_backend
    #                     (page-native fused kernel vs gathering jnp ref)
    # decode_paged(params, state, token (S,), page_table, active)
    # decode_runahead(params, state, token (S,), page_table, active, key,
    #                 remaining, done, *, horizon, temperature, top_k,
    #                 eos_id) — H fused decode micro-steps with on-device
    #                 sampling + EOS/budget masking in one lax.scan
    #                 dispatch (DESIGN.md §18); returns the (H, S) token
    #                 block plus the carries that seed the next horizon
    # copy_pages(state, src, dst) — COW page copy across segment pools
    # decode_paged_collect / commit_paged — the speculative verify split
    # (sequential reference): collect is decode_paged that also returns
    # per-layer post-RoPE kv; commit re-appends one span position's saved
    # kv. verify_span / commit_span are the batched production pair: all
    # Q span positions in one dispatch + one fused multi-row append
    # (spec/verify.py picks the pair per cfg.decode_backend)
    init_paged_state: Callable[..., Any] | None = None
    prefill_paged: Callable[..., Any] | None = None
    prefill_paged_chunk: Callable[..., Any] | None = None
    decode_paged: Callable[..., Any] | None = None
    decode_runahead: Callable[..., Any] | None = None
    copy_pages: Callable[..., Any] | None = None
    decode_paged_collect: Callable[..., Any] | None = None
    commit_paged: Callable[..., Any] | None = None
    verify_span: Callable[..., Any] | None = None
    commit_span: Callable[..., Any] | None = None
    # cache_layer_bytes(state) -> physical cache bytes per layer (None for
    # families without per-layer KV caches)
    cache_layer_bytes: Callable[[Any], list[int]] | None = None

    def decode_state_specs(self, shape: ShapeConfig):
        """ShapeDtypeStructs of the decode state (no allocation)."""
        return jax.eval_shape(
            lambda: self.init_decode_state(shape.global_batch, shape.seq_len))


def _token_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    b, t = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f = jnp.dtype(cfg.dtype)
    if shape.kind == "train":
        text = t - (cfg.frontend_tokens if cfg.family == "vlm" else 0)
        specs = {"tokens": jax.ShapeDtypeStruct((b, text + 1), i32)}
        if cfg.family == "encdec":
            specs["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_tokens, cfg.frontend_dim), f)
        if cfg.family == "vlm":
            specs["patches"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_tokens, cfg.frontend_dim), f)
        return specs
    if shape.kind == "prefill":
        text = t - (cfg.frontend_tokens if cfg.family == "vlm" else 0)
        specs = {"tokens": jax.ShapeDtypeStruct((b, text), i32)}
        if cfg.family == "encdec":
            specs["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_tokens, cfg.frontend_dim), f)
        if cfg.family == "vlm":
            specs["patches"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_tokens, cfg.frontend_dim), f)
        return specs
    # decode: one new token against a state of size seq_len
    return {"token": jax.ShapeDtypeStruct((b,), i32)}


def get_model(cfg: ModelConfig) -> Model:
    specs = functools.partial(_token_specs, cfg)
    if cfg.family not in ("dense", "moe", "vlm") and not cfg.policy.is_uniform:
        raise ValueError(
            f"per-layer cache policies are not supported for the "
            f"{cfg.family!r} family (its decode state stacks one cache "
            "shape across layers)")
    if cfg.family in ("dense", "moe", "vlm"):
        paged = {}
        # vlm prefill needs the patch frontend; the paged attention path
        # has no sliding-window masking, so windowed configs are excluded
        if cfg.family != "vlm" and cfg.window == 0:
            # the serving programs are named functions: jax.jit calls
            # each compiled program jit_<name>, the name a device trace
            # shows it by
            def prefill(p, toks, s, slot, row, tl):
                return TF.prefill_paged_fn(p, toks, cfg, s, slot, row, tl)

            def prefill_chunk(p, toks, s, slot, row, start, cl):
                return TF.prefill_paged_chunk_fn(p, toks, cfg, s, slot, row,
                                                 start, cl)

            def decode_step(p, s, t, table, active):
                return TF.decode_paged_fn(p, s, t, table, active, cfg)

            def runahead_horizon(p, s, t, table, active, key, rem, done,
                                 horizon, temperature, top_k, eos_id):
                return TF.decode_runahead_fn(
                    p, s, t, table, active, key, rem, done, cfg,
                    horizon=horizon, temperature=temperature, top_k=top_k,
                    eos_id=eos_id)

            def copy_pages(caches, src, dst):
                return TF.copy_state_pages(caches, src, dst)

            paged = dict(
                init_paged_state=lambda layout: TF.init_paged_caches(
                    cfg, layout),
                prefill_paged=prefill,
                prefill_paged_chunk=prefill_chunk,
                decode_paged=decode_step,
                decode_runahead=runahead_horizon,
                copy_pages=copy_pages,
                decode_paged_collect=lambda p, s, t, table, active:
                    TF.decode_paged_collect_fn(p, s, t, table, active, cfg),
                commit_paged=lambda s, kv, table, keep:
                    TF.commit_paged_fn(s, kv, table, keep, cfg),
                verify_span=lambda p, s, t, table, active:
                    TF.verify_span_fn(p, s, t, table, active, cfg),
                commit_span=lambda s, kv, table, n_keep:
                    TF.commit_span_paged_fn(s, kv, table, n_keep, cfg),
            )
        return Model(
            cfg=cfg,
            init=functools.partial(TF.init_params, cfg=cfg),
            loss=lambda p, b, **kw: TF.lm_loss(p, b, cfg, **kw),
            init_decode_state=lambda batch, max_len: TF.init_decode_caches(
                cfg, batch, max_len),
            prefill=lambda p, b, s: TF.prefill_fn(p, b, cfg, s),
            decode=lambda p, s, t: TF.decode_fn(p, s, t, cfg),
            input_specs=specs,
            cache_layer_bytes=lambda state: TF.per_layer_cache_bytes(
                cfg, state),
            **paged,
        )
    if cfg.family == "encdec":
        return Model(
            cfg=cfg,
            init=functools.partial(ED.init_params, cfg=cfg),
            loss=lambda p, b, **kw: ED.lm_loss(p, b, cfg, **kw),
            init_decode_state=lambda batch, max_len: ED.init_decode_state(
                cfg, batch, max_len, cfg.frontend_tokens),
            prefill=lambda p, b, s: ED.prefill_fn(p, b, cfg, s),
            decode=lambda p, s, t: ED.decode_fn(p, s, t, cfg),
            input_specs=specs,
        )
    if cfg.family == "ssm":
        return Model(
            cfg=cfg,
            init=functools.partial(SSM.init_params, cfg=cfg),
            loss=lambda p, b, **kw: SSM.lm_loss(p, b, cfg, **kw),
            init_decode_state=lambda batch, max_len: SSM.init_decode_state(
                cfg, batch),
            prefill=lambda p, b, s: SSM.prefill_fn(p, b, cfg, s),
            decode=lambda p, s, t: SSM.decode_fn(p, s, t, cfg),
            input_specs=specs,
        )
    if cfg.family == "hybrid":
        return Model(
            cfg=cfg,
            init=functools.partial(HY.init_params, cfg=cfg),
            loss=lambda p, b, **kw: HY.lm_loss(p, b, cfg, **kw),
            init_decode_state=lambda batch, max_len: HY.init_decode_state(
                cfg, batch, max_len),
            prefill=lambda p, b, s: HY.prefill_fn(p, b, cfg, s),
            decode=lambda p, s, t: HY.decode_fn(p, s, t, cfg),
            input_specs=specs,
        )
    raise ValueError(f"unknown family {cfg.family!r}")
