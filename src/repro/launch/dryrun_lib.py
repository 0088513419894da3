"""Dry-run library: lower + compile every (arch x shape) on a given mesh.

Used by ``dryrun.py`` (which force-creates 512 host devices BEFORE any jax
import) and by tests (on small meshes). For each cell we:

  1. build ShapeDtypeStruct stand-ins for every step input (no allocation),
  2. jit with explicit in/out shardings and ``.lower().compile()``,
  3. record ``memory_analysis()`` (fits-in-HBM proof), ``cost_analysis()``
     (FLOPs/bytes for the roofline), and the per-collective byte census
     parsed from the partitioned HLO.

Skip table (DESIGN.md §5): ``long_500k`` needs sub-quadratic attention —
only ssm/hybrid run it; every other cell must compile or the cell FAILS.
"""
from __future__ import annotations

import json
import os
import re
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed import ctx
from repro.distributed import sharding as shd
from repro.launch.chips import DRYRUN_TARGET, chip_peaks
from repro.models.registry import Model, get_model
from repro.train.train_step import StepConfig, lower_train_step

# the chip the dry-run's roofline terms are computed against
TARGET = chip_peaks(DRYRUN_TARGET)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\]))\S*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

# bytes-on-wire multiplier per collective kind (ring algorithms ~ 1x the
# payload per chip; all-reduce = reduce-scatter + all-gather ~ 2x)
_COLL_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}


def _shape_bytes(type_str: str) -> int:
    """'bf16[2,512,128]' -> bytes; '(f32[..], f32[..])' -> sum."""
    total = 0
    for m in re.finditer(r"([a-z0-9]+)\[([0-9,]*)\]", type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_census(hlo_text: str) -> dict:
    """Per-kind op counts and wire bytes (per chip) from partitioned HLO."""
    out: dict[str, dict[str, float]] = {}
    for m in _COLL_RE.finditer(hlo_text):
        type_str, kind = m.group(1), m.group(2)
        b = _shape_bytes(type_str) * _COLL_FACTOR[kind]
        rec = out.setdefault(kind, {"count": 0, "bytes": 0.0})
        rec["count"] += 1
        rec["bytes"] += b
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def should_skip(cfg: ModelConfig, shape: ShapeConfig) -> Optional[str]:
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return ("full-attention arch: long_500k requires sub-quadratic "
                "attention (skip noted in DESIGN.md §5)")
    return None


# ---------------------------------------------------------------------------
# Lowering per shape kind
# ---------------------------------------------------------------------------


def _decode_in_specs(model: Model, shape: ShapeConfig, mesh: Mesh):
    cfg = model.cfg
    state_shapes = model.decode_state_specs(shape)
    state_pspecs = shd.decode_state_pspecs(state_shapes, mesh,
                                           shape.global_batch)
    param_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    param_pspecs = shd.param_pspecs(param_shapes, mesh, cfg)
    mk = lambda sh, sp: jax.ShapeDtypeStruct(
        sh.shape, sh.dtype, sharding=NamedSharding(mesh, sp))
    params_in = jax.tree_util.tree_map(mk, param_shapes, param_pspecs,
                                       is_leaf=lambda x: isinstance(
                                           x, (jax.ShapeDtypeStruct, P)))
    state_in = jax.tree_util.tree_map(mk, state_shapes, state_pspecs,
                                      is_leaf=lambda x: isinstance(
                                          x, (jax.ShapeDtypeStruct, P)))
    daxes = [a for a in ("pod", "data") if a in mesh.shape]
    dp = int(np.prod([mesh.shape[a] for a in daxes])) if daxes else 1
    tok_spec = P(tuple(daxes) if len(daxes) > 1 else (daxes[0] if daxes else None)) \
        if shape.global_batch % max(dp, 1) == 0 else P(None)
    token_in = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32,
                                    sharding=NamedSharding(mesh, tok_spec))
    return params_in, state_in, token_in, (param_pspecs, state_pspecs, tok_spec)


def lower_cell(arch: str, shape_name: str, mesh: Mesh,
               step_cfg: StepConfig = StepConfig(),
               shape_override: Optional[ShapeConfig] = None,
               quant_override: Optional[dict] = None,
               rules_override: Optional[dict] = None,
               cfg_override: Optional[dict] = None):
    """Lower one (arch x shape) on ``mesh``. Returns jax.stages.Lowered.

    ``quant_override``: dataclasses.replace kwargs applied to cfg.quant
    (e.g. {'lut_impl': 'gather', 'value_bits': 2}) — used by the §Perf A/Bs.
    ``rules_override``: logical-rule overrides (e.g. {'ssm_heads': None}).
    """
    import dataclasses
    cfg = get_config(arch)
    if cfg_override:
        cfg = dataclasses.replace(cfg, **cfg_override)
    if quant_override:
        cfg = dataclasses.replace(
            cfg, quant=dataclasses.replace(cfg.quant, **quant_override))
    shape = shape_override or SHAPES[shape_name]
    skip = should_skip(cfg, shape)
    if skip:
        raise SkipCell(skip)
    model = get_model(cfg)
    rules = shd.logical_rules(cfg, mesh, shape.global_batch)
    if rules_override:
        rules = dict(rules, **rules_override)

    with ctx.use_sharding(mesh, rules):
        if shape.kind == "train":
            return lower_train_step(model, mesh, step_cfg,
                                    shape.global_batch,
                                    model.input_specs(shape))
        if shape.kind == "prefill":
            params_in, state_in, _, (pp, sp, _) = _decode_in_specs(
                model, shape, mesh)
            batch_specs = model.input_specs(shape)
            bspecs = shd.batch_pspecs(batch_specs, mesh, shape.global_batch)
            batch_in = {k: jax.ShapeDtypeStruct(
                v.shape, v.dtype, sharding=NamedSharding(mesh, bspecs[k]))
                for k, v in batch_specs.items()}

            def prefill_step(params, batch, state):
                with ctx.use_sharding(mesh, rules):
                    return model.prefill(params, batch, state)

            fn = jax.jit(prefill_step, donate_argnums=(2,))
            return fn.lower(params_in, batch_in, state_in)

        # decode
        params_in, state_in, token_in, _ = _decode_in_specs(model, shape, mesh)

        def serve_step(params, state, token):
            with ctx.use_sharding(mesh, rules):
                return model.decode(params, state, token)

        fn = jax.jit(serve_step, donate_argnums=(1,))
        return fn.lower(params_in, state_in, token_in)


class SkipCell(Exception):
    pass


def run_cell(arch: str, shape_name: str, mesh: Mesh, out_dir: str,
             mesh_tag: str, step_cfg: StepConfig = StepConfig(),
             shape_override: Optional[ShapeConfig] = None,
             hbm_limit: float = 16e9, variant: str = "",
             **lower_kw) -> dict:
    """Lower + compile one cell; dump the JSON record. Raises on failure."""
    t0 = time.monotonic()
    rec: dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_tag, "variant": variant,
                           "devices": int(np.prod(list(mesh.shape.values())))}
    try:
        lowered = lower_cell(arch, shape_name, mesh, step_cfg, shape_override,
                             **lower_kw)
    except SkipCell as e:
        rec["status"] = "skip"
        rec["reason"] = str(e)
        _dump(rec, out_dir, mesh_tag, arch, shape_name)
        return rec
    rec["lower_s"] = time.monotonic() - t0

    t0 = time.monotonic()
    compiled = lowered.compile()
    rec["compile_s"] = time.monotonic() - t0

    mem = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes",
                                        None),
    }
    arg_b = rec["memory"]["argument_bytes"] or 0
    tmp_b = rec["memory"]["temp_bytes"] or 0
    out_b = rec["memory"]["output_bytes"] or 0
    alias_b = rec["memory"]["alias_bytes"] or 0
    rec["memory"]["peak_per_device"] = arg_b + tmp_b + out_b - alias_b
    rec["memory"]["fits_16g_hbm"] = bool(
        rec["memory"]["peak_per_device"] <= hbm_limit)

    # XLA's cost_analysis counts while (scan) bodies ONCE — kept only for
    # reference. The loop-aware static model (hlo_cost) is authoritative.
    cost = compiled.cost_analysis() or {}
    rec["cost_xla_raw"] = {k: float(v) for k, v in cost.items()
                           if isinstance(v, (int, float)) and
                           k in ("flops", "bytes accessed", "transcendentals")}
    hlo = compiled.as_text()
    from repro.launch import hlo_cost
    static = hlo_cost.analyze_text(hlo)
    rec["cost"] = {"flops": static["flops"],
                   "bytes accessed": static["hbm_bytes"],
                   "transcendentals": static["transcendentals"]}
    rec["collectives"] = dict(static["collective_detail"],
                              total_bytes=static["collective_bytes"])
    rec["collectives_unrolled_once"] = collective_census(hlo)
    rec["hlo_bytes"] = len(hlo)
    rec["status"] = "ok"
    _dump(rec, out_dir, mesh_tag, arch,
          shape_name + (f"__{variant}" if variant else ""))
    return rec


def roofline_terms(rec: dict, n_devices: int) -> dict:
    """The three roofline terms (seconds) from a cell record.

    cost_analysis on the CPU backend reports whole-program (per-device)
    flops/bytes for the partitioned module."""
    flops = rec.get("cost", {}).get("flops", 0.0)
    bytes_acc = rec.get("cost", {}).get("bytes accessed", 0.0)
    coll = rec.get("collectives", {}).get("total_bytes", 0.0)
    return {
        "compute_s": flops / TARGET.bf16_flops,
        "memory_s": bytes_acc / TARGET.hbm_bw,
        "collective_s": coll / TARGET.ici_bw,
    }


def _dump(rec: dict, out_dir: str, mesh_tag: str, arch: str, shape: str):
    d = os.path.join(out_dir, mesh_tag, arch)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, f"{shape}.json"), "w") as f:
        json.dump(rec, f, indent=1)
