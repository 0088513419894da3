"""Roofline analysis from dry-run artifacts (EXPERIMENTS.md §Roofline).

Reads artifacts/dryrun/<mesh>/<arch>/<shape>.json (produced by
repro.launch.dryrun) and derives, per cell:

  compute_s    = HLO_FLOPs_per_device / peak_FLOPs
  memory_s     = HLO_bytes_per_device / HBM_bw
  collective_s = collective_wire_bytes_per_device / ICI_bw

with the target chip's published peaks from ``repro.launch.chips``.

plus MODEL_FLOPS (analytic 6ND / 2ND) vs compiled-FLOPs utilization.
``compiled.cost_analysis()`` on the SPMD module reports per-device values;
collective bytes come from the partitioned-HLO census (per-device payload
x ring factor).
"""
from __future__ import annotations

import json
import os
from typing import Optional

from repro.configs import SHAPES, get_config
from repro.launch.dryrun_lib import TARGET, roofline_terms

ART = os.environ.get("DRYRUN_ARTIFACTS", "artifacts/dryrun")


def model_flops(arch: str, shape_name: str) -> float:
    """Analytic 'useful' FLOPs for the whole step (all devices)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n = cfg.param_count()
    # exclude the input-embedding TABLE (a gather, ~0 flops) but keep the
    # lm-head matmul (for 256K-vocab archs it IS the dominant matmul); a
    # tied table serves as the lm head, so only the untied case subtracts.
    n_eff = n - (cfg.vocab_size * cfg.d_model if not cfg.tie_embeddings else 0)
    if cfg.family == "moe":
        eff = cfg.moe_d_ff or cfg.d_ff
        routed = cfg.num_experts * 3 * cfg.d_model * eff * cfg.num_layers
        active = (cfg.top_k / cfg.num_experts) * routed
        n_eff = n_eff - routed + active + \
            cfg.num_shared_experts * 3 * cfg.d_model * eff * cfg.num_layers
    tokens = shape.global_batch * shape.seq_len
    kv_span = min(shape.seq_len, cfg.window) if cfg.window else shape.seq_len
    n_attn_layers = cfg.num_layers
    if cfg.family == "hybrid" and cfg.block_pattern:
        n_attn_layers = sum(
            1 for i in range(cfg.num_layers)
            if cfg.block_pattern[i % len(cfg.block_pattern)] == "attn")
    if shape.kind == "train":
        base = 6.0 * n_eff * tokens
        attn = 12.0 * n_attn_layers * cfg.num_heads * cfg.head_dim * \
            shape.global_batch * shape.seq_len * kv_span / 2 \
            if cfg.num_heads else 0.0
        return base + attn
    if shape.kind == "prefill":
        base = 2.0 * n_eff * tokens
        attn = 4.0 * n_attn_layers * cfg.num_heads * cfg.head_dim * \
            shape.global_batch * shape.seq_len * kv_span / 2 \
            if cfg.num_heads else 0.0
        return base + attn
    # decode: one token per sequence
    base = 2.0 * n_eff * shape.global_batch
    attn = (4.0 * n_attn_layers * cfg.num_heads * cfg.head_dim *
            shape.global_batch * kv_span if cfg.num_heads else 0.0)
    return base + attn


def load_cell(mesh_tag: str, arch: str, shape: str) -> Optional[dict]:
    path = os.path.join(ART, mesh_tag, arch, f"{shape}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def analyze(mesh_tag: str = "single_16x16", devices: int = 256) -> list[dict]:
    from repro.configs import ARCH_IDS
    rows = []
    for arch in ARCH_IDS:
        for shape in sorted(SHAPES):
            rec = load_cell(mesh_tag, arch, shape)
            if rec is None:
                continue
            if rec["status"] == "skip":
                rows.append({"arch": arch, "shape": shape, "status": "skip",
                             "reason": rec["reason"]})
                continue
            terms = roofline_terms(rec, devices)
            dom = max(terms, key=terms.get)
            mf = model_flops(arch, shape)
            hlo_global = rec["cost"].get("flops", 0.0) * devices
            util = mf / hlo_global if hlo_global else 0.0
            bound = max(terms.values())
            frac = (terms["compute_s"] / bound) if bound else 0.0
            rows.append({
                "arch": arch, "shape": shape, "status": "ok",
                **{k: v for k, v in terms.items()},
                "dominant": dom.replace("_s", ""),
                "model_flops": mf,
                "hlo_flops_global": hlo_global,
                "useful_ratio": util,
                "roofline_fraction": frac,
                "peak_gib": rec["memory"]["peak_per_device"] / 2 ** 30,
                "fits": rec["memory"]["fits_16g_hbm"],
            })
    return rows


_MITIGATE = {
    "compute": "raise MXU utilization (larger per-device tiles, fuse "
               "elementwise chains, bf16 everywhere)",
    "memory": "cut HBM traffic (quantized cache reads, fuse dequant into "
              "consumers, avoid fp32 spills)",
    "collective": "re-shard to remove the largest all-gather/all-reduce or "
                  "overlap it with compute",
}


def print_table(mesh_tag: str = "single_16x16", devices: int = 256) -> None:
    rows = analyze(mesh_tag, devices)
    print(f"# Roofline [{mesh_tag}] peak={TARGET.bf16_flops/1e12:.0f}TF "
          f"hbm={TARGET.hbm_bw/1e9:.0f}GB/s "
          f"ici={TARGET.ici_bw/1e9:.0f}GB/s")
    hdr = ("arch,shape,compute_s,memory_s,collective_s,dominant,"
           "useful_ratio,roofline_frac,peak_gib,fits,mitigation")
    print(hdr)
    for r in rows:
        if r["status"] == "skip":
            print(f"{r['arch']},{r['shape']},SKIP,,,,,,,,{r['reason'][:50]}")
            continue
        print(f"{r['arch']},{r['shape']},{r['compute_s']:.2e},"
              f"{r['memory_s']:.2e},{r['collective_s']:.2e},{r['dominant']},"
              f"{r['useful_ratio']:.3f},{r['roofline_fraction']:.3f},"
              f"{r['peak_gib']:.2f},{r['fits']},"
              f"\"{_MITIGATE[r['dominant']]}\"")


def run() -> None:
    for tag, dev in [("single_16x16", 256), ("multi_2x16x16", 512)]:
        if os.path.isdir(os.path.join(ART, tag)):
            print_table(tag, dev)


if __name__ == "__main__":
    run()
