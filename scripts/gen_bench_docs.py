#!/usr/bin/env python
"""Regenerate README.md's benchmark table from BENCH_serving.json.

The README's serving-benchmark section lives between the
``<!-- BENCH_TABLE_START -->`` / ``<!-- BENCH_TABLE_END -->`` markers and
is *generated*, never hand-edited — this script is the single writer.

    python scripts/gen_bench_docs.py            # print the rendered table
    python scripts/gen_bench_docs.py --write    # update README.md in place
    python scripts/gen_bench_docs.py --check    # exit 1 if README is stale
                                                # (CI runs this)

Regenerate the numbers themselves with::

    PYTHONPATH=src python -m benchmarks.run --only serving
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
START = "<!-- BENCH_TABLE_START -->"
END = "<!-- BENCH_TABLE_END -->"


def _fmt_ms(s: float) -> str:
    return f"{s * 1e3:.1f}"


def render(bench: dict) -> str:
    """The README serving section, markers included."""
    wl = bench["workload"]
    lines = [
        START,
        "<!-- generated by scripts/gen_bench_docs.py from "
        "BENCH_serving.json; do not edit by hand -->",
        "",
        f"Workload: `{bench['arch']}` ({bench['quant']} quant, "
        f"{wl['requests']} Poisson requests @ {wl['rate']}/s, "
        f"{wl['slots']} slots, max_len {wl['max_len']}, CPU smoke "
        "shapes). Regenerate with "
        "`PYTHONPATH=src python -m benchmarks.run --only serving`, then "
        "`python scripts/gen_bench_docs.py --write`.",
        "",
        "| engine arm | tokens/s | p50 latency (ms) | p99 (ms) | "
        "TTFT p50/p99 (ms) | ITL p50/p99 (ms) | decode step (ms) |",
        "|---|---|---|---|---|---|---|",
    ]
    rows = [
        (f"continuous batching / `{bench['backend']}`", bench["continuous"]),
        ("continuous batching / gathered baseline (PR-2 formulation)",
         bench["gathered_baseline"]),
        ("static batching (dense cache)", bench["static"]),
    ]
    for name, r in rows:
        step = (_fmt_ms(r["decode_step_s_mean"])
                if "decode_step_s_mean" in r else "—")
        # per-token stream percentiles exist only for the event-driven
        # continuous-batching arms (the static engine has no stream)
        ttft = itl = "—"
        if "ttft_s" in r:
            ttft = f"{_fmt_ms(r['ttft_s']['p50'])} / " \
                   f"{_fmt_ms(r['ttft_s']['p99'])}"
            itl = f"{_fmt_ms(r['itl_s']['p50'])} / " \
                  f"{_fmt_ms(r['itl_s']['p99'])}"
        lines.append(
            f"| {name} | {r['tokens_per_s']:.1f} | "
            f"{_fmt_ms(r['p50_latency_s'])} | {_fmt_ms(r['p99_latency_s'])} "
            f"| {ttft} | {itl} | {step} |")
    lines += [
        "",
        f"Speedups: **{bench['speedup_cb_vs_static']:.2f}x** tokens/s vs "
        "static batching, "
        f"**{bench['speedup_fused_vs_gathered']:.2f}x** vs the gathered "
        "baseline.",
    ]

    sweep = bench.get("context_sweep") or []
    if sweep:
        lines += [
            "",
            "Decode-step latency vs pool capacity (fixed live context — "
            "flat is the \"no full-cache gather\" signature):",
            "",
            "| max_len | gathered baseline (ms) | page-native (ms) |",
            "|---|---|---|",
        ]
        by_len: dict[int, dict[str, float]] = {}
        for arm in sweep:
            by_len.setdefault(arm["max_len"], {})[arm["arm"]] = \
                arm["decode_step_s_mean"]
        for ml in sorted(by_len):
            a = by_len[ml]
            lines.append(
                f"| {ml} | {_fmt_ms(a.get('gathered_baseline', 0))} | "
                f"{_fmt_ms(a.get('paged_fused', 0))} |")

    psweep = bench.get("prefill_sweep") or []
    if psweep:
        chunk = psweep[0]["prefill_chunk"]
        lines += [
            "",
            f"Long-prompt TTFT, chunked prefill (chunk {chunk}): "
            "gathering jnp reference vs page-native fused prefill "
            "(`prefill_backend=paged_fused`), same decode settings:",
            "",
            "| prompt len | TTFT jnp (ms) | TTFT page-native (ms) | "
            "speedup | greedy outputs |",
            "|---|---|---|---|---|",
        ]
        for arm in psweep:
            ident = ("bit-identical" if arm["outputs_bit_identical"]
                     else "DIVERGED")
            lines.append(
                f"| {arm['prompt_len']} | "
                f"{_fmt_ms(arm['jnp']['ttft_s']['p50'])} | "
                f"{_fmt_ms(arm['paged_fused']['ttft_s']['p50'])} | "
                f"{arm['ttft_speedup_fused_over_jnp']:.2f}x | {ident} |")

    sp = bench.get("shared_prefix")
    if sp:
        reuse = sp["reuse"]
        lines += [
            "",
            f"Shared-prefix page reuse ({sp['prefix_len']}-token system "
            f"prompt, prefill chunk {sp['prefill_chunk']}):",
            "",
            "| metric | value |",
            "|---|---|",
            f"| prefix hit rate | {reuse['prefix_hit_rate'] * 100:.1f}% |",
            f"| prompt tokens skipped | {sp['prefill_tokens_skipped']} |",
            f"| pages adopted (refcount-shared) | {sp['adopted_pages']} |",
            f"| pool bytes shared | "
            f"{sp['prefix_pool_bytes_saved'] / 2**20:.2f} MiB |",
            f"| fresh pages per request | "
            f"{sp['fresh_pages_per_request_baseline']:.1f} -> "
            f"{sp['fresh_pages_per_request_reuse']:.1f} |",
            f"| greedy outputs vs no-reuse baseline | "
            f"{'bit-identical' if sp['outputs_bit_identical'] else 'DIVERGED'} |",
        ]
    spec = bench.get("spec_sweep")
    if spec:
        wls = spec["workloads"]
        lines += [
            "",
            "Speculative multi-token decode (ngram self-speculation, "
            f"{spec['slots']} slots, {spec['max_new']} tokens/request; "
            "greedy outputs bit-identical to plain decode in every arm):",
            "",
            "| workload | k | tokens/s | speedup vs off | acceptance | "
            "accepted/step | outputs |",
            "|---|---|---|---|---|---|---|",
        ]
        for kind in ("repetitive", "random"):
            w = wls.get(kind)
            if not w:
                continue
            lines.append(
                f"| {kind} | off | {w['baseline_tokens_per_s']:.1f} | "
                "1.00x | — | — | — |")
            for a in w["arms"]:
                ident = ("bit-identical" if a["outputs_bit_identical"]
                         else "DIVERGED")
                lines.append(
                    f"| {kind} | {a['k']} | {a['tokens_per_s']:.1f} | "
                    f"{a['speedup_vs_baseline']:.2f}x | "
                    f"{a['acceptance_rate'] * 100:.1f}% | "
                    f"{a['mean_accepted_per_step']:.2f} | {ident} |")
        lines += [
            "",
            f"Best repetitive-workload speedup: "
            f"**{spec['best_speedup_repetitive']:.2f}x** tokens/s over "
            "non-speculative decode.",
        ]
    ra = bench.get("runahead_sweep")
    if ra and ra.get("arms"):
        ident = ("bit-identical" if ra["outputs_bit_identical"]
                 else "DIVERGED")
        lines += [
            "",
            "Run-ahead fused decode (DESIGN.md §18): H decode "
            "micro-steps — paged append + LUT attention + on-device "
            "sampling + EOS/budget masking — per fused dispatch, with "
            "the next horizon pipelined while a block is in flight. "
            f"Decode-bound workload ({ra['max_new']} tokens/request, "
            f"{ra['prompt_len']}-token prompts); greedy outputs vs the "
            f"H=1 per-token dispatch baseline: {ident}.",
            "",
            "| slots | H | tokens/s | speedup vs H=1 | horizons | "
            "host sync (ms) | outputs |",
            "|---|---|---|---|---|---|---|",
        ]
        for arm in ra["arms"]:
            ident = ("bit-identical" if arm["outputs_bit_identical"]
                     else "DIVERGED")
            horizons = arm.get("horizons", "—")
            sync = (_fmt_ms(arm["host_sync_s"])
                    if "host_sync_s" in arm else "—")
            lines.append(
                f"| {arm['slots']} | {arm['h']} | "
                f"{arm['tokens_per_s']:.1f} | "
                f"{arm['speedup_vs_h1']:.2f}x | {horizons} | {sync} | "
                f"{ident} |")
        lines += [
            "",
            f"Best low-batch (1-4 slot) speedup: "
            f"**{ra['best_speedup_low_slots']:.2f}x** tokens/s over the "
            "per-token dispatch baseline.",
        ]

    msweep = bench.get("mesh_sweep")
    if msweep and msweep.get("arms"):
        ident = ("bit-identical" if msweep["outputs_identical_across_devices"]
                 else "DIVERGED")
        lines += [
            "",
            "Multi-device mesh sweep (DESIGN.md §17): KV page pools "
            "head-sharded over a 1xN (data x model) mesh where kv_heads "
            "divides N, replicated fallback otherwise. Forced-host CPU "
            "devices (`--xla_force_host_platform_device_count`) partition "
            "host cores, so this tracks sharding orchestration overhead "
            "and correctness, not a speedup; total pool bytes are fixed "
            f"across arms. Greedy outputs across device counts: {ident}.",
            "",
            "| devices | head-sharded | tokens/s | TTFT p50/p95 (ms) | "
            "decode step (ms) |",
            "|---|---|---|---|---|",
        ]
        for arm in msweep["arms"]:
            hs = "yes" if arm["head_sharded"] else "no (GQA fallback)"
            lines.append(
                f"| {arm['devices']} | {hs} | {arm['tokens_per_s']:.1f} | "
                f"{_fmt_ms(arm['ttft_s']['p50'])} / "
                f"{_fmt_ms(arm['ttft_s']['p95'])} | "
                f"{_fmt_ms(arm['decode_step_s_mean'])} |")

    adv = bench.get("adversarial")
    if adv:
        lines += [
            "",
            "Adversarial serving arms (DESIGN.md §16) — headline is "
            "**goodput-under-SLA**: tokens/s counting only requests whose "
            f"TTFT met the deadline (SLO {_fmt_ms(adv['slo_s'])} ms = 4x "
            "unloaded TTFT p50). Each arm A/Bs the QoS engine (weighted "
            "fair queueing + deadline shedding + graceful degradation) "
            "against the no-QoS FCFS baseline on the same hostile "
            "workload:",
            "",
            "| arm | goodput FCFS (tok/s) | goodput QoS (tok/s) | win | "
            "deadline-met FCFS -> QoS | shed |",
            "|---|---|---|---|---|---|",
        ]
        for name in ("soak", "burst", "tenants", "stragglers"):
            arm = adv.get(name)
            if not arm:
                continue
            a, b = arm["fcfs"]["goodput"], arm["qos"]["goodput"]
            lines.append(
                f"| {name} | {a['goodput_tokens_per_s']:.1f} | "
                f"{b['goodput_tokens_per_s']:.1f} | "
                f"{arm['goodput_win']:.2f}x | "
                f"{a['deadline_met_rate'] * 100:.0f}% -> "
                f"{b['deadline_met_rate'] * 100:.0f}% | "
                f"{arm['qos']['n_shed']} |")
        flood = adv.get("cancel_flood")
        if flood:
            lines += [
                "",
                f"Cancellation flood: {flood['storm_cancels']} storm "
                f"cancels mid-run, survivors at "
                f"{flood['chaos']['tokens_per_s']:.1f} tok/s (clean run "
                f"{flood['clean']['tokens_per_s']:.1f}); event-stream and "
                "allocator invariants asserted post-drain. The soak arm's "
                "QoS-beats-FCFS goodput margin "
                f"(**{adv['soak']['goodput_win']:.2f}x**) is the "
                "benchmark's acceptance gate.",
            ]
    lines.append(END)
    return "\n".join(lines)


def splice(readme: str, table: str) -> str:
    lo = readme.find(START)
    hi = readme.find(END)
    if lo < 0 or hi < 0:
        raise SystemExit(
            f"README.md is missing the {START} / {END} markers")
    return readme[:lo] + table + readme[hi + len(END):]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--write", action="store_true",
                    help="update README.md in place")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 when README.md is stale (CI)")
    ap.add_argument("--bench", default=str(ROOT / "BENCH_serving.json"))
    ap.add_argument("--readme", default=str(ROOT / "README.md"))
    args = ap.parse_args(argv)

    with open(args.bench) as f:
        table = render(json.load(f))
    readme_path = Path(args.readme)
    if args.check or args.write:
        readme = readme_path.read_text()
        updated = splice(readme, table)
        if args.check:
            if updated != readme:
                print("README.md benchmark table is stale — run "
                      "`python scripts/gen_bench_docs.py --write` "
                      "(after `python -m benchmarks.run --only serving` "
                      "if the numbers changed)", file=sys.stderr)
                return 1
            print("README.md benchmark table is up to date")
            return 0
        readme_path.write_text(updated)
        print(f"wrote {readme_path}")
        return 0
    print(table)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
