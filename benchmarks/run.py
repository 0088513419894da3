"""Benchmark entrypoint: one harness per paper table (DESIGN.md §6).

Emits ``name,us_per_call,derived`` CSV rows. Run as:
    PYTHONPATH=src python -m benchmarks.run [--only <substr>]
"""
from __future__ import annotations

import argparse
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="substring filter")
    ap.add_argument("--trace-dir", default="",
                    help="write a jax.profiler trace of the selected "
                         "suites to this directory (host-vs-device "
                         "timeline: dispatch gaps, blocking fetches, "
                         "kernel spans; view with TensorBoard or "
                         "ui.perfetto.dev)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_bitwidth, bench_eviction_compat,
                            bench_group_size, bench_kernel_latency,
                            bench_kv_sensitivity, bench_quant_error,
                            bench_serving, bench_throughput, roofline)

    def serving_json():
        """Small serving run + context sweep -> BENCH_serving.json, so the
        decode-step perf trajectory is tracked across PRs. The CB arms run
        through the streaming event API, so the JSON also records honest
        per-token TTFT / inter-token-latency percentiles."""
        rc = bench_serving.main([
            "--requests", "10", "--slots", "3", "--max-len", "192",
            "--out-lo", "4", "--out-hi", "24",
            "--sweep", "192,512,2048", "--shared-prefix", "96",
            "--prefill-sweep", "2048,4096,8192",
            "--spec-sweep", "2,4,8",
            "--runahead-sweep", "1,2,4,8",
            "--adversarial", "--adversarial-requests", "14",
            "--mesh-sweep", "1,2,4",
            "--json", "BENCH_serving.json"])
        if rc:
            raise RuntimeError(
                "serving regression: continuous batching lost to the "
                "static baseline, prefix reuse / the fused prefill "
                "backend / speculative decode / run-ahead fused decode "
                "changed greedy outputs, QoS lost to FCFS on "
                "deadline-met goodput under the overload soak, or the "
                "mesh sweep's sharded greedy outputs diverged across "
                "device counts")

    suites = [
        ("quant_error(T1)", bench_quant_error.run),
        ("reasoning_proxy(T2/T3)", bench_quant_error.run_reasoning_proxy),
        ("kernel_latency(T4/F3)", bench_kernel_latency.run),
        ("throughput(T4)", bench_throughput.run),
        ("group_size(T5)", bench_group_size.run),
        ("bitwidth(T6)", bench_bitwidth.run),
        ("bitwidth_mixed(KVTuner)", bench_bitwidth.run_mixed_policies),
        ("kv_sensitivity(T7/T9)", bench_kv_sensitivity.run),
        ("eviction(T8)", bench_eviction_compat.run),
        ("serving(CB/paged-fused)", serving_json),
        ("roofline(dryrun)", roofline.run),
    ]
    if args.trace_dir:
        import jax
        jax.profiler.start_trace(args.trace_dir)
    failures = 0
    try:
        for name, fn in suites:
            if args.only and args.only not in name:
                continue
            print(f"== {name} ==")
            t0 = time.monotonic()
            try:
                fn()
            except Exception:  # noqa: BLE001
                failures += 1
                traceback.print_exc()
            print(f"== {name} done in {time.monotonic() - t0:.1f}s ==")
    finally:
        if args.trace_dir:
            import jax
            jax.profiler.stop_trace()
            print(f"wrote jax.profiler trace to {args.trace_dir}")
    if failures:
        raise SystemExit(f"{failures} benchmark suites failed")


if __name__ == "__main__":
    main()
