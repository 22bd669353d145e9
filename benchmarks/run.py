"""Benchmark harness — one module per paper table/figure.

  bench_array    — Figs 9/11  (array-level CiM/read/write vs NM, every
                   registered technology; emits BENCH_array.json)
  bench_system   — Figs 12/13 (system-level speedup/energy, 5 DNNs) +
                   registry-arch projections (emits BENCH_system.json)
  bench_accuracy — Section III.2 resilience (ADC clamp + sensing errors)
  bench_ablation — N_A / ADC-precision design-point sweep (Sections III.2, IV.4)
  bench_kernels  — kernel micro-bench (CPU wall time + cost profile)
  bench_mac      — decode-shaped MAC fast path vs the pre-pad path
                   (M sweep x packed/unpacked x exact/blocked; emits
                   BENCH_mac.json)
  bench_roofline — §Roofline table from the dry-run artifacts
  bench_serve    — serving throughput: fused ragged-position decode vs
                   the per-slot-loop baseline (emits BENCH_serve.json)
  bench_calibrate— profile -> calibrate -> replay: fit the cost model to
                   measured kernel/step times, replay a holdout serve
                   run, gate on prediction error (emits BENCH_calib.json)
  bench_traffic  — Poisson arrivals through the async front door: p50/p99
                   TTFT, per-token latency, goodput for 1 and 2 router
                   replicas (emits BENCH_traffic.json)

Usage: PYTHONPATH=src python -m benchmarks.run [--only <name>]
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_ablation,
        bench_accuracy,
        bench_array,
        bench_calibrate,
        bench_kernels,
        bench_mac,
        bench_roofline,
        bench_serve,
        bench_system,
        bench_traffic,
    )

    suites = {
        "array": bench_array,
        "system": bench_system,
        "accuracy": bench_accuracy,
        "ablation": bench_ablation,
        "kernels": bench_kernels,
        "mac": bench_mac,
        "roofline": bench_roofline,
        "serve": bench_serve,
        "calibrate": bench_calibrate,
        "traffic": bench_traffic,
    }
    names = [args.only] if args.only else list(suites)
    for name in names:
        print(f"\n===== bench:{name} =====")
        suites[name].run()


if __name__ == "__main__":
    main()
