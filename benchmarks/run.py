"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Numbers labelled fig*/table1/rnn_*
reproduce the paper's artifacts via the calibrated V100 device model (plus
real interpret-mode Pallas executions for correctness); roofline/* reads the
TPU-v5e multi-pod dry-run results.
"""
from __future__ import annotations

import sys
import traceback

from benchmarks.common import header
from benchmarks import (compiled_autotune_bench, dispatch_bench,
                        e2e_slo_attainment,
                        fig3_batch_utilization,
                        fig4_time_multiplexing, fig5_spatial_variance,
                        fig6_coalescing, fig7_clustering,
                        moe_coalescing_bench, multi_device_bench,
                        plan_cache_bench,
                        prefill_coalescing_bench, rnn_gemv_coalescing,
                        roofline_report, table1_autotuning)

MODULES = [
    ("fig3", fig3_batch_utilization),
    ("fig4", fig4_time_multiplexing),
    ("fig5", fig5_spatial_variance),
    ("fig6", fig6_coalescing),
    ("fig7", fig7_clustering),
    ("table1", table1_autotuning),
    ("rnn_gemv", rnn_gemv_coalescing),
    ("roofline", roofline_report),
    ("e2e", e2e_slo_attainment),
    ("plan_cache", plan_cache_bench),
    ("prefill_coalescing", prefill_coalescing_bench),
    ("dispatch", dispatch_bench),
    ("moe_coalescing", moe_coalescing_bench),
    ("multi_device", multi_device_bench),
    ("compiled_autotune", compiled_autotune_bench),
]


def main() -> None:
    only = sys.argv[1] if len(sys.argv) > 1 else None
    header()
    failures = []
    for name, mod in MODULES:
        if only and only != name:
            continue
        try:
            mod.run()
        except Exception as e:  # noqa: BLE001 — report and continue
            traceback.print_exc()
            failures.append((name, str(e)))
    if failures:
        print(f"# {len(failures)} benchmark module(s) FAILED: {failures}",
              file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
