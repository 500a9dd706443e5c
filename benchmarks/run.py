"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run table3     # one table

``table3`` additionally writes the machine-readable per-layer conv sweep
``BENCH_conv.json`` (path via ``REPRO_BENCH_OUT``; reduced shapes via
``REPRO_BENCH_SPATIAL_CAP``, default 28) — the artifact CI uploads to
track the perf trajectory across PRs.  The file is merged, never
overwritten: each run refreshes the per-layer snapshot (now including
the batched multi-tile-row fused variant) and APPENDS a timestamped
git-SHA entry to ``BENCH_conv.json["trajectory"]``, so the accumulated
history rides the committed file across PRs.  ``scaleout`` appends the
SPMD per-shard-count rows to the same artifact (it needs two or more
devices in this process); ``roofline`` appends the
dry-run roofline cells under ``"roofline"``; ``costmodel`` fits the
analytic cost model and appends its predicted-vs-measured validation
(rank correlation, top-1/top-k agreement, coefficients) under the
``"costmodel"`` key.
"""
import sys
import time


def main() -> None:
    import os

    from repro.runtime import use_compilation_cache
    use_compilation_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import (appendixB_iterative, fig4_accuracy_vs_bops,
                            fig5_layer_mse, roofline, scaleout,
                            table1_algorithms, table3_throughput,
                            table45_granularity)
    suites = {
        "table1": table1_algorithms.run,
        "fig4": fig4_accuracy_vs_bops.run,
        "table3": table3_throughput.run,
        "table45": table45_granularity.run,
        "fig5": fig5_layer_mse.run,
        "appendixB": appendixB_iterative.run,
        "roofline": roofline.run,
        "costmodel": roofline.run_costmodel,
        "scaleout": scaleout.run,
    }
    selected = sys.argv[1:] or list(suites)
    t0 = time.time()
    artifacts = []
    for name in selected:
        print(f"\n===== {name} =====")
        result = suites[name]()
        if isinstance(result, dict) and "bench_path" in result:
            artifacts.append(result["bench_path"])
    print(f"\nall benchmarks done in {time.time()-t0:.1f}s")
    for path in artifacts:
        print(f"artifact: {path}")


if __name__ == "__main__":
    main()
