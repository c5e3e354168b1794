"""Benchmark entry point: one function per paper table/figure + kernels +
serving + roofline.  Prints ``name,us_per_call,derived`` CSV."""

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import bench_kernels, bench_online, bench_serving, \
        paper_tables, roofline

    benches = [
        paper_tables.bench_table3,
        paper_tables.bench_table4,
        paper_tables.bench_table5,
        paper_tables.bench_table6,
        paper_tables.bench_fig6,
        paper_tables.bench_fig8,
        paper_tables.bench_table7,
        paper_tables.bench_variable_thresholds,
        paper_tables.bench_med_throughput,
        bench_kernels.bench_kernels,
        bench_kernels.bench_impact_scan_sweep,
        bench_kernels.bench_kernel_service_compiles,
        bench_kernels.bench_cascade_latency,
        bench_kernels.bench_serving,
        bench_serving.bench_dynamic_vs_fixed,
        bench_serving.bench_compile_amortization,
        bench_serving.bench_admission_service,
        bench_serving.bench_continuous_scheduler,
        bench_serving.bench_paced_deadlines,
        bench_serving.bench_sharded_vs_single,
        bench_online.bench_online_adaptation,
        roofline.bench_roofline,
    ]
    print("name,us_per_call,derived")
    failed: list[str] = []
    serving_rows = []
    online_rows = []
    for b in benches:
        try:
            for row in b():
                name, us, derived = row
                if name.startswith("serving/"):
                    serving_rows.append(row)
                if name.startswith("online/"):
                    online_rows.append(row)
                print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception:
            failed.append(b.__name__)
            print(f"{b.__name__},nan,ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)
    if serving_rows:   # the cross-PR perf trajectory record
        path = bench_serving.write_bench_json(serving_rows)
        print(f"wrote {path}", file=sys.stderr)
    if online_rows:    # committed summary only at tiny scale (see
        path = bench_online.write_online_json(rows=online_rows)  # writer)
        print(f"wrote {path}", file=sys.stderr)
    if "bench_impact_scan_sweep" not in failed:
        # only persist a complete sweep (a partial one would overwrite
        # the committed summary with incomplete data at tiny scale)
        path = bench_kernels.write_kernels_json()
        print(f"wrote {path}", file=sys.stderr)
    failures = len(failed)
    if failures:
        raise SystemExit(f"{failures} benchmark(s) failed")


if __name__ == "__main__":
    main()
