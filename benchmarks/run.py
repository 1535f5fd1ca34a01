"""Benchmark harness — one module per paper table/figure.

Prints ``name,...,derived`` CSV rows.  Every row corresponds to a paper
table/figure (see DESIGN.md §12) or a beyond-paper integration measurement.
Assertions inside the benches enforce the paper's claims (SMMS balance,
Theorem 6 bound, statistics-collection overhead, ...).
"""
from __future__ import annotations

import sys
import time
from typing import List


def main() -> None:
    from benchmarks import (bench_alpha_k, bench_join, bench_kernels,
                            bench_moe_dispatch, bench_serve, bench_sort,
                            trace_report)

    rows: List[str] = []
    suites = [
        ("Figs 8-10: sort imbalance+runtime", bench_sort.run),
        ("Table 1: sort scaling", bench_sort.run_scaling),
        ("Kernel dispatch on/off -> BENCH_sort.json",
         bench_sort.run_kernel_compare),
        ("Fusion dispatch-count budget", bench_sort.run_dispatch_budget),
        ("Figs 11-14: join balance+runtime", bench_join.run),
        ("Tables 2-3/Fig 15: StatJoin stats overhead",
         bench_join.run_statjoin_overhead),
        ("Thms 1/2/3/6: alpha-k verification", bench_alpha_k.run),
        ("MoE dispatch (beyond-paper) -> BENCH_moe.json",
         bench_moe_dispatch.run),
        ("MoE cluster dispatch-count budget",
         bench_moe_dispatch.run_dispatch_budget),
        ("Pallas kernels", bench_kernels.run),
        ("Serving engine vs one-shot -> BENCH_serve.json",
         bench_serve.run),
        ("Traced query + roofline join -> TRACE_query.json",
         trace_report.run),
        ("Tracing-off overhead gate", trace_report.run_overhead_gate),
    ]
    failures = []
    for name, fn in suites:
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        mark = len(rows)
        try:
            fn(rows)
        except Exception as exc:  # keep the harness going, report at end
            failures.append((name, repr(exc)))
            rows.append(f"SUITE_FAILED,{name},{exc!r}")
        for row in rows[mark:]:
            print(row, flush=True)
        print(f"# ({time.time() - t0:.1f}s)", flush=True)

    print(f"# total rows: {len(rows)}")
    if failures:
        print("# FAILURES:", failures)
        sys.exit(1)
    print("# ALL BENCHMARK SUITES PASSED")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
