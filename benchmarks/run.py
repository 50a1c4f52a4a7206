"""Benchmark harness — one module per paper table/figure plus the roofline
table from the dry-run artifacts. Prints ``name,us_per_call,derived`` CSV.
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (fig1_naive_sampling, fig2_seq_vs_parallel,
                            fig3_vi_convergence, fig4_sort2aggregate,
                            fig56_yahoo_day2, kernels_bench, roofline_table,
                            scaling, sweep_scaling)
    print("name,us_per_call,derived")
    failed = []
    for mod in (fig1_naive_sampling, fig2_seq_vs_parallel,
                fig3_vi_convergence, fig4_sort2aggregate, fig56_yahoo_day2,
                scaling, sweep_scaling, kernels_bench, roofline_table):
        try:
            mod.main()
        except Exception as e:   # run the rest, then fail the harness
            print(f"{mod.__name__},0.0,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
            failed.append(mod.__name__)
    if failed:
        sys.exit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
