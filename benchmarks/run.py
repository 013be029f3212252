"""One function per paper table. Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run            # all tables
  PYTHONPATH=src python -m benchmarks.run fig5       # substring filter

The roofline analysis is separate (it needs the 512-device dry-run
artifacts): ``PYTHONPATH=src python -m benchmarks.roofline``.
"""
from __future__ import annotations

import sys


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        compression_bench,
        fig3_fig4_oneshot,
        fig5_latency,
        permgraph_bench,
        serve_bench,
        table1_deit,
        table2_gradual,
        table3_ablation,
    )

    suites = {
        "fig3_fig4": fig3_fig4_oneshot.run,
        "table1": table1_deit.run,
        "table2": table2_gradual.run,
        "table3": table3_ablation.run,
        "fig5": fig5_latency.run,
        "compression": compression_bench.run,
        "permgraph": permgraph_bench.run,
        "serve": serve_bench.run,
        "serve_spec": serve_bench.run_spec,
        "serve_replay": serve_bench.run_replay,
    }
    pattern = sys.argv[1] if len(sys.argv) > 1 else ""
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if pattern and pattern not in name:
            continue
        fn()


if __name__ == "__main__":
    main()
