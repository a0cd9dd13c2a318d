"""Benchmark harness: one bench per paper table/figure + systems benches.

Prints ``name,us_per_call,derived`` CSV lines (plus markdown tables where
a bench renders one).  Heavy paper-scale settings are opt-in via each
bench's CLI; the defaults here finish on a CPU container.
"""
from __future__ import annotations

import sys
import time
import traceback


def _run(name, fn):
    print(f"# --- {name} ---", flush=True)
    t0 = time.time()
    try:
        fn()
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        return True
    except Exception:
        traceback.print_exc()
        print(f"# {name} FAILED", flush=True)
        return False


def main() -> None:
    ok = True

    def table1():
        from benchmarks import bench_table1
        sys.argv = ["bench_table1", "--rounds", "20", "--n-per-class",
                    "250", "--columns", "2"]
        bench_table1.main()

    def curves():
        from benchmarks import bench_curves
        sys.argv = ["bench_curves", "--rounds", "12", "--n-per-class",
                    "250"]
        bench_curves.main()

    def kernels():
        from benchmarks import bench_kernels
        bench_kernels.main()

    def dist():
        from benchmarks import bench_distributed_agg
        bench_distributed_agg.main()

    def roofline():
        from benchmarks import bench_roofline
        bench_roofline.main("pod1")

    for name, fn in [("table1 (paper Table 1)", table1),
                     ("curves (paper Figs 5-10)", curves),
                     ("kernels", kernels),
                     ("distributed_agg", dist),
                     ("roofline (dry-run artifacts)", roofline)]:
        ok = _run(name, fn) and ok
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
