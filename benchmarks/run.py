"""Benchmark runner: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig6,fig8] [--skip-slow]

Prints ``name,us_per_call,derived`` CSV rows (repo contract).
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--skip-slow", action="store_true",
                    help="skip fig8 device-scaling subprocesses")
    args = ap.parse_args(argv)

    from . import (algorithms_bench, fusion_ablation, kernel_bench,
                   paper_figures, scaling, storage_bench)
    fns = (list(paper_figures.ALL) + list(kernel_bench.ALL)
           + list(fusion_ablation.ALL) + list(storage_bench.ALL)
           + list(algorithms_bench.ALL))
    if not args.skip_slow:
        fns += list(scaling.ALL)
    if args.only:
        keys = args.only.split(",")
        fns = [f for f in fns if any(k in f.__name__ for k in keys)]

    print("name,us_per_call,derived")
    failures = 0
    for fn in fns:
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{fn.__name__},nan,ERROR:{type(e).__name__}:{e}",
                  file=sys.stdout)
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
