"""Observability smoke for CI: export a real Chrome trace and explain a plan.

Runs the acceptance scenario — an out-of-core two-pass ``scale(X,
save='disk')`` over a disk-tier matrix — under ``fm.trace(...)``, writes the
Chrome-trace JSON (the bench job uploads it as an artifact), and validates
the span structure:

  * one ``materialize`` span, one ``pass`` span per scheduled pass;
  * per-pass ``partition`` spans with ``stage`` / ``prefetch_wait`` /
    ``device_step`` / ``combine`` activity;
  * the prefetcher's staging thread on its OWN track (thread_name metadata);
  * exactly ONE ``epilogue`` span per pass that schedules one.

Then prints ``fm.explain`` for the same program on both backends (the
explain smoke step).  Exits non-zero if the trace structure is wrong.

    PYTHONPATH=src python benchmarks/trace_smoke.py [--out trace.json]
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import tempfile


def run(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="trace.json",
                    help="Chrome-trace JSON output path")
    ap.add_argument("--n", type=int, default=16384)
    ap.add_argument("--p", type=int, default=8)
    args = ap.parse_args(argv)

    import numpy as np

    from repro.core import fm
    from repro.core import materialize as mz
    from repro.core import matrix as matrix_mod

    tmp = tempfile.mkdtemp(prefix="fm-trace-smoke-")
    # Small I/O partitions so the run streams several partitions per pass.
    old_io = matrix_mod.IO_PARTITION_BYTES
    fm.set_conf(data_dir=tmp, io_partition_bytes=128 * 1024)
    try:
        rng = np.random.default_rng(0)
        X = fm.load_dense_matrix(
            rng.normal(size=(args.n, args.p)).astype(np.float32), "smoke_x")
        Z = fm.scale(X, save="disk")
        with fm.trace(export=args.out):
            (Zm,) = fm.materialize(Z)
        st = mz.exec_stats()

        doc = json.load(open(args.out, encoding="utf-8"))
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        counts = collections.Counter(e["name"] for e in spans)
        threads = {e["args"]["name"] for e in doc["traceEvents"]
                   if e.get("ph") == "M" and e["name"] == "thread_name"}
        n_passes = st["passes"]
        epi_passes = st["epilogue_launches"]

        failures = []
        if counts["materialize"] != 1:
            failures.append(f"materialize spans: {counts['materialize']}")
        if counts["pass"] != n_passes or n_passes < 2:
            failures.append(
                f"pass spans {counts['pass']} != passes {n_passes} (>=2)")
        if counts["partition"] != st["partition_steps"] \
                or counts["partition"] <= n_passes:
            failures.append(
                f"partition spans {counts['partition']} != partition_steps "
                f"{st['partition_steps']} (or no real streaming)")
        for required in ("stage", "prefetch_wait", "device_step", "combine"):
            if counts[required] == 0:
                failures.append(f"no {required!r} spans recorded")
        if counts["epilogue"] != epi_passes:
            failures.append(f"epilogue spans {counts['epilogue']} != "
                            f"epilogue launches {epi_passes}")
        if "fm-prefetch" not in threads:
            failures.append(f"no prefetch-thread track (threads={threads})")

        print(f"trace_smoke: {len(spans)} spans -> {args.out}")
        print(f"trace_smoke: span counts {dict(counts)}")
        print(f"trace_smoke: thread tracks {sorted(threads)}")
        print()
        plan = fm.scale(X)  # the same two-pass structure, freshly lazy
        print("=== fm.explain (xla) ===")
        print(fm.explain(plan))
        print()
        print("=== fm.explain (pallas) ===")
        print(fm.explain(fm.crossprod(plan), backend="pallas"))
        if failures:
            print("\ntrace_smoke: FAIL")
            for f in failures:
                print("  " + f)
            return 1
        print("\ntrace_smoke: OK")
        return 0
    finally:
        matrix_mod.IO_PARTITION_BYTES = old_io


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(run())
