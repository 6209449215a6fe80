"""CI perf-regression gate over the BENCH rows (ISSUE 5 satellite).

Runs a small fixed ``fusion_ablation`` + ``algorithms_bench`` grid
in-process, collects the machine-readable ``BENCH {json}`` rows, and
compares them against the committed ``benchmarks/baseline.json``:

* **counters must not drift** — ``passes``, ``passes_over_sources``,
  ``bytes_in``, ``epilogue_launches`` / ``epilogue_launches_per_materialize``,
  ``epilogue_nodes`` and the pallas ``kernels`` list are engine *evidence*
  (how many streaming passes a plan takes, whether the epilogue fused,
  which kernels dispatched); any change is a planner behavior change and
  fails the gate outright;
* **wall time may not regress by more than the gate percentage**
  (default 25%, ``BENCH_GATE_PCT``) after machine-speed normalization: the
  baseline stores a numpy-matmul calibration time, the current machine is
  re-calibrated, and thresholds scale by the speed ratio so a slower CI
  runner does not false-fail.  A per-row absolute slack
  (``BENCH_GATE_SLACK_US``, default 50 ms) keeps sub-millisecond rows out
  of the noise.

Usage:

    PYTHONPATH=src python benchmarks/check_regression.py            # gate
    PYTHONPATH=src python benchmarks/check_regression.py --update   # rebase

``--update`` rewrites baseline.json from the current run — commit the
result together with any intentional counter change (the diff shows the
reviewer exactly which evidence moved).
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time

import numpy as np

try:
    from . import algorithms_bench, fusion_ablation
except ImportError:  # direct `python benchmarks/check_regression.py`
    import algorithms_bench
    import fusion_ablation

from repro.launch import serve as serve_loadgen

BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "baseline.json")

#: The gated grid: small enough for a CI job, large enough to cover every
#: workload × mode × backend cell including the multi-pass scale plan.
#: iters are deliberately ≥3: the rows are milliseconds-scale, so a
#: median over too few samples turns one scheduler/GC hiccup into a
#: false wall-time failure.
FUSION_ARGS = ["--n", "40000", "--pallas-n", "5000", "--iters", "5",
               "--skip-nofuse"]
ALGO_ARGS = ["--n", "12000", "--pallas-n", "3000", "--iters", "3"]
#: The serving load generator (ISSUE 8): serial vs served arms over one
#: named disk matrix.  The arms run with mid-stream admission off and
#: one wave per admission window, so the gated counters are exact.
SERVE_ARGS = ["--n", "40000", "--p", "8", "--clients", "3", "--waves", "2",
              "--partition-kib", "64", "--name", "ci_serve_x"]

#: Engine-evidence fields compared EXACTLY (any drift fails the gate).
#: ``partition_steps`` is deterministic (n and io_partition_bytes are
#: fixed by the grid); the timing-derived telemetry the rows also carry
#: (stream_bandwidth_bytes_s, prefetch_wait_frac, p50/p99 latency) is
#: reported, not gated.  ``streams`` (ISSUE 7) is gated exactly: the
#: batched arm reading its group's sources in ONE streaming drive (vs k
#: serially) is a scheduler contract, not a timing artifact — as are the
#: serve rows' ``bytes_per_request``/``requests`` (ISSUE 8): the served
#: arm's bytes-per-request is serial's divided by the window's client
#: count, or window coalescing has regressed.  ``shards``/``shard_merges``
#: (ISSUE 9) gate the sharded-execution contract: one shard per mesh
#: data-axis device per streamed pass, one combine merge per shard
#: boundary (deterministic on the bench runner's single-device mesh).
COUNTER_KEYS = ("passes", "passes_over_sources", "bytes_in",
                "epilogue_launches", "epilogue_launches_per_materialize",
                "epilogue_nodes", "kernels", "partition_steps", "streams",
                "bytes_per_request", "requests", "shards", "shard_merges")

GATE_PCT = float(os.environ.get("BENCH_GATE_PCT", "25"))
#: Absolute per-row slack: most rows are single-digit milliseconds where
#: 25% is below OS-jitter level — the percentage gate is really for the
#: slow (hundreds of ms+) rows, and the counters catch behavioral drift
#: on the fast ones.
SLACK_US = float(os.environ.get("BENCH_GATE_SLACK_US", "100000"))


def calibrate() -> float:
    """Machine-speed probe: best-of-5 µs for a fixed numpy matmul.  Stored
    in the baseline so thresholds transfer across runner generations."""
    rng = np.random.default_rng(0)
    a = rng.normal(size=(512, 512))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        float((a @ a).sum())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _row_key(rec: dict) -> str:
    parts = [str(rec.get(k)) for k in ("bench", "workload", "algo", "arm",
                                       "mode", "backend")
             if rec.get(k) is not None]
    return "/".join(parts)


def collect() -> dict:
    """Run the gated grid and return {row_key: BENCH record}."""
    from repro.core import matrix as matrix_mod
    old_io = matrix_mod.IO_PARTITION_BYTES
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            fusion_ablation.run(FUSION_ARGS)
            algorithms_bench.run(ALGO_ARGS)
            serve_loadgen.run(SERVE_ARGS)
    finally:
        matrix_mod.IO_PARTITION_BYTES = old_io
    rows = {}
    for line in buf.getvalue().splitlines():
        if not line.startswith("BENCH "):
            continue
        rec = json.loads(line[len("BENCH "):])
        rows[_row_key(rec)] = rec
    return rows


def _counters_equal(a, b) -> bool:
    if isinstance(a, float) or isinstance(b, float):
        return abs(float(a) - float(b)) <= 1e-6
    return a == b


def compare(current: dict, cal_us: float, baseline: dict) -> list:
    """Return a list of human-readable failure strings (empty = pass)."""
    failures = []
    base_rows = baseline["rows"]
    # Machine-speed normalization, floored at 1.0: a faster runner must
    # not shrink the budget below the recorded baseline.
    ratio = max(cal_us / max(baseline["calibration_us"], 1e-9), 1.0)
    for key, base in base_rows.items():
        cur = current.get(key)
        if cur is None:
            failures.append(f"{key}: row MISSING from current run")
            continue
        for ck in COUNTER_KEYS:
            if ck not in base:
                continue
            if ck not in cur or not _counters_equal(cur[ck], base[ck]):
                failures.append(
                    f"{key}: counter drift {ck}: baseline={base[ck]!r} "
                    f"current={cur.get(ck)!r}")
        budget = base["us_per_call"] * ratio * (1.0 + GATE_PCT / 100.0) \
            + SLACK_US
        if cur["us_per_call"] > budget:
            failures.append(
                f"{key}: wall-time regression {cur['us_per_call']:.0f}us > "
                f"budget {budget:.0f}us (baseline "
                f"{base['us_per_call']:.0f}us, speed ratio {ratio:.2f}, "
                f"gate {GATE_PCT:.0f}% + {SLACK_US:.0f}us slack)")
    for key in current:
        if key not in base_rows:
            failures.append(
                f"{key}: NEW row not in baseline — rerun with --update and "
                f"commit benchmarks/baseline.json")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--update", action="store_true",
                    help="rewrite baseline.json from the current run")
    ap.add_argument("--baseline", default=BASELINE_PATH)
    args = ap.parse_args(argv)

    cal_us = calibrate()
    rows = collect()
    print(f"check_regression: {len(rows)} BENCH rows, "
          f"calibration {cal_us:.0f}us")
    if args.update:
        payload = {
            "calibration_us": round(cal_us, 1),
            "grid": {"fusion_ablation": FUSION_ARGS,
                     "algorithms_bench": ALGO_ARGS,
                     "serve_loadgen": SERVE_ARGS},
            "rows": rows,
        }
        with open(args.baseline, "w", encoding="utf-8") as fh:
            json.dump(payload, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"baseline written: {args.baseline}")
        return 0

    with open(args.baseline, encoding="utf-8") as fh:
        baseline = json.load(fh)
    grid = {"fusion_ablation": FUSION_ARGS, "algorithms_bench": ALGO_ARGS,
            "serve_loadgen": SERVE_ARGS}
    if baseline.get("grid") != grid:
        print("check_regression: grid definition changed — rerun with "
              "--update and commit the new baseline")
        return 1
    failures = compare(rows, cal_us, baseline)
    if failures:
        print(f"check_regression: FAIL ({len(failures)} finding(s))")
        for f in failures:
            print("  " + f)
        return 1
    print(f"check_regression: OK — {len(baseline['rows'])} rows within "
          f"{GATE_PCT:.0f}% of baseline, no counter drift")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
