"""Fusion/backend ablation: the engine's two-level fusion knobs in a grid.

    PYTHONPATH=src python benchmarks/fusion_ablation.py [--n N] [--p P]

Four paper workloads — the six-statistic summary (apply→agg.col chains),
the Gram contraction (correlation/SVD hot loop), the colMeans/colSds
moment pair (sink + post-sink EPILOGUE math in one plan), and the
standardized Gram ``crossprod(scale(X))`` (the MULTI-PASS planner:
moment pass → sweep+Gram pass in one materialize) — are timed over
every combination of:

    fuse     on | off    off = materialize every DAG node separately (the
                         paper's "MLlib materializes aggregation separately"
                         strawman; out-of-core it roundtrips the host tier)
    mode     whole | ooc whole = device-resident single computation;
                         ooc = host-tier source streamed partition-by-
                         partition through the prefetcher
    backend  xla | pallas  the lowering layer (core/lowering.py): generic
                         trace vs kernels/ dispatch.  On this CPU container
                         the pallas backend runs the *interpreter* — the
                         timings are not meaningful on CPU (expect O(100×)
                         slowdown), the rows demonstrate the engine
                         dispatching to the kernels and the results
                         matching; on TPU the same rows time Mosaic.

Derived columns report the Plan cost counters (FLOPs, bytes in/out), the
EXECUTION counters for the measured cell — ``passes_over_sources`` (bytes
read / bytes of sources: 1.0 = each matrix streamed once) and
``epilogue_launches`` per materialize (1 for fused epilogue plans; the
nofuse arm shows the post-sink math exploding into separate tiny
executions instead) — and, for pallas rows, the kernels the engine
dispatched to plus the max abs deviation from the xla result — the
acceptance check that engine-level kernel lowering matches the generic
trace.

Rows follow the repo-wide ``name,us_per_call,derived`` contract; every
FUSED cell additionally prints a machine-readable ``BENCH {json}`` row
(wall time, ``passes``, ``passes_over_sources``, ``bytes_in``,
``epilogue_launches``, ``streams``, ``prefetch_reuse_hits``) — the grid
benchmarks/check_regression.py gates against the committed baseline in
CI.  A final batched-vs-serial arm (``batch3-*`` rows) runs the same
three requests as three solo materializes vs one ``fm.batch`` over
device / host-RAM / disk tiers: ``streams`` drops k× (gated exactly)
and the slow-tier rows show the wall-time win of the single scan.
"""
from __future__ import annotations

import argparse
import json

import numpy as np

try:
    from .common import emit, pallas_dispatch_info, summary_outs, time_call
except ImportError:  # direct `python benchmarks/fusion_ablation.py`
    from common import emit, pallas_dispatch_info, summary_outs, time_call


def _moment_outs(fm, X):
    """colSums sinks + the /n and sqrt((Σx²−(Σx)²/n)/(n−1)) EPILOGUE
    chains — the post-sink lazy math the engine evaluates once after the
    partition-loop merge.  One definition feeds both the timed workload
    and the plan-counter evidence."""
    return (fm.colMeans(X), fm.colSds(X))


def _workloads(fm):
    return {
        "summary": lambda X, **kw: [
            fm.as_np(o) for o in fm.materialize(*summary_outs(fm, X), **kw)],
        "gram": lambda X, **kw: [
            fm.as_np(fm.materialize(fm.crossprod(X), **kw)[0])],
        "moments": lambda X, **kw: [
            fm.as_np(o)
            for o in fm.materialize(*_moment_outs(fm, X), **kw)],
        # The multi-pass tentpole: ONE materialize schedules the moment
        # pass and the sweep+Gram pass (exec passes == 2).
        "scale": lambda X, **kw: [
            fm.as_np(fm.materialize(fm.crossprod(fm.scale(X)), **kw)[0])],
    }


def _plan_counters(fm, outs):
    from repro.core.fusion import Plan
    plan = Plan([o.m for o in outs])
    src_bytes = max(1, sum(m.nbytes() for _, m in plan.staged_sources()))
    return plan, (f"flops={plan.flop_count():.2e};"
                  f"bytes_in={plan.bytes_in():.2e};"
                  f"bytes_out={plan.bytes_out():.2e};"
                  f"passes_over_sources={plan.bytes_in() / src_bytes:.3f}")


def run(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--p", type=int, default=16)
    ap.add_argument("--pallas-n", type=int, default=20_000,
                    help="row count for interpret-mode pallas rows (CPU)")
    ap.add_argument("--partition-mib", type=int, default=4)
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--skip-nofuse", action="store_true",
                    help="fused cells only (the BENCH grid the CI "
                         "regression gate measures — the eager arm is an "
                         "ablation, not a gated surface)")
    args = ap.parse_args(argv)

    import jax

    from repro.core import fm
    from repro.core import materialize as mz
    from repro.observability import metrics as obs_metrics

    fm.set_conf(io_partition_bytes=args.partition_mib << 20)
    on_tpu = jax.default_backend() == "tpu"

    rng = np.random.default_rng(0)
    rows = []
    for backend in ("xla", "pallas"):
        # Interpret-mode pallas on CPU is a correctness path, not a speed
        # path: shrink the matrix so the grid sweep finishes quickly.
        n = args.n if (backend == "xla" or on_tpu) else args.pallas_n
        X_np = rng.normal(size=(n, args.p)).astype(np.float32)
        X_dev = fm.conv_R2FM(X_np)
        X_ram = fm.conv_R2FM(X_np, host=True)
        for wname, work in _workloads(fm).items():
            for mode, X in (("whole", X_dev), ("ooc", X_ram)):
                for fuse in ((True,) if args.skip_nofuse else (True, False)):
                    mz.clear_plan_cache()
                    kw = dict(mode=mode, fuse=fuse, backend=backend)
                    mz.reset_exec_stats()
                    res = work(X, **kw)
                    st = mz.exec_stats()
                    us = time_call(lambda: work(X, **kw), iters=args.iters)
                    # Execution evidence for ONE materialize of this cell:
                    # a fused epilogue plan launches exactly once; the
                    # nofuse arm materializes every post-sink node as its
                    # own tiny execution (partition_steps balloons).
                    derived = (f"epilogue_launches={st['epilogue_launches']};"
                               f"partition_steps={st['partition_steps']}")
                    if fuse:
                        outs = (summary_outs(fm, X) if wname == "summary"
                                else _moment_outs(fm, X)
                                if wname == "moments"
                                else (fm.crossprod(fm.scale(X)),)
                                if wname == "scale"
                                else (fm.crossprod(X),))
                        plan, counters = _plan_counters(fm, outs)
                        derived = counters + ";" + derived
                        src_bytes = max(1, sum(
                            m.nbytes() for _, m in plan.staged_sources()))
                        record = {
                            "bench": "fusion", "workload": wname,
                            "mode": mode, "backend": backend,
                            "n": n, "p": args.p,
                            "us_per_call": round(us, 1),
                            "bytes_in": plan.bytes_in(),
                            "passes": len(plan.passes),
                            "passes_over_sources": round(
                                plan.bytes_in() / src_bytes, 3),
                            "epilogue_launches": round(
                                st["epilogue_launches"]
                                / max(st["materialize_calls"], 1), 3),
                            # Stream-fusion evidence (ISSUE 7): streaming
                            # drives this cell's measured run performed
                            # (0 for whole-mode cells) and resident final
                            # partitions served without a re-read.
                            "streams": st["streams"],
                            "prefetch_reuse_hits":
                                st["prefetch_reuse_hits"],
                        }
                        if backend == "pallas":
                            # Acceptance check: engine-level kernel lowering
                            # matches the generic trace on the same data.
                            ref = work(X, mode=mode, fuse=True,
                                       backend="xla")
                            derived += ";" + pallas_dispatch_info(
                                plan, res, ref)
                            record["kernels"] = sorted(
                                {u.kernel for u in
                                 plan.program("pallas").kernel_units})
                        print("BENCH " + json.dumps(record, sort_keys=True))
                    rows.append(
                        (f"fusion/{wname}/{mode}/"
                         f"{'fuse' if fuse else 'nofuse'}/{backend}",
                         us, derived))

    # ------------------------------------------------------------------
    # Batched vs serial arm (cross-materialize stream fusion): the SAME
    # three independent requests — colMeans, colSds, crossprod — run as
    # three solo materializes (k streams over X) vs one ``fm.batch``
    # (k plans × 1 stream).  `streams` is the counter-gated proof; the
    # ooc / ooc-disk rows are the wall-time proof the one-scan schedule
    # wins where the source actually lives on a slow tier.
    X_np = rng.normal(size=(args.n, args.p)).astype(np.float32)
    batch_tiers = (
        ("whole", fm.conv_R2FM(X_np), "whole"),
        ("ooc", fm.conv_R2FM(X_np, host=True), "ooc"),
        ("ooc-disk", fm.load_dense_matrix(X_np, "ablation_batch_x"),
         "auto"),
    )
    for mode, X, exec_mode in batch_tiers:
        for arm in ("serial", "batched"):
            def work(X=X, exec_mode=exec_mode, arm=arm):
                reqs = (fm.colMeans(X), fm.colSds(X), fm.crossprod(X))
                if arm == "batched":
                    return [fm.as_np(r)
                            for r in fm.batch(*reqs, mode=exec_mode)]
                return [fm.as_np(fm.materialize(r, mode=exec_mode)[0])
                        for r in reqs]
            mz.clear_plan_cache()
            mz.reset_exec_stats()
            work()
            st = mz.exec_stats()
            streamed = int(obs_metrics.root_counter("bytes_streamed"))
            us = time_call(work, iters=args.iters)
            record = {
                "bench": "fusion", "workload": f"batch3-{arm}",
                "mode": mode, "backend": "xla",
                "n": args.n, "p": args.p,
                "us_per_call": round(us, 1),
                "streams": st["streams"],
                "passes": st["passes"],
                "prefetch_reuse_hits": st["prefetch_reuse_hits"],
                "bytes_streamed": streamed,
            }
            print("BENCH " + json.dumps(record, sort_keys=True))
            rows.append((f"fusion/batch3/{mode}/{arm}/xla", us,
                         f"streams={st['streams']};"
                         f"passes={st['passes']};"
                         f"bytes_streamed={streamed:.2e}"))

    # ------------------------------------------------------------------
    # Sharded arm (sharded multi-device execution): the standardized-Gram
    # multi-pass workload streamed single-device vs with the partition
    # loop split across the default host mesh (`materialize(mesh=...)`).
    # `shards` / `shard_merges` are the counter-gated proof: one shard
    # per mesh data-axis device per streamed pass, one combine merge per
    # shard boundary.  On the single-device CI bench runner the mesh has
    # one data shard, so the gated counters are deterministic; under
    # XLA_FLAGS=--xla_force_host_platform_device_count=8 the same rows
    # show the 8-way split.
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()
    shard_tiers = (
        ("ooc", fm.conv_R2FM(X_np, host=True)),
        ("ooc-disk", fm.load_dense_matrix(X_np, "ablation_shard_x")),
    )
    for mode, X in shard_tiers:
        for arm, kw in (("single", {}), ("sharded", {"mesh": mesh})):
            def work(X=X, kw=kw):
                return fm.as_np(
                    fm.materialize(fm.crossprod(fm.scale(X)),
                                   mode="stream", **kw)[0])
            mz.clear_plan_cache()
            mz.reset_exec_stats()
            work()
            st = mz.exec_stats()
            us = time_call(work, iters=args.iters)
            record = {
                "bench": "fusion", "workload": f"scale-{arm}",
                "mode": mode, "backend": "xla",
                "n": args.n, "p": args.p,
                "us_per_call": round(us, 1),
                "passes": st["passes"],
                "streams": st["streams"],
                "shards": st["shards"],
                "shard_merges": st["shard_merges"],
            }
            print("BENCH " + json.dumps(record, sort_keys=True))
            rows.append((f"fusion/scale-shard/{mode}/{arm}/xla", us,
                         f"shards={st['shards']};"
                         f"shard_merges={st['shard_merges']};"
                         f"streams={st['streams']}"))
    return emit(rows)


def fusion_ablation():
    """run.py entry: reduced size, restores engine config afterwards."""
    from repro.core import matrix as matrix_mod
    old = matrix_mod.IO_PARTITION_BYTES
    try:
        return run(["--n", "100000", "--pallas-n", "8000", "--iters", "2"])
    finally:
        matrix_mod.IO_PARTITION_BYTES = old


ALL = [fusion_ablation]


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
