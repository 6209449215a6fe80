"""Paper-scale algorithm grid (paper §IV, Fig. 8/10): the "out-of-core
tracks in-memory" experiment over the full suite.

    PYTHONPATH=src python benchmarks/algorithms_bench.py [--n N] [--p P]

Grid: algorithm (glm-logistic / pca / nmf / naive-bayes / kmeans)
      × mode (mem | stream | ooc-disk)
      × backend (xla | pallas),
plus the sparse track (ISSUE 10): glm-sparse — logistic regression on a
one-hot CSR/ELL design matrix — over the same mode × backend grid, with
the pallas cells gated on dispatching the spmm kernels.

Each cell prints TWO lines:

  * the repo-wide ``name,us_per_call,derived`` CSV row, and
  * a machine-readable ``BENCH {json}`` row with the timing plus the
    engine evidence: the iteration Plan's cost counters —
    ``passes`` (scheduled streaming passes: 1 for IRLS/NMF iterations, 2
    for pca's moment→centered-Gram plan) and ``passes_over_sources`` =
    bytes_in / bytes(sources), the proof that a one-pass iteration
    streams X exactly ONCE however many leaves reference it (staging
    dedupe) while the two-pass pca plan honestly reads it twice;
    ``epilogue_nodes`` / ``epilogue_launches_per_materialize`` = the
    post-sink math (the GLM Newton solve, the NB moment division) running
    as ONE on-device epilogue launch inside the same plan — and, for
    pallas cells, the kernels the engine dispatched to (the weighted-gram
    segment must show ``wgram``) with the max abs deviation from the xla
    backend.

On this CPU container the pallas backend runs the interpreter (expect
O(100×) slower rows — correctness evidence, not speed); on TPU the same
rows time Mosaic-compiled kernels.
"""
from __future__ import annotations

import argparse
import json

import numpy as np

try:
    from .common import emit, time_call
except ImportError:  # direct `python benchmarks/algorithms_bench.py`
    from common import emit, time_call


def _make_data(n, p, k, rng):
    X = np.abs(rng.normal(size=(n, p))).astype(np.float32) + 0.1
    beta = rng.normal(size=p)
    pv = 1.0 / (1.0 + np.exp(-(X.astype(np.float64) @ beta / np.sqrt(p))))
    y_bin = (rng.uniform(size=n) < pv).astype(np.float32)
    y_cls = rng.integers(0, k, size=n).astype(np.float32)
    return X, y_bin, y_cls


def _tiered(fm, arr, mode, name):
    """Place an array on the tier a grid mode reads from."""
    if mode == "ooc-disk":
        return fm.load_dense_matrix(arr, name)
    return fm.conv_R2FM(arr)


def _exec_mode(mode):
    return {"mem": "whole", "stream": "stream", "ooc-disk": "auto"}[mode]


def _workloads(fm, k):
    """name -> (run(X, y_bin, y_cls, mode, backend) -> comparable np array,
                iteration_plan(X, y_bin, y_cls) or None)."""
    from repro.algorithms import glm, naive_bayes, nmf, pca
    from repro.algorithms.glm import glm_iteration_plan
    from repro.algorithms.kmeans import kmeans_iteration
    from repro.core.fusion import Plan

    def run_glm(X, yb, yc, mode, backend):
        r = glm(X, yb, family="logistic", max_iter=4, mode=mode,
                backend=backend)
        return r.beta

    def plan_glm(X, yb, yc):
        return glm_iteration_plan(X, yb, np.zeros(X.ncol), "logistic")

    def run_pca(X, yb, yc, mode, backend):
        return pca(X, k=min(4, X.ncol), mode=mode).sdev

    def plan_pca(X, yb, yc):
        # The covariance of the LAZILY centered matrix: a two-pass plan
        # (moment pass → sweep+Gram pass) — what pca() now materializes in
        # one call.
        return Plan([fm.crossprod(fm.scale(X, scale=False)).m])

    def run_nmf(X, yb, yc, mode, backend):
        return np.array([nmf(X, k=k, max_iter=3, seed=0, mode=mode,
                             backend=backend).objective])

    def plan_nmf(X, yb, yc):
        # Pass A of one multiplicative update: both contraction sinks.
        W = fm.conv_R2FM(np.abs(np.random.default_rng(0).normal(
            size=(X.nrow, k))).astype(np.float32))
        return Plan([fm.crossprod(W, X).m, fm.crossprod(W).m])

    def run_nb(X, yb, yc, mode, backend):
        m = naive_bayes(X, yc, k, mode=mode, backend=backend)
        return m.means

    def plan_nb(X, yb, yc):
        # The exact gaussian training DAG (grouped sinks + lazy per-class
        # moment epilogue), from the algorithm's own builder.
        from repro.algorithms.naive_bayes import nb_gaussian_outputs
        return Plan([o.m for o in nb_gaussian_outputs(X, yc, k)])

    def run_kmeans(X, yb, yc, mode, backend):
        C = np.abs(np.random.default_rng(0).normal(
            size=(k, X.ncol))).astype(np.float32)
        newC, _, wss, _ = kmeans_iteration(X, C, mode=mode)
        return newC

    def plan_kmeans(X, yb, yc):
        C = np.abs(np.random.default_rng(0).normal(
            size=(k, X.ncol))).astype(np.float32)
        D = fm.inner_prod(X, C.T, "squared_diff", "sum")
        labels = fm.which_min_row(D)
        return Plan([fm.rowsum(X, labels, k).m, fm.table_(labels, k).m,
                     fm.sum_(fm.rowMins(D)).m, labels.m])

    return {
        "glm-logistic": (run_glm, plan_glm),
        "pca": (run_pca, plan_pca),
        "nmf": (run_nmf, plan_nmf),
        "naive-bayes": (run_nb, plan_nb),
        "kmeans": (run_kmeans, plan_kmeans),
    }


def _sparse_glm_rows(fm, mz, args, on_tpu, rows):
    """The Criteo-shaped track: logistic regression on a one-hot sparse
    design matrix (ISSUE 10).  mem/stream cells read the in-RAM ELL tier,
    ooc-disk reads a CSR .fmat; counters prove the bytes streamed are
    nnz-proportional and (pallas) that the spmm kernels claimed the IRLS
    contractions."""
    import json as _json

    import numpy as np

    from repro.algorithms.glm import glm, glm_iteration_plan

    levels = (24, 16, 8)
    for backend in ("xla", "pallas"):
        n = args.n if (backend == "xla" or on_tpu) else args.pallas_n
        rng = np.random.default_rng(0)
        codes = [rng.integers(0, lv, n) for lv in levels]
        p = sum(levels)
        dense = np.zeros((n, p), np.float32)
        off = np.cumsum([0] + list(levels[:-1]))
        for c, o in zip(codes, off):
            dense[np.arange(n), c + o] = 1.0
        beta = rng.normal(0, 0.5, p)
        pv = 1.0 / (1.0 + np.exp(-(dense.astype(np.float64) @ beta)))
        yb_n = (rng.uniform(size=n) < pv).astype(np.float32)[:, None]
        oracle = None
        for mode in ("mem", "stream", "ooc-disk"):
            X = fm.one_hot(*[fm.as_factor(c, lv)
                             for c, lv in zip(codes, levels)])
            if mode == "ooc-disk":
                X = fm.persist(X, tier="disk",
                               name=f"bench_sparse_x_{backend}")
            yb = _tiered(fm, yb_n, mode, f"bench_sparse_yb_{backend}")
            mz.clear_plan_cache()
            fm.set_conf(backend=backend)
            exec_mode = _exec_mode(mode)

            def work():
                # ridge: a one-hot design is rank-deficient (each factor's
                # columns sum to the ones vector) — unridged Newton
                # diverges.
                return glm(X, yb, family="logistic", max_iter=4,
                           ridge=1e-3, mode=exec_mode,
                           backend=backend).beta

            mz.reset_exec_stats()
            res = np.asarray(work())
            st = mz.exec_stats()
            us = time_call(work, iters=args.iters)
            if oracle is None:
                fm.set_conf(backend="xla")
                oracle = np.asarray(
                    glm(fm.conv_R2FM(dense), yb, family="logistic",
                        max_iter=4, ridge=1e-3, mode="whole",
                        backend="xla").beta)
                fm.set_conf(backend=backend)
            plan = glm_iteration_plan(X, yb, np.zeros(p), "logistic")
            src_bytes = sum(m.nbytes() for _, m in plan.staged_sources())
            err = float(np.max(np.abs(res.astype(np.float64)
                                      - oracle.astype(np.float64))))
            record = {
                "bench": "algorithms",
                "algo": "glm-sparse", "mode": mode, "backend": backend,
                "n": n, "p": p, "us_per_call": round(us, 1),
                # nnz-proportionality evidence: bytes_in counts the CSR/
                # ELL payload, a small fraction of n·p dense bytes.
                "bytes_in": plan.bytes_in(),
                "passes": len(plan.passes),
                "passes_over_sources": round(
                    plan.bytes_in() / max(src_bytes, 1), 3),
                "epilogue_nodes": len(plan.epilogue_nodes),
                "epilogue_launches_per_materialize": round(
                    st["epilogue_launches"]
                    / max(st["materialize_calls"], 1), 3),
                "partition_steps": st["partition_steps"],
                "streams": st["streams"],
                "maxerr_vs_xla_mem": err,
            }
            if backend == "pallas":
                # The dispatch contract: the IRLS weighted-gram and
                # moment contractions must ride the spmm kernels.
                record["kernels"] = sorted(
                    {u.kernel
                     for u in plan.program("pallas").kernel_units})
            print("BENCH " + _json.dumps(record, sort_keys=True))
            rows.append(
                (f"algorithms/glm-sparse/{mode}/{backend}", us,
                 f"passes={record['passes_over_sources']};"
                 f"bytes_in={record['bytes_in']:.2e};"
                 f"maxerr={err:.2e}"))


def run(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--p", type=int, default=16)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--pallas-n", type=int, default=8_000,
                    help="row count for interpret-mode pallas rows (CPU)")
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--partition-mib", type=int, default=4)
    args = ap.parse_args(argv)

    import jax

    from repro.core import fm
    from repro.core import materialize as mz
    from repro.observability import metrics as obs_metrics

    fm.set_conf(io_partition_bytes=args.partition_mib << 20)
    on_tpu = jax.default_backend() == "tpu"
    rows = []
    try:
        for backend in ("xla", "pallas"):
            # Interpret-mode pallas on CPU is a correctness path, not a
            # speed path: shrink the matrix so grid sweeps finish quickly.
            n = args.n if (backend == "xla" or on_tpu) else args.pallas_n
            rng = np.random.default_rng(0)
            Xn, yb_n, yc_n = _make_data(n, args.p, args.k, rng)
            baseline = {}
            for mode in ("mem", "stream", "ooc-disk"):
                X = _tiered(fm, Xn, mode, f"bench_x_{backend}")
                yb = _tiered(fm, yb_n, mode, f"bench_yb_{backend}")
                yc = _tiered(fm, yc_n, mode, f"bench_yc_{backend}")
                for algo, (work, plan_fn) in _workloads(fm, args.k).items():
                    mz.clear_plan_cache()
                    # Route every materialize in the cell (including the
                    # algorithms without a backend parameter) through the
                    # engine-wide backend default.
                    fm.set_conf(backend=backend)
                    exec_mode = _exec_mode(mode)
                    mz.reset_exec_stats()
                    # Scoped I/O telemetry over the measured run: staging
                    # read bandwidth and the fraction of streaming time the
                    # compute thread spent blocked on the prefetch queue
                    # (0.0 for whole-mode cells — nothing streams).
                    with obs_metrics.collect() as obs_scope:
                        res = np.asarray(work(X, yb, yc, exec_mode, backend))
                    obs = obs_scope.stats()
                    st = mz.exec_stats()
                    us = time_call(
                        lambda: work(X, yb, yc, exec_mode, backend),
                        iters=args.iters)
                    plan = plan_fn(X, yb, yc)
                    src_bytes = sum(m.nbytes()
                                    for _, m in plan.staged_sources())
                    record = {
                        "bench": "algorithms",
                        "algo": algo, "mode": mode, "backend": backend,
                        "n": n, "p": args.p, "us_per_call": round(us, 1),
                        # The pass-count proof: one-pass iterations read
                        # each source matrix exactly once (staging dedupe,
                        # bytes_in == bytes(sources)); the two-pass pca
                        # plan honestly reports passes == 2 and
                        # passes_over_sources == 2.0.
                        "bytes_in": plan.bytes_in(),
                        "passes": len(plan.passes),
                        "passes_over_sources": round(
                            plan.bytes_in() / max(src_bytes, 1), 3),
                        "flops": plan.flop_count(),
                        # Epilogue-stage evidence: nodes the iteration plan
                        # evaluates after the merge (the GLM Newton solve,
                        # the NB moment division), and the launches the
                        # measured run actually performed — 1.0 per
                        # materialize = the whole post-sink chain ran as
                        # ONE on-device launch inside the same plan.
                        "epilogue_nodes": len(plan.epilogue_nodes),
                        "epilogue_launches_per_materialize": round(
                            st["epilogue_launches"]
                            / max(st["materialize_calls"], 1), 3),
                        # Two-level-partitioning evidence: how many
                        # I/O-level partition steps the measured run took
                        # (deterministic given n and io_partition_bytes —
                        # gated exactly by check_regression).
                        "partition_steps": st["partition_steps"],
                        # Stream-fusion evidence (ISSUE 7): streaming
                        # drives the measured run performed (0 for mem
                        # cells; with the iteration inspector each driver
                        # iteration is exactly one) and resident final
                        # partitions the next iteration consumed without
                        # a re-read.
                        "streams": st["streams"],
                        "prefetch_reuse_hits": st["prefetch_reuse_hits"],
                        # Measured I/O telemetry (timing-derived: reported,
                        # not gated): slow-tier staging bandwidth and
                        # prefetch-queue wait fraction of the run.
                        "stream_bandwidth_bytes_s": round(
                            obs["stream_bandwidth_bytes_s"], 1),
                        "prefetch_wait_frac": round(
                            obs["prefetch_wait_frac"], 4),
                    }
                    if mode == "mem":
                        # The cell every other mode/backend is judged
                        # against: the xla in-memory run on the SAME data.
                        if backend == "xla":
                            baseline[algo] = res
                        else:
                            fm.set_conf(backend="xla")
                            baseline[algo] = np.asarray(
                                work(X, yb, yc, exec_mode, "xla"))
                            fm.set_conf(backend=backend)
                    if backend == "pallas":
                        record["kernels"] = sorted(
                            {u.kernel
                             for u in plan.program("pallas").kernel_units})
                    err = float(np.max(np.abs(
                        res.astype(np.float64)
                        - baseline[algo].astype(np.float64))))
                    record["maxerr_vs_xla_mem"] = err
                    print("BENCH " + json.dumps(record, sort_keys=True))
                    rows.append(
                        (f"algorithms/{algo}/{mode}/{backend}", us,
                         f"passes={record['passes_over_sources']};"
                         f"bytes_in={record['bytes_in']:.2e};"
                         f"epilogue="
                         f"{record['epilogue_launches_per_materialize']};"
                         f"maxerr={err:.2e}"))
        _sparse_glm_rows(fm, mz, args, on_tpu, rows)
    finally:
        fm.set_conf(backend="auto")
    return emit(rows)


def algorithms_bench():
    """run.py entry: reduced size, restores engine config afterwards."""
    from repro.core import matrix as matrix_mod
    old = matrix_mod.IO_PARTITION_BYTES
    try:
        return run(["--n", "20000", "--pallas-n", "4000", "--iters", "1"])
    finally:
        matrix_mod.IO_PARTITION_BYTES = old


ALL = [algorithms_bench]


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
