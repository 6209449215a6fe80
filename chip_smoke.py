"""Bring-up smoke of the FlashR engine on a TPU.

    python chip_smoke.py              # phases (a)-(d) on one chip
    python chip_smoke.py --chips 4    # the sharded phase only, on four chips

Drives the engine's main path through the entry points a user calls
(``repro.core.fm``, ``repro.algorithms``, ``fm.serve``) with the default
``backend='auto'``, at the paper's dense width (32 columns, MixGaussian)
and 2^24 rows, with data generated from a fixed seed:

  (a) device-resident dense: ``summary``, ``fm.crossprod``, 3 ``kmeans``
      iterations (k = 10) and 4 logistic ``glm`` iterations over a 2 GiB X;
  (b) disk-streamed dense: the same X written with ``fm.load_dense_matrix``,
      reopened with ``fm.get_dense_matrix`` and streamed (``mode='ooc'``);
  (c) sparse from CSR: a one-hot design of three factors with 24/16/8
      levels persisted with ``fm.persist(..., tier='disk')``, fitted by a
      ridged logistic ``glm``;
  (d) served requests: four threads submit column reductions over the (b)
      matrix through one ``fm.serve()`` engine.

``--chips 4`` runs crossprod and logistic glm over the disk matrix sharded
with ``mesh=make_host_mesh()``, compared with the one-device result of the
same process and with the reference, and nothing else.

Every result is compared with a float64 numpy reference of the same
semantics (``repro.algorithms.reference``), and every phase asserts which
Pallas kernels ran (the ``kernel_calls.*`` counters): a matcher that
declines on the chip fails the smoke instead of falling back in silence.
Each operation runs twice; the lines before the last give the first
(cold: compiles included) and the second (warm) wall time, the kernels the
warm run dispatched and the largest error against the reference.  The last
line is one JSON object naming the device.  With no TPU the script exits
non-zero at once and prints no result.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import threading
import time
import traceback

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
DATA_DIR = ROOT / ".smoke_data"
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

from repro.algorithms import glm, kmeans, reference, summary  # noqa: E402
from repro.algorithms.kmeans import _init_centers  # noqa: E402
from repro.core import fm, lowering  # noqa: E402
from repro.core import materialize as mz  # noqa: E402
from repro.kernels.common import default_interpret  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.observability import metrics  # noqa: E402

N_ROWS = 1 << 24          # 2^24 x 32 f32 = 2 GiB, about 1/8 of a v5e's HBM
N_COLS = 32               # the paper's dense width (MixGaussian, PageGraph-32ev)
K = 10                    # k-means clusters (and mixture components)
KMEANS_ITERS = 3
GLM_ITERS = 4
LEVELS = (24, 16, 8)      # benchmarks/algorithms_bench.py's sparse design
SPARSE_RIDGE = 1e-3       # a one-hot design is rank-deficient without it
SEED = 0

# Tolerances, from the CPU tests of the same quantity: tests/test_algorithms
# (summary, glm beta), tests/test_kernels (k-means), tests/test_sparse
# (sparse Gram).  ``norm`` tolerances bound max|got - want| / max|want|.
TOL = {
    "mean": dict(rtol=1e-3, atol=1e-3),
    "var": dict(rtol=1e-2),
    "minmax": dict(rtol=1e-7),
    "l1": dict(rtol=1e-3),
    "gram": dict(norm=1e-4),
    "centers": dict(rtol=1e-3, atol=1e-3),
    "wss": dict(rtol=1e-3),
    "beta": dict(rtol=1e-5, atol=1e-6),
    # Loosened from atol 1e-6: a TPU v5e measured 5.324e-6 (3.883 of that
    # tolerance).  At 2^24 rows the diagonal of the one-hot XᵀWX is 1e5 to
    # 5e5, where float32's spacing is 0.016 to 0.031, so adding the 1e-3
    # ridge changes no entry: the design's null space is pinned by rounding
    # instead of the ridge, and the float32 beta carries a component there
    # that the predictor cancels only to float32 precision.
    "eta": dict(rtol=1e-5, atol=2e-5),
}


class SmokeFailure(AssertionError):
    pass


#: Every failed check and phase of this run, reported at the end.
FAILURES: list[str] = []


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (jax.devices()[0] is {dev.platform}); "
                 "refusing to run on another backend")
    return dev


# ---------------------------------------------------------------------------
# Data, generated from the seed
# ---------------------------------------------------------------------------

def mixgaussian(n: int, p: int, k: int, seed: int):
    """Rows of a k-component Gaussian mixture (unit noise, component means
    8 sd apart on average) and a logistic response over them."""
    rng = np.random.default_rng(seed)
    means = (rng.standard_normal((k, p)) * 8).astype(np.float32)
    labels = rng.integers(0, k, n)
    X = rng.standard_normal((n, p), dtype=np.float32)
    X += means[labels]
    beta = rng.standard_normal(p) / (8 * np.sqrt(p))   # eta has unit sd
    eta = X @ beta.astype(np.float32)
    y = (rng.random(n, dtype=np.float32)
         < 1 / (1 + np.exp(-eta))).astype(np.float32)
    return X, y.reshape(-1, 1)


def factor_design(n: int, levels, seed: int):
    """Integer codes of len(levels) factors and a logistic response over
    their one-hot design."""
    rng = np.random.default_rng(seed + 1)
    codes = [rng.integers(0, lv, n) for lv in levels]
    effects = [rng.normal(0, 0.5, lv) for lv in levels]
    eta = sum(e[c] for e, c in zip(effects, codes))
    y = (rng.random(n) < 1 / (1 + np.exp(-eta))).astype(np.float32)
    return codes, y.reshape(-1, 1)


class OneHotRows:
    """Row slices of the one-hot design of integer factors, densified on
    demand — what the float64 reference reads in place of the CSR matrix."""

    def __init__(self, codes, levels):
        self.codes = codes
        self.offsets = np.cumsum((0,) + tuple(levels[:-1]))
        self.shape = (len(codes[0]), int(sum(levels)))

    def __getitem__(self, rows: slice):
        cs = [c[rows] for c in self.codes]
        out = np.zeros((len(cs[0]), self.shape[1]))
        r = np.arange(len(cs[0]))
        for c, off in zip(cs, self.offsets):
            out[r, c + off] = 1.0
        return out


def level_predictor(beta, levels):
    """The linear predictor at every combination of levels: what a one-hot
    fit identifies (beta itself is not, the design being rank-deficient)."""
    parts = np.split(np.asarray(beta, np.float64), np.cumsum(levels)[:-1])
    grid = parts[0]
    for part in parts[1:]:
        grid = grid[..., None] + part
    return grid.reshape(-1)


# ---------------------------------------------------------------------------
# Checks and timing
# ---------------------------------------------------------------------------

def compare(what: str, got, want, *, rtol=0.0, atol=0.0, norm=None) -> str:
    """Check ``got`` against the float64 reference; returns the measured
    error as text, prefixed ``FAIL`` when it is over the tolerance."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return f"FAIL {what}: shape {got.shape} != {want.shape}"
    if not np.isfinite(got).all():
        return f"FAIL {what}: non-finite values"
    diff = np.abs(got - want)
    if norm is not None:
        err = float(diff.max() / max(np.abs(want).max(), 1e-300))
        text = f"{what} normwise rel err {err:.3e} (tol {norm:g})"
        bad = err > norm
    else:
        ratio = diff / (atol + rtol * np.abs(want)) if (atol or rtol) \
            else np.where(diff > 0, np.inf, 0.0)
        worst = float(ratio.max())
        text = (f"{what} max abs err {float(diff.max()):.3e}, "
                f"{worst:.3f} of tol (rtol {rtol:g}, atol {atol:g})")
        bad = worst > 1.0
    return "FAIL " + text if bad else text


def kernel_calls() -> dict:
    return {k.split(".", 1)[1]: int(v) for k, v in metrics.stats().items()
            if k.startswith("kernel_calls.")}


def run_op(phase: str, name: str, fn, check, kernels):
    """Run ``fn`` cold then warm, check the warm result, assert that each
    of ``kernels`` was launched by the warm run, and print one line."""
    fm.reset_exec_stats()
    t0 = time.perf_counter()
    fn()
    cold = time.perf_counter() - t0
    fm.reset_exec_stats()
    t0 = time.perf_counter()
    out = fn()
    warm = time.perf_counter() - t0
    calls = kernel_calls()
    stats = fm.exec_stats()
    disp = ",".join(f"{k}x{v}" for k, v in sorted(calls.items())) or "none"
    print(f"[{phase}] {name}: cold {cold:.3f} s, warm {warm:.3f} s, "
          f"partition steps {stats['partition_steps']}, kernels {disp}",
          flush=True)
    # A failed check is recorded, not raised, so one run goes on to report
    # every error it can measure; main() fails the run at the end.
    for line in check(out):
        print(f"  {line}", flush=True)
        if line.startswith("FAIL "):
            FAILURES.append(f"[{phase}] {name}: {line[5:]}")
    missing = [k for k in kernels if not calls.get(k)]
    if missing:
        FAILURES.append(f"[{phase}] {name}: kernels {missing} did not run "
                        f"(dispatched: {disp})")
    return out


def device_peak(dev) -> str:
    st = dev.memory_stats() or {}
    peak = st.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def dense_checks(ref):
    """Check functions for the dense operations, against ``ref``."""
    n = N_ROWS

    def summary_check(s):
        mean = ref["summary"]["sum"] / n
        var = (ref["summary"]["sumsq"] - n * mean ** 2) / (n - 1)
        yield compare("mean", s.mean, mean, **TOL["mean"])
        yield compare("var", s.var, var, **TOL["var"])
        yield compare("min", s.col_min, ref["summary"]["min"], **TOL["minmax"])
        yield compare("max", s.col_max, ref["summary"]["max"], **TOL["minmax"])
        yield compare("l1", s.l1, ref["summary"]["l1"], **TOL["l1"])
        yield compare("nnz", s.nnz, ref["summary"]["nnz"])

    def gram_check(g):
        yield compare("crossprod", g, ref["gram"], **TOL["gram"])

    def kmeans_check(res):
        centers, wss, iters = ref["kmeans"]
        if res.iters != iters:
            yield (f"FAIL kmeans ran {res.iters} iterations, the "
                   f"reference {iters}")
        yield compare("kmeans centers", res.centers, centers, **TOL["centers"])
        yield compare("kmeans wss", res.wss, wss, **TOL["wss"])

    def glm_check(res):
        if res.iters != GLM_ITERS:
            yield f"FAIL glm stopped after {res.iters} iterations"
        yield compare("glm beta", res.beta, ref["beta"], **TOL["beta"])

    return summary_check, gram_check, kmeans_check, glm_check


def phase_device(X_np, y_np, ref, dev):
    summary_check, gram_check, kmeans_check, glm_check = dense_checks(ref)
    X = fm.conv_R2FM(X_np)
    y = fm.conv_R2FM(y_np)
    run_op("a", "summary", lambda: summary(X), summary_check,
           ["fused_apply_agg"])
    run_op("a", "crossprod",
           lambda: fm.as_np(fm.materialize(fm.crossprod(X))[0]),
           gram_check, ["gram"])
    run_op("a", f"kmeans k={K} x{KMEANS_ITERS}",
           lambda: kmeans(X, k=K, max_iter=KMEANS_ITERS, seed=SEED),
           kmeans_check, ["kmeans_assign"])
    run_op("a", f"glm logistic x{GLM_ITERS}",
           lambda: glm(X, y, family="logistic", max_iter=GLM_ITERS),
           glm_check, ["wgram"])
    print(f"[a] device peak memory {device_peak(dev)}", flush=True)


def phase_disk(Xd, yd, ref, dev):
    _, gram_check, kmeans_check, glm_check = dense_checks(ref)
    run_op("b", "crossprod ooc",
           lambda: fm.as_np(fm.materialize(fm.crossprod(Xd), mode="ooc")[0]),
           gram_check, ["gram"])
    run_op("b", f"kmeans ooc k={K} x{KMEANS_ITERS}",
           lambda: kmeans(Xd, k=K, max_iter=KMEANS_ITERS, seed=SEED,
                          mode="ooc"),
           kmeans_check, ["kmeans_assign"])
    run_op("b", f"glm logistic ooc x{GLM_ITERS}",
           lambda: glm(Xd, yd, family="logistic", max_iter=GLM_ITERS,
                       mode="ooc"),
           glm_check, ["wgram"])
    print(f"[b] device peak memory {device_peak(dev)}", flush=True)


def phase_sparse(dev):
    codes, y_np = factor_design(N_ROWS, LEVELS, SEED)
    t0 = time.perf_counter()
    X = fm.one_hot(*[fm.as_factor(c, lv) for c, lv in zip(codes, LEVELS)])
    X = fm.persist(X, tier="disk", name="smoke_sparse_x")
    y = fm.load_dense_matrix(y_np, "smoke_sparse_y")
    print(f"[c] wrote the {N_ROWS}x{sum(LEVELS)} one-hot CSR matrix in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    ref_beta = reference.irls_logistic(OneHotRows(codes, LEVELS), y_np,
                             max_iter=GLM_ITERS, ridge=SPARSE_RIDGE)

    def check(res):
        if res.iters != GLM_ITERS:
            yield f"FAIL sparse glm stopped after {res.iters} iterations"
        yield compare("sparse glm linear predictor",
                      level_predictor(res.beta, LEVELS),
                      level_predictor(ref_beta, LEVELS), **TOL["eta"])

    run_op("c", f"sparse glm logistic ridge={SPARSE_RIDGE:g} ooc "
                f"x{GLM_ITERS}",
           lambda: glm(X, y, family="logistic", max_iter=GLM_ITERS,
                       ridge=SPARSE_RIDGE, mode="ooc"),
           check, ["spmm_wgram", "spmm_xty"])
    print(f"[c] device peak memory {device_peak(dev)}", flush=True)


def phase_serve(Xd, ref, dev):
    n = N_ROWS
    s = ref["summary"]
    mix = [("colMeans", fm.colMeans, s["sum"] / n, TOL["mean"]),
           ("colSums", fm.colSums, s["sum"], TOL["l1"]),
           ("colMaxs", fm.colMaxs, s["max"], TOL["minmax"]),
           ("sum", fm.sum_, s["sum"].sum(), TOL["l1"])]

    def wave():
        results = [None] * len(mix)
        errors = []
        barrier = threading.Barrier(len(mix))
        with fm.serve(window_ms=60_000, max_window_requests=len(mix),
                      midstream_admission=False) as eng:
            def client(i):
                try:
                    req = mix[i][1](Xd)
                    barrier.wait(timeout=60)
                    results[i] = fm.as_np(eng.submit(req).result(timeout=600))
                except Exception as exc:  # noqa: BLE001 - re-raised below
                    errors.append(exc)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(mix))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        if errors:
            raise errors[0]
        return results, fm.exec_stats()["streams"]

    def check(out):
        results, streams = out
        yield f"{len(mix)} requests served in {streams} stream(s)"
        for (name, _, want, tol), got in zip(mix, results):
            yield compare(f"served {name}", np.reshape(got, np.shape(want)),
                          want, **tol)

    run_op("d", f"fm.serve {len(mix)} threads", wave, check,
           ["fused_apply_agg"])
    print(f"[d] device peak memory {device_peak(dev)}", flush=True)


def phase_sharded(Xd, yd, ref):
    _, gram_check, _, glm_check = dense_checks(ref)
    devices = jax.devices()
    if len(devices) < 4:
        raise SmokeFailure(f"--chips 4 needs four devices, have {devices}")
    mesh = make_host_mesh()

    def gram():
        return fm.as_np(fm.materialize(fm.crossprod(Xd), mode="ooc")[0])

    def fit():
        return glm(Xd, yd, family="logistic", max_iter=GLM_ITERS, mode="ooc")

    g1 = run_op("4", "crossprod ooc, one device", gram, gram_check, ["gram"])
    b1 = run_op("4", f"glm logistic ooc x{GLM_ITERS}, one device", fit,
                glm_check, ["wgram"]).beta
    with fm.conf(mesh=mesh):
        def sharded_check(check, one, key):
            def run(out):
                st = fm.exec_stats()
                shards, by = st["shards"], st["shard_bytes_in"]
                devs = st["shard_devices"]
                yield (f"shards {shards} over {st['passes']} passes, "
                       f"shard_bytes_in {by}, shard devices {devs}")
                if shards != 4 * st["passes"]:
                    yield f"FAIL expected 4 shards per pass, have {shards}"
                if len(by) != 4 or min(by) <= 0:
                    yield f"FAIL expected 4 non-empty shards: {by}"
                flat = [d for ds in devs for d in ds]
                if len(devs) != 4 or sorted(set(flat)) != sorted(flat) \
                        or len(flat) != 4:
                    yield ("FAIL shard partials did not sit on four "
                           f"distinct devices: {devs}")
                yield from check(out)
                got = out if key is None else getattr(out, key)
                yield compare("sharded vs one device", got, one,
                              **(TOL["gram"] if key is None else TOL["beta"]))
            return run

        run_op("4", "crossprod ooc, 4-way mesh", gram,
               sharded_check(gram_check, g1, None), ["gram"])
        run_op("4", f"glm logistic ooc x{GLM_ITERS}, 4-way mesh", fit,
               sharded_check(glm_check, b1, "beta"), ["wgram"])


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded phase, over four chips")
    args = ap.parse_args(argv)

    dev = require_tpu()
    enable_compile_cache()
    if lowering.resolve_backend() != "pallas" or default_interpret():
        raise SmokeFailure("backend 'auto' did not resolve to compiled "
                           "Pallas kernels on the TPU")
    print(f"device {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}", flush=True)

    def attempt(label, fn, *a):
        try:
            fn(*a)
        except Exception as exc:  # noqa: BLE001 - every phase is reported
            traceback.print_exc()
            FAILURES.append(f"[{label}] {type(exc).__name__}: {exc}")
            print(f"[{label}] FAILED: {type(exc).__name__}: "
                  f"{str(exc)[:500]}", flush=True)
        finally:
            mz.clear_plan_cache()

    shutil.rmtree(DATA_DIR, ignore_errors=True)
    fm.set_conf(data_dir=str(DATA_DIR))
    try:
        t0 = time.perf_counter()
        X_np, y_np = mixgaussian(N_ROWS, N_COLS, K, SEED)
        fm.load_dense_matrix(X_np, "smoke_x")
        fm.load_dense_matrix(y_np, "smoke_y")
        Xd = fm.get_dense_matrix("smoke_x")
        yd = fm.get_dense_matrix("smoke_y")
        print(f"generated and wrote {X_np.nbytes / 2**30:.1f} GiB in "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
        t0 = time.perf_counter()
        ref = {"summary": reference.column_summary(X_np),
               "gram": reference.gram(X_np),
               "beta": reference.irls_logistic(X_np, y_np,
                                               max_iter=GLM_ITERS)}
        if args.chips == 1:
            ref["kmeans"] = reference.lloyd(
                X_np, _init_centers(Xd, K, SEED), max_iter=KMEANS_ITERS)
        print(f"float64 references in {time.perf_counter() - t0:.3f} s",
              flush=True)
        if args.chips == 4:
            attempt("4", phase_sharded, Xd, yd, ref)
        else:
            attempt("a", phase_device, X_np, y_np, ref, dev)
            attempt("b", phase_disk, Xd, yd, ref, dev)
            attempt("d", phase_serve, Xd, ref, dev)
            del X_np
            attempt("c", phase_sparse, dev)
    finally:
        shutil.rmtree(DATA_DIR, ignore_errors=True)

    if FAILURES:
        print("chip_smoke FAILED: " + " | ".join(FAILURES), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
