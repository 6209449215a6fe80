"""The paper's algorithm suite vs numpy references, in-memory AND
out-of-core (the central claim: identical results, one code path, all
tiers)."""
import numpy as np
import pytest

from repro.core import fm
from repro.algorithms import (correlation, glm, gmm, kmeans, naive_bayes,
                              nb_predict, nmf, pca, summary, svd_tall)
from repro.algorithms.glm import glm_iteration_plan
from repro.algorithms.reference import irls_logistic

RNG = np.random.default_rng(11)


@pytest.fixture(scope="module")
def X_np():
    return (RNG.normal(size=(3000, 10)) * 2 + 1).astype(np.float32)


@pytest.fixture(scope="module")
def blobs():
    centers = RNG.normal(size=(5, 8)) * 12
    pts = np.concatenate([c + RNG.normal(size=(400, 8)) for c in centers])
    return pts.astype(np.float32), centers


@pytest.mark.parametrize("host", [False, True])
def test_summary(X_np, host):
    s = summary(fm.conv_R2FM(X_np, host=host))
    np.testing.assert_allclose(s.mean, X_np.mean(0), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(s.var, X_np.var(0, ddof=1), rtol=1e-2)
    np.testing.assert_allclose(s.col_min, X_np.min(0))
    np.testing.assert_allclose(s.col_max, X_np.max(0))
    np.testing.assert_allclose(s.l1, np.abs(X_np).sum(0), rtol=1e-3)
    np.testing.assert_array_equal(s.nnz, (X_np != 0).sum(0))


@pytest.mark.parametrize("host", [False, True])
@pytest.mark.parametrize("two_pass", [False, True])
def test_correlation(X_np, host, two_pass):
    c = correlation(fm.conv_R2FM(X_np, host=host), two_pass=two_pass)
    np.testing.assert_allclose(c, np.corrcoef(X_np.T), rtol=2e-2, atol=2e-3)


@pytest.mark.parametrize("host", [False, True])
def test_svd(X_np, host):
    r = svd_tall(fm.conv_R2FM(X_np, host=host), k=6, compute_u=True)
    ref = np.linalg.svd(X_np.astype(np.float64), compute_uv=False)[:6]
    np.testing.assert_allclose(r.s, ref, rtol=1e-3)
    U = fm.as_np(r.U)
    np.testing.assert_allclose(U.T @ U, np.eye(6), atol=2e-2)
    # factorization consistency: X·V == U·diag(s) on the computed subspace
    np.testing.assert_allclose(X_np @ r.V, U @ np.diag(r.s),
                               rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("host", [False, True])
def test_kmeans_recovers_blobs(blobs, host):
    pts, centers = blobs
    res = kmeans(fm.conv_R2FM(pts, host=host), k=5, max_iter=30, seed=1)
    d = np.linalg.norm(res.centers[:, None] - centers[None], axis=-1)
    assert (d.min(1) < 1.0).all()
    assert res.wss < pts.shape[0] * 8 * 2.0  # ~within-cluster variance


@pytest.mark.parametrize("host", [False, True])
def test_gmm_loglik_monotone(blobs, host):
    pts, _ = blobs
    res = gmm(fm.conv_R2FM(pts, host=host), k=5, max_iter=6, seed=1)
    t = np.array(res.loglik_trace)
    assert (np.diff(t) > -1e-2 * np.abs(t[:-1])).all()
    np.testing.assert_allclose(res.weights.sum(), 1.0, rtol=1e-6)


# ---------------------------------------------------------------------------
# GLM / IRLS
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def logit_data():
    X = RNG.normal(size=(4000, 6)).astype(np.float32)
    true_beta = np.array([1.5, -2.0, 0.5, 0.0, 1.0, -0.5])
    pvec = 1.0 / (1.0 + np.exp(-(X.astype(np.float64) @ true_beta)))
    y = (RNG.uniform(size=4000) < pvec).astype(np.float32)
    return X, y


@pytest.mark.parametrize("host", [False, True])
def test_glm_logistic_matches_numpy_irls(logit_data, host):
    X, y = logit_data
    res = glm(fm.conv_R2FM(X, host=host), fm.conv_R2FM(y, host=host),
              family="logistic")
    ref = irls_logistic(X, y)
    np.testing.assert_allclose(res.beta, ref, rtol=1e-5, atol=1e-6)
    assert res.converged
    t = np.array(res.loglik_trace)
    assert (np.diff(t) > -1e-6 * np.abs(t[:-1])).all()  # IRLS ascends


def test_glm_logistic_float32_accuracy_at_scale():
    """2^18 rows of a well-separated Gaussian mixture (κ(XᵀWX) ≈ 400): the
    float32 engine stays within 1e-6 of the float64 IRLS.  Accumulating
    the moment XᵀWz in float32 missed this by about 3x; the Newton
    increment from the score Xᵀ(y − μ) does not."""
    rng = np.random.default_rng(0)
    n, p, k = 1 << 18, 32, 10
    means = rng.standard_normal((k, p)).astype(np.float32) * 8
    X = rng.standard_normal((n, p), dtype=np.float32)
    X += means[rng.integers(0, k, n)]
    eta = X @ (rng.standard_normal(p) / (8 * np.sqrt(p))).astype(np.float32)
    y = (rng.random(n) < 1 / (1 + np.exp(-eta))).astype(np.float32)
    y = y.reshape(-1, 1)
    res = glm(fm.conv_R2FM(X), fm.conv_R2FM(y), family="logistic",
              max_iter=4)
    ref = irls_logistic(X, y, max_iter=4)
    np.testing.assert_allclose(res.beta, ref, rtol=1e-5, atol=1e-6)


def test_glm_logistic_ooc_disk_one_pass_and_wgram_dispatch(
        logit_data, tmp_path, monkeypatch):
    """ISSUE 3 acceptance: logistic GLM on an ooc-DISK matrix matches the
    numpy IRLS reference within 1e-5; the iteration plan's cost counters
    prove one streaming pass over X per iteration; the weighted-gram
    segment lowers onto the pallas wgram kernel."""
    from repro import storage
    monkeypatch.setitem(storage.registry._CONF, "data_dir", None)
    fm.set_conf(data_dir=str(tmp_path / "fmdata"))
    X, y = logit_data
    Xd = fm.load_dense_matrix(X, "glm_x")
    yd = fm.load_dense_matrix(y, "glm_y")
    assert Xd.m.on_disk and yd.m.on_disk

    # Plan counters: ONE pass — bytes_in is exactly X + y (each staged once
    # per partition despite the many leaves referencing them).
    plan = glm_iteration_plan(Xd, yd, np.zeros(X.shape[1]), "logistic")
    assert len(plan.source_groups) == 2            # {X, y}, deduped
    assert plan.bytes_in() == Xd.m.nbytes() + yd.m.nbytes()

    # Engine dispatch: the XᵀWX segment is claimed by the wgram kernel.
    kernels = sorted(u.kernel
                     for u in plan.program("pallas").kernel_units)
    assert "wgram" in kernels, plan.program("pallas").describe()

    res = glm(Xd, yd, family="logistic")
    ref = irls_logistic(X, y)
    np.testing.assert_allclose(res.beta, ref, rtol=1e-5, atol=1e-6)


def test_glm_gaussian_is_ols(logit_data):
    X, _ = logit_data
    true_beta = np.array([0.5, 1.0, -1.0, 2.0, 0.0, -0.3])
    y = (X.astype(np.float64) @ true_beta
         + 0.01 * RNG.normal(size=X.shape[0])).astype(np.float32)
    res = glm(fm.conv_R2FM(X), fm.conv_R2FM(y), family="gaussian")
    ref = np.linalg.lstsq(X.astype(np.float64), y.astype(np.float64),
                          rcond=None)[0]
    np.testing.assert_allclose(res.beta, ref, rtol=1e-4, atol=1e-5)
    assert res.iters == 1                      # constant weights: one step
    rss = float(((X.astype(np.float64) @ res.beta - y) ** 2).sum())
    # loglik = −RSS/2 via the quadratic expansion of f32 sinks: cancellation
    # (RSS ≈ 0.4 out of yᵀy ≈ 1e5) bounds the precision — diagnostic only.
    np.testing.assert_allclose(res.loglik, -0.5 * rss, atol=0.05)


def test_glm_poisson(logit_data):
    X, _ = logit_data
    true_beta = np.array([0.3, -0.2, 0.1, 0.4, 0.0, -0.1])
    lam = np.exp(X.astype(np.float64) @ true_beta)
    y = RNG.poisson(lam).astype(np.float32)
    res = glm(fm.conv_R2FM(X), fm.conv_R2FM(y), family="poisson")
    np.testing.assert_allclose(res.beta, true_beta, atol=0.1)
    assert res.converged


def test_glm_singular_raises(logit_data):
    """The on-device epilogue solve cannot raise like the old eager f64
    path — glm restores the diagnostic with a finite check on beta."""
    X, y = logit_data
    Xs = np.concatenate([X[:, :3], X[:, :1]], axis=1)  # duplicated column
    with pytest.raises(np.linalg.LinAlgError, match="ridge"):
        glm(fm.conv_R2FM(Xs), fm.conv_R2FM(y), family="logistic")


def test_glm_predict(logit_data):
    X, y = logit_data
    res = glm(fm.conv_R2FM(X), fm.conv_R2FM(y), family="logistic")
    from repro.algorithms import glm_predict
    (mu,) = fm.materialize(glm_predict(res, fm.conv_R2FM(X)))
    acc = ((fm.as_np(mu).reshape(-1) > 0.5) == (y > 0.5)).mean()
    assert acc > 0.8


# ---------------------------------------------------------------------------
# PCA
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("host", [False, True])
def test_pca_matches_numpy(X_np, host):
    r = pca(fm.conv_R2FM(X_np, host=host), k=4, compute_scores=True)
    Xc = X_np.astype(np.float64) - X_np.mean(0)
    ref_s = np.linalg.svd(Xc, compute_uv=False)[:4]
    np.testing.assert_allclose(r.sdev, ref_s / np.sqrt(X_np.shape[0] - 1),
                               rtol=1e-3)
    np.testing.assert_allclose(r.center, X_np.mean(0), rtol=1e-3, atol=1e-3)
    scores = fm.as_np(r.scores)
    # Scores equal the centered projection up to per-component sign.
    ref_scores = Xc @ r.rotation
    sign = np.sign((scores * ref_scores).sum(0))
    np.testing.assert_allclose(scores * sign, ref_scores * sign,
                               rtol=1e-2, atol=1e-2)


def test_pca_scaled_matches_correlation_eigs(X_np):
    r = pca(fm.conv_R2FM(X_np), k=10, scale=True)
    evals = np.sort(np.linalg.eigvalsh(np.corrcoef(X_np.T)))[::-1]
    np.testing.assert_allclose(np.sort(r.sdev ** 2)[::-1], evals,
                               rtol=2e-2, atol=1e-3)


# ---------------------------------------------------------------------------
# NMF
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("host", [False, True])
def test_nmf_reconstructs(host):
    W0 = np.abs(RNG.normal(size=(1500, 4))).astype(np.float32)
    H0 = np.abs(RNG.normal(size=(4, 9))).astype(np.float32)
    Xn = (W0 @ H0).astype(np.float32)
    res = nmf(fm.conv_R2FM(Xn, host=host), k=4, max_iter=60, seed=3)
    t = np.array(res.objective_trace)
    assert (np.diff(t) <= 1e-3 * np.maximum(np.abs(t[:-1]), 1.0)).all()
    rel = res.objective / float((Xn.astype(np.float64) ** 2).sum())
    assert rel < 0.01, f"relative reconstruction error {rel}"
    # objective trace is consistent with the actual factors
    recon = fm.as_np(res.W).astype(np.float64) @ res.H
    direct = float(((Xn - recon) ** 2).sum())
    # (trace logs the objective one W-update earlier, so allow slack)
    assert direct <= res.objective_trace[-1] * 1.5 + 1e-6


def test_nmf_disk_spill(tmp_path, monkeypatch):
    """save='disk': the tall factor streams write-through to the disk tier
    every iteration and the result matches the in-memory run."""
    from repro import storage
    monkeypatch.setitem(storage.registry._CONF, "data_dir", None)
    fm.set_conf(data_dir=str(tmp_path / "fmdata"))
    W0 = np.abs(RNG.normal(size=(1200, 3))).astype(np.float32)
    H0 = np.abs(RNG.normal(size=(3, 7))).astype(np.float32)
    Xn = (W0 @ H0).astype(np.float32)
    Xd = fm.load_dense_matrix(Xn, "nmf_x")
    r_disk = nmf(Xd, k=3, max_iter=15, seed=1, save="disk")
    assert r_disk.W.m.on_disk
    # Superseded spill files are reclaimed: only the live W remains.
    spills = list((tmp_path / "fmdata" / "spill").glob("*.fmat"))
    assert len(spills) == 1, spills
    r_mem = nmf(fm.conv_R2FM(Xn), k=3, max_iter=15, seed=1, mode="stream")
    np.testing.assert_allclose(r_disk.objective, r_mem.objective,
                               rtol=1e-3)
    np.testing.assert_allclose(fm.as_np(r_disk.W), fm.as_np(r_mem.W),
                               rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# Naive Bayes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def nb_data(blobs):
    pts, centers = blobs
    labels = np.repeat(np.arange(5), 400).astype(np.float32)
    perm = RNG.permutation(len(pts))
    return pts[perm], labels[perm]


@pytest.mark.parametrize("host", [False, True])
def test_gaussian_nb_matches_numpy(nb_data, host):
    Xn, yn = nb_data
    model = naive_bayes(fm.conv_R2FM(Xn, host=host),
                        fm.conv_R2FM(yn, host=host), 5)
    for j in range(5):
        sel = Xn[yn == j].astype(np.float64)
        np.testing.assert_allclose(model.means[j], sel.mean(0), rtol=1e-4,
                                   atol=1e-4)
        np.testing.assert_allclose(model.variances[j], sel.var(0),
                                   rtol=1e-3, atol=1e-3)
    pred = fm.as_np(nb_predict(model, fm.conv_R2FM(Xn))).reshape(-1)
    assert (pred == yn.astype(np.int32)).mean() > 0.95


def test_multinomial_nb(nb_data):
    rng = np.random.default_rng(5)
    k, p, n_per = 3, 12, 500
    probs = rng.dirichlet(np.ones(p) * 0.3, size=k)
    X = np.concatenate([rng.multinomial(40, probs[j], size=n_per)
                        for j in range(k)]).astype(np.int32)
    y = np.repeat(np.arange(k), n_per).astype(np.int32)
    model = naive_bayes(fm.conv_R2FM(X), fm.conv_R2FM(y), k,
                        kind="multinomial")
    counts = np.stack([X[y == j].sum(0) for j in range(k)]) + 1.0
    expected = np.log(counts / counts.sum(1, keepdims=True))
    np.testing.assert_allclose(model.feature_log_prob, expected, rtol=1e-5)
    pred = fm.as_np(nb_predict(model, fm.conv_R2FM(X))).reshape(-1)
    assert (pred == y).mean() > 0.9


def test_kmeans_matches_pallas_kernel(blobs):
    """The fused GenOps iteration and the Pallas kernel agree."""
    import jax.numpy as jnp
    from repro.algorithms.kmeans import kmeans_iteration, _init_centers
    from repro.kernels import ops
    pts, _ = blobs
    X = fm.conv_R2FM(pts)
    C = _init_centers(X, 5, 0)
    newC, counts, wss, _ = kmeans_iteration(X, C)
    lab_k, sums_k, cnt_k, wss_k = ops.kmeans_assign(jnp.asarray(pts),
                                                    jnp.asarray(C),
                                                    block_rows=256)
    np.testing.assert_allclose(np.asarray(cnt_k), counts)
    np.testing.assert_allclose(float(wss_k[0]), wss, rtol=1e-3)
    kernC = np.where(np.asarray(cnt_k)[:, None] > 0,
                     np.asarray(sums_k) / np.maximum(np.asarray(cnt_k)[:, None], 1),
                     C)
    np.testing.assert_allclose(kernC, newC, rtol=1e-3, atol=1e-3)
