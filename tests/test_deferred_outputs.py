"""Deferred routing of row-addressed long outputs in a streamed pass.

In ``ooc`` mode each partition's long outputs (k-means labels here) start
their copy to the host when the step returns, and are written into their
host buffer or disk store a bounded window of partitions later: the
window is the stream's prefetch depth.  Whatever the target, the
prefetcher and the partition count, the rows written equal the whole-mode
result exactly, and no more than the window's partitions are ever left
pending.
"""
import numpy as np
import pytest
from helpers_cache import watch_pass_execs

from repro import storage
from repro.core import fm
from repro.core import materialize as mz
from repro.core import matrix as matrix_mod
from repro.observability import metrics

COLS, K = 8, 4
PART_ROWS = 64        # rows per partition of the Lloyd pass below
PART_BYTES = 8192     # the I/O partition budget that gives PART_ROWS


@pytest.fixture()
def data_dir(tmp_path, monkeypatch):
    monkeypatch.setitem(storage.registry._CONF, "data_dir", None)
    fm.set_conf(data_dir=str(tmp_path / "fmdata"))


@pytest.fixture()
def small_partitions(monkeypatch):
    monkeypatch.setattr(matrix_mod, "IO_PARTITION_BYTES", PART_BYTES)
    mz.clear_plan_cache()
    yield
    mz.clear_plan_cache()


def _blobs(n: int, seed: int = 0):
    """``n`` rows around ``K`` well-separated means, and centers near them."""
    rng = np.random.default_rng(seed)
    means = rng.normal(scale=20.0, size=(K, COLS)).astype(np.float32)
    rows = means[rng.integers(0, K, n)] + rng.normal(size=(n, COLS))
    return rows.astype(np.float32), means + 0.5


def _lloyd_pass(X, centers, *, labels_to=None, **kw):
    """The fused pass of ``kmeans_iteration``, its labels (the one long
    output) kept on ``labels_to``: returns (counts, labels)."""
    D = fm.inner_prod(X, centers.T, "squared_diff", "sum")
    labels = fm.which_min_row(D)
    if labels_to is not None:
        fm.persist(labels, labels_to)
    _, counts, _, labels = fm.materialize(
        fm.rowsum(X, labels, K), fm.table_(labels, K),
        fm.sum_(fm.rowMins(D)), labels, **kw)
    return counts, labels


@pytest.mark.parametrize("n_rows", [PART_ROWS, 2 * PART_ROWS,
                                    3 * PART_ROWS, 4 * PART_ROWS + 44],
                         ids=["one", "window", "window+1", "ragged"])
@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetch", "inline"])
@pytest.mark.parametrize("target", ["host", "disk"])
def test_deferred_long_outputs(target, prefetch, n_rows, data_dir,
                               small_partitions, monkeypatch):
    A, centers = _blobs(n_rows)
    want_counts, want_labels = _lloyd_pass(fm.conv_R2FM(A), centers,
                                           mode="whole")
    seen = watch_pass_execs(monkeypatch)
    metrics.REGISTRY.reset()
    counts, labels = _lloyd_pass(
        fm.conv_R2FM(A, host=True), centers,
        labels_to="disk" if target == "disk" else None,
        mode="ooc", prefetch=prefetch)

    parts = -(-n_rows // PART_ROWS)
    st = metrics.stats()
    assert st["partition_steps"] == parts
    assert st["deferred_output_copies"] == parts   # one long output
    assert 0 < st["output_drain_seconds"] <= st["pass_seconds"]
    assert labels.m.on_disk == (target == "disk")
    np.testing.assert_array_equal(fm.as_np(labels), fm.as_np(want_labels))
    np.testing.assert_array_equal(fm.as_np(counts), fm.as_np(want_counts))

    window = storage.get_conf("prefetch_depth")
    (member,) = seen.members
    assert [w for _, w in seen.pending] == [window] * parts
    pending = [n for n, _ in seen.pending]
    assert max(pending) == min(parts, window)
    assert not member.pending


def test_hbm_pass_defers_nothing():
    """A device-resident source runs whole: its labels stay on the device
    and no host copy is queued."""
    A, centers = _blobs(300)
    metrics.REGISTRY.reset()
    _lloyd_pass(fm.conv_R2FM(A), centers)
    st = metrics.stats()
    assert st["partition_steps"] == 1
    assert "deferred_output_copies" not in st
    assert "output_drain_seconds" not in st
