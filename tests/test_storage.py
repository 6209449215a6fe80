"""Storage-tier tests: on-disk format, MmapStore, prefetcher, spill, registry.

The invariants the disk tier must hold:
  * header/body round-trip preserves shape/dtype/layout exactly,
  * MmapStore.block() == the in-memory slice (both layouts, any range),
  * the prefetcher delivers every partition, in order, and shuts down
    cleanly even when the consumer abandons the stream,
  * spill-to-disk outputs equal their in-memory counterparts (k-means,
    correlation — the paper's EM == IM contract),
  * the plan cache survives mode changes and evicts LRU.
"""
import pathlib

import numpy as np
import pytest

from repro.core import fm
from repro.core import materialize as mz
from repro.core.matrix import DenseStore, FMMatrix
from repro import storage


@pytest.fixture()
def data_dir(tmp_path, monkeypatch):
    """Point the registry at a fresh directory (and restore the old one)."""
    monkeypatch.setitem(storage.registry._CONF, "data_dir", None)
    fm.set_conf(data_dir=str(tmp_path / "fmdata"))
    return tmp_path / "fmdata"


def _arr(n=1000, p=7, seed=0):
    return (np.random.default_rng(seed).normal(size=(n, p)) * 3
            ).astype(np.float32)


# ---------------------------------------------------------------------------
# Format
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["row", "col"])
@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32])
def test_header_roundtrip(tmp_path, layout, dtype):
    A = _arr().astype(dtype)
    path = tmp_path / "a.fmat"
    written = storage.save_matrix(path, A, layout=layout)
    header = storage.read_header(path)
    assert header == written
    assert header.shape == A.shape
    assert header.dtype == np.dtype(dtype)
    assert header.layout == layout
    assert header.body_offset % 4096 == 0
    st = storage.open_matrix(path)
    np.testing.assert_array_equal(np.asarray(st.logical()), A)


def test_bad_magic_rejected(tmp_path):
    path = tmp_path / "junk.fmat"
    path.write_bytes(b"NOTAMATRIX" * 10)
    with pytest.raises(ValueError, match="magic"):
        storage.read_header(path)


def test_vector_becomes_one_column(tmp_path):
    v = np.arange(10, dtype=np.float32)
    storage.save_matrix(tmp_path / "v.fmat", v)
    st = storage.open_matrix(tmp_path / "v.fmat")
    assert st.header.shape == (10, 1)


# ---------------------------------------------------------------------------
# MmapStore
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["row", "col"])
def test_mmap_block_matches_memory(tmp_path, layout):
    A = _arr(500, 6)
    storage.save_matrix(tmp_path / "a.fmat", A, layout=layout)
    st = storage.open_matrix(tmp_path / "a.fmat")
    for start, stop in [(0, 500), (0, 1), (7, 130), (499, 500), (128, 256)]:
        np.testing.assert_array_equal(np.asarray(st.block(start, stop)),
                                      A[start:stop])
    assert st.nbytes() == A.nbytes
    assert st.on_host and st.on_disk


def test_mmap_transpose_zero_copy(tmp_path):
    A = _arr(64, 5)
    storage.save_matrix(tmp_path / "a.fmat", A)
    mat = FMMatrix(A.shape, A.dtype, store=storage.open_matrix(tmp_path / "a.fmat"))
    t = mat.transpose()
    assert t.shape == (5, 64)
    assert t.store.on_disk  # still the same file, no materialization
    np.testing.assert_array_equal(np.asarray(t.block(1, 3)), A.T[1:3])


def test_write_rows_roundtrip(tmp_path):
    A = _arr(200, 4)
    st = storage.create_matrix(tmp_path / "w.fmat", A.shape, A.dtype)
    for start in range(0, 200, 64):
        st.write_rows(start, A[start:start + 64])
    st.flush()
    reopened = storage.open_matrix(tmp_path / "w.fmat")
    np.testing.assert_array_equal(np.asarray(reopened.logical()), A)
    with pytest.raises(ValueError, match="read-only"):
        reopened.write_rows(0, A[:1])


def test_dense_store_col_layout_block():
    """Regression: col-layout block() must slice the stored buffer and
    transpose only the block (never the whole buffer)."""
    A = _arr(100, 3)
    st = DenseStore(np.ascontiguousarray(A.T), "col")
    np.testing.assert_array_equal(np.asarray(st.block(10, 20)), A[10:20])
    # the returned block is a view of the stored buffer, not of a full
    # transposed copy
    blk = st.block(10, 20)
    assert blk.base is st.data or blk.base is getattr(st.data, "base", None)


# ---------------------------------------------------------------------------
# Prefetcher
# ---------------------------------------------------------------------------

def test_prefetcher_ordering(tmp_path):
    A = _arr(1000, 5)
    B = _arr(1000, 3, seed=1)
    storage.save_matrix(tmp_path / "a.fmat", A)
    sa = storage.open_matrix(tmp_path / "a.fmat")
    sb = DenseStore(B)
    with storage.PartitionPrefetcher([(0, sa), (1, sb)], 128, 1000,
                                     stage_to_device=False) as pf:
        seen = []
        for start, stop, blocks in pf:
            seen.append((start, stop))
            np.testing.assert_array_equal(np.asarray(blocks[0]), A[start:stop])
            np.testing.assert_array_equal(np.asarray(blocks[1]), B[start:stop])
    expected = [(s, min(s + 128, 1000)) for s in range(0, 1000, 128)]
    assert seen == expected  # every partition, exactly once, in order


def test_prefetcher_shutdown_midstream(tmp_path):
    A = _arr(10_000, 4)
    storage.save_matrix(tmp_path / "a.fmat", A)
    st = storage.open_matrix(tmp_path / "a.fmat")
    pf = storage.PartitionPrefetcher([(0, st)], 64, 10_000,
                                     stage_to_device=False)
    for i, _ in enumerate(pf):
        if i == 2:
            break  # abandon with ~150 partitions outstanding
    pf.close()
    assert not pf.alive
    pf.close()  # idempotent


def test_prefetcher_error_propagates():
    class Exploding:
        def block(self, start, stop):
            raise OSError("bad sector")

    pf = storage.PartitionPrefetcher([(0, Exploding())], 8, 64)
    with pytest.raises(storage.PrefetchError, match="bad sector"):
        for _ in pf:
            pass
    pf.close()


# ---------------------------------------------------------------------------
# End-to-end: disk tier through the engine
# ---------------------------------------------------------------------------

def test_registry_roundtrip(data_dir):
    A = _arr()
    X = fm.load_dense_matrix(A, "mat_a")
    assert "mat_a" in storage.list_matrices()
    Y = fm.get_dense_matrix("mat_a")
    np.testing.assert_array_equal(fm.as_np(Y), A)
    with pytest.raises(KeyError):
        fm.get_dense_matrix("nope")


def test_conv_store_disk(data_dir):
    A = _arr()
    X = fm.conv_R2FM(A)
    Xd = fm.persist(X, tier="disk", name="spilled")
    assert Xd.m.on_disk
    np.testing.assert_array_equal(fm.as_np(Xd), A)
    np.testing.assert_array_equal(fm.as_np(fm.get_dense_matrix("spilled")), A)


def test_ingest_csv_and_binary(data_dir, tmp_path):
    A = _arr(300, 4)
    csv = tmp_path / "a.csv"
    np.savetxt(csv, A, delimiter=",", comments="", header="a,b,c,d")
    X = fm.load_dense_matrix(str(csv), "from_csv", skip_header=1,
                             chunk_rows=64)
    np.testing.assert_allclose(fm.as_np(X), A, rtol=1e-6)

    raw = tmp_path / "a.bin"
    A.tofile(raw)
    Y = fm.load_dense_matrix(str(raw), "from_bin", ncol=4, chunk_rows=100)
    np.testing.assert_array_equal(fm.as_np(Y), A)


@pytest.mark.parametrize("prefetch", [True, False])
def test_ooc_disk_equals_memory_correlation(data_dir, prefetch):
    from repro.algorithms import correlation
    A = _arr(5000, 6)
    Xd = fm.load_dense_matrix(A, "corr")
    Xm = fm.conv_R2FM(A)
    mz.clear_plan_cache()
    G = fm.crossprod(Xd)
    s = fm.colSums(Xd)
    Gm, sm = fm.materialize(G, s, prefetch=prefetch)
    G2, s2 = fm.materialize(fm.crossprod(Xm), fm.colSums(Xm), mode="stream")
    np.testing.assert_allclose(fm.as_np(Gm), fm.as_np(G2), rtol=1e-5)
    np.testing.assert_allclose(correlation(Xd), correlation(Xm),
                               rtol=1e-4, atol=1e-5)


def test_ooc_disk_equals_memory_kmeans(data_dir):
    from repro.algorithms import kmeans
    rng = np.random.default_rng(0)
    centers = rng.normal(size=(3, 5)) * 10
    A = np.concatenate(
        [c + rng.normal(size=(400, 5)) for c in centers]).astype(np.float32)
    Xd = fm.load_dense_matrix(A, "km")
    Xm = fm.conv_R2FM(A)
    r_disk = kmeans(Xd, k=3, max_iter=10, seed=1)
    r_mem = kmeans(Xm, k=3, max_iter=10, seed=1, mode="stream")
    np.testing.assert_allclose(r_disk.centers, r_mem.centers, atol=1e-5)
    assert abs(r_disk.wss - r_mem.wss) <= 1e-4 * max(1.0, abs(r_mem.wss))


@pytest.mark.parametrize("layout", ["row", "col"])
def test_direct_io_reads_correct_and_cold(data_dir, layout):
    """direct_io=True (cache-bypass benchmarking) must not change results:
    blocks are materialized copies and the pages are dropped after the
    read (best-effort posix_fadvise DONTNEED)."""
    A = _arr(3000, 4)
    Xd = fm.load_dense_matrix(A, f"dio_{layout}", layout=layout)
    fm.set_conf(direct_io=True)
    try:
        blk = Xd.m.store.block(100, 200)
        assert isinstance(blk, np.ndarray) and not isinstance(blk, np.memmap)
        np.testing.assert_array_equal(blk, A[100:200])
        G, s = fm.materialize(fm.crossprod(Xd), fm.colSums(Xd))
        np.testing.assert_allclose(
            fm.as_np(G), A.T.astype(np.float64) @ A.astype(np.float64),
            rtol=1e-4)
        np.testing.assert_allclose(fm.as_np(s).reshape(-1), A.sum(0),
                                   rtol=1e-4)
    finally:
        fm.set_conf(direct_io=False)
    # normal mode again serves lazy views
    blk2 = Xd.m.store.block(0, 10)
    np.testing.assert_array_equal(np.asarray(blk2), A[:10])


def test_staging_dedupes_shared_matrix_reads(data_dir):
    """Regression (ROADMAP open item): a DAG referencing one physical
    matrix through k leaves (crossprod + two agg.col chains here) must read
    each partition from the store ONCE, not k times."""
    from repro.core.fusion import Plan
    A = _arr(20_000, 4)
    fm.set_conf(io_partition_bytes=1 << 18)  # force many partitions
    try:
        Xd = fm.load_dense_matrix(A, "dedupe")
        store = Xd.m.store
        reads = []
        orig_block = store.block
        store.block = lambda start, stop: (reads.append((start, stop)),
                                           orig_block(start, stop))[1]
        outs = (fm.crossprod(Xd), fm.colSums(Xd), fm.colSums(Xd ** 2))
        plan = Plan([o.m for o in outs])
        assert len(plan.sources) >= 3          # three leaves ...
        assert len(plan.source_groups) == 1    # ... one physical matrix
        Gm, sm, qm = fm.materialize(*outs, prefetch=False)
        n_partitions = -(-A.shape[0] // plan.partition_rows)
        assert len(reads) == n_partitions, \
            f"{len(reads)} reads for {n_partitions} partitions"
        np.testing.assert_allclose(
            fm.as_np(Gm), A.T.astype(np.float64) @ A, rtol=1e-4)
        np.testing.assert_allclose(fm.as_np(sm).reshape(-1), A.sum(0),
                                   rtol=1e-4)
        np.testing.assert_allclose(fm.as_np(qm).reshape(-1), (A * A).sum(0),
                                   rtol=1e-4)
    finally:
        fm.set_conf(io_partition_bytes=64 << 20)


def test_staging_alias_structure_in_plan_signature():
    """Two structurally identical cuts that alias sources differently (one
    matrix through two leaves vs two distinct matrices) must not share a
    compiled plan — the staged-block layout differs."""
    from repro.core.fusion import Plan
    A = _arr(256, 3)
    X = fm.conv_R2FM(A)
    Y = fm.conv_R2FM(A.copy())
    shared = Plan([fm.crossprod(X, X).m])
    distinct = Plan([fm.crossprod(X, Y).m])
    assert len(shared.source_groups) == 1
    assert len(distinct.source_groups) == 2
    assert shared.signature() != distinct.signature()
    (g1,) = fm.materialize(fm.crossprod(X, X))
    (g2,) = fm.materialize(fm.crossprod(X, Y))  # same sig shape, new aliases
    np.testing.assert_allclose(fm.as_np(g1), fm.as_np(g2), rtol=1e-5)


def test_spill_to_disk_output(data_dir):
    """save='disk' long-dimension outputs stream into an on-disk matrix and
    equal the in-memory result."""
    A = _arr(4000, 4)
    Xd = fm.load_dense_matrix(A, "base")
    Z = fm.abs_(Xd) * 2.0 - 1.0
    fm.persist(Z, tier="disk")
    (Zm,) = fm.materialize(Z)
    assert Zm.m.on_disk
    np.testing.assert_allclose(fm.as_np(Zm), np.abs(A) * 2.0 - 1.0, rtol=1e-6)

    # whole-mode spill of a device-resident computation
    W = fm.conv_R2FM(A)
    Z2 = fm.sqrt(fm.abs_(W))
    fm.persist(Z2, tier="disk")
    (Z2m,) = fm.materialize(Z2, mode="whole")
    assert Z2m.m.on_disk
    np.testing.assert_allclose(fm.as_np(Z2m), np.sqrt(np.abs(A)), rtol=1e-6)


def test_disk_source_disk_sink_pipeline(data_dir):
    """Full EM pipeline: disk in, disk out, nothing big in RAM."""
    A = _arr(3000, 3)
    Xd = fm.load_dense_matrix(A, "pipe_in")
    Z = (Xd - 1.0) / 2.0
    fm.persist(Z, tier="disk")
    (Zm,) = fm.materialize(Z)
    out = fm.persist(Zm, tier="disk", name="pipe_out")
    np.testing.assert_allclose(fm.as_np(fm.get_dense_matrix("pipe_out")),
                               (A - 1.0) / 2.0, rtol=1e-6)


# ---------------------------------------------------------------------------
# Plan cache (satellite: keying + LRU)
# ---------------------------------------------------------------------------

def test_plan_cache_survives_mode_change(data_dir):
    """Reusing a cached plan under a different execution mode (retrace)
    must not skip sinks — regression for the stale cached_store bug."""
    A = _arr(2000, 4)
    mz.clear_plan_cache()
    Xd = fm.load_dense_matrix(A, "pc")
    (Gd,) = fm.materialize(fm.crossprod(Xd))          # ooc
    Xm = fm.conv_R2FM(A)
    (Gm,) = fm.materialize(fm.crossprod(Xm))          # whole, same signature
    expected = A.T.astype(np.float64) @ A.astype(np.float64)
    np.testing.assert_allclose(fm.as_np(Gd), expected, rtol=1e-4)
    np.testing.assert_allclose(fm.as_np(Gm), expected, rtol=1e-4)


def test_spill_to_disk_survives_plan_cache(data_dir):
    """Regression: a cache-hit save='disk' materialization must still spill
    (the first execution zeroes the cached template's save flags)."""
    A = _arr(2000, 3)
    mz.clear_plan_cache()
    for i in range(3):  # identical signature each round → cache hit on 2nd+
        Xd = fm.load_dense_matrix(A + i, f"sp{i}")
        Z = fm.abs_(Xd) * 2.0
        fm.persist(Z, tier="disk")
        (Zm,) = fm.materialize(Z)
        assert Zm.m.on_disk, f"round {i} lost the disk spill target"
        np.testing.assert_allclose(fm.as_np(Zm), np.abs(A + i) * 2.0,
                                   rtol=1e-6)


def test_partition_budget_change_misses_cache(data_dir):
    """Regression: fm.set_conf(io_partition_bytes=...) must not be ignored
    for already-cached signatures — partition size is part of the key."""
    from repro.core import matrix as matrix_mod
    old = matrix_mod.IO_PARTITION_BYTES
    mz.clear_plan_cache()
    try:
        A = _arr(100_000, 4)
        Xd = fm.load_dense_matrix(A, "budget")
        fm.materialize(fm.colSums(Xd))
        assert len(mz._PLANS) == 1
        fm.set_conf(io_partition_bytes=1 << 18)  # 256 KiB
        (s,) = fm.materialize(fm.colSums(fm.get_dense_matrix("budget")))
        assert len(mz._PLANS) == 2  # new partition size ⇒ new cache entry
        np.testing.assert_allclose(fm.as_np(s).reshape(-1), A.sum(0),
                                   rtol=1e-4)
    finally:
        matrix_mod.IO_PARTITION_BYTES = old
        mz.clear_plan_cache()


def test_plan_cache_hit_preserves_first_dag(data_dir):
    """Regression: borrowing a cached plan must not clobber the first
    caller's persisted cut points — a later structurally identical
    computation once overwrote them, silently corrupting downstream
    virtual matrices of the original DAG."""
    mz.clear_plan_cache()
    A = fm.conv_R2FM(np.full((64, 2), 2.0, np.float32))
    VA = A + 0.0
    fm.persist(VA, tier="device")       # persisted cut point
    VB = VA * 10.0                        # depends on VA's persisted value
    fm.materialize(VA)
    # structurally identical DAG over different data → cache hit
    VC = fm.conv_R2FM(np.full((64, 2), 5.0, np.float32)) + 0.0
    fm.persist(VC, tier="device")
    (VCm,) = fm.materialize(VC)
    np.testing.assert_allclose(fm.as_np(VCm), 5.0)
    (VBm,) = fm.materialize(VB)
    np.testing.assert_allclose(fm.as_np(VBm), 20.0)  # not 50.0


def test_plan_cache_lru_eviction():
    mz.clear_plan_cache()
    old_limit = mz.PLAN_CACHE_LIMIT
    mz.PLAN_CACHE_LIMIT = 2
    try:
        A = _arr(64, 3)
        X = fm.conv_R2FM(A)
        sigs = []
        for const in (1.0, 2.0, 3.0):  # Smalls don't change the signature
            for p in ((X + const), (X * const), fm.abs_(X + const)):
                fm.materialize(fm.colSums(p))
            assert len(mz._PLANS) <= 2  # evicts, never bypasses
    finally:
        mz.PLAN_CACHE_LIMIT = old_limit
        mz.clear_plan_cache()


def test_plan_cache_mesh_key_not_id(monkeypatch):
    """Cache keys must use mesh structure, not id(mesh) (which the GC can
    reissue to a different mesh object)."""
    import jax
    from jax.sharding import Mesh
    mz.clear_plan_cache()
    devs = np.array(jax.devices()[:1])
    m1 = Mesh(devs, ("data",))
    m2 = Mesh(devs, ("data",))
    assert mz._mesh_key(m1) == mz._mesh_key(m2)
    assert mz._mesh_key(m1) != mz._mesh_key(None)
    A = _arr(64, 3)
    X = fm.conv_R2FM(A)
    fm.materialize(fm.colSums(X * 2.0), mesh=m1)
    n_before = len(mz._PLANS)
    fm.materialize(fm.colSums(fm.conv_R2FM(A) * 2.0), mesh=m2)
    assert len(mz._PLANS) == n_before  # structurally equal mesh ⇒ cache hit


# ---------------------------------------------------------------------------
# Registry-owned temp-dir cleanup (ISSUE 9 satellite)
# ---------------------------------------------------------------------------

def test_registry_cleanup_removes_owned_dirs_only(tmp_path, monkeypatch):
    """Lazily-mkdtemp'd fm-data-* dirs are removed by cleanup() and
    forgotten; a user-configured data_dir is never touched."""
    reg = storage.registry
    monkeypatch.setitem(reg._CONF, "data_dir", None)
    saved_owned = list(reg._OWNED_DIRS)
    reg._OWNED_DIRS[:] = []
    try:
        lazy = reg.data_dir()            # lazy init -> registry-owned
        assert lazy.exists() and lazy.name.startswith("fm-data-")
        assert lazy in reg._OWNED_DIRS
        removed = storage.cleanup()
        assert lazy in removed
        assert not lazy.exists()
        assert reg._OWNED_DIRS == []
        assert reg._CONF["data_dir"] is None  # forgotten, re-inits fresh

        # User-supplied dirs are never owned, never removed.
        user = tmp_path / "user-data"
        fm.set_conf(data_dir=str(user))
        assert reg.data_dir() == user
        assert storage.cleanup() == []
        assert user.exists()
        assert reg._CONF["data_dir"] == user  # a user dir is not forgotten
    finally:
        reg._OWNED_DIRS[:] = saved_owned


def test_engine_close_release_storage(tmp_path, monkeypatch):
    """Engine.close(release_storage=True) routes to registry.cleanup()."""
    reg = storage.registry
    monkeypatch.setitem(reg._CONF, "data_dir", None)
    saved_owned = list(reg._OWNED_DIRS)
    reg._OWNED_DIRS[:] = []
    try:
        lazy = reg.data_dir()
        assert lazy.exists()
        eng = fm.serve(window_ms=1)
        eng.close(release_storage=True)
        assert not lazy.exists()
    finally:
        reg._OWNED_DIRS[:] = saved_owned


@pytest.mark.slow
def test_registry_cleanup_runs_at_interpreter_exit():
    """The atexit hook removes a lazily-created data dir when the process
    exits normally — repeated runs no longer accumulate fm-data-* litter."""
    import subprocess, sys, os
    code = (
        "import json\n"
        "from repro.storage import registry\n"
        "d = registry.data_dir()\n"
        "assert d.exists()\n"
        "print(json.dumps(str(d)))\n")
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, env=env,
                          timeout=120, cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    import json
    leaked = pathlib.Path(json.loads(proc.stdout.strip().splitlines()[-1]))
    assert not leaked.exists(), f"atexit cleanup left {leaked}"


# ---------------------------------------------------------------------------
# Prefetcher shutdown on interrupted streams (ISSUE 9 satellite)
# ---------------------------------------------------------------------------

def test_interrupted_stream_leaks_no_prefetcher_state(data_dir,
                                                     monkeypatch):
    """A staging fault mid-stream must tear the prefetch pipeline down
    completely: worker thread joined, queued staged partitions drained
    (not pinned on device), TLS residents cleared — thread count and
    pinned-partition census return to baseline.  The long output whose
    host copies were pending when the fault came registers no result, and
    its member holds no pending device values."""
    import threading, time
    from helpers_cache import StagingFault, watch_pass_execs
    from repro.core import matrix as matrix_mod

    old_budget = matrix_mod.IO_PARTITION_BYTES
    fm.set_conf(io_partition_bytes=4096)  # force a real multi-partition sweep
    try:
        A = _arr(4096, 4)
        X = fm.persist(fm.conv_R2FM(A), tier="disk")
        store = X.m.store
        orig_block, reads = store.block, {"n": 0}

        def flaky_block(start, stop):
            reads["n"] += 1
            if reads["n"] > 2:
                raise StagingFault("injected disk fault")
            return orig_block(start, stop)

        store.block = flaky_block  # instance attr shadows the method

        seen = watch_pass_execs(monkeypatch)
        Y = X * X
        n_threads0 = threading.active_count()
        with pytest.raises((StagingFault, storage.PrefetchError)):
            fm.materialize(fm.colSums(Y), Y, mode="ooc", prefetch=True)
        # Two partitions staged before the fault, both still pending.
        assert max(n for n, _ in seen.pending) == 2
        assert Y.m.is_virtual, "an interrupted pass registered its output"
        assert all(not m.pending for m in seen.members)

        deadline = time.time() + 10
        while time.time() < deadline and (
                storage.live_prefetchers()
                or threading.active_count() > n_threads0):
            time.sleep(0.02)
        assert storage.live_prefetchers() == [], "worker thread still alive"
        assert storage.staged_leaks() == [], "staged partitions pinned"
        assert threading.active_count() <= n_threads0
        assert mz._tls_residents() is None  # interrupted run pins nothing
    finally:
        matrix_mod.IO_PARTITION_BYTES = old_budget
        mz.clear_plan_cache()


def test_abandoned_prefetcher_close_drains_late_enqueue(data_dir):
    """close() must win the race against a worker parked in the bounded
    queue's put(): repeatedly abandon a stream mid-flight with a FULL
    queue and assert no staged block survives shutdown."""
    A = _arr(4096, 4)
    X = fm.persist(fm.conv_R2FM(A), tier="disk")
    pairs = [(0, X.m)]
    for _ in range(10):
        pf = storage.PartitionPrefetcher(pairs, 256, 4096, depth=1)
        it = iter(pf)
        next(it)          # worker now racing to refill the full queue
        pf.close()
        assert not pf.alive
        assert pf.queued == 0, "block enqueued after shutdown drain"
    assert storage.staged_leaks() == []
