"""Observability layer: span tracer, scoped metrics, plan explain (ISSUE 6).

What the layer must guarantee:
  * spans nest (children lie inside their parent's interval) and the
    disabled tracer records nothing at near-zero cost,
  * ``fm.collect_stats()`` isolates per-request telemetry even when two
    materializes run CONCURRENTLY on different threads — including the
    counters recorded on the prefetcher's background thread,
  * the acceptance trace: an out-of-core two-pass ``scale(X, save='disk')``
    carries per-pass/per-partition ``stage``/``prefetch_wait``/
    ``device_step``/``combine`` spans, the prefetch thread on its own
    track, and exactly one ``epilogue`` span per pass that schedules one,
  * ``fm.explain`` output is stable (golden) for the two-pass scale plan,
  * prefetch-thread failures surface with partition range + source name,
  * ``exec_stats()`` stays a faithful compatibility view of the registry.
"""
import collections
import json
import re
import threading

import numpy as np
import pytest

from repro import storage
from repro.core import fm
from repro.core import materialize as mz
from repro.core import matrix as matrix_mod
from repro.observability import metrics
from repro.observability.trace import TRACER


@pytest.fixture()
def data_dir(tmp_path, monkeypatch):
    monkeypatch.setitem(storage.registry._CONF, "data_dir", None)
    fm.set_conf(data_dir=str(tmp_path / "fmdata"))
    return tmp_path / "fmdata"


@pytest.fixture()
def small_partitions():
    """Tiny I/O partitions so even small matrices stream multi-partition."""
    old = matrix_mod.IO_PARTITION_BYTES
    fm.set_conf(io_partition_bytes=4096)
    mz.clear_plan_cache()
    yield
    matrix_mod.IO_PARTITION_BYTES = old
    mz.clear_plan_cache()


def _arr(n=800, p=4, seed=0):
    return np.random.default_rng(seed).normal(size=(n, p)).astype(np.float32)


# ---------------------------------------------------------------------------
# Span tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_containment():
    TRACER.start()
    with TRACER.span("outer", idx=1):
        with TRACER.span("inner"):
            pass
        with TRACER.span("inner"):
            pass
    TRACER.stop()
    evs = TRACER.events()
    # Spans record on exit, so both children precede their parent.
    assert [e["name"] for e in evs] == ["inner", "inner", "outer"]
    outer = evs[-1]
    assert outer["args"] == {"idx": 1}
    for inner in evs[:2]:
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_disabled_tracer_records_nothing():
    with TRACER.span("x", a=1):
        pass
    TRACER.record("y", 0.0, 1.0)
    assert TRACER.events() == []
    # Disabled spans are one shared null object — no per-span allocation.
    assert TRACER.span("x") is TRACER.span("y")


def test_chrome_trace_export(tmp_path):
    with fm.trace():
        with TRACER.span("work", rows=7):
            pass
    path = tmp_path / "trace.json"
    fm.trace_export(str(path))
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    complete = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert [e["name"] for e in complete] == ["work"]
    assert complete[0]["dur"] >= 0 and complete[0]["args"] == {"rows": 7}
    assert any(m["name"] == "thread_name" for m in meta)
    assert any(m["name"] == "process_name" for m in meta)


def test_trace_context_manager_resets_by_default():
    with fm.trace():
        with TRACER.span("first"):
            pass
    assert [e["name"] for e in fm.trace_events()] == ["first"]
    with fm.trace():
        pass
    assert fm.trace_events() == []          # reset=True dropped "first"
    assert not TRACER.enabled               # and the tracer is off again


# ---------------------------------------------------------------------------
# Scoped metrics
# ---------------------------------------------------------------------------

def test_collect_stats_isolates_concurrent_materializes(small_partitions):
    """Two threads materialize different matrices at once; each scope must
    see only its own counters — including stage bytes recorded on each
    materialize's own prefetcher thread."""
    a = _arr(n=2048, p=4, seed=1)
    b = _arr(n=4096, p=4, seed=2)
    results = {}
    barrier = threading.Barrier(2)

    def work(tag, arr):
        X = fm.conv_R2FM(arr, host=True)
        G = fm.crossprod(X)
        barrier.wait()
        with fm.collect_stats(tag) as scope:
            fm.materialize(G, mode="stream")
        results[tag] = scope.stats()

    threads = [threading.Thread(target=work, args=("a", a)),
               threading.Thread(target=work, args=("b", b))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    for tag, arr in (("a", a), ("b", b)):
        st = results[tag]
        assert st["materialize_calls"] == 1
        assert st["passes"] == 1
        assert st["pass_bytes_in"] == (arr.nbytes,)
        # Prefetch-thread staging attributed to the right scope.
        assert st["stage_bytes_read"] == arr.nbytes
    # b is twice as many rows as a: twice the partition steps, per scope.
    assert results["a"]["partition_steps"] > 1
    assert results["b"]["partition_steps"] == \
        2 * results["a"]["partition_steps"]


def test_pass_bytes_scoped_per_execution_and_set_on_cache_hit():
    mz.reset_exec_stats()
    mz.clear_plan_cache()
    a = _arr(n=128)
    X = fm.conv_R2FM(a)
    fm.materialize(fm.crossprod(X))
    assert mz.exec_stats()["pass_bytes_in"] == (a.nbytes,)
    # Re-executing the cached plan must still publish its own bytes.
    with fm.collect_stats() as scope:
        fm.materialize(fm.crossprod(X))
    assert scope.stats()["pass_bytes_in"] == (a.nbytes,)
    st = mz.exec_stats()
    assert st["plan_cache_hits"] == 1 and st["plan_cache_misses"] == 1
    assert metrics.stats()["plan_cache_hit_ratio"] == 0.5


def test_exec_stats_compat_view():
    mz.reset_exec_stats()
    mz.clear_plan_cache()
    X = fm.conv_R2FM(_arr(n=200))
    fm.materialize(fm.scale(X))
    st = mz.exec_stats()
    assert st["materialize_calls"] == 1
    assert st["passes"] == 2                     # scale is the two-pass plan
    assert st["epilogue_launches"] >= 1
    assert len(st["pass_bytes_in"]) == 2
    for key in mz.EXEC_COUNTERS:
        assert isinstance(st[key], int), key
    # The registry view carries the derived telemetry too.
    full = metrics.stats()
    assert 0.0 <= full["prefetch_wait_frac"] <= 1.0
    assert full["stream_bandwidth_bytes_s"] >= 0.0


# ---------------------------------------------------------------------------
# Acceptance: out-of-core two-pass scale under the tracer
# ---------------------------------------------------------------------------

def test_ooc_disk_scale_trace(data_dir, small_partitions):
    a = _arr(n=1024, p=4, seed=3)
    X = fm.load_dense_matrix(a, "trace_x")
    Z = fm.scale(X, save="disk")
    mz.reset_exec_stats()
    with fm.trace():
        fm.materialize(Z)
    st = mz.exec_stats()
    evs = fm.trace_events()
    counts = collections.Counter(e["name"] for e in evs)

    assert counts["materialize"] == 1
    assert counts["pass"] == st["passes"] == 2
    assert counts["partition"] == st["partition_steps"] > 2
    for required in ("stage", "prefetch_wait", "device_step", "combine"):
        assert counts[required] > 0, required
    # Exactly one epilogue span per pass that schedules one.
    assert counts["epilogue"] == st["epilogue_launches"] == 1

    # The prefetcher's staging runs on its own track, not the main thread.
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    main_tid = threading.get_ident()
    stage_tids = {e["tid"] for e in by_name["stage"]}
    assert main_tid not in stage_tids
    assert TRACER.chrome_trace() and any(
        m.get("args", {}).get("name") == "fm-prefetch"
        for m in TRACER.chrome_trace()["traceEvents"] if m["ph"] == "M")

    # Every partition span falls inside some pass span's interval.
    passes = [(p["ts"], p["ts"] + p["dur"]) for p in by_name["pass"]]
    for part in by_name["partition"]:
        lo, hi = part["ts"], part["ts"] + part["dur"]
        assert any(p0 <= lo and hi <= p1 for p0, p1 in passes)
    # And the device_step/combine spans inside some partition span.
    parts = [(p["ts"], p["ts"] + p["dur"]) for p in by_name["partition"]]
    for name in ("device_step", "combine"):
        for e in by_name[name]:
            lo, hi = e["ts"], e["ts"] + e["dur"]
            assert any(p0 <= lo and hi <= p1 for p0, p1 in parts), name


# ---------------------------------------------------------------------------
# Prefetch error context (satellite)
# ---------------------------------------------------------------------------

def test_prefetch_error_carries_partition_and_source():
    class Exploding:
        name = "bad_matrix"

        def block(self, start, stop):
            raise OSError("bad sector")

    pf = storage.PartitionPrefetcher([(0, Exploding())], 8, 64)
    with pytest.raises(storage.PrefetchError,
                       match=r"rows \[0, 8\) of source 'bad_matrix'"):
        for _ in pf:
            pass
    pf.close()


def test_prefetch_error_names_unnamed_source_by_type():
    class Nameless:
        def block(self, start, stop):
            raise ValueError("boom")

    pf = storage.PartitionPrefetcher([(0, Nameless())], 4, 8)
    with pytest.raises(storage.PrefetchError, match=r"source 'Nameless'"):
        for _ in pf:
            pass
    pf.close()


# ---------------------------------------------------------------------------
# fm.explain (golden)
# ---------------------------------------------------------------------------

EXPLAIN_GOLDEN = """\
Plan: passes=2 long_dim=100 backend=xla
  cost: flops=2.700e+03 bytes_in=2.3 KiB bytes_out=1.2 KiB
pass 0: io_partition_rows=16384
  source leaf#N: 100x3 float32 tier=device streamed 1.2 KiB/pass (read once for 3 leaves)
  seg#N [sink_update] root=agg.col[sum] nodes=1 width=3 dtype=float32 flops/row=3.0 block_rows=32768
    -> xla generic trace
  seg#N [sink_update] root=agg.col[sum] nodes=2 width=3 dtype=float32 flops/row=6.0 block_rows=32768
    -> xla generic trace
  seg#N [sink_update] root=agg.col[sum] nodes=1 width=3 dtype=float32 flops/row=3.0 block_rows=32768
    -> xla generic trace
  seg#N [epilogue] root=sapply#N nodes=7 width=3 dtype=float32 flops/row=48.0 block_rows=16384
    -> post-merge epilogue (single launch per pass)
pass 1: io_partition_rows=32768
  bindings (from earlier passes): mapply#N, sapply#N
  source leaf#N: 100x3 float32 tier=device streamed 1.2 KiB/pass
  seg#N [row_local] root=mapply_row#N nodes=2 width=3 dtype=float32 flops/row=15.0 block_rows=16384
    -> xla generic trace"""


def test_explain_golden_two_pass_scale():
    old_io = matrix_mod.IO_PARTITION_BYTES
    old_vmem = matrix_mod.VMEM_PARTITION_BYTES
    fm.set_conf(io_partition_bytes=1 << 20, vmem_partition_bytes=1 << 20)
    try:
        X = fm.conv_R2FM(np.ones((100, 3), np.float32))
        text = fm.explain(fm.scale(X), backend="xla")
    finally:
        matrix_mod.IO_PARTITION_BYTES = old_io
        matrix_mod.VMEM_PARTITION_BYTES = old_vmem
    assert re.sub(r"#\d+", "#N", text) == EXPLAIN_GOLDEN


def test_explain_pallas_dispatch_reasons():
    X = fm.conv_R2FM(_arr(n=256))
    text = fm.explain(fm.crossprod(X), backend="pallas")
    assert "pallas:gram (claimed by " in text
    assert "backend=pallas" in text


def test_explain_nothing_virtual():
    X = fm.conv_R2FM(_arr(n=16))
    assert "already materialized" in fm.explain(X)


def test_plan_explain_method_matches_fm_explain():
    from repro.core.fusion import Plan
    X = fm.conv_R2FM(_arr(n=64))
    Z = fm.scale(X)
    assert Plan([Z.m]).explain(backend="xla") == fm.explain(
        Z, backend="xla")


# ---------------------------------------------------------------------------
# The profiler sink: spans in the JAX profiler's trace
# ---------------------------------------------------------------------------

def _profiler(log_dir):
    """A profiler session as the benchmark opens one: without the Python
    tracer, whose events would reach a new thread before the thread has
    its name."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return jax.profiler.trace(str(log_dir), profiler_options=opts)


def _profile_lines(log_dir):
    """{line: [(span name, {arg: value})]} of the ``fm.*`` events in the
    one ``.xplane.pb`` under ``log_dir``, per host line."""
    import glob

    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(ev.name.split("#")[0], dict(ev.stats))
                   for ev in line.events if ev.name.startswith("fm.")]
            if evs:
                lines.append((line.name, evs))
    return lines


@pytest.fixture()
def profiled_ooc_kmeans(tmp_path, small_partitions):
    """One Lloyd step over a host-RAM X, streamed by the prefetcher, under
    ``jax.profiler.trace``; the ``fm.trace()`` recording stays off."""
    from repro.algorithms.kmeans import kmeans_iteration
    X = fm.conv_R2FM(_arr(n=2048, p=4, seed=5), host=True)
    centers = _arr(n=3, p=4, seed=6)
    kmeans_iteration(X, centers)
    metrics.reset()
    with _profiler(tmp_path / "prof"):
        kmeans_iteration(X, centers)
    return _profile_lines(tmp_path / "prof"), metrics.stats()


def test_profiler_sink_puts_spans_on_the_working_threads(
        profiled_ooc_kmeans):
    lines, st = profiled_ooc_kmeans
    assert not TRACER.enabled and TRACER.events() == []
    names = {name: {n for n, _ in evs} for name, evs in lines}
    compute = [n for n, got in names.items() if "fm.pass" in got]
    assert len(compute) == 1
    assert {"fm.plan", "fm.pass", "fm.stream", "fm.partition",
            "fm.device_step", "fm.combine", "fm.prefetch_wait",
            "fm.fetch"} <= names[compute[0]]
    assert "fm.stage" not in names[compute[0]]
    assert {"fm.stage", "fm.stage_put"} <= names["fm-prefetch"]
    # One span of each per partition step and per fetch: each
    # partition's labels to their host buffer, then the three results the
    # caller fetches and counts (sums, counts and the objective).
    stages = [a for n, a in dict(lines)["fm-prefetch"] if n == "fm.stage"]
    assert len(stages) == st["partition_steps"] > 2
    fetches = [n for n, _ in dict(lines)[compute[0]] if n == "fm.fetch"]
    assert st["host_fetches"] == 3
    assert len(fetches) == st["partition_steps"] + st["host_fetches"]


def test_stage_spans_carry_the_pass_sequence_id(profiled_ooc_kmeans):
    lines, _ = profiled_ooc_kmeans
    by_line = dict(lines)
    compute = next(evs for _, evs in lines
                   if any(n == "fm.pass" for n, _ in evs))
    (pass_seq,) = {a["seq"] for n, a in compute if n == "fm.pass"}
    (stream_seq,) = {a["seq"] for n, a in compute if n == "fm.stream"}
    stage_seqs = {a["seq"] for n, a in by_line["fm-prefetch"]
                  if n == "fm.stage"}
    assert pass_seq == stream_seq and stage_seqs == {pass_seq}


def test_both_sinks_record_together(tmp_path, small_partitions):
    X = fm.conv_R2FM(_arr(n=1024, seed=7), host=True)
    with _profiler(tmp_path / "prof"), fm.trace():
        fm.conv_FM2R(fm.crossprod(X))
    recorded = collections.Counter(e["name"] for e in fm.trace_events())
    profiled = collections.Counter(
        n[len("fm."):] for _, evs in _profile_lines(tmp_path / "prof")
        for n, _ in evs)
    assert recorded == profiled
    assert recorded["stage_put"] == recorded["stage"] > 1


def test_span_is_the_null_span_with_both_sinks_off():
    from repro.observability import trace
    assert not TRACER.enabled and not trace.profiler_active()
    assert TRACER.span("device_step", rows=1) is trace._NULL_SPAN


def test_span_is_a_trace_me_under_the_profiler(tmp_path):
    from repro.observability import trace
    with _profiler(tmp_path):
        assert trace.profiler_active()
        assert TRACER.span("pass", seq=1) is not trace._NULL_SPAN
    assert TRACER.span("pass", seq=1) is trace._NULL_SPAN


def test_no_device_synchronization_in_the_executor():
    """Spans never block on device values: tracing leaves the pipeline's
    asynchronous dispatch as it is."""
    import inspect

    from repro.observability import trace
    assert "block_until_ready" not in inspect.getsource(mz)
    assert "block_until_ready" not in inspect.getsource(trace)


def test_stage_read_seconds_holds_the_device_put(small_partitions):
    """For a host block the read ends once ``device_put`` has taken the
    bytes: the put's time (its ``stage_put`` spans) lies inside the
    read's, and both inside the whole staging call's."""
    X = fm.conv_R2FM(_arr(n=4096, seed=8), host=True)
    with fm.collect_stats() as scope, fm.trace():
        fm.materialize(fm.crossprod(X), mode="ooc")
    st = scope.stats()
    put_s = sum(e["dur"] for e in fm.trace_events()
                if e["name"] == "stage_put") / 1e6
    assert st["stage_bytes_read"] == X.m.nbytes()
    assert 0 < put_s <= st["stage_read_seconds"] <= st["stage_seconds"]
    assert st["stream_bandwidth_bytes_s"] == pytest.approx(
        st["stage_bytes_read"] / st["stage_read_seconds"])


def test_plan_and_fetch_counters():
    from repro.algorithms.kmeans import kmeans_iteration
    X = fm.conv_R2FM(_arr(n=512, seed=9))
    centers = _arr(n=3, seed=10)
    with fm.collect_stats() as scope:
        for _ in range(2):
            centers = kmeans_iteration(X, centers)[0]
    st = scope.stats()
    # Sums, counts and the objective: three device results per iteration.
    assert st["host_fetches"] == 6
    assert st["plan_seconds"] > 0
    # A host array is no fetch.
    with fm.collect_stats() as scope:
        fm.conv_FM2R(fm.conv_R2FM(_arr(n=8), host=True))
    assert "host_fetches" not in scope.stats()
