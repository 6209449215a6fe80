"""The device's idle time by the engine's spans (``bench/span_idle.py``),
and the counter metrics the engine's spans come with.

The sweep is tested on hand-built intervals; the reduction on a trace
recorded on a TPU v5e by ``record_spans.py``: two Lloyd steps of
``kmeans_iteration`` over 2^20 x 32 rows in device memory."""
import json
import pathlib

import numpy as np
import pytest

from bench import run, span_idle, spec, trace_reduce

DATA = pathlib.Path(__file__).with_name("data")


def test_sweep_credits_the_innermost_span():
    busy = [(10, 20), (30, 40)]
    events = [(0, 50, "fm.pass"), (5, 15, "fm.partition"),
              (22, 28, "fm.fetch"), (42, 45, "fm.plan")]
    assert span_idle.sweep(busy, 0, 50, events) == {
        "fm.pass": 16, "fm.partition": 5, "fm.fetch": 6, "fm.plan": 3}


def test_sweep_credits_none_where_no_span_is_open():
    got = span_idle.sweep([(10, 20)], 0, 30, [(22, 28, "fm.fetch")])
    assert got == {"none": 14, "fm.fetch": 6}
    assert span_idle.sweep([(0, 30)], 0, 30, [(0, 30, "fm.pass")]) == {}


def test_sweep_of_spans_that_start_together():
    # Of two spans that start together, the shorter is the inner one.
    got = span_idle.sweep([], 0, 10, [(0, 10, "fm.pass"), (0, 4, "fm.plan")])
    assert got == {"fm.plan": 4, "fm.pass": 6}


def test_sweep_clips_to_the_window():
    got = span_idle.sweep([(-5, 3), (8, 20)], 0, 10,
                          [(-10, 5, "fm.plan"), (6, 30, "fm.fetch")])
    assert got == {"fm.plan": 2, "none": 1, "fm.fetch": 2}


@pytest.mark.parametrize("seed", range(5))
def test_sweep_sums_to_the_window_less_busy(seed):
    rng = np.random.default_rng(seed)
    lo, hi = 0, 10_000
    starts = rng.integers(-500, hi, 40)
    busy = [(int(s), int(s + d)) for s, d in
            zip(starts, rng.integers(1, 400, 40))]
    events = []
    for _ in range(30):  # nested runs of spans, as one thread makes them
        s = int(rng.integers(-200, hi))
        e = s + int(rng.integers(1, 2000))
        for depth in range(int(rng.integers(1, 4))):
            events.append((s, e, f"fm.level{depth}"))
            s, e = s + (e - s) // 4, e - (e - s) // 4
    got = span_idle.sweep(busy, lo, hi, events)
    union = sum(min(e, hi) - max(s, lo)
                for s, e in trace_reduce._union(busy) if e > lo and s < hi)
    assert sum(got.values()) == hi - lo - union


def _sweep_args(profile):
    """``span_idle.sweep``'s tuples from a trace, by the reduction's own
    rules: the window of the ``bench.op<i>`` annotations, the first
    device's ``XLA Ops`` intervals clipped to it, and the ``fm.*`` events
    of the host lines that carry the annotations."""
    marks, spans = [], []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for ev in line.events]
            line_marks = [(s, e) for s, e, name in evs
                          if name.startswith(trace_reduce.OP_PREFIX)]
            if line_marks:
                marks += line_marks
                spans += [(s, e, name.split("#", 1)[0]) for s, e, name in evs
                          if name.startswith(span_idle.SPAN_PREFIX)]
    lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    device = next(p for p in profile.planes
                  if trace_reduce.DEVICE_PLANE.match(p.name))
    (ops,) = [line for line in device.lines
              if line.name == trace_reduce.OPS_LINE]
    busy = [trace_reduce._clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                               lo, hi) for ev in ops.events]
    return [(s, e) for s, e in busy if e > s], lo, hi, spans


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    meta = json.loads((DATA / "spans.json").read_text())
    profile = ProfileData.from_file(str(DATA / "spans.xplane.pb"))
    return meta, _sweep_args(profile), trace_reduce.reduce(
        profile, ["kmeans_assign"])


def test_recorded_idle_by_span(recorded):
    meta, (busy, lo, hi, spans), tr = recorded
    assert tr["ops"] == meta["ops"] == 2
    # The same window and the same device busy time as the reduction.
    window_s = (hi - lo) / 1e9
    busy_s = sum(e - s for s, e in trace_reduce._union(busy)) / 1e9
    assert window_s == pytest.approx(tr["window_s"], abs=1e-12)
    assert busy_s == pytest.approx(tr["busy_s"], abs=1e-12)
    idle = {k: v / 1e9 for k, v in span_idle.sweep(busy, lo, hi,
                                                     spans).items()}
    assert sum(idle.values()) == pytest.approx(window_s - busy_s, abs=1e-9)
    assert idle["fm.fetch"] > 0 and idle["fm.plan"] > 0
    assert all(k == "none" or k.startswith("fm.") for k in idle)
    device_idle = 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    shares = {k: 100.0 * v / window_s for k, v in idle.items()}
    assert shares["fm.fetch"] + shares["fm.plan"] <= device_idle


def test_recorded_fetches_per_iter(recorded):
    meta, _, _ = recorded

    class Run:
        counters = meta["counters"]
        op_seconds = [0.0] * meta["ops"]

    assert spec.load_module("metrics", "fetches_per_iter.mem").read(Run) \
        == 3.0


NEW_COUNTER_METRICS = ("fetches_per_iter.mem", "plan_ms_per_iter.mem",
                       "stage_GBps.host", "dispatch_ms_per_step.host")


@pytest.mark.parametrize("name", NEW_COUNTER_METRICS)
def test_counter_metric_reads_nothing_without_its_counters(name):
    class Run:
        counters = {"partition_steps": 4.0, "stage_bytes_read": 1e9}
        op_seconds = [0.1, 0.1]

    assert spec.load_module("metrics", name).read(Run) is None


@pytest.mark.parametrize("cell", ["kmeans.mixgauss32.hbm",
                                  "kmeans.mixgauss32.host"])
def test_counter_metrics_of_a_cpu_run(cell, monkeypatch):
    """A traced run at a tiny size, its device trace stood in for (the
    CPU has no TPU plane): every per-layer counter metric reads.  X
    streams in several partitions, so that the one the iteration scope
    keeps resident from op to op is not the only one."""
    from repro.core import materialize as mz
    from repro.core import matrix as matrix_mod
    monkeypatch.setattr(matrix_mod, "IO_PARTITION_BYTES", 4096 * 32 * 4 // 8)
    mz.clear_plan_cache()
    fake = {"ops": 1, "window_s": 1.0, "busy_s": 0.5, "kernel_seconds": {},
            "breakdown": {"device_ops": [], "idle_gaps": []}}
    monkeypatch.setattr(run, "traced_ops", lambda *a: fake)
    args = run.parse_args(["--workload", cell, "--seed", str(2**31 + 7),
                           "--seconds", "0.05", "--trace", "1"])
    res = run.measure(args, platform="cpu", rows=4096, compile_cache=False)
    assert res["correct"], res["compared"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    if cell.endswith(".hbm"):
        assert got["fetches_per_iter.mem"] == 3.0
        assert got["plan_ms_per_iter.mem"] > 0
        assert got["steps_per_iter.mem"] == 1.0
    else:
        assert got["stage_GBps.host"] > 0
        assert got["dispatch_ms_per_step.host"] > 0
        assert got["steps_per_iter.host"] > 1
