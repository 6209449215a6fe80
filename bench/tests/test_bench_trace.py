"""The trace reduction and the work functions, on a trace recorded on a
TPU v5e by ``record_trace.py``: two ops, each launching ``kmeans_assign``
over 2^22 x 32 rows, ``wgram`` over 2^22 x 39 rows and an XLA column sum."""
import json
import pathlib

import pytest

from bench import readers, trace_reduce, work

DATA = pathlib.Path(__file__).with_name("data")
KERNELS = ["kmeans_assign", "wgram"]


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    meta = json.loads((DATA / "small.json").read_text())
    profile = ProfileData.from_file(str(DATA / "small.xplane.pb"))
    return meta, trace_reduce.reduce(profile, KERNELS)


def test_window_busy_and_kernels(recorded):
    meta, tr = recorded
    assert tr["ops"] == meta["ops"] == 2
    assert 0 < tr["busy_s"] <= tr["window_s"]
    km, wg = tr["kernel_seconds"]["kmeans_assign"], tr["kernel_seconds"]["wgram"]
    assert 0 < km and 0 < wg and km + wg <= tr["busy_s"]
    names = [name for name, _ in tr["breakdown"]["device_ops"]]
    assert names[:2] == ["kmeans_assign", "wgram"] or \
        names[:2] == ["wgram", "kmeans_assign"]
    assert len(tr["breakdown"]["device_ops"]) <= trace_reduce.TOP
    gaps = tr["breakdown"]["idle_gaps"]
    assert 0 < len(gaps) <= trace_reduce.TOP
    assert sum(g for _, g in gaps) <= tr["window_s"] - tr["busy_s"] + 1e-9


def test_numbers_as_first_reduced(recorded):
    """The reduction of this trace when it was recorded (TPU v5 lite): a
    change to the reduction that moves them changes the yardstick."""
    _, tr = recorded
    assert tr["window_s"] == pytest.approx(0.011642079, abs=1e-12)
    assert tr["busy_s"] == pytest.approx(0.005921018, abs=1e-12)
    assert tr["kernel_seconds"] == pytest.approx(
        {"kmeans_assign": 0.001733337, "wgram": 0.002766171}, abs=1e-12)


def test_rooflines_stay_under_the_peaks(recorded):
    meta, tr = recorded

    class Run:
        trace = tr
        device_kind = meta["device_kind"]

    flops, nbytes = work.kmeans_assign(*meta["kmeans_assign"])
    share = readers.roofline(Run, "kmeans_assign", flops, nbytes)
    assert 5.0 < share <= 100.0, share


def test_work_functions():
    assert work.kmeans_assign(1 << 20, 32, 10) == (
        4.0 * (1 << 20) * 32 * 10, (1 << 20) * (32 * 4 + 4))
    assert work.least_seconds(819e9, 819e9, "TPU v5 lite") == 1.0
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
