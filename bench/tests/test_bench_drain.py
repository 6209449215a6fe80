"""The ``drain_stall_share.host`` reader: the share of the streaming passes
that the compute thread spent writing drained long outputs to their
targets, from the ``output_drain_seconds`` and ``pass_seconds`` counters."""
import types

import pytest

from bench import run, spec

NAME = "drain_stall_share.host"


def _read(counters):
    return spec.load_module("metrics", NAME).read(
        types.SimpleNamespace(counters=counters, op_seconds=[0.1, 0.1]))


@pytest.mark.parametrize("counters, share", [
    ({}, None),
    ({"partition_steps": 4.0, "stage_bytes_read": 1e9}, None),
    ({"output_drain_seconds": 0.5}, None),
    ({"pass_seconds": 2.0}, None),
    ({"pass_seconds": 0.0, "output_drain_seconds": 0.5}, None),
    ({"pass_seconds": 2.0, "output_drain_seconds": 0.0}, 0.0),
    ({"pass_seconds": 2.0, "output_drain_seconds": 0.5}, 25.0),
])
def test_drain_stall_share(counters, share):
    assert _read(counters) == share


def test_drain_stall_share_of_a_cpu_run(monkeypatch):
    """A traced run of the host cell at a tiny size, X in several
    partitions, its device trace stood in for (the CPU has no TPU plane):
    the share is read, and lies between 0 and 100."""
    from repro.core import materialize as mz
    from repro.core import matrix as matrix_mod
    monkeypatch.setattr(matrix_mod, "IO_PARTITION_BYTES", 4096 * 32 * 4 // 8)
    mz.clear_plan_cache()
    fake = {"ops": 1, "window_s": 1.0, "busy_s": 0.5, "kernel_seconds": {},
            "breakdown": {"device_ops": [], "idle_gaps": []}}
    monkeypatch.setattr(run, "traced_ops", lambda *a: fake)
    args = run.parse_args(["--workload", "kmeans.mixgauss32.host",
                           "--seed", str(2**31 + 11), "--seconds", "0.05",
                           "--trace", "1"])
    res = run.measure(args, platform="cpu", rows=4096, compile_cache=False)
    assert res["correct"], res["compared"]
    assert 0 < res["metrics"][NAME]["value"] <= 100
