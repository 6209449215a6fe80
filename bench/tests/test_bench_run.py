"""Whole runs of ``bench/run.py`` on the CPU at a tiny size.

Each run skips the look for a chip (``platform="cpu"``) and drives the
rest: generation, placement on the cell's tier, the window, the
reference's comparison.  A sound run comes out correct; a run whose timed
path is broken underneath, by each fault a cell can have, does not.
"""
import importlib
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import run, spec

ROWS = 4096
SEED = 2**31 + 99
CELLS = ("kmeans.mixgauss32.hbm", "kmeans.mixgauss32.host")


def _measure(cell, seed=SEED):
    args = run.parse_args(["--workload", cell, "--seed", str(seed),
                           "--seconds", "0.05", "--trace", "0"])
    return run.measure(args, platform="cpu", rows=ROWS, compile_cache=False)


def _kmeans_faults():
    km = importlib.import_module("repro.algorithms.kmeans")
    from repro.core import fm
    real = km.kmeans_iteration

    def unchanged(X, centers, **kw):
        _, counts, wss, labels = real(X, centers, **kw)
        return centers, counts, wss, labels

    def half_batch(X, centers, **kw):
        data = np.asarray(X.m.logical_data())
        return real(fm.conv_R2FM(data[: len(data) // 2]), centers, **kw)

    def altered(X, centers, **kw):
        new, counts, wss, labels = real(X, centers, **kw)
        lab = np.asarray(fm.as_np(labels)).reshape(-1).copy()
        lab[0] = (lab[0] + 1) % centers.shape[0]
        return new, counts, wss, fm.conv_R2FM(lab.reshape(-1, 1))

    return km, "kmeans_iteration", {"unchanged": unchanged,
                                    "half_batch": half_batch,
                                    "altered": altered}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = _measure(cell)
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"]
                                   for m in spec.load_cell(cell).end_to_end}
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    mod, name, faults = _kmeans_faults()
    monkeypatch.setattr(mod, name, faults[fault])
    res = _measure(cell)
    assert not res["correct"], res["compared"]
    assert res["failed"] >= 1


def test_no_result_without_a_tpu():
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "5", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == "", out.stdout
    assert "no result" in out.stderr


def test_no_result_from_the_benchmark_files_alone(tmp_path):
    """Without the program beside it, a run past the chip check fails."""
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys; sys.path.insert(0, '.'); from bench import run; "
            f"sys.exit(run.main(['--workload', '{CELLS[0]}', '--seed', '5', "
            "'--seconds', '1'], platform='cpu', rows=4096, "
            "compile_cache=False))")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         env=dict(os.environ, JAX_PLATFORMS="cpu",
                                  PYTHONPATH=""),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "No module named 'repro'" in out.stderr
