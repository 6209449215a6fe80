"""The control comes out not correct, the program correct, at a size a test
run holds.

The control (``bench/control/<op>.py``) is the reference put in the
program's place, its products in bfloat16: one step below the
configuration's float32.  It computes the same sampled ops
from the same inputs, and the reference compares it as it compares the
program.  The limits are the configuration's own.
"""
import pytest

from bench import calibrate, run, spec

ROWS = 1 << 17


@pytest.mark.parametrize("cell", ["kmeans.mixgauss32.hbm",
                                  "kmeans.mixgauss32.host"])
def test_control_fails_a_limit_the_program_meets(cell):
    limits = spec.load_cell(cell).config["limits"]
    for seed in (2, 3):
        args = run.parse_args(["--workload", cell, "--seed", str(seed),
                               "--seconds", "0.05"])
        res = run.measure(args, platform="cpu", rows=ROWS,
                          compile_cache=False,
                          after=calibrate.control_readings(
                              spec.load_cell(cell)))
        assert res["correct"], res["compared"]
        control = res["after"]["control"]
        assert any(control[k] > lim for k, lim in limits.items()), control
