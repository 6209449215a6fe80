"""The control's readings beside the program's, from which a
configuration's limits are set.

    python3 bench/calibrate.py --workload kmeans.mixgauss32.hbm --seed 11 \\
        --seconds 3

One run of the cell as ``bench/run.py`` makes it (set-up, a window of
``--seconds``, the reference's comparison of the sampled ops); then the
control (``bench/control/<op>.py``: the reference put in the program's
place, its products in bfloat16) computes the same
sampled ops from the same inputs, and the reference compares it the same
way.  Prints one JSON line: the program's and the control's readings of
every number the reference computes, compared or not.
One seed per process: the chip's host does not hand freed memory back
soon enough for a second seed's data.  The benchmark's own runs never run
the control.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run, spec  # noqa: E402


def _worst(readings: list) -> dict:
    return {k: max(r[k] for r in readings) for k in readings[0]}


def control_readings(cell: spec.Cell):
    """An ``after`` hook for ``run.measure`` that reads the control on the
    run's sampled ops."""
    ctrl = spec.load_module("control", cell.config["op"])
    ref = spec.load_module("ref", cell.config["op"])

    def after(data, config, samples, program_readings):
        outs = [ctrl.step(data, config, rec) for rec, _ in samples]
        return {"program": _worst(program_readings),
                "control": _worst(ref.compare(data, config, outs))}

    return after


def main(argv=None, **kw) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    run_args = run.parse_args(["--workload", args.workload, "--seed",
                               str(args.seed), "--seconds",
                               str(args.seconds)])
    res = run.measure(run_args, after=control_readings(
        spec.load_cell(args.workload)), **kw)
    print(json.dumps({"seed": args.seed, "correct": res["correct"],
                      "ops": res["attempted"], **res["after"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
