"""A cell, configuration and metric are found by name from files alone.

The test copies the benchmark, adds a configuration, a cell and a
per-layer metric as new files and new ``BENCHMARK.json`` entries, edits no
file that was there, and runs the new cell from the copy.
"""
import json
import os
import shutil
import subprocess
import sys

from bench import spec

NEW_METRIC = '''"""Ops in the window."""


def read(run):
    return float(len(run.op_seconds))
'''

SCRIPT = """
import json, sys
sys.path.insert(0, '.')
from bench import run, spec
cell = spec.load_cell('kmeans.mixgauss8.host')
assert [m['name'] for m in cell.per_layer] == ['ops_in_window'], cell.per_layer
args = run.parse_args(['--workload', 'kmeans.mixgauss8.host', '--seed', '3',
                       '--seconds', '0.05'])
res = run.measure(args, platform='cpu', rows=2048, compile_cache=False)
reader = spec.load_module('metrics', 'ops_in_window')
fake = run.Run(cell=cell, rows=2048, device_kind='cpu', setup_s=1.0,
               op_seconds=[0.1] * 3, window_s=0.3, counters={})
print(json.dumps({'result': res, 'ops_in_window': reader.read(fake)}))
"""


def test_files_and_entries_alone_add_a_cell(tmp_path):
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(spec.ROOT / "src", tmp_path / "src")
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}

    cfg = json.loads((spec.BENCH_DIR / "configs"
                      / "mixgauss32-k10.json").read_text())
    cfg.update(name="mixgauss8-k4", cols=8, k=4, components=4)
    (tmp_path / "bench/configs/mixgauss8-k4.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/metrics/ops_in_window.py").write_text(NEW_METRIC)
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "mixgauss8-k4",
                             "source": "https://arxiv.org/abs/1604.06414",
                             "file": "bench/configs/mixgauss8-k4.json",
                             "reduced": ["rows"], "why": "a test"})
    bench["workloads"].append({"name": "kmeans.mixgauss8.host",
                               "config": "mixgauss8-k4", "traffic": "host",
                               "chips": 1, "why": "a test"})
    for m in bench["end_to_end"]:
        if m["name"] == "iter_s":
            m["workloads"].append("kmeans.mixgauss8.host")
    bench["per_layer"].append({"name": "ops_in_window", "unit": "ops",
                               "better": "higher", "source": "host_clock",
                               "layer": "executor", "moves": "iter_s",
                               "workloads": ["kmeans.mixgauss8.host"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    out = subprocess.run([sys.executable, "-c", SCRIPT], cwd=tmp_path,
                         env=dict(os.environ, JAX_PLATFORMS="cpu",
                                  PYTHONPATH=""),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["result"]["correct"]
    assert set(got["result"]["metrics"]) == {"iter_s", "setup_s"}
    assert got["ops_in_window"] == 3.0
    assert all(p.read_bytes() == b for p, b in before.items())
