"""Record the small chip trace that ``test_bench_spans.py`` reduces.

    python3 bench/tests/record_spans.py OUT_DIR      # on a TPU

Two Lloyd steps of the library's ``kmeans_iteration`` over a 2^20 x 32
float32 X in device memory (k = 10), each inside the benchmark's
``bench.op<i>`` annotation, at the benchmark's host tracer level: the
engine's ``fm.*`` spans on the compute thread's line beside the device's
``kmeans_assign`` launches.  Writes ``OUT_DIR/spans.xplane.pb`` and
``OUT_DIR/spans.json`` (the shapes, the device kind and the engine's
counters over the two traced ops); copy both into ``bench/tests/data``.
"""
import glob
import json
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench.run import HOST_TRACER_LEVEL  # noqa: E402
from bench.trace_reduce import OP_PREFIX  # noqa: E402
from repro.algorithms.kmeans import kmeans_iteration  # noqa: E402
from repro.core import fm  # noqa: E402
from repro.observability import metrics  # noqa: E402

N, P, K, OPS = 1 << 20, 32, 10, 2


def main(out_dir: str) -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("record_spans: needs a TPU")
    x = jax.random.normal(jax.random.PRNGKey(0), (N, P), jax.numpy.float32)
    X = fm.conv_R2FM(x)
    centers = np.asarray(x[:K])
    with fm.inspect_iterations():
        for _ in range(2):
            centers = kmeans_iteration(X, centers)[0]
        metrics.reset()
        tmp = tempfile.mkdtemp()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = HOST_TRACER_LEVEL
        with jax.profiler.trace(tmp, profiler_options=opts):
            for i in range(OPS):
                with jax.profiler.TraceAnnotation(f"{OP_PREFIX}{i}"):
                    centers = kmeans_iteration(X, centers)[0]
    counters = {k: v for k, v in metrics.stats().items()
                if isinstance(v, float)}
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    (path,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    shutil.copy(path, out / "spans.xplane.pb")
    shutil.rmtree(tmp)
    (out / "spans.json").write_text(json.dumps(
        {"device_kind": dev.device_kind, "ops": OPS,
         "kmeans_assign": [N, P, K], "counters": counters}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
