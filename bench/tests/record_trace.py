"""Record the small chip trace that ``test_bench_trace.py`` reduces.

    python3 bench/tests/record_trace.py OUT_DIR      # on a TPU

Two ops, each inside the benchmark's ``bench.op<i>`` annotation, each
launching the ``kmeans_assign`` kernel over 2^22 x 32 rows, the ``wgram``
kernel over 2^22 x 39 rows and one XLA reduction.  Writes
``OUT_DIR/small.xplane.pb`` and ``OUT_DIR/small.json`` with the shapes and
the device kind; copy both into ``bench/tests/data``.
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
import jax.numpy as jnp  # noqa: E402

from bench.trace_reduce import OP_PREFIX  # noqa: E402
from repro.kernels.kmeans_assign import kmeans_assign  # noqa: E402
from repro.kernels.weighted_gram import wgram  # noqa: E402

N, P_KM, K, P_WG = 1 << 22, 32, 10, 39


def main(out_dir: str) -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    x = jax.random.normal(jax.random.PRNGKey(0), (N, P_KM), jnp.float32)
    c = x[:K]
    z = jax.random.normal(jax.random.PRNGKey(1), (N, P_WG), jnp.float32)
    w = jax.random.uniform(jax.random.PRNGKey(2), (N,), jnp.float32)
    colsum = jax.jit(lambda a: a.sum(0))
    jax.block_until_ready((kmeans_assign(x, c), wgram(z, w), colsum(x)))
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(tmp, profiler_options=opts):
        for i in range(2):
            with jax.profiler.TraceAnnotation(f"{OP_PREFIX}{i}"):
                jax.block_until_ready(kmeans_assign(x, c))
                jax.block_until_ready(wgram(z, w))
                jax.block_until_ready(colsum(x))
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    (path,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    shutil.copy(path, out / "small.xplane.pb")
    shutil.rmtree(tmp)
    (out / "small.json").write_text(json.dumps(
        {"device_kind": dev.device_kind, "ops": 2,
         "kmeans_assign": [N, P_KM, K], "wgram": [N, P_WG]}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
