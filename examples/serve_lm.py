"""Multi-tenant serving example: concurrent analytics over one SSD matrix.

    PYTHONPATH=src python examples/serve_lm.py

Three tenants submit independent requests against the same named
disk-resident matrix from their own threads.  The engine's admission
window coalesces them onto ONE streaming pass — the disk tier is read
once, and each tenant's ``fm.collect_stats()`` scope still reports its
own plan's share.
"""
import threading

import numpy as np

from repro.core import fm
from repro.core import materialize as mz
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

X_np = np.random.default_rng(0).normal(size=(50_000, 8)).astype(np.float32)
X = fm.load_dense_matrix(X_np, "served_features")  # SSD-analog tier

mz.reset_exec_stats()
with fm.serve(window_ms=200, max_window_requests=3) as engine:
    barrier = threading.Barrier(3)
    results = {}

    def tenant(name, output):
        with fm.collect_stats(name) as scope:
            barrier.wait()
            value = engine.submit(output).result(timeout=120)
        results[name] = (fm.as_np(value), scope.stats())

    threads = [
        threading.Thread(target=tenant, args=("means", fm.colMeans(X))),
        threading.Thread(target=tenant, args=("sds", fm.colSds(X))),
        threading.Thread(target=tenant, args=("gram", fm.crossprod(X))),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

st = mz.exec_stats()
print(f"3 tenants -> streams={st['streams']} passes={st['passes']}")
assert st["streams"] == 1  # one shared scan of the disk tier

for name, (value, stats) in sorted(results.items()):
    print(f"  {name}: shape={np.asarray(value).shape} "
          f"streams={stats['streams']} bytes={stats['bytes_streamed']}")

np.testing.assert_allclose(results["means"][0].ravel(), X_np.mean(0),
                           rtol=1e-3, atol=1e-4)
print("parity with numpy: OK")
