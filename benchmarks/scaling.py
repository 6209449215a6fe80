"""Fig 8: parallel scaling of the engine.

On this container, wall-clock SPMD scaling is NOT measurable: one XLA-CPU
"device" already multithreads across every physical core, so adding host
devices only adds partitioning overhead on a shared pool (measured: ~0.2x
"speedup" - reported honestly rather than massaged).  The paper's Fig 8
claim - work partitions evenly with no replication, sinks merge with one
reduction - is instead verified *structurally*: the same global GenOps
workload (crossprod + colSums over 200k x 64) is lowered and compiled for
1/2/4/8 devices and the loop-aware per-device FLOPs must fall as 1/N with
only O(p^2) reduction traffic.  On real hardware the identical lowering is
what executes, so per-device work proportional to 1/N IS linear scaling.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from .common import emit

_WORKER = textwrap.dedent("""
    import os, sys, json
    n = int(sys.argv[1])
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.hlo_analysis import analyze

    mesh = jax.make_mesh((n,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))

    def work(x):
        z = jnp.abs(x * 2.0 - 1.0)
        return z.T @ z, z.sum(0)

    spec = jax.ShapeDtypeStruct((200_000, 64), jnp.float32)
    sh = NamedSharding(mesh, P("data", None))
    rep = NamedSharding(mesh, P())
    compiled = jax.jit(work, in_shardings=sh,
                       out_shardings=(rep, rep)).lower(spec).compile()
    la = analyze(compiled.as_text())
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    print(json.dumps({"n": n, "flops_per_dev": la["dot_flops"],
                      "coll_bytes": la["collective_bytes_total"],
                      "bytes_accessed": float(ca.get("bytes accessed", 0))}))
""")


def fig8_scaling():
    rows = []
    base = None
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # The workers are a compile-only structural study on forced host
    # devices: they must never contend with this process for an
    # accelerator it may already hold.
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    for n in (1, 2, 4, 8):
        proc = subprocess.run([sys.executable, "-c", _WORKER, str(n)],
                              capture_output=True, text=True, env=env,
                              cwd=root, timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(f"fig8 worker for {n} devices failed:\n"
                               f"{proc.stderr[-2000:]}")
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        if base is None:
            base = out["flops_per_dev"]
        eff = base / (n * out["flops_per_dev"]) if out["flops_per_dev"] else 0
        rows.append((f"fig8/devices{n}", out["flops_per_dev"],
                     f"parallel_efficiency={eff:.3f};"
                     f"coll_bytes={out['coll_bytes']:.2e}"))
    return emit(rows)


ALL = [fig8_scaling]
