"""Storage-tier benchmark: the paper's "out-of-core tracks in-memory" figure
against a real slow tier.

    PYTHONPATH=src python benchmarks/storage_bench.py [--n N] [--p P]

One fused analytics pass (Gram matrix + column sums — the correlation
workload, O(n·p²) FLOPs on O(n·p) bytes) is timed in every execution mode:

    whole            device-resident, one XLA computation
    stream           device-resident, explicit I/O-partition loop
    ooc-ram          host numpy source, streamed host→device
    ooc-ram-nopf     ... with the async prefetcher disabled
    ooc-disk         MmapStore source (the on-disk matrix format)
    ooc-disk-nopf    ... with the async prefetcher disabled

The ooc-disk vs ooc-disk-nopf pair is the paper's I/O/compute-overlap
ablation: prefetch-on stages partition i+1 (disk read + H2D copy) on a
background thread while partition i computes.  Interpretation caveat for
this CPU container: the matrix file usually sits in the page cache and the
XLA CPU "device" already saturates every core, so there is no I/O latency
to hide and the staging thread can only add contention — expect parity or
a small overhead here, and the actual win on a machine where the slow tier
has real latency (SSD cold reads, network storage) and the device computes
without stealing host cores.

Rows follow the repo-wide ``name,us_per_call,derived`` contract; derived is
the streamed bandwidth in GiB/s.
"""
import argparse
import sys
import tempfile

import numpy as np

try:
    from .common import emit, time_call
except ImportError:  # direct `python benchmarks/storage_bench.py` invocation
    from common import emit, time_call


def run(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=400_000)
    ap.add_argument("--p", type=int, default=32)
    ap.add_argument("--partition-mib", type=int, default=4)
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args(argv)

    from repro.core import fm

    tmp = tempfile.TemporaryDirectory(prefix="fm-bench-")  # removed at exit
    fm.set_conf(data_dir=tmp.name, io_partition_bytes=args.partition_mib << 20)

    n, p = args.n, args.p
    nbytes = n * p * 4
    rng = np.random.default_rng(0)
    X_np = rng.normal(size=(n, p)).astype(np.float32)

    X_dev = fm.conv_R2FM(X_np)
    X_ram = fm.conv_R2FM(X_np, host=True)
    X_disk = fm.load_dense_matrix(X_np, "bench")
    print(f"# {n}x{p} f32 = {nbytes / 2**20:.0f} MiB, partition budget "
          f"{args.partition_mib} MiB", file=sys.stderr)

    def scan(X, **kw):
        G = fm.crossprod(X)
        s = fm.colSums(X)
        Gm, sm = fm.materialize(G, s, **kw)
        return fm.as_np(Gm)

    variants = [
        ("storage/whole", X_dev, {"mode": "whole"}, False),
        ("storage/stream", X_dev, {"mode": "stream"}, False),
        ("storage/ooc-ram", X_ram, {"mode": "ooc", "prefetch": True}, False),
        ("storage/ooc-ram-nopf", X_ram, {"mode": "ooc", "prefetch": False},
         False),
        ("storage/ooc-disk", X_disk, {"mode": "ooc", "prefetch": True}, False),
        ("storage/ooc-disk-nopf", X_disk, {"mode": "ooc", "prefetch": False},
         False),
        # Cold-read arms: direct_io drops each partition's pages after the
        # read (posix_fadvise DONTNEED), so every pass re-reads from the
        # device — the prefetch-overlap measurement the warm page cache
        # hides on this container.
        ("storage/ooc-disk-cold", X_disk,
         {"mode": "ooc", "prefetch": True}, True),
        ("storage/ooc-disk-cold-nopf", X_disk,
         {"mode": "ooc", "prefetch": False}, True),
    ]

    rows = []
    baseline = None
    for name, X, kw, direct_io in variants:
        fm.set_conf(direct_io=direct_io)
        try:
            us = time_call(scan, X, iters=args.iters, **kw)
        finally:
            fm.set_conf(direct_io=False)
        gibps = nbytes / (us * 1e-6) / 2**30
        rows.append((name, us, f"{gibps:.2f}GiB/s"))
        if name == "storage/whole":
            baseline = us
    emit(rows)
    disk_pf = next(us for nm, us, _ in rows if nm == "storage/ooc-disk")
    disk_np = next(us for nm, us, _ in rows if nm == "storage/ooc-disk-nopf")
    print(f"# ooc-disk is {disk_pf / baseline:.2f}x whole;"
          f" prefetch saves {(disk_np - disk_pf) / disk_np * 100:.0f}% "
          f"({disk_np:.0f}us -> {disk_pf:.0f}us)", file=sys.stderr)
    return rows


def storage_tiers():
    """run.py entry: a quick pass at reduced size.  Restores the engine
    config afterwards so later benchmarks keep the default partition
    budget."""
    from repro.core import matrix as matrix_mod
    from repro import storage
    old_budget = matrix_mod.IO_PARTITION_BYTES
    old_dir = storage.registry._CONF["data_dir"]
    try:
        return run(["--n", "200000", "--iters", "2"])
    finally:
        matrix_mod.IO_PARTITION_BYTES = old_budget
        storage.registry._CONF["data_dir"] = old_dir


ALL = [storage_tiers]


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
