"""Serving load generator: concurrent tenants over one disk matrix.

    PYTHONPATH=src python -m repro.launch.serve --clients 4 --waves 3

Drives the async serving layer (``fm.serve`` / `core/serve.Engine`) the
way the paper's workflow is actually deployed: many clients concurrently
request independent analytics over the SAME named SSD-resident matrix.
Two arms over identical request traffic:

  serial   every request is its own ``fm.materialize`` — k clients ×
           w waves pay k·w full scans of the source;
  serve    requests go through an Engine admission window — each wave's
           k same-source strangers coalesce onto ONE streaming drive
           (``exec_stats()['streams'] == waves``), so the disk tier is
           read once per wave, not once per request.

Emits one machine-readable ``BENCH {json}`` row per arm: requests/sec,
p50/p99 latency (reported, NOT gated — thread scheduling jitters them),
plus the deterministic engine evidence the CI regression gate compares
exactly — ``streams`` and ``bytes_per_request`` (bytes streamed off the
disk tier divided by requests served).  Window coalescing is what moves
``bytes_per_request``: the serve arm's value is the serial arm's divided
by the number of clients.

The arms run with mid-stream admission disabled and the window held open
for exactly one wave (``max_window_requests=clients`` + a client-side
barrier), so the schedule — and therefore every gated counter — is
deterministic.
"""
from __future__ import annotations

import argparse
import json
import logging
import threading
import time

import numpy as np

log = logging.getLogger("repro.serve")

#: The per-client request mix: client i of a wave submits mix[i % len].
#: All single-pass over the shared source, so every wave forms ONE group.
def _request_mix(fm):
    return (fm.colMeans, fm.colSums, lambda X: fm.colMaxs(X), fm.sum_)


def _percentile(sorted_us, q):
    if not sorted_us:
        return 0.0
    idx = min(len(sorted_us) - 1, int(round(q * (len(sorted_us) - 1))))
    return sorted_us[idx]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=60_000)
    ap.add_argument("--p", type=int, default=8)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--waves", type=int, default=3)
    ap.add_argument("--window-ms", type=float, default=2000.0,
                    help="admission window upper bound; each wave closes "
                         "it early via max_window_requests")
    ap.add_argument("--partition-kib", type=int, default=256)
    ap.add_argument("--name", default="serve_loadgen_x")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO, format="%(message)s")

    from repro.core import fm, lowering
    from repro.core import materialize as mz
    from repro.core import matrix as matrix_mod
    from repro.observability import metrics

    old_io = matrix_mod.IO_PARTITION_BYTES
    fm.set_conf(io_partition_bytes=args.partition_kib << 10)
    try:
        rng = np.random.default_rng(0)
        X_np = rng.normal(size=(args.n, args.p)).astype(np.float32)
        X = fm.load_dense_matrix(X_np, args.name)  # the disk tier
        mix = _request_mix(fm)
        k, waves = args.clients, args.waves
        n_requests = k * waves
        records = []

        for arm in ("serial", "serve"):
            mz.clear_plan_cache()
            mz.reset_exec_stats()
            latencies_us = []
            lat_lock = threading.Lock()
            t_arm = time.perf_counter()

            if arm == "serial":
                for _ in range(waves):
                    for i in range(k):
                        t0 = time.perf_counter()
                        fm.materialize(mix[i % len(mix)](X))
                        latencies_us.append(
                            1e6 * (time.perf_counter() - t0))
            else:
                eng = fm.serve(window_ms=args.window_ms,
                               max_window_requests=k,
                               midstream_admission=False)
                try:
                    for _ in range(waves):
                        barrier = threading.Barrier(k)
                        errors = []

                        def client(i):
                            try:
                                out = mix[i % len(mix)](X)
                                barrier.wait(timeout=30)
                                t0 = time.perf_counter()
                                eng.submit(out).result(timeout=300)
                                us = 1e6 * (time.perf_counter() - t0)
                                with lat_lock:
                                    latencies_us.append(us)
                            except Exception as exc:  # noqa: BLE001
                                errors.append(exc)

                        threads = [threading.Thread(target=client, args=(i,))
                                   for i in range(k)]
                        for t in threads:
                            t.start()
                        for t in threads:
                            t.join(timeout=600)
                        if errors:
                            raise errors[0]
                finally:
                    eng.close()

            wall_s = time.perf_counter() - t_arm
            st = mz.exec_stats()
            streamed = int(metrics.root_counter("bytes_streamed"))
            lat = sorted(latencies_us)
            record = {
                "bench": "serve", "workload": "mixed-analytics",
                "arm": arm, "mode": "disk",
                "backend": lowering.resolve_backend(),
                "n": args.n, "p": args.p,
                "clients": k, "waves": waves, "requests": n_requests,
                "us_per_call": round(1e6 * wall_s / n_requests, 1),
                "rps": round(n_requests / max(wall_s, 1e-9), 1),
                "us_p50": round(_percentile(lat, 0.50), 1),
                "us_p99": round(_percentile(lat, 0.99), 1),
                # Deterministic engine evidence (CI gates these exactly):
                # serve = one stream per wave; serial = one per request.
                "streams": st["streams"],
                "bytes_per_request": streamed // n_requests,
            }
            print("BENCH " + json.dumps(record, sort_keys=True))
            log.info(
                "%-6s %d requests (%d clients x %d waves): %.1f req/s, "
                "p50 %.1fms p99 %.1fms, streams=%d, %.2f MiB/request",
                arm, n_requests, k, waves, record["rps"],
                record["us_p50"] / 1e3, record["us_p99"] / 1e3,
                record["streams"], record["bytes_per_request"] / 2**20)
            records.append(record)

        serial, served = records
        assert served["streams"] == waves, (
            "window coalescing broken: expected one stream per wave, got "
            f"{served['streams']} for {waves} waves")
        assert served["bytes_per_request"] * n_requests \
            < serial["bytes_per_request"] * n_requests, (
            "serve arm must read strictly fewer bytes than serial")
        log.info("coalescing: %d same-source requests/window -> 1 stream; "
                 "bytes/request %.2f MiB -> %.2f MiB (%.1fx)",
                 k, serial["bytes_per_request"] / 2**20,
                 served["bytes_per_request"] / 2**20,
                 serial["bytes_per_request"]
                 / max(served["bytes_per_request"], 1))
        return records
    finally:
        matrix_mod.IO_PARTITION_BYTES = old_io


def run(argv=None):
    """benchmarks/check_regression.py entry point."""
    return main(argv)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
