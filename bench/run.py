"""Benchmark of the FlashR engine on the chip: one run of one cell.

    python3 bench/run.py --workload kmeans.mixgauss32.hbm --seed 7 \\
        --seconds 30 --trace 0

The cell, its configuration, traffic, data generator, op, reference and
metrics are found by name (``bench/spec.py``).  A run:

1. refuses to start unless JAX finds a TPU with as many chips as the cell
   asks for: it exits non-zero and prints no result;
2. set-up: generates the data from ``--seed``, puts it on the cell's tier
   (``bench/tiers.py``), builds the op and runs it once, which compiles
   or loads every program the window uses from the persistent cache;
3. the window: repeats the op, each one algorithm iteration (one fused
   pass over X) with the iterate carried from op to op, inside one
   ``fm.inspect_iterations()`` scope as the library's own loops run, and
   stops at the first op boundary after ``--seconds``;
4. with ``--trace 1``, traces the traffic's ``trace_ops`` further ops with
   the JAX profiler and reduces the trace (``bench/trace_reduce.py``);
5. reads the device's peak memory, frees the program's state, and
   compares two ops with a plain float64 reference (``bench/ref/<op>.py``):
   the warm-up op, which moves the iterate most, and one op of the window
   drawn from the seed.

Set-up notes, the compiles seen inside the window and the numbers compared
go to standard error, the compared numbers last; the last line of standard
output is the result: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown``, and last ``compared``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import spec, tiers  # noqa: E402
from bench.gen import _blocks  # noqa: E402

log = tiers.log


class NoChip(RuntimeError):
    """JAX found no accelerator of the kind the cell needs."""


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cell: spec.Cell
    rows: int
    device_kind: str
    setup_s: float
    op_seconds: list
    window_s: float
    counters: dict
    trace: dict | None = None


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(platform: str, chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < chips:
        raise NoChip(f"need {chips} {platform} device(s); JAX has "
                     f"{len(devices)} {devices[0].platform} "
                     f"({devices[0].device_kind})")
    return devices


class CompileCounter:
    """Counts the programs JAX compiles, or loads from its persistent
    cache, while it is on."""

    PROGRAM = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring
        self.programs = self.hits = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._program)
        jax.monitoring.register_event_listener(self._hit)

    def _program(self, event, duration, **kw):
        if self.on and event == self.PROGRAM:
            self.programs += 1

    def _hit(self, event, **kw):
        if self.on and event == self.CACHE_HIT:
            self.hits += 1

    def take(self) -> str:
        """What was seen since the last ``take``, as text."""
        text = (f"{self.programs} programs compiled or loaded, "
                f"{self.hits} of them from the persistent cache")
        self.programs = self.hits = 0
        return text

    def close(self):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._program)
        jax.monitoring.unregister_event_listener(self._hit)


def window(op, seconds: float, seed: int):
    """Repeat ``op.step()`` until the first op boundary after ``seconds``;
    returns (per-op seconds, window seconds, sample).  The sample is one
    op's outputs, drawn from the seed by reservoir sampling; only it keeps
    its per-row outputs."""
    pick = _blocks.rng(seed, 2)
    op_seconds, sample = [], None
    t0 = time.perf_counter()
    while True:
        ts = time.perf_counter()
        out = op.step()
        te = time.perf_counter()
        op_seconds.append(te - ts)
        if pick.random() * len(op_seconds) < 1.0:
            sample = out
        del out
        if te - t0 >= seconds:
            return op_seconds, te - t0, sample


#: The profiler's host tracer level: 1 records the benchmark's annotations
#: and the library's (``Transpose``, ``np.asarray(jax.Array)``), which name
#: the idle gaps; the runtime's events that 2 adds are not read.
HOST_TRACER_LEVEL = 1


def traced_ops(op, traffic: dict, kernels: list):
    """Trace the traffic's ``trace_ops`` further ops, each inside a host
    annotation of the benchmark's own, and reduce the trace."""
    import jax
    from bench import trace_reduce
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = HOST_TRACER_LEVEL
        with jax.profiler.trace(tmp, profiler_options=opts):
            for i in range(int(traffic["trace_ops"])):
                with jax.profiler.TraceAnnotation(
                        f"{trace_reduce.OP_PREFIX}{i}"):
                    op.step()
        return trace_reduce.reduce_dir(tmp, kernels)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def measure(args, *, platform: str = "tpu", rows: int | None = None,
            compile_cache: bool = True, after=None) -> dict:
    """One run; returns the result object.  ``platform``, ``rows`` and
    ``compile_cache`` exist for the CPU tests, which drive a run at a tiny
    size without the chip; ``after(data, config, samples, readings)``,
    when given, runs once the reference has compared, and what it returns
    is kept under ``after`` (``bench/calibrate.py`` reads the control
    there)."""
    cell = spec.load_cell(args.workload)
    traffic, cfg = cell.traffic, cell.config
    devices = require_chips(platform, cell.chips)
    dev = devices[0]
    if compile_cache:
        from repro.launch.compile_cache import enable_compile_cache
        log(f"compile cache {enable_compile_cache()}")
    from repro.core import fm
    from repro.observability import metrics

    compiles = CompileCounter()
    rows = int(rows or cfg["rows"])
    gen = spec.load_module("gen", cfg["generator"])
    op_mod = spec.load_module("ops", cfg["op"])
    ref_mod = spec.load_module("ref", cfg["op"])

    t = time.perf_counter()
    data = gen.generate(cfg, args.seed, rows)
    log(f"generated {rows} rows of {cfg['name']} in "
        f"{time.perf_counter() - t:.3f} s")
    mats = tiers.place(traffic["tier"], data)
    op = None
    try:
        compiles.on = True
        with fm.inspect_iterations():
            op = op_mod.Op(mats, cfg, args.seed)
            # The warm-up op compiles or loads every program; it is also
            # the first sample, the step that moves the iterate most.
            warm = op.step()
            setup_s = time.perf_counter() - T_START
            log(f"set-up {setup_s:.3f} s; {compiles.take()}")
            metrics.REGISTRY.reset()
            op_seconds, window_s, drawn = window(op, args.seconds,
                                                 args.seed)
            samples = [warm, drawn]
            del warm, drawn
            counters = dict(metrics.stats())
            q = sorted(op_seconds)
            log(f"window {window_s:.6f} s, {len(op_seconds)} ops (op seconds "
                f"min {q[0]:.6f}, median {q[len(q) // 2]:.6f}, max "
                f"{q[-1]:.6f}); inside it {compiles.take()}")
            trace = None
            if args.trace:
                trace = traced_ops(op, traffic, cfg["kernels"])
                log(f"traced {trace['ops']} ops: busy {trace['busy_s']} s "
                    f"of {trace['window_s']} s, kernels "
                    f"{trace['kernel_seconds']}")
        stats = dev.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
    finally:
        compiles.close()
        if op is not None:
            op.close()
        mats.clear()
        gc.collect()

    log("reference starts")
    t = time.perf_counter()
    readings = ref_mod.compare(data, cfg, samples)
    log(f"reference compared {len(samples)} ops in "
        f"{time.perf_counter() - t:.3f} s")
    log(f"readings of the warm-up op and of a window op: {readings}")
    limits = cfg["limits"]
    worst = {k: max(r[k] for r in readings) for k in limits}
    failed = sum(any(not (r[k] <= lim) for k, lim in limits.items())
                 for r in readings)

    run = Run(cell=cell, rows=rows, device_kind=dev.device_kind,
              setup_s=setup_s, op_seconds=op_seconds, window_s=window_s,
              counters=counters, trace=trace)
    wanted = cell.per_layer if args.trace else cell.end_to_end
    kind = "metrics" if args.trace else "e2e"
    out_metrics = {}
    for m in wanted:
        value = spec.load_module(kind, m["name"]).read(run)
        if value is not None:
            out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": failed == 0 and all(
                  math.isfinite(v) for v in worst.values()),
              "attempted": len(op_seconds), "failed": failed,
              "metrics": out_metrics, "device": device}
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = trace["breakdown"]
    if after is not None:
        result["after"] = after(data, cfg, samples, readings)
    result["compared"] = {k: {"value": worst[k], "limit": limits[k]}
                          for k in limits}
    return result


def main(argv=None, **kw) -> int:
    args = parse_args(argv)
    try:
        result = measure(args, **kw)
    except NoChip as exc:
        log(f"no result: {exc}")
        return 2
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
