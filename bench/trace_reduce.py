"""From a JAX profiler trace to the traced run's numbers.

The trace is the ``.xplane.pb`` that ``jax.profiler.trace`` writes.  The
benchmark wraps each traced op in a host annotation named ``bench.op<i>``;
the traced window runs from the first annotation's start to the last one's
end.  On each device plane (``/device:TPU:<n>``), the ``XLA Ops`` line
holds one event per operation the device ran, named by its HLO text.

    busy_s          the union of the device's operation intervals inside
                    the window, averaged over the devices that ran any
    window_s        the window's length
    kernel_seconds  for each kernel named, the summed device time of its
                    Pallas launches (``%<kernel>.<n> = ... custom-call(...)``),
                    per device
    breakdown       ``device_ops``: the ten operations that took most device
                    time; ``idle_gaps``: the ten longest gaps between them,
                    each named by the innermost host event at its middle
"""
from __future__ import annotations

import glob
import os
import re

OP_PREFIX = "bench.op"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
TOP = 10


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _op_name(event_name: str) -> str:
    """``%wgram.3`` of the HLO text ``%wgram.3 = f32[...] custom-call(...)``
    that names an op event."""
    return event_name.split(" = ", 1)[0].strip()


def _kernel_of(event_name: str, kernels):
    """The kernel an op event is a launch of: a ``tpu_custom_call`` (a
    Pallas kernel) whose HLO instruction carries the kernel's name, as
    ``%kmeans_assign.1 = (...) custom-call(...)``."""
    if "custom-call(" not in event_name:
        return None
    op = _op_name(event_name).lstrip("%")
    for k in kernels:
        if re.fullmatch(re.escape(k) + r"(\.\d+)?", op):
            return k
    return None


def _host_events(profile):
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                yield from line.events


def reduce(profile, kernels) -> dict:
    """Numbers of the traced window of a ``jax.profiler.ProfileData``.
    Host events are streamed, never held: a traced pass of a streamed cell
    carries millions of them."""
    annotations = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in _host_events(profile)
                   if ev.name.startswith(OP_PREFIX)]
    if not annotations:
        raise ValueError(f"no {OP_PREFIX}<i> annotation in the trace")
    lo = min(a[1] for a in annotations)
    hi = max(a[2] for a in annotations)

    busy, kernel_ns, by_op, used = [], {k: 0.0 for k in kernels}, {}, 0
    first_busy = None
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            spans = []
            for ev in line.events:
                s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
                if e <= s:
                    continue
                spans.append((s, e))
                k = _kernel_of(ev.name, kernels)
                if k is not None:
                    kernel_ns[k] += e - s
                label = k or _op_name(ev.name)
                by_op[label] = by_op.get(label, 0.0) + (e - s)
            if spans:
                used += 1
                merged = _union(spans)
                busy.append(sum(e - s for s, e in merged))
                if first_busy is None:
                    first_busy = merged
    if not used:
        raise ValueError("no operation on a device plane's 'XLA Ops' line "
                         "inside the traced window")

    edges = [lo] + [x for s, e in first_busy for x in (s, e)] + [hi]
    longest = sorted(((e - s, s, e) for s, e in zip(edges[0::2], edges[1::2])
                      if e > s), reverse=True)[:TOP]
    # The innermost host event around each gap's middle says what the host
    # was doing while the device waited.
    names = [None] * len(longest)
    spans_of = [float("inf")] * len(longest)
    for ev in _host_events(profile):
        s, d = ev.start_ns, ev.duration_ns
        for i, (_, gs, ge) in enumerate(longest):
            if s <= (gs + ge) / 2 <= s + d and d < spans_of[i]:
                names[i], spans_of[i] = ev.name, d
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "ops": len({a[0] for a in annotations}),
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / used / 1e9,
        "kernel_seconds": {k: v / used / 1e9 for k, v in kernel_ns.items()
                           if v > 0},
        "breakdown": {
            "device_ops": [[name, ns / used / 1e9] for name, ns in top_ops],
            "idle_gaps": [[name or "no host event", length / 1e9]
                          for name, (length, _, _) in zip(names, longest)],
        },
    }


def reduce_dir(log_dir, kernels) -> dict:
    """``reduce`` of the one trace ``jax.profiler.trace(log_dir)`` wrote."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, "
                         f"found {paths}")
    return reduce(ProfileData.from_file(paths[0]), kernels)
