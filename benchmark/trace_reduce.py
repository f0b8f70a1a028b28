"""Profiler trace -> device busy time, idle share and where it went.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX.  Busy time is the union of the intervals in which an operation ran
on a device (the ``XLA Ops`` line of each ``/device:TPU:n`` plane),
clipped to the benchmark's own ``bench.window`` span and averaged over
the devices that ran anything.  Each idle gap inside the window is
named by the innermost ``bench.*`` host span that covers its middle:
what the consumer was waiting on while the device idled.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


def load_planes(path: str) -> list:
    """The trace as plain data: ``[(plane, line, [(name, t0, t1)])]``,
    times in nanoseconds."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            out.append((plane.name, line.name, evs))
    return out


def _union(intervals: list) -> list:
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if b > lo and a < hi]


def _program(name: str) -> str:
    """A jitted program's stable name: ``jit_f(12)`` -> ``jit_f``."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce_planes(planes: list, top: int = 10) -> dict:
    spans = [(n, a, b) for p, _, evs in planes if p.startswith("/host")
             for n, a, b in evs if n.startswith("bench.")]
    windows = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    lo, hi = windows[0]
    spans = [s for s in spans if s[0] != WINDOW_SPAN]

    ops_by_dev, programs = {}, {}
    for p, line, evs in planes:
        if not DEVICE_PLANE.match(p):
            continue
        if line == OPS_LINE:
            ops_by_dev.setdefault(p, []).extend([a, b] for _, a, b in evs)
        elif line == PROGRAMS_LINE:
            for n, a, b in evs:
                d = max(0, min(b, hi) - max(a, lo))
                programs[_program(n)] = programs.get(_program(n), 0) + d
    busy_by_dev = {p: _union(_clip(iv, lo, hi))
                   for p, iv in ops_by_dev.items()}
    busy_by_dev = {p: iv for p, iv in busy_by_dev.items() if iv}
    # a window in which no device operation ran is busy 0 s, idle
    # throughout: a reading, not an error
    busy = sum(sum(b - a for a, b in iv) for iv in busy_by_dev.values()) \
        / max(len(busy_by_dev), 1)

    # the idle gaps of the first device, each named by the innermost
    # host span over its middle
    ivs = busy_by_dev[sorted(busy_by_dev)[0]] if busy_by_dev else []
    gaps, t = [], lo
    for a, b in ivs:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    named = []
    for a, b in gaps:
        mid = (a + b) / 2
        inside = [(sb - sa, n) for n, sa, sb in spans if sa <= mid < sb]
        named.append((min(inside)[1] if inside else "bench.other",
                      (b - a) / 1e9))
    idle_by_span = {}
    for n, s in named:
        idle_by_span[n] = idle_by_span.get(n, 0.0) + s
    return {
        "busy_s": busy / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": len(busy_by_dev),
        "device_ops": sorted(([n, d / 1e9] for n, d in programs.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([n, s] for n, s in named),
                            key=lambda x: -x[1])[:top],
        "idle_by_span": idle_by_span,
    }


def reduce_file(path: str) -> dict:
    return reduce_planes(load_planes(path))


def reduce_dir(trace_dir: str) -> dict:
    """Reduce the newest trace ``jax.profiler`` wrote under the
    directory."""
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(found[-1])
