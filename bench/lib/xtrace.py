"""Reduction of a profiler trace (``.xplane.pb``) to device busy time, the
device operations that took most time, and the device's idle gaps named by
the host span that covers each.

Read with ``jax.profiler.ProfileData``.  A device is a plane named
``/device:TPU:<k>``; its operations are the events of its ``XLA Ops``
line.  Host spans are the events of the ``/host:CPU`` plane whose names
the caller asks for: the harness mirrors the service's spans there with
``jax.profiler.TraceAnnotation`` (see ``harness.ProfiledTracer``), and
marks the traced window with a ``bench_window`` event.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import Counter

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench_window"
IDLE_HOST = "no host span"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: list  # per device, in the window
    device_ops: list  # [[name, seconds]], most time first, at most 10
    idle_gaps: list  # [[host span, seconds]], longest first, at most 10


def merge(intervals: list) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(ivs: list, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in ivs if b > lo and a < hi]


def short_name(op: str) -> str:
    """``%fusion.12 = pred[8,8]{...} fusion(...)`` -> ``fusion.12``."""
    return op.split(" = ", 1)[0].lstrip("%")


def self_times(events: list) -> Counter:
    """Per operation name, its time less that of the operations nested in
    it on the same line (a ``while`` holds its body's operations)."""
    out: Counter = Counter()
    stack: list = []  # [name, end] of the enclosing operations
    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= end - start
        out[name] += end - start
        stack.append([name, end])
    return out


def _cover(gap: tuple, spans: list) -> str:
    """The host span that overlaps most of ``gap``."""
    best, name = 0.0, IDLE_HOST
    for a, b, nm in spans:
        ov = min(b, gap[1]) - max(a, gap[0])
        if ov > best:
            best, name = ov, nm
    return name


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def reduce_trace(path: str, host_spans: set, top: int = 10) -> Reduced:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, ops, spans, window = [], Counter(), [], None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                    short_name(ev.name))
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            ops.update(self_times(evs))
            devices.append(merge([(a, b) for a, b, _ in evs]))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    end = ev.start_ns + ev.duration_ns
                    if ev.name == WINDOW:
                        window = (ev.start_ns, end)
                    elif ev.name in host_spans:
                        spans.append((ev.start_ns, end, ev.name))
    if window is None:
        every = [x for d in devices for iv in d for x in iv]
        if not every:
            return Reduced(0.0, [], [], [])
        window = (min(every), max(every))
    lo, hi = window
    busy = [_clip(d, lo, hi) for d in devices]
    gaps = []
    if busy:
        edges = [lo] + [x for iv in busy[0] for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=[sum(b - a for a, b in d) * 1e-9 for d in busy],
        device_ops=[[k, v * 1e-9] for k, v in ops.most_common(top)],
        idle_gaps=[[_cover(g, spans), (g[1] - g[0]) * 1e-9]
                   for g in gaps[:top]])
