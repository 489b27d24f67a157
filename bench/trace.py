"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the numbers the
benchmark reports: device busy time, the length of the traced window, device
time per operation, and the idle gaps between operations, each labelled by
the benchmark's own host span (``jax.profiler.TraceAnnotation``) that covers
most of it.

Device planes are the ``/device:...`` planes of the trace.  Busy time is the
union of the intervals of the events on a device's ``XLA Ops`` line; idle is
the rest of the window.  The window is the span of the host annotation named
:data:`WINDOW_SPAN`, which the benchmark opens around every traced window.
Host and device events of one trace share its clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os

#: Host span that marks the traced window (written by the benchmark).
WINDOW_SPAN = "bench.window"
#: Prefix of the benchmark's own host spans (used to label idle gaps).
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
TOP = 10


@dataclasses.dataclass
class TraceSummary:
    busy_s: float                  # union of device-op time, mean over devices
    window_s: float                # length of the traced window
    devices: int                   # device planes that held events
    device_ops: list               # [[name, seconds]] most time first (mean over devices)
    idle_gaps: list                # [[label, seconds]] longest first (first device)

    @property
    def idle_share(self) -> float:
        return max(0.0, 1.0 - self.busy_s / self.window_s)


def find_xplane(directory: str) -> str:
    """The one ``*.xplane.pb`` under a ``jax.profiler`` trace directory."""
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _union(intervals: list) -> list:
    """Merge (start, end) intervals; returns sorted disjoint intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _ops_line(plane):
    for line in plane.lines:
        if line.name == OPS_LINE:
            return line
    return None


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def host_spans(profile, prefix: str = SPAN_PREFIX) -> list:
    """[(name, start_ns, end_ns)] of the benchmark's host spans."""
    spans = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    spans.append((ev.name, ev.start_ns, ev.end_ns))
    return spans


def reduce(profile, *, top: int = TOP) -> TraceSummary:
    """Reduce a loaded trace (``ProfileData``) to a :class:`TraceSummary`.

    Raises ``ValueError`` when the trace has no window span, or when no
    device plane holds an event: a trace in which nothing ran on the device
    measures nothing."""
    spans = host_spans(profile)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    per_device = []
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        line = _ops_line(plane)
        if line is None:
            continue
        events = [(ev.name, ev.start_ns, ev.end_ns) for ev in line.events]
        if events:
            per_device.append(events)
    if not per_device:
        raise ValueError("the trace holds no device events")

    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    window_ns = hi - lo

    busy, op_time = [], {}
    first_busy = None
    for events in per_device:
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                  if e > lo and s < hi]
        merged = _union([(s, e) for _, s, e in inside])
        busy.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged
        for n, s, e in inside:
            op_time[n] = op_time.get(n, 0.0) + (e - s)
    n_dev = len(per_device)
    ops = sorted(([n, t / n_dev / 1e9] for n, t in op_time.items()),
                 key=lambda x: -x[1])[:top]

    # Idle gaps of the first device, labelled by the covering host span.
    gaps, cursor = [], lo
    for s, e in first_busy + [[hi, hi]]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    labelled = []
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, label = 0.0, "no benchmark span"
        for n, s, e in inner:
            ov = _overlap(g0, g1, s, e)
            if ov > best:
                best, label = ov, n
        labelled.append([label, (g1 - g0) / 1e9])
    return TraceSummary(busy_s=sum(busy) / n_dev / 1e9,
                        window_s=window_ns / 1e9, devices=n_dev,
                        device_ops=ops, idle_gaps=labelled)


def reduce_dir(directory: str, *, top: int = TOP) -> TraceSummary:
    return reduce(load(find_xplane(directory)), top=top)

