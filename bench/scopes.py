"""Per-layer reduction of a JAX profiler trace: device time by the program's
named scopes, and the program's own host spans, on the trace's one clock.

Scopes.  A device op's scope is the last ``heat.*`` or ``topk.*`` token of
its ``tf_op`` stat, the HLO ``op_name`` path that ``jax.named_scope`` writes
(``.../while/body/heat.sample/...``, or inside JAX's wrappers
``.../transpose(jvp(heat.ccl))/...``).  The stat sits in the event metadata
of the ``/device:`` planes, which ``jax.profiler.ProfileData`` does not
expose, so :func:`read_op_scopes` decodes just those planes' metadata from
the ``.xplane.pb`` wire format and skips the event lines by their length.

Self time.  Each op is charged the part of its interval, inside the traced
window, in which it is the innermost op on its device's ``XLA Ops`` line:
a ``while`` keeps only the time its body's ops leave it.  The scopes' self
times plus the unscoped self time are the device's busy time (the union of
its op intervals, as ``bench/trace.py`` computes it).

Spans.  The program's host spans (``train.*``, ``serve.*``) inside the
window are listed with their durations, and each idle gap of the first
device is labelled by the program span that overlaps it most, or by the
benchmark's own span (``bench.*``) where no program span overlaps it.
"""
from __future__ import annotations

import dataclasses
import re

from bench import trace

SCOPE = re.compile(r"(?<![\w.])(?:heat|topk)\.[a-z_]+")
PROGRAM_SPAN_PREFIXES = ("train.", "serve.")
TF_OP = "tf_op"
NO_SPAN = "no span"


@dataclasses.dataclass
class LayerSummary:
    busy_s: float                  # union of device-op time, mean over devices
    window_s: float                # length of the traced window
    scope_s: dict                  # scope -> self seconds, mean over devices
    unscoped_s: float              # self seconds of ops under no scope
    program_spans: dict            # span name -> [seconds] inside the window
    idle_gaps: list                # [[label, seconds]], longest first


# -- the XSpace wire format (only what the scope table needs) ------------------

def _varint(buf, i: int):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, start: int, end: int):
    """(field number, value) of each field in ``buf[start:end]``; a
    length-delimited value is its (start, end) span, not read."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, span):
    """The value spans of a protobuf map entry list (key 1, value 2)."""
    for field, value in _fields(buf, *span):
        if field == 2:
            yield value


def read_op_scopes(path: str) -> dict:
    """Event name -> ``tf_op`` stat of every device op's event metadata in
    the ``/device:`` planes of an ``.xplane.pb`` (XSpace.planes = 1;
    XPlane: name 2, lines 3, event_metadata 4, stat_metadata 5;
    XEventMetadata: name 2, stats 5; XStatMetadata: id 1, name 2; XStat:
    metadata_id 1, str_value 5, ref_value 7)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: dict = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, event_md, stat_md = "", [], []
        for pf, value in _fields(buf, *plane):
            if pf == 2:
                name = _text(buf, value)
            elif pf == 4:
                event_md.append(value)
            elif pf == 5:
                stat_md.append(value)
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for entry in stat_md:
            for md in _map_values(buf, entry):
                sid, sname = None, ""
                for sf, value in _fields(buf, *md):
                    if sf == 1:
                        sid = value
                    elif sf == 2:
                        sname = _text(buf, value)
                stat_names[sid] = sname
        tf_op_ids = {i for i, n in stat_names.items() if n == TF_OP}
        if not tf_op_ids:
            continue
        for entry in event_md:
            for md in _map_values(buf, entry):
                ev_name, op = None, None
                for ef, value in _fields(buf, *md):
                    if ef == 2:
                        ev_name = _text(buf, value)
                    elif ef == 5:
                        op = _tf_op(buf, value, tf_op_ids, stat_names) or op
                if ev_name is not None and op is not None:
                    out[ev_name] = op
    return out


def _tf_op(buf, span, tf_op_ids, stat_names):
    stat_id, text = None, None
    for xf, value in _fields(buf, *span):
        if xf == 1:
            stat_id = value
        elif xf == 5:
            text = _text(buf, value)
        elif xf == 7:
            text = stat_names.get(value)
    return text if stat_id in tf_op_ids else None


# -- the reduction -------------------------------------------------------------

def scope_of(tf_op: str):
    """The last program scope named in an op's ``tf_op`` path, or None."""
    found = SCOPE.findall(tf_op or "")
    return found[-1] if found else None


def self_times(intervals: list) -> list:
    """Self time of each (start, end) interval: the part of it in which it
    is the innermost open interval (the one that started last).  The self
    times add up to the length of the intervals' union."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    own = [0.0] * len(intervals)
    stack: list = []               # open intervals, innermost last
    t = 0.0                        # time charged up to
    for i in order:
        start = intervals[i][0]
        while stack and intervals[stack[-1]][1] <= start:
            j = stack.pop()
            own[j] += max(0.0, intervals[j][1] - t)
            t = max(t, intervals[j][1])
        if stack:
            own[stack[-1]] += start - t
        stack.append(i)
        t = start
    while stack:
        j = stack.pop()
        own[j] += max(0.0, intervals[j][1] - t)
        t = max(t, intervals[j][1])
    return own


def _label(g0, g1, spans) -> str:
    best, label = 0.0, None
    for n, s, e in spans:
        ov = trace._overlap(g0, g1, s, e)
        if ov > best:
            best, label = ov, n
    return label


def reduce(profile, *, op_scopes=None, top: int = trace.TOP) -> LayerSummary:
    """Reduce a loaded trace (``ProfileData``) with the event name ->
    ``tf_op`` map of :func:`read_op_scopes` (``None``: no op has a scope).

    Raises ``ValueError`` where ``bench.trace.reduce`` does: no window span,
    or no device event."""
    op_scopes = op_scopes or {}
    bench_spans = trace.host_spans(profile)
    windows = [(s, e) for n, s, e in bench_spans if n == trace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {trace.WINDOW_SPAN!r} span")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    program = [sp for prefix in PROGRAM_SPAN_PREFIXES
               for sp in trace.host_spans(profile, prefix)]

    busy, scope_ns, unscoped_ns, first = [], {}, 0.0, None
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        line = trace._ops_line(plane)
        if line is None:
            continue
        events = [(ev.name, ev.start_ns, ev.end_ns) for ev in line.events]
        if not events:
            continue
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                  if e > lo and s < hi]
        merged = trace._union([(s, e) for _, s, e in inside])
        busy.append(sum(e - s for s, e in merged))
        first = merged if first is None else first
        own = self_times([(s, e) for _, s, e in inside])
        for (name, _, _), t in zip(inside, own):
            scope = scope_of(op_scopes.get(name))
            if scope is None:
                unscoped_ns += t
            else:
                scope_ns[scope] = scope_ns.get(scope, 0.0) + t
    if not busy:
        raise ValueError("the trace holds no device events")
    n_dev = len(busy)

    spans: dict = {}
    for n, s, e in program:
        if s >= lo and e <= hi:
            spans.setdefault(n, []).append((e - s) / 1e9)
    gaps, cursor = [], lo
    for s, e in first + [[hi, hi]]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    inner = [sp for sp in bench_spans if sp[0] != trace.WINDOW_SPAN]
    labelled = [[_label(g0, g1, program) or _label(g0, g1, inner) or NO_SPAN,
                 (g1 - g0) / 1e9]
                for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:top]]
    return LayerSummary(
        busy_s=sum(busy) / n_dev / 1e9, window_s=(hi - lo) / 1e9,
        scope_s={k: v / n_dev / 1e9 for k, v in sorted(scope_ns.items())},
        unscoped_s=unscoped_ns / n_dev / 1e9, program_spans=spans,
        idle_gaps=labelled)


def reduce_file(path: str, *, top: int = trace.TOP) -> LayerSummary:
    return reduce(trace.load(path), op_scopes=read_op_scopes(path), top=top)


def reduce_dir(directory: str, *, top: int = trace.TOP) -> LayerSummary:
    return reduce_file(trace.find_xplane(directory), top=top)
