"""The trace reduction (``bench/trace.py``), exact on hand-made traces laid
out as a TPU trace is: device planes with ``XLA Ops`` and ``XLA Modules``
lines (only the ops are read), the benchmark's spans on a host plane."""
from types import SimpleNamespace as NS

import pytest

from bench import trace

def ev(name, start, end):
    return NS(name=name, start_ns=float(start), end_ns=float(end),
              duration_ns=float(end - start))


def profile(device_events, host_events, modules=()):
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev(*m) for m in modules]),
        NS(name="XLA Ops", events=[ev(*e) for e in device_events])])
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev(*e) for e in host_events])])
    return NS(planes=[host, dev])


def test_busy_is_the_union_inside_the_window():
    p = profile(
        [("a", 0, 40), ("b", 30, 60), ("a", 100, 150), ("c", 190, 260)],
        [("bench.window", 10, 200), ("bench.run_window", 10, 70),
         ("bench.host_sleep", 60, 100), ("bench.host_sleep", 150, 190)],
        modules=[("jit_step", 0, 60), ("jit_step", 100, 150)])
    s = trace.reduce(p)
    # inside [10, 200]: [10, 60] + [100, 150] + [190, 200] = 110 ns busy
    assert s.window_s == pytest.approx(190e-9)
    assert s.busy_s == pytest.approx(110e-9)
    assert s.idle_share == pytest.approx(80 / 190)
    assert s.device_ops[0][0] == "a"
    assert s.device_ops[0][1] == pytest.approx(80e-9)      # 30 + 50 inside
    assert [g[0] for g in s.idle_gaps] == ["bench.host_sleep"] * 2
    assert sorted(g[1] for g in s.idle_gaps) == pytest.approx([40e-9, 40e-9])


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        trace.reduce(profile([("a", 5, 10), ("b", 20, 25)], []))


def test_a_trace_without_device_events_is_refused():
    with pytest.raises(ValueError):
        trace.reduce(profile([], [("bench.window", 0, 10)]))


def test_a_trace_recorded_on_the_chip():
    """``record_trace.py`` on one v5e: three dispatches of a small program,
    each followed by a 20 ms host sleep inside the window span."""
    from pathlib import Path
    path = Path(__file__).parent / "data" / "small.xplane.pb"
    s = trace.reduce(trace.load(str(path)))
    assert s.devices == 1
    assert 0 < s.busy_s < 0.005 < 0.06 < s.window_s < 0.1
    assert "fusion" in s.device_ops[0][0]
    longest = s.idle_gaps[:3]
    assert [g[0] for g in longest] == ["bench.host_sleep"] * 3
    assert all(0.019 < g[1] < 0.03 for g in longest)
    assert s.idle_share > 0.9
