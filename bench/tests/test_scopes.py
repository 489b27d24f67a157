"""The per-layer trace reduction (``bench/scopes.py``): the XSpace wire
reader, self time by scope, program spans and gap labels, on hand-made
traces laid out as a TPU trace is, and on traces recorded on the chip."""
import random
from pathlib import Path

import pytest

from bench import scopes, trace
from bench.tests.test_trace import profile

DATA = Path(__file__).parent / "data"


def test_self_time_excludes_nested_ops_and_sums_to_busy():
    """A ``while`` holding two ops, one of which holds a third: each keeps
    only the time its children leave it, and the scopes plus the unscoped
    time are the busy time."""
    p = profile([("while", 0, 100), ("a", 10, 30), ("b", 40, 90),
                 ("c", 50, 60), ("d", 120, 130)],
                [("bench.window", 0, 125)])
    op_scopes = {"while": "jit(run_window)/while",
                 "a": "jit(run_window)/while/body/heat.gather/gather",
                 "b": "jit(run_window)/while/body/heat.ccl/dot_general",
                 "c": "jit(run_window)/while/body/heat.sample/gather",
                 "d": "jit(run_window)/while/body/heat.tile/add"}
    s = scopes.reduce(p, op_scopes=op_scopes)
    assert s.busy_s == pytest.approx(trace.reduce(p).busy_s)
    assert s.busy_s == pytest.approx(105e-9)
    assert s.scope_s == pytest.approx({"heat.gather": 20e-9,
                                       "heat.ccl": 40e-9,
                                       "heat.sample": 10e-9,
                                       "heat.tile": 5e-9})
    assert s.unscoped_s == pytest.approx(30e-9)        # the loop's own time
    assert sum(s.scope_s.values()) + s.unscoped_s == pytest.approx(s.busy_s)


def test_without_a_scope_table_every_op_is_unscoped():
    p = profile([("a", 0, 40), ("b", 30, 60)], [("bench.window", 0, 60)])
    s = scopes.reduce(p)
    assert s.scope_s == {} and s.unscoped_s == pytest.approx(60e-9)


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(run_window)/while/body/closed_call/transpose(jvp(heat.ccl))/"
     "dot_general", "heat.ccl"),
    ("jit(run_window)/while/body/heat.batch/jit(_randint)/heat.sample/add",
     "heat.sample"),
    ("jit(_recommend)/while/body/topk.merge/sort", "topk.merge"),
    ("jit(<lambda>)/dot_general:", None),
    ("jit(f)/wheat.x/topk/add", None),
    (None, None),
])
def test_the_scope_is_the_last_program_token(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def test_self_times_add_up_to_the_union():
    rng = random.Random(0)
    for _ in range(200):
        intervals = []
        for _ in range(rng.randint(1, 12)):
            s = rng.uniform(0, 100)
            intervals.append((s, s + rng.uniform(0, 40)))
        own = scopes.self_times(intervals)
        union = sum(e - s for s, e in trace._union(intervals))
        assert all(t >= 0 for t in own)
        assert sum(own) == pytest.approx(union)


def test_gaps_take_the_program_span_inside_a_benchmark_span():
    p = profile(
        [("a", 0, 50), ("b", 110, 150), ("c", 170, 200)],
        [("bench.window", 0, 200), ("bench.run_window", 0, 200),
         ("train.dispatch", 60, 100), ("train.readback", 40, 55),
         ("train.dispatch", 300, 310)])
    s = scopes.reduce(p)
    assert s.idle_gaps[0] == ["train.dispatch", pytest.approx(60e-9)]
    # no program span overlaps [150, 170]: the benchmark's span labels it
    assert s.idle_gaps[1] == ["bench.run_window", pytest.approx(20e-9)]
    assert s.program_spans == {"train.dispatch": [pytest.approx(40e-9)],
                               "train.readback": [pytest.approx(15e-9)]}


def test_refused_like_the_trace_reduction():
    with pytest.raises(ValueError):
        scopes.reduce(profile([("a", 5, 10)], []))
    with pytest.raises(ValueError):
        scopes.reduce(profile([], [("bench.window", 0, 10)]))


# -- the wire reader on a hand-encoded XSpace -----------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key, value):
    return _field(1, key) + _field(2, value)


def _xspace():
    stat_md = [(7, "tf_op"), (8, "flops"), (9, "jit(f)/heat.tile/add")]
    stats_of = {
        "fusion.1": _field(1, 7) + _field(5, "jit(f)/while/heat.gather/x"),
        "fusion.2": _field(1, 8) + _field(3, 100),       # no tf_op stat
        "fusion.3": _field(1, 7) + _field(7, 9),         # by reference
    }
    events = b"".join(
        _field(4, _entry(i, _field(1, i) + _field(2, name)
                         + _field(5, stats)))
        for i, (name, stats) in enumerate(stats_of.items(), start=1))
    device = (_field(1, 3) + _field(2, "/device:TPU:0")
              + _field(3, _field(1, 1) + _field(2, "XLA Ops") + b"\x01" * 40)
              + events
              + b"".join(_field(5, _entry(i, _field(1, i) + _field(2, n)))
                         for i, n in stat_md))
    host = (_field(2, "/host:CPU")
            + _field(4, _entry(1, _field(2, "fusion.9")
                               + _field(5, _field(1, 7)
                                        + _field(5, "heat.batch"))))
            + _field(5, _entry(7, _field(1, 7) + _field(2, "tf_op"))))
    return _field(1, host) + _field(1, device) + _field(4, "hostname")


def test_the_wire_reader_finds_tf_op_in_device_planes_only(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    assert scopes.read_op_scopes(str(path)) == {
        "fusion.1": "jit(f)/while/heat.gather/x",
        "fusion.3": "jit(f)/heat.tile/add"}


# -- traces recorded on the chip ------------------------------------------------

def test_the_small_chip_trace_reduces_as_before():
    """``small.xplane.pb`` (no scopes, no program spans): the busy time and
    window equal ``bench/trace.py``'s, all of it unscoped, and the gaps keep
    the benchmark's labels."""
    path = str(DATA / "small.xplane.pb")
    base = trace.reduce(trace.load(path))
    s = scopes.reduce_file(path)
    assert (s.busy_s, s.window_s) == (base.busy_s, base.window_s)
    assert s.scope_s == {} and s.unscoped_s == pytest.approx(base.busy_s)
    assert s.idle_gaps == base.idle_gaps
    assert s.program_spans == {}


@pytest.fixture(scope="module")
def scoped():
    path = str(DATA / "scoped.xplane.pb")
    return path, scopes.reduce_file(path)


def test_the_wire_reader_finds_both_scopes_on_the_chip_trace(scoped):
    """``record_scoped_trace.py`` on one v5e: a 4-step scan with two named
    scopes, dispatched three times after a 20 ms sleep in a program span."""
    path, _ = scoped
    found = {scopes.scope_of(op) for op in scopes.read_op_scopes(path).values()}
    assert {"heat.gather", "heat.ccl"} <= found


def test_scoped_chip_trace_self_times_sum_to_busy(scoped):
    _, s = scoped
    assert set(s.scope_s) == {"heat.gather", "heat.ccl"}
    total = sum(s.scope_s.values()) + s.unscoped_s
    assert total == pytest.approx(s.busy_s, rel=0.01)
    assert s.unscoped_s < 0.5 * s.busy_s


def test_scoped_chip_trace_sleep_gaps_take_the_program_span(scoped):
    _, s = scoped
    longest = s.idle_gaps[:3]
    assert [g[0] for g in longest] == ["train.dispatch"] * 3
    assert all(0.019 < g[1] < 0.05 for g in longest)
    assert len(s.program_spans["train.dispatch"]) == 3
    assert s.window_s > 0.06
