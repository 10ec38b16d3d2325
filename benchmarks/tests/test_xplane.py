"""The trace reduction: interval arithmetic by hand, and the recorded
trace `data/small.xplane.pb` (six jitted calls on one TPU v5e under the
harness's client marks, recorded by `record_fixture.py`) against a
microsecond-by-microsecond count made here."""

import os

import pytest

from harness import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "small.xplane.pb")


def test_union_and_covered():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)]) == [[0, 3], [5, 8]]
    assert xplane.covered([(0, 2), (1, 3), (5, 8)]) == 6.0
    assert xplane.covered([]) == 0.0


def test_pieces_cut_intervals_by_segments():
    segments = xplane.mark_segments([("a", 2, 6), ("b", 4, 8)], 0, 10)
    assert segments == [(0, 2, ""), (2, 4, "a"), (4, 6, "a+b"), (6, 8, "b"), (8, 10, "")]
    assert list(xplane.pieces([[1, 5], [7, 9]], segments)) == [
        (1, 2, ""), (2, 4, "a"), (4, 5, "a+b"), (7, 8, "b"), (8, 9, "")]


def test_gaps():
    busy = [[2, 4], [6, 7]]
    assert xplane.gaps(busy, 0, 10) == [[0, 2], [4, 6], [7, 10]]
    assert xplane.gaps(busy, 3, 6) == [[4, 6]]
    assert xplane.gaps([], 0, 5) == [[0, 5]]
    assert xplane.gaps([[0, 9]], 0, 5) == []


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def test_reduce_on_a_hand_made_profile():
    dev = _Plane("/device:TPU:0", [
        _Line("XLA Modules", [_Ev("jit_f", 0, 1000)]),           # not the ops line: ignored
        _Line("XLA Ops", [_Ev("fusion.1", 100, 200), _Ev("copy", 250, 100), _Ev("fusion.1", 600, 100)]),
    ])
    host = _Plane("/host:CPU", [
        _Line("python3", [_Ev("stmt:q1", 0, 400), _Ev("stmt:q6", 500, 300)]),
        _Line("python3", [_Ev("np.asarray(jax.Array)", 360, 60), _Ev("PjitFunction(program)", 700, 50)]),
        _Line("pjrt-tpu-tasks/1", [_Ev("D2H Dispatch", 0, 5000)]),     # no Python thread: not a host call
    ])
    got = xplane.reduce(_Profile([host, dev]), window_s=1e-6)
    assert got["planes"] == ["/device:TPU:0"]
    assert got["busy_s"] == pytest.approx((250 + 100) / 1e9)      # 100..350 and 600..700
    assert got["device_ops"] == [["fusion.1", pytest.approx(300 / 1e9)], ["copy", pytest.approx(100 / 1e9)]]
    idle = dict(got["idle_gaps"])
    # window of the marks and ops: 0..800, idle 0..100, 350..600, 700..800.
    # 360..420 and 700..750 lie under traced host calls; of the rest 0..100 and
    # 350..360 wait on q1, 420..500 on nothing, 500..600 and 750..800 on q6
    assert idle == {"np.asarray(jax.Array)": pytest.approx(60 / 1e9),
                    "PjitFunction(program)": pytest.approx(50 / 1e9),
                    "no traced host call, in flight: q1": pytest.approx(110 / 1e9),
                    "no traced host call, in flight: q6": pytest.approx(150 / 1e9),
                    "no statement in flight": pytest.approx(80 / 1e9)}


def test_no_device_plane_reads_nothing():
    host = _Plane("/host:CPU", [_Line("thread", [_Ev("stmt:q1", 0, 400)])])
    assert xplane.reduce(_Profile([host]), window_s=1.0) is None


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_trace():
    import jax

    profile = jax.profiler.ProfileData.from_file(RECORDED)
    events = xplane.device_events(profile)
    assert list(events) == ["/device:TPU:0"]
    ops = events["/device:TPU:0"]
    assert len(ops) >= 6
    # an independent count: mark every nanosecond-rounded-to-100ns slot an op covers
    lo = min(s for _, s, _ in ops)
    slots = set()
    for _, s, e in ops:
        slots.update(range(int((s - lo) // 100), int(-(-(e - lo) // 100))))
    got = xplane.reduce(profile, window_s=0.1)
    assert got["busy_s"] == pytest.approx(len(slots) * 100 / 1e9, rel=0.02)
    assert 0 < got["busy_s"] < 0.1
    marks = xplane.client_marks(profile)
    assert sorted({m for m, _, _ in marks}) == ["q1", "q6"] and len(marks) == 6
    assert sum(v for _, v in got["idle_gaps"]) == pytest.approx(
        (max(e for _, _, e in marks + ops) - min(s for _, s, _ in marks + ops)) / 1e9 - got["busy_s"], rel=1e-6)
    assert sum(v for _, v in got["device_ops"]) >= got["busy_s"] * 0.99
