"""The trace reduction on known intervals, on a trace recorded on a TPU
v5e (``testdata/record_trace.py``), and on captures that hold nothing."""

import json
import os

import pytest

import pb_trace
from pb_trace import Event

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"


def _ops(*intervals):
    return [Event(DEV, pb_trace.OPS_LINE, f"op{i}", s, e - s)
            for i, (s, e) in enumerate(intervals)]


def _synthetic():
    ev = _ops((0, 10), (5, 20), (30, 40))
    ev.append(Event(DEV, pb_trace.MODULES_LINE, "jit__packed_restart(7)",
                    0, 20))
    ev.append(Event(DEV, pb_trace.MODULES_LINE, "jit_other", 30, 10))
    ev.append(Event("/host:CPU", "python", pb_trace.MARK, 1000, 5))
    return ev


def test_busy_idle_and_program_time_on_known_intervals():
    ev = _synthetic()
    win = [[0, 50]]
    assert pb_trace.busy_ns(ev, win) == 30
    assert pb_trace.idle_gaps(ev, win) == [(20, 30), (40, 50)]
    assert pb_trace.program_ns(ev, "_packed_restart", win) == 20
    assert pb_trace.program_ns(ev, "_packed_restart", [[10, 15]]) == 5
    # Two windows: only what lies inside them counts.
    assert pb_trace.busy_ns(ev, [[0, 8], [35, 60]]) == 8 + 5
    assert pb_trace.top_ops(ev, win) == [["op1", 15e-9], ["op0", 10e-9],
                                         ["op2", 10e-9]]


def test_gaps_are_labelled_by_the_innermost_host_span():
    ev = _synthetic()
    spans = [(0, 50, "partition"), (25, 45, "partition/solve")]
    got = dict(pb_trace.labelled_gaps(ev, [[0, 50]], spans))
    assert got == {"partition": 10e-9, "partition/solve": 10e-9}
    # Idle time before the first span lies outside every span.
    got = dict(pb_trace.labelled_gaps(ev, [[0, 50]], [(45, 50, "post")]))
    assert got == {"outside-spans": 15e-9, "post": 5e-9}


def test_clock_offset_from_the_mark():
    assert pb_trace.clock_offset(_synthetic(), 400) == 600


def test_a_capture_with_nothing_to_read_raises(tmp_path):
    with pytest.raises(pb_trace.CaptureError):
        pb_trace.newest_xplane(str(tmp_path))
    with pytest.raises(pb_trace.CaptureError):
        pb_trace.load_events(str(tmp_path))
    host_only = [Event("/host:CPU", "python", "x", 0, 1)]
    with pytest.raises(pb_trace.CaptureError):
        pb_trace.busy_ns(host_only, [[0, 1]])
    with pytest.raises(pb_trace.CaptureError):
        pb_trace.clock_offset(host_only, 0)


def test_a_capture_without_a_device_is_not_read_as_an_idle_device(tmp_path):
    import jax.numpy as jnp

    with pb_trace.Capture(str(tmp_path)) as cap:
        (jnp.ones(8) * 2).block_until_ready()
    events = pb_trace.load_events(str(tmp_path))
    pb_trace.clock_offset(events, cap.mark_pc_ns)      # the mark is there
    if not any(e.plane.startswith(pb_trace.DEVICE_PREFIX) for e in events):
        with pytest.raises(pb_trace.CaptureError):
            pb_trace.busy_ns(events, [[0, 1e18]])


def test_recorded_trace_three_runs_of_one_program():
    with open(os.path.join(HERE, "testdata", "three_runs.json")) as f:
        meta = json.load(f)
    ev = pb_trace.load_events(os.path.join(HERE, "testdata",
                                           "three_runs.xplane.pb"))
    off = pb_trace.clock_offset(ev, meta["mark_pc_ns"])
    # The profiler puts device events on the host clock to within about a
    # millisecond (here each module starts 0.13-0.2 ms before the host call
    # that launched it); the runs are 50 ms apart, so each is matched
    # without doubt.
    skew = 2e6
    runs = [[s + off - skew, e + off + skew] for s, e in meta["runs_pc_ns"]]
    mods = [e for e in ev if e.line == pb_trace.MODULES_LINE
            and meta["program"] in e.name]
    assert len(mods) == 3
    # Each run's device work lies inside the host interval that waited
    # for it.
    for (s, e), m in zip(runs, sorted(mods, key=lambda m: m.start_ns)):
        assert s <= m.start_ns and m.end_ns <= e
    busy = pb_trace.busy_ns(ev, runs)
    prog = pb_trace.program_ns(ev, meta["program"], runs)
    assert 0 < busy <= prog == sum(m.dur_ns for m in mods)
    # Between the runs the host slept: the device was idle at least that
    # long.
    span = [[runs[0][0], runs[-1][1]]]
    idle = sum(b - a for a, b in pb_trace.idle_gaps(ev, span))
    assert idle >= 2 * meta["sleep_s"] * 1e9
