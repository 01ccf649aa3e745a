"""The readers of the program's host spans, counter and in-trace
annotations, on span trees and trace events made by hand."""

import pytest

import pb_harness
import pb_trace
from pb_trace import Event
from repro.obs import Span

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _call(tree):
    return pb_harness.Call(t0=0.0, t1=1.0, labels=None, raw=None, stages=[],
                           levels=[], records=[], span=tree)


def _run(*trees, events=None):
    return pb_harness.Run(calls=[_call(t) for t in trees], nparts=2,
                          graph=None, device_kind="TPU v5 lite",
                          events=events)


def _tree(sub_seconds, launches):
    """A call's span tree: one level per entry, with its ``sub`` span's
    seconds and its ``restarts`` span's launch counter."""
    levels = [{"name": f"level:{i}", "seconds": 1.0, "children": [
        {"name": "reorder", "seconds": 0.25},
        {"name": "sub", "seconds": s},
        {"name": "solve", "seconds": 0.5, "children": [
            {"name": "restarts", "seconds": 0.25,
             "counters": {"restart_launches": n}}]}]}
        for i, (s, n) in enumerate(zip(sub_seconds, launches))]
    return Span.from_dict({"name": "partition", "seconds": 9.0,
                           "children": [{"name": "engine", "seconds": 8.0,
                                         "children": levels}]})


@pytest.mark.parametrize("metric, want", [
    ("rsb_sub_s", (0.5 + 0.25 + 1.0) / 2),
    ("rsb_reorder_s", (0.25 * 2 + 0.25) / 2),
    ("restart_launches", (3 + 3 + 2) / 2),
    ("pack_s", None),
    ("warm_start_s", None),
    ("dual_graph_s", None),
])
def test_span_readers_sum_per_call(metric, want):
    run = _run(_tree([0.5, 0.25], [3, 3]), _tree([1.0], [2]))
    got = pb_harness.load_reader(metric).read(run)
    if want is None:                  # no such span in these trees
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_span_readers_read_nothing_without_a_span_tree():
    run = _run(_tree([0.5], [3]), None)        # a call with REPRO_OBS=off
    for metric in ("rsb_sub_s", "restart_launches"):
        assert pb_harness.load_reader(metric).read(run) is None


def _annotated():
    """Device ops busy over [0, 20) and [30, 40); two ``restarts``
    annotations on the host, [0, 25) and [28, 50)."""
    ev = [Event(DEV, pb_trace.OPS_LINE, f"op{i}", s, e - s)
          for i, (s, e) in enumerate([(0, 10), (5, 20), (30, 40)])]
    ev.append(Event(HOST, "python", "restarts", 0, 25))
    ev.append(Event(HOST, "python", "restarts", 28, 22))
    ev.append(Event(HOST, "python", "solve", 0, 60))
    return ev


def test_restart_idle_is_the_idle_time_inside_the_annotations():
    read = pb_harness.load_reader("restart_idle_s").read
    # Window 25 + 22 = 47 ns, busy inside it 20 + 10 = 30 ns.
    assert read(_run(None, events=_annotated())) == pytest.approx(17e-9)
    assert read(_run(None, None, events=_annotated())) == \
        pytest.approx(8.5e-9)


def test_restart_idle_ignores_device_events_of_the_same_name():
    ev = _annotated() + [Event(DEV, pb_trace.MODULES_LINE, "restarts",
                               50, 100)]
    read = pb_harness.load_reader("restart_idle_s").read
    assert read(_run(None, events=ev)) == pytest.approx(17e-9)


def test_restart_idle_reads_nothing_without_annotations():
    read = pb_harness.load_reader("restart_idle_s").read
    assert read(_run(None)) is None                     # untraced run
    no_marks = [e for e in _annotated() if e.name != "restarts"]
    assert read(_run(None, events=no_marks)) is None    # no annotations
