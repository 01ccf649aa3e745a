"""Profiler capture and its reduction to device metrics.

A capture writes one ``.xplane.pb``; :func:`load_events` flattens it into
:class:`Event` rows (plane, line, name, start, duration in trace
nanoseconds).  The benchmark makes one ``TraceAnnotation`` (:data:`MARK`)
at a ``perf_counter_ns`` time it records, so that host spans, which the
program stamps with ``perf_counter``, and device events share one clock
(:func:`clock_offset`).

Device time is read from the device planes (``/device:...``): busy time is
the union of the intervals of the ``XLA Ops`` line, and a program's time is
the sum of its events on the ``XLA Modules`` line.  Every function raises
:class:`CaptureError` where the capture holds nothing to read, so a failed
capture is never reduced as an idle device.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import time

MARK = "perfbench:clock-mark"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:"


class CaptureError(RuntimeError):
    """The profiler capture is missing or holds nothing to read."""


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Capture:
    """``with Capture(dir) as cap:`` traces the block (Python tracer off)
    and records ``cap.mark_pc_ns``, the ``perf_counter_ns`` of :data:`MARK`.
    A profiler that fails to start raises."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.mark_pc_ns: int | None = None

    def __enter__(self) -> "Capture":
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.raise_error_on_start_failure = True
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.mark_pc_ns = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(MARK):
            pass
        return self

    def __exit__(self, *exc) -> bool:
        import jax

        jax.profiler.stop_trace()
        return False


def newest_xplane(out_dir: str) -> str:
    files = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise CaptureError(f"no .xplane.pb under {out_dir}")
    return max(files, key=os.path.getmtime)


def load_events(path: str) -> list:
    """Every event of one ``.xplane.pb`` (or the newest under a capture
    directory)."""
    import jax

    if os.path.isdir(path):
        path = newest_xplane(path)
    data = jax.profiler.ProfileData.from_file(path)
    out = [Event(plane.name, line.name, ev.name, float(ev.start_ns),
                 float(ev.duration_ns))
           for plane in data.planes for line in plane.lines
           for ev in line.events]
    if not out:
        raise CaptureError(f"{path} holds no events")
    return out


def clock_offset(events: list, mark_pc_ns: int) -> float:
    """Trace time minus ``perf_counter_ns`` time, from :data:`MARK`."""
    marks = [e for e in events if e.name == MARK]
    if len(marks) != 1:
        raise CaptureError(f"expected one {MARK!r} event, found {len(marks)}")
    return marks[0].start_ns - float(mark_pc_ns)


def device_planes(events: list) -> list:
    planes = sorted({e.plane for e in events
                     if e.plane.startswith(DEVICE_PREFIX)
                     and e.line == OPS_LINE})
    if not planes:
        raise CaptureError("the capture has no device plane with XLA ops")
    return planes


def _merge(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals: list, windows: list) -> list:
    """Intersections of merged ``intervals`` with merged ``windows``."""
    out = []
    for ws, we in windows:
        for s, e in intervals:
            lo, hi = max(s, ws), min(e, we)
            if hi > lo:
                out.append([lo, hi])
    return out


def busy_intervals(events: list, plane: str, windows: list) -> list:
    """Merged intervals in which an op ran on ``plane``, inside
    ``windows`` (trace ns)."""
    ops = [(e.start_ns, e.end_ns) for e in events
           if e.plane == plane and e.line == OPS_LINE]
    return _merge(_clip(_merge(ops), _merge(windows)))


def busy_ns(events: list, windows: list) -> float:
    """Busy time inside ``windows``, averaged over the device planes."""
    planes = device_planes(events)
    total = sum(e - s for p in planes
                for s, e in busy_intervals(events, p, windows))
    return total / len(planes)


def idle_gaps(events: list, windows: list) -> list:
    """(start, end) of every stretch inside ``windows`` in which no op ran
    on the first device plane."""
    busy = busy_intervals(events, device_planes(events)[0], windows)
    gaps = []
    for ws, we in _merge(windows):
        t = ws
        for s, e in busy:
            if e <= ws or s >= we:
                continue
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if we > t:
            gaps.append((t, we))
    return gaps


def program_ns(events: list, substring: str, windows: list) -> float:
    """Device time of the programs whose name holds ``substring`` inside
    ``windows``, summed over modules and averaged over device planes."""
    planes = device_planes(events)
    total = 0.0
    for p in planes:
        mods = [(e.start_ns, e.end_ns) for e in events
                if e.plane == p and e.line == MODULES_LINE
                and substring in e.name]
        total += sum(e - s for s, e in _clip(mods, _merge(windows)))
    return total / len(planes)


def op_name(hlo: str) -> str:
    """``%fusion.85 = f32[1048576]{0:T(1024)} fusion(...), ...`` →
    ``%fusion.85 = f32[1048576] fusion``: the op, its result and its kind
    (a tuple result, such as a loop's carry, reads ``(tuple)``)."""
    flat = re.sub(r"\{[^{}]*\}", "", hlo)
    m = re.match(r"\s*(%?[\w.-]+) = (\([^()]*\)|\S+) ([\w-]+)\(", flat)
    if m is None:
        return flat[:80]
    name, shape, kind = m.groups()
    return f"{name} = {'(tuple)' if shape.startswith('(') else shape} {kind}"


def top_ops(events: list, windows: list, k: int = 10) -> list:
    """The ``k`` ops with the most device time inside ``windows`` (first
    device plane), by :func:`op_name`: ``[[name, seconds], ...]``.  A
    loop's own event spans the ops of its body, which are listed too."""
    plane = device_planes(events)[0]
    ws = _merge(windows)
    per: dict = {}
    for e in events:
        if e.plane == plane and e.line == OPS_LINE:
            t = sum(hi - lo for lo, hi in _clip([[e.start_ns, e.end_ns]], ws))
            if t > 0:
                name = op_name(e.name)
                per[name] = per.get(name, 0.0) + t
    top = sorted(per.items(), key=lambda kv: -kv[1])[:k]
    return [[name, t / 1e9] for name, t in top]


def _segments(spans: list) -> tuple[list, list]:
    """Cut time at every span boundary; label each elementary segment by
    the innermost (shortest) span that covers it."""
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    labels = []
    for a, b in zip(cuts[:-1], cuts[1:]):
        mid, best, best_len = (a + b) / 2, "outside-spans", float("inf")
        for s, e, path in spans:
            if s <= mid < e and e - s < best_len:
                best, best_len = path, e - s
        labels.append(best)
    return cuts, labels


def labelled_gaps(events: list, windows: list, spans: list,
                  k: int = 10) -> list:
    """Idle time inside ``windows`` summed by the host span it fell in:
    the ``k`` largest, ``[[span path, seconds], ...]``.  ``spans`` holds
    ``(start_ns, end_ns, path)`` in trace time; a gap is split wherever
    the innermost span changes, so each piece is labelled by what the host
    was doing in it."""
    cuts, labels = _segments(spans)
    # Segment i spans [cuts[i-1], cuts[i]); the two unbounded ends lie
    # outside every span.
    labels = ["outside-spans"] + labels + ["outside-spans"]
    per: dict = {}
    for gs, ge in idle_gaps(events, windows):
        i, t = bisect.bisect_right(cuts, gs), gs
        while t < ge:
            end = min(ge, cuts[i]) if i < len(cuts) else ge
            per[labels[i]] = per.get(labels[i], 0.0) + (end - t)
            t, i = end, i + 1
    top = sorted(per.items(), key=lambda kv: -kv[1])[:k]
    return [[label, t / 1e9] for label, t in top]


def span_rows(root, offset_ns: float, prefix: str = "") -> list:
    """Flatten an obs span tree (``name``, ``t0``/``t1`` in perf_counter
    seconds, ``children``) into ``(start_ns, end_ns, path)`` in trace
    time."""
    path = f"{prefix}/{root.name}" if prefix else root.name
    rows = [(root.t0 * 1e9 + offset_ns, root.t1 * 1e9 + offset_ns, path)]
    for c in root.children:
        rows.extend(span_rows(c, offset_ns, path))
    return rows
