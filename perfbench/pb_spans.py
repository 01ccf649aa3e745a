"""Per-call sums over the program's own span tree (``Call.span``), read
by name.  A run whose calls carry no span of that name, such as one with
``REPRO_OBS=off`` or of a program without it, reads ``None``."""

from __future__ import annotations


def _trees(run):
    trees = [c.span for c in run.calls]
    return None if any(t is None for t in trees) else trees


def seconds_per_call(run, name: str):
    """The seconds of every span named ``name``, summed per call, mean
    over the calls."""
    trees = _trees(run)
    if trees is None:
        return None
    per_call = [[s.seconds for s in t.find_all(name)] for t in trees]
    if not any(per_call):
        return None
    return sum(map(sum, per_call)) / len(per_call)


def counter_per_call(run, name: str):
    """Counter ``name`` summed over each call's tree, mean over the
    calls."""
    trees = _trees(run)
    if trees is None:
        return None
    per_call = [t.total_counters().get(name) for t in trees]
    if all(v is None for v in per_call):
        return None
    return sum(v or 0.0 for v in per_call) / len(per_call)
