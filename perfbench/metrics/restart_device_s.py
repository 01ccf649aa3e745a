"""Device seconds per call of the Lanczos restart program
(``_packed_restart``), from the profiler trace."""

import pb_trace

PROGRAM = "_packed_restart"


def read(run):
    if run.events is None:
        return None
    t = pb_trace.program_ns(run.events, PROGRAM, run.windows())
    return t / 1e9 / len(run.calls) if t > 0 else None
