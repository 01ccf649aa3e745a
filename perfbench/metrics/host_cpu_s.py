"""The process's CPU seconds per call, mean over the window: the host's
work, whether or not other load on the host held a core from it."""


def read(run):
    return sum(c.cpu_s for c in run.calls) / len(run.calls)
