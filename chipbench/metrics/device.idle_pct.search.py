"""Share of the window in which no operation ran on the device (%),
from the profiler trace, averaged over the chips used."""


def read(run):
    if run.trace is None or run.kind != "search":
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.window_s)
