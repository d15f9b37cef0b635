"""Candidates newly scored per second over the whole window."""


def read(run):
    if run.kind != "search":
        return None
    return run.cands / run.window_s
