"""Lowering time per op built (us): the window's `planner.lower` time
over the ops the `lm.ops_lowered` counter gained in the window."""


def read(run):
    spans = run.spans_named("planner.lower")
    ops = run.counter("lm.ops_lowered")
    if not spans or ops <= 0:
        return None
    return 1e6 * sum(run.clipped(s) for s in spans) / ops
