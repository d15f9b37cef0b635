"""Share of the window inside the planner's `planner.lower` spans (%):
lowering every candidate step plan to its op graph, before the graphs
are scored."""


def read(run):
    spans = run.spans_named("planner.lower")
    if not spans:
        return None
    return 100.0 * sum(run.clipped(s) for s in spans) / run.window_s
