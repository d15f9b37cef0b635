"""Share of the window inside the service's `service.featurize` spans
(%): the fused-graph rewrite where a setting asks for it, and the
per-op-type grouping of the fresh graphs' feature matrices."""


def read(run):
    spans = run.spans_named("service.featurize")
    if not spans:
        return None
    return 100.0 * sum(run.clipped(s) for s in spans) / run.window_s
