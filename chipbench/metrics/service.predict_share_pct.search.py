"""Share of the window inside the service's `service.predict_batch`
spans (%); the rest is the search engine's own host work."""


def read(run):
    spans = run.spans_named("service.predict_batch")
    if not spans:
        return None
    return 100.0 * sum(run.clipped(s) for s in spans) / run.window_s
