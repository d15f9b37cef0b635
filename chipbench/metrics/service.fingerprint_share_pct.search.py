"""Share of the window inside the service's `service.fingerprint`
spans (%): hashing each queried graph before the report cache is
looked up, just before each `service.predict_batch` span opens."""


def read(run):
    spans = run.spans_named("service.fingerprint")
    if not spans:
        return None
    return 100.0 * sum(run.clipped(s) for s in spans) / run.window_s
