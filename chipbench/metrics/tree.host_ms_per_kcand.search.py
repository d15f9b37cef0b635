"""Host time in the tree kernel layer per 1,000 candidates scored (ms):
the union of the window's `tree.stage`, `tree.dispatch` and `tree.wait`
spans, less the part of it in which the device was busy (from the
profiler trace), averaged over the chips used."""

from chipbench.trace import _union

SPANS = ("tree.stage", "tree.dispatch", "tree.wait")


def _overlap(xs, ys):
    """Length of the intersection of two sorted disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(0.0, b - a)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(run):
    if run.trace is None or not run.trace.devices or not run.cands:
        return None
    spans = [s for name in SPANS for s in run.spans_named(name)]
    if not spans:
        return None
    host = _union([(max(s["start"], run.t0), min(s["end"], run.t1))
                   for s in spans])
    length = sum(b - a for a, b in host)
    devs = run.trace.devices
    idle = sum(length - _overlap(host, run.trace.busy_intervals(d))
               for d in devs) / len(devs)
    return 1e3 * idle / (run.cands / 1000.0)
