"""Device time of the fused tree-traversal programs, from the profiler
trace, per 1,000 candidates scored (ms)."""

from chipbench.trace import FUSED_PROGRAM


def read(run):
    if run.trace is None or not run.cands:
        return None
    t = run.trace.program_s(FUSED_PROGRAM)
    if t <= 0:
        return None
    return 1e3 * t / (run.cands / 1000.0)
