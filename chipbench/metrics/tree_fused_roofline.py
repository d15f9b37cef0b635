"""Share of the roofline reached by the fused tree traversal (%): the
least time of every fused call in the window, counted from shapes by
`chipbench.roofline`, over the device time of the fused programs in
the trace."""

from chipbench.roofline import least_time_s, traversal_work
from chipbench.trace import FUSED_PROGRAM


def read(run):
    if run.trace is None:
        return None
    device_s = run.trace.program_s(FUSED_PROGRAM)
    calls = [c for c in run.kernel_calls() if c.get("fused")]
    if device_s <= 0 or not calls:
        return None
    least = 0.0
    for c in calls:
        b = run.bank_shapes[c["op_type"]]
        ops, nbytes = traversal_work(c["rows"], b["trees"], b["depth"],
                                     b["features"], b["bank_bytes"])
        least += least_time_s(ops, nbytes, run.peak)
    return 100.0 * least / device_s
