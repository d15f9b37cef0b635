"""The whole window's share of the chips' roofline (%): the least time
of every per-type traversal call in the window, whichever tier ran it,
counted from shapes by `chipbench.roofline`, over the window's length
times the number of chips."""

from chipbench.roofline import least_time_s, traversal_work


def read(run):
    calls = [c for c in run.kernel_calls() if c["op_type"] in run.bank_shapes]
    if not calls:
        return None
    least = 0.0
    for c in calls:
        b = run.bank_shapes[c["op_type"]]
        ops, nbytes = traversal_work(c["rows"], b["trees"], b["depth"],
                                     b["features"], b["bank_bytes"])
        least += least_time_s(ops, nbytes, run.peak)
    return 100.0 * least / (run.window_s * run.chips)
