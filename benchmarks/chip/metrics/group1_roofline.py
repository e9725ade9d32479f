"""Fused group 1's share of its roofline, in percent.

Layer: the XLA operations on the device that compute group 1 (the stem
conv, its max-pool and stage 1), told apart by their outputs' heights
(the adapter's ``group_work``) in the operation names of the trace.  The
least time the chip could take for the group's work is the larger of its
FLOPs over the peak at the configuration's precision and its minimal
bytes (input map, weights, output map, each moved once) over HBM
bandwidth; the share is that, for every batch of the traced window, over
the device time of the group's operations.
"""

GROUP = "group1"


def read(run):
    work = run.adapter.group_work(run.cfg, GROUP, run.traffic["batch"])
    seconds = run.trace.op_seconds(work["rows"])
    batches = run.traced.attempted
    if seconds <= 0 or batches == 0:
        return None
    least = max(work["flops"] / run.flops_peak,
                work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * batches / seconds
