"""``packed_agg``'s share of its roofline: the rounds' necessary bytes
(``work.round_work``) over HBM bandwidth, over the summed time of the
kernel's events in the trace.  The kernel is the Pallas custom call in
the plan's ``combine_fn`` module."""
import tracing
import work


def read(run):
    tr = run.trace
    if run.family != "sync" or not tr or not run.peaks:
        return None
    sec, n = tracing.op_seconds(tr, r"combine_fn", tracing.PALLAS_KERNEL)
    if not n or sec <= 0:
        return None
    return 100.0 * work.least_seconds(run.work, run.peaks,
                                      "bf16_flops_per_s") / sec
