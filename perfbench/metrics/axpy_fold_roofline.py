"""``axpy_fold``'s share of its roofline: the folds' necessary bytes
(``work.fold_work``: the upload's live rows, and the state's same rows
read and written) over HBM bandwidth, over the summed time of the
kernel's events in the trace (the Pallas custom call in ``fold_fn``)."""
import tracing
import work


def read(run):
    tr = run.trace
    if run.family != "async" or not tr or not run.peaks:
        return None
    sec, n = tracing.op_seconds(tr, r"fold_fn", tracing.PALLAS_KERNEL)
    if not n or sec <= 0:
        return None
    return 100.0 * work.least_seconds(run.work, run.peaks,
                                      "bf16_flops_per_s") / sec
