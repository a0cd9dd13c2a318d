"""Device-to-host reads the host waited on per step (round or fold): the
program's ``host_syncs_total`` counter, summed over its sites, over the
steps that ran it.  The counter is the process's, read after the
window, so its steps are the loop's warm-up (``warmup_rounds`` or
``warmup_folds``) and the window's; every step makes the same walks, so
the share is the window's.  A program without the counter gives no
value."""


def read(run):
    from repro.obs import get_registry
    syncs = get_registry().get("host_syncs_total")
    t = run.cell.traffic
    steps = run.steps + int(t.get("warmup_rounds", t.get("warmup_folds", 0)))
    if syncs is None or not run.steps:
        return None
    return sum(syncs.samples().values()) / steps
