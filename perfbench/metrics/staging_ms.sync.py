"""Device time per round of the ops before the combine kernels: the
cohort's stacking (eager ``jnp.stack``: one ``broadcast_in_dim`` per
client and leaf, then a ``concatenate``) and the plan's ``pack_fn``
(``core/plan.py``), from the trace."""
import tracing

STAGING = r"^jit_(broadcast_in_dim|concatenate|stack|pack_fn)$"


def read(run):
    tr = run.trace
    if run.family != "sync" or not tr or not run.steps:
        return None
    return 1e3 * tracing.module_seconds(tr, STAGING) / run.steps
