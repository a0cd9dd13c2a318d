"""Host time of ``aggregate_adapters`` from its call to its return
(stacking dispatch, plan lookup or build, dispatch), mean per round."""


def read(run):
    if run.family != "sync" or not run.host_ms:
        return None
    return sum(run.host_ms) / len(run.host_ms)
