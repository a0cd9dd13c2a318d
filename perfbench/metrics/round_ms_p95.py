"""95th percentile, by nearest rank, over every round of the window: from
the ``aggregate_adapters`` call, the cohort's uploads on the device, to
the global being ready (host clock)."""
from harness import nearest_rank


def read(run):
    if run.family != "sync" or not run.latency_ms:
        return None
    return nearest_rank(run.latency_ms, 0.95)
