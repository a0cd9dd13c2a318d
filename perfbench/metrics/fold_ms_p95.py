"""95th percentile, by nearest rank, over every upload of the window:
from ``AsyncAggregator.submit`` to the folded state being ready (host
clock)."""
from harness import nearest_rank


def read(run):
    if run.family != "async" or not run.latency_ms:
        return None
    return nearest_rank(run.latency_ms, 0.95)
