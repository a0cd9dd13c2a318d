"""Host ingest per upload: the program's own ``obs_span_seconds{stage=
"submit"}`` (``AsyncAggregator._validate_update`` and the bookkeeping
before the fold), its sum over its count across the window."""


def read(run):
    c = run.counters
    if run.family != "async" or not c.get("submit_count"):
        return None
    return 1e3 * c["submit_sum_s"] / c["submit_count"]
