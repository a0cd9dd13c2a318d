"""Uploads aggregated into a global per second: uploads completed over
the whole window, on the host clock."""


def read(run):
    return run.units / run.window_s if run.window_s > 0 else None
