"""Share of the traced window in which no op ran on the device:
1 - busy / window, busy being the union of the device's op intervals."""


def read(run):
    tr = run.trace
    if not tr or not tr["n_chips"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
