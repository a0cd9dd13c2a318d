"""Process start to the first measured step: imports, the upload pool
made on the device, executors compiled or loaded from the persistent
cache, and the warm-up steps."""


def read(run):
    return run.setup_s
