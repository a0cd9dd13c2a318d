"""Device meshes: the devices present (launchers), the v5e-256 pod
(dry-run only), and host-count test meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh


def make_device_mesh() -> Mesh:
    """A ``(1, n_devices)`` ("data", "model") mesh over every device
    present: the launchers' ``--preset full`` mesh -- one chip runs as
    ``(1, 1)``, a four-chip host shards the model axis four ways."""
    devices = jax.devices()
    return Mesh(np.asarray(devices).reshape(1, len(devices)),
                ("data", "model"))


def make_pod_mesh(*, multi_pod: bool = False) -> Mesh:
    """The production v5e-256 pod mesh (two pods with ``multi_pod``) for
    ``launch/dryrun.py``, which forces 512 host devices to lower it."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes)
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)}; "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512")
    dev = np.asarray(devices[:n]).reshape(shape)
    return Mesh(dev, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    n = int(np.prod(shape))
    dev = np.asarray(jax.devices()[:n]).reshape(shape)
    return Mesh(dev, axes)
