"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
XLA_FLAGS before any jax initialization and then calls it.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model).

    Uses the first prod(shape) devices so a 512-device dry-run process can
    build both meshes."""
    import numpy as np
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} — the dry-run must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax")
    arr = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(arr, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests: 8 fake CPU devices)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _make_1d_mesh(axis: str, num_devices=None):
    n = len(jax.devices()) if num_devices is None else int(num_devices)
    if n < 1 or n > len(jax.devices()):
        raise ValueError(
            f"requested {n} devices for axis {axis!r}, have "
            f"{len(jax.devices())}")
    return jax.make_mesh((n,), (axis,),
                         axis_types=(jax.sharding.AxisType.Auto,))


def make_mc_mesh(num_devices=None):
    """Monte-Carlo trajectory mesh: 1-D, axis ``("mc",)``, over all devices
    by default.  `repro.sim.sharded` shards the flattened seeds × SNR
    trajectory grid along ``mc`` — the embarrassingly parallel axis of a
    scenario sweep — with `repro.dist.sharding_rules.trajectory_specs`
    fitting the leading trajectory dim to this mesh."""
    return _make_1d_mesh("mc", num_devices)


def make_client_mesh(num_devices=None):
    """Client-parallel mesh: 1-D, axis ``("clients",)``.  Used by
    `repro.sim.sharded.run_rounds_client_sharded` to split the stacked
    K-client axis of one large-K trajectory across devices (K must divide
    by the axis size; `sharding_rules.client_specs` fits the specs)."""
    return _make_1d_mesh("clients", num_devices)


def fsdp_axes(mesh) -> tuple:
    """The axes used for fully-sharded parameter dims (pod joins FSDP)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def batch_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
