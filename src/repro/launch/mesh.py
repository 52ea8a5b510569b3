"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init, while tests and benches must keep seeing 1 device.

Target hardware: TPU v5e — 256 chips/pod arranged (16, 16) as
("data", "model"); multi-pod adds a leading "pod" axis over DCN:
(2, 16, 16) = 512 chips.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def _validate_axis_names(axis_names) -> tuple:
    names = tuple(axis_names)
    if not all(isinstance(a, str) and a for a in names):
        raise ValueError(f"mesh axis names must be non-empty strings: {names!r}")
    if len(set(names)) != len(names):
        raise ValueError(f"mesh axis names collide: {names!r}")
    return names


def _grid_mesh(shape, axis_names) -> Mesh:
    """Mesh over the first prod(shape) visible devices, in
    ``jax.devices()`` order."""
    names = _validate_axis_names(axis_names)
    if len(names) != len(shape):
        raise ValueError(f"mesh shape {shape} has {len(shape)} dims but "
                         f"{len(names)} axis names: {names!r}")
    devs = jax.devices()
    total = int(np.prod(shape))
    if total > len(devs):
        raise ValueError(f"mesh shape {shape} needs {total} devices but only "
                         f"{len(devs)} are visible (hint: "
                         f"XLA_FLAGS=--xla_force_host_platform_device_count={total})")
    return Mesh(np.asarray(devs[:total]).reshape(shape), names)


def make_client_mesh(num_shards: int | None = None, *,
                     axis_name: str = "clients") -> Mesh:
    """1-D mesh over the *client* dimension for the sharded round engine.

    ``num_shards`` defaults to every visible device (``None`` or ``<= 0``);
    an explicit count takes the first ``num_shards`` devices.
    """
    devs = jax.devices()
    n = len(devs) if num_shards is None or num_shards <= 0 else num_shards
    return _grid_mesh((n,), (axis_name,))


def make_fed_mesh(mesh_shape, *,
                  axis_names=("clients", "model")) -> Mesh:
    """1-D or 2-D mesh for the federated engines.

    ``mesh_shape`` is a tuple of 1 or 2 ints: ``(c,)`` shards only the
    client dimension (equivalent to ``make_client_mesh(c)``); ``(c, m)``
    lays ``c * m`` devices out row-major so the leading axis shards client
    state and the trailing axis shards each cohort client's parameters.
    At most one entry may be 0, meaning "fill with the visible devices
    divided by the other entry".
    """
    shape = tuple(int(s) for s in mesh_shape)
    if len(shape) not in (1, 2) or any(s < 0 for s in shape):
        raise ValueError(f"mesh_shape must be 1 or 2 non-negative ints, "
                         f"got {mesh_shape!r}")
    if sum(1 for s in shape if s == 0) > 1:
        raise ValueError(f"at most one mesh_shape entry may be 0 (= fill "
                         f"with visible devices), got {mesh_shape!r}")
    names = _validate_axis_names(axis_names)[:len(shape)]
    if 0 in shape:
        fixed = int(np.prod([s for s in shape if s]))
        fill = len(jax.devices()) // fixed
        if fill < 1:
            raise ValueError(f"mesh_shape {mesh_shape!r} cannot be filled: "
                             f"only {len(jax.devices())} devices visible")
        shape = tuple(s if s else fill for s in shape)
    return _grid_mesh(shape, names)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _grid_mesh(shape, axes)


def make_debug_mesh():
    """1x1 mesh over however many devices exist — for CPU smoke tests."""
    n = len(jax.devices())
    return _grid_mesh((n, 1), ("data", "model"))


def data_axes(mesh) -> tuple:
    """The axes carrying batch / FSDP splits ('pod' folds into data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


# Hardware constants for the roofline model (TPU v5e)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (~per chip effective)
CHIPS_PER_POD = 256
