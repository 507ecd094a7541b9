"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init, and tests/benches must keep seeing 1 device.

Topology: TPU v5e pods of 256 chips as a (data=16, model=16) mesh; the
multi-pod mesh adds a leading "pod" axis — in this framework the pod axis IS
the federated-learning client axis (DESIGN.md §2.3): gradients all-reduce
over (pod, data) during joint training, and the FL aggregation step pmean's
parameters over "pod" exactly as the paper's Eq. (1) server does over the
simulated WAN.
"""

from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    # Auto axes: the sharding layer places arrays with
    # with_sharding_constraint, which jax.make_mesh's default Explicit
    # axes reject.
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU-subprocess sharding tests."""
    return _auto_mesh(shape, axes)


def mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
