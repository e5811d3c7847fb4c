"""Production mesh construction.

``make_production_mesh`` is a function (not a module-level constant) so that
importing this module never touches JAX device state — the CPU tests see a
4-way split of the host; only ``dryrun.py`` forces 512 host devices.

Topology: one v5e pod = 256 chips arranged ``(data=16, model=16)``; the
multi-pod mesh adds a leading pure-DP ``pod`` axis (DCN between pods, ICI
within — the ``pod`` axis only ever carries gradient all-reduces, which is
what DCN can sustain).
"""

from __future__ import annotations

import os
from typing import Optional

import jax

__all__ = [
    "make_production_mesh",
    "make_host_mesh",
    "make_serving_mesh",
    "simulate_host_devices",
    "MESH_AXES",
    "SERVING_AXIS",
]

MESH_AXES = ("data", "model")

#: the tensor-parallel axis sharded serving decodes over (1-D mesh)
SERVING_AXIS = "model"


def simulate_host_devices(n: int = 4) -> None:
    """Split the host CPU into ``n`` XLA devices (bayespec-style).

    Appends ``--xla_force_host_platform_device_count`` to ``XLA_FLAGS``,
    which XLA reads at backend initialization — call this before the
    first computation (importing jax is fine; using a device is not).
    A pre-existing device-count flag is respected, so nesting harnesses
    (conftest → bench → example) never fight over the count.

    The flag splits only the CPU backend and never picks the platform:
    run with ``JAX_PLATFORMS=cpu`` to get the split, and on a TPU host
    the real chips are the devices.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


def make_serving_mesh(devices: Optional[int] = None, *, offset: int = 0):
    """1-D ``("model",)`` mesh for tensor-parallel serving.

    Uses ``devices`` host devices starting at ``offset`` — replicas can
    carve disjoint sub-meshes out of one simulated host (replica 0 on
    devices 0–1, replica 1 on 2–3, ...).
    """
    avail = jax.devices()
    n = devices if devices is not None else len(avail)
    if n < 1:
        raise ValueError(f"serving mesh needs at least 1 device, got {n}")
    if offset + n > len(avail):
        raise ValueError(
            f"need devices [{offset}, {offset + n}) but only "
            f"{len(avail)} exist — on the CPU, call "
            "simulate_host_devices() before the first jax computation"
        )
    return jax.sharding.Mesh(avail[offset:offset + n], (SERVING_AXIS,))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(data: Optional[int] = None, model: int = 1):
    """Mesh over whatever devices exist (tests / single-host runs)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    return jax.make_mesh((data, model), ("data", "model"))
