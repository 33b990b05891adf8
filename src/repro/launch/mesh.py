"""Production mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips ("data","model");
multi-pod: 2x16x16 = 512 chips ("pod","data","model").  Every mesh
declares its axes ``jax.sharding.AxisType.Auto``.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax

from repro import platform as _platform


def _auto(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU multi-device tests (host platform devices)."""
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


# ----------------------------------------------------------------------------
# host-device emulation (CPU "devices" via --xla_force_host_platform_device_count)
# ----------------------------------------------------------------------------

_HOST_COUNT_FLAG = _platform.HOST_DEVICE_COUNT_FLAG


def make_host_mesh(
    n_devices: Optional[int] = None,
    axes: Sequence[str] = ("data",),
    devices=None,
):
    """1-D (by default) mesh over the first ``n_devices`` local devices.

    The serving engine's mesh: one ``"data"`` axis the slot pool shards
    over, on the real chips of an accelerator host or on emulated CPU
    devices.  ``n_devices=None`` takes every visible device; asking for
    more than are visible raises — on CPU with the
    ``--xla_force_host_platform_device_count`` hint, on an accelerator
    with the host's chip count.
    """
    devs = list(jax.devices()) if devices is None else list(devices)
    n = len(devs) if n_devices is None else n_devices
    if n > len(devs):
        if devs[0].platform == "cpu":
            raise RuntimeError(
                f"asked for a {n}-device mesh but only {len(devs)} devices "
                f"are visible; on CPU, export XLA_FLAGS={_HOST_COUNT_FLAG}="
                f"{n} (or call repro.platform.request_mesh_devices) before "
                "any jax device use"
            )
        raise RuntimeError(
            f"asked for a {n}-chip mesh but this host has {len(devs)} "
            f"{devs[0].platform} chip(s) ({devs[0].device_kind})"
        )
    if len(axes) != 1:
        raise ValueError(
            "make_host_mesh builds 1-D meshes; use make_test_mesh for "
            f"multi-axis shapes (got axes={tuple(axes)})"
        )
    return jax.make_mesh((n,), tuple(axes), devices=devs[:n],
                         axis_types=_auto(1))
