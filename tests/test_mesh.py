"""Mesh-construction unit tests: Auto axis types on every mesh builder,
host-device helpers, and slot-pool placement rules.

``jax.make_mesh`` is replaced by a recorder where a mesh bigger than the
test process's devices is asked for, so no real >1-device mesh is needed
in the fast gate (the real 8/512-device builds run in the slow
subprocess tests).
"""
import os

import jax
import pytest

from repro import platform as pf
from repro.launch import mesh as mesh_mod


class _Recorder:
    def __init__(self):
        self.calls = []

    def __call__(self, shape, axes, **kwargs):
        self.calls.append((tuple(shape), tuple(axes), kwargs))
        return ("mesh", tuple(shape), tuple(axes))


# ----------------------------------------------------------------------------
# axis types
# ----------------------------------------------------------------------------

def test_make_meshes_declare_auto_axes(monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(jax, "make_mesh", rec)

    mesh_mod.make_production_mesh()
    mesh_mod.make_production_mesh(multi_pod=True)
    mesh_mod.make_test_mesh((2, 4))
    mesh_mod.make_host_mesh(1)
    (s1, a1, kw1), (s2, a2, kw2), (s3, a3, kw3), (s4, a4, kw4) = rec.calls
    assert (s1, a1) == ((16, 16), ("data", "model"))
    assert (s2, a2) == ((2, 16, 16), ("pod", "data", "model"))
    assert (s3, a3) == ((2, 4), ("data", "model"))
    assert (s4, a4) == ((1,), ("data",))
    for axes, kw in ((a1, kw1), (a2, kw2), (a3, kw3), (a4, kw4)):
        assert kw["axis_types"] == (jax.sharding.AxisType.Auto,) * len(axes)


def test_make_test_mesh_builds_on_pinned_jax():
    """The real ``jax.make_mesh`` call path; single-device shape so the
    fast gate needs no XLA flags."""
    m = mesh_mod.make_test_mesh((1, 1))
    assert dict(m.shape) == {"data": 1, "model": 1}


# ----------------------------------------------------------------------------
# host-device helpers
# ----------------------------------------------------------------------------

def test_make_host_mesh_default_takes_all_devices():
    m = mesh_mod.make_host_mesh()
    assert dict(m.shape) == {"data": len(jax.devices())}


def test_make_host_mesh_too_many_devices_raises():
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        mesh_mod.make_host_mesh(len(jax.devices()) + 1)


def test_make_host_mesh_too_many_chips_names_the_chips():
    class _Chip:
        platform, device_kind = "tpu", "TPU v5 lite"

    with pytest.raises(RuntimeError,
                       match=r"2-chip mesh but this host has 1 tpu chip"):
        mesh_mod.make_host_mesh(2, devices=[_Chip()])


def test_ensure_host_device_count(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "")
    n = len(jax.devices())
    pf.ensure_host_device_count(n)   # satisfiable: no raise
    # the flag lands in the process XLA_FLAGS
    assert f"--xla_force_host_platform_device_count={n}" in \
        os.environ["XLA_FLAGS"]
    monkeypatch.setenv("XLA_FLAGS", "")
    with pytest.raises(RuntimeError, match="already initialized"):
        pf.ensure_host_device_count(n + 1)


# ----------------------------------------------------------------------------
# slot-pool placement rules
# ----------------------------------------------------------------------------

def test_slot_pool_rules_single_device_mesh():
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as shd

    mesh = mesh_mod.make_host_mesh(1)
    assert shd.slot_shard_count(mesh) == 1
    assert shd.pad_pool(5, mesh) == 5
    assert shd.slot_pool_spec(mesh) == P(("data",))
    ns = shd.slot_pool_sharding(mesh)
    assert ns.mesh is mesh and ns.spec == P(("data",))


def test_pad_pool_rounds_up(monkeypatch):
    from repro.distributed import sharding as shd

    class FakeMesh:
        shape = {"pod": 2, "data": 4, "model": 16}

    assert shd.slot_shard_count(FakeMesh()) == 8
    assert shd.pad_pool(6, FakeMesh()) == 8
    assert shd.pad_pool(8, FakeMesh()) == 8
    assert shd.pad_pool(9, FakeMesh()) == 16
