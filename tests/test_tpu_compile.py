"""Ahead-of-time compiles of the served path for a described TPU v5e.

The Pallas interpreter accepts kernels that Mosaic, the TPU compiler,
refuses (unaligned or dynamic slices, blocks that break the 8x128 rule,
VMEM over its limit), so every kernel and program the streaming service
runs on the chip is compiled here for one chip of a ``v5e:2x2`` topology
at the QVGA deployment's width: 240x320 with two polarity planes, a
4-slot pool.  Nothing executes; a compile that fails here would fail on
the chip.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and
pytest-xdist workers import every test file.  Keep these tests in this
one file for the same reason.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.isc_qvga import CONFIG
from repro.core import edram
from repro.core import time_surface as ts
from repro.kernels import ops
from repro.serve import heads as heads_mod
from repro.serve import spec as rs
from repro.serve import ts_engine as te

S, P, H, W = 4, 2, CONFIG.h, CONFIG.w
N = 2048        # events per ingest chunk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _cfg():
    gesture = rs.ReadoutSpec(surface=rs.surface(), stcf=rs.stcf(),
                             logits=rs.classify(inputs=("surface",)))
    telemetry = rs.ReadoutSpec(surface=rs.surface(), count=rs.count())
    cfg = te.TSEngineConfig(
        h=H, w=W, polarities=P, n_slots=S, chunk_capacity=N,
        cmem_f=CONFIG.cmem_f, tau_tw=CONFIG.tau_tw,
        stcf_radius=CONFIG.stcf_radius, backend="pallas",
        specs=(gesture, telemetry),
    )
    return cfg, {"gesture": gesture, "telemetry": telemetry}


def _structs(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a),
                                       sharding=sharding), tree)


def _kernel_lowering(name, on):
    """(jitted fn, abstract args) of one kernel entry at 240x320x2."""
    sae = jax.ShapeDtypeStruct((S, P, H, W), jnp.float32, sharding=on)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=on)
    params = _structs(edram.decay_params_for_cmem(CONFIG.cmem_f), on)
    if name == "ts_decay":
        return jax.jit(lambda s, tt, p: ops.ts_decay(
            s, tt, p, backend="pallas")), (sae, t, params)
    if name == "ts_decay_with_mask":
        return jax.jit(lambda s, tt, p: ops.ts_decay_with_mask(
            s, tt, p, 0.385, backend="pallas")), (sae, t, params)
    if name == "stcf_support_fused":
        return jax.jit(lambda s, p, tt: ops.stcf_support_fused(
            s, p, 0.385, tt, radius=CONFIG.stcf_radius,
            backend="pallas")), (sae, params, t)
    if name == "stcf_support":
        mask = jax.ShapeDtypeStruct((S, P, H, W), jnp.bool_, sharding=on)
        return jax.jit(lambda m: ops.stcf_support(
            m, radius=CONFIG.stcf_radius, backend="pallas")), (mask,)
    if name == "ts_analog_read":
        return jax.jit(lambda s, tt, p, e: ops.ts_analog_read(
            s, tt, p, eps=e, backend="pallas")), (sae, t, params, sae)
    if name == "chunk_scatter":
        ev = ts.EventBatch(*(
            jax.ShapeDtypeStruct((S, N), d, sharding=on)
            for d in (jnp.int32, jnp.int32, jnp.float32, jnp.int32,
                      jnp.bool_)))
        return jax.jit(lambda s, e: ops.chunk_scatter(
            s, e, backend="pallas")), (sae, ev)
    if name == "decay_scan":
        a = jax.ShapeDtypeStruct((S, 256, 256), jnp.float32, sharding=on)
        s0 = jax.ShapeDtypeStruct((S, 256), jnp.float32, sharding=on)
        return jax.jit(lambda aa, xx, ss: ops.decay_scan(
            aa, xx, ss, backend="pallas")), (a, a, s0)
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "ts_decay", "ts_decay_with_mask", "stcf_support_fused", "stcf_support",
    "ts_analog_read", "chunk_scatter", "decay_scan",
])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_lowering(name, one_chip)
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel in program"


@pytest.mark.parametrize("tier", ["gesture", "telemetry"])
def test_engine_read_program_compiles_for_v5e(one_chip, tier):
    """The engine's fused stage-0 (+ head) read program, as
    ``TimeSurfaceEngine.read`` dispatches it for each tier's spec."""
    cfg, specs = _cfg()
    spec = specs[tier]
    state = _structs(jax.eval_shape(lambda: te.init_state(cfg)), one_chip)
    dyn = _structs(rs.resolve_dynamic(spec, cfg), one_chip)
    head_params = None
    if spec.has_heads:
        head_params = _structs(
            {n: heads_mod.resolve_head_params(h, cfg)
             for n, h in spec.head_products()}, one_chip)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = te.read_spec_products.lower(
        state.surfaces.sae, state.counts, t, dyn, spec=spec, cfg=cfg,
        backend="pallas", statics=rs.resolve_static(spec, cfg),
        head_params=head_params,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_engine_ingest_program_compiles_for_v5e(one_chip):
    """The donated ring-ingest scatter the stream runtime dispatches."""
    cfg, _ = _cfg()
    state = _structs(jax.eval_shape(lambda: te.init_state(cfg)), one_chip)
    b = 8
    ev = ts.EventBatch(*(
        jax.ShapeDtypeStruct((b, N), d, sharding=one_chip)
        for d in (jnp.int32, jnp.int32, jnp.float32, jnp.int32, jnp.bool_)))
    sids = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    compiled = te.ingest_step_donated.lower(
        state, sids, ev, polarities=P).compile()
    assert compiled.memory_analysis().alias_size_in_bytes > 0  # donated
