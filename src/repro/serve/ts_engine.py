"""Batched streaming time-surface serving engine (multi-sensor front end).

The public surface is **sessions + declarative readout specs**:
``engine.attach()`` returns a ``serve.api.SensorSession`` owning one
slot's lifecycle (``push`` / ``read`` / ``push_and_read`` / ``detach``),
and every read takes a ``serve.spec.ReadoutSpec`` — a static, hashable
description of *what to read* (decayed surface, STCF support map,
comparator mask, event-count / EBBI / raw-SAE / wrap-quantized-TS
baselines).  The spec is part of the jit cache key exactly like the
``backend`` selector: each unique spec compiles **one fused batched
dispatch** returning all of its products over the whole pool, and every
session shares that entry.  The method-per-feature names of earlier
revisions (``acquire`` / ``ingest`` / ``readout`` / ``readout_with_mask``
/ ``support_map`` / ``ingest_and_read``) survive one release as
deprecated shims over the session/spec path, value-identical to it.

A fixed pool of per-sensor *slots*, each holding one ``SurfaceState``
(SAE + polarity metadata), batched along a leading slot axis so the whole
pool is one pytree:

  * **ingest** — variable-length AER event chunks (packed 64-bit words or
    host ``EventStream``s) are padded to a fixed chunk capacity and
    scattered into the batched SAE with a single jit'd max-combine scatter,
    regardless of how many sensors ingest in one call.  O(#events) writes —
    the paper's event-driven cost structure, served.
  * **readout** — the Pallas ``ts_decay`` kernel runs batched over all
    slots (leading dims vmapped inside ``kernels.ops``), optionally with
    the STCF comparator fused so the denoiser front end never re-reads the
    surface.  Backend selection (``"pallas" | "interpret" | "ref"``) is one
    static argument threaded through ``kernels.ops``.

Slots are acquired/released between calls (the static-shape analogue of
continuous batching, mirroring ``serve.engine.ServeEngine``); releasing and
re-acquiring a slot resets its surface to "never written", so sensors can
come and go without retracing anything.

Both decay modes run through the *same* kernel: the ideal exponential TS is
the double-exponential eDRAM transient with ``a1=1, a2=0, b=0, tau1=tau``,
so readout is bit-identical to the offline ``core.time_surface`` pipeline
in either mode.

**Fused ingest->readout path** — ``serve_step(items, spec, t_now)``
(session form: ``push_and_read``) scatters the chunks and serves the
spec's products from one jit'd program (the serving form of the
``kernels.ops.ts_fused`` family).  Its speed comes from the *dirty-tile
cache* carried in the slot-pool pytree (``ReadoutCache``):

  * the last surface readout is cached tiled as (S, TP, block_h,
    block_w) next to a (S, TP) dirty mask; every scatter (fused or plain
    push) marks the tiles its events touched,
  * a repeat call under the **same cache epoch** — same ``t_now``, same
    surface product — re-reads only the dirty tiles through the same
    ``ts_decay`` kernel and patches them into the cache
    (``ops.ts_fused_dirty``) — O(touched tiles) transcendentals instead of
    O(H*W), the in-sensor cost structure served,
  * when the epoch moves (``t_now`` changed or a different surface
    product took the cache over, both tracked host-side in
    ``_cache_t``/``_cache_surface``), or more than ``max_dirty_tiles``
    tiles are dirty, the call falls back to one dense pass that refills
    the whole cache — never a wrong answer, only a slower one.

The cache is *spec-keyed at the host*: the device state tracks which
tiles are stale, the host tracks what the clean tiles hold (which
surface product, read at which ``t_now``), so interleaving fused reads
of different specs can never serve one product's bits as another's.
Cache coherence is preserved by every state transition: plain pushes
mark dirty tiles, and attach/detach wipe a slot's cache rows to zeros —
exactly the readout of a never-written surface at any ``t_now``, so a
reset never invalidates the pool-wide cache epoch.  Incremental and dense
readouts are bit-identical (clean tiles hold bits the same kernel produced
at the same ``t_now``), which ``benchmarks/bench_serve.py`` and the
equivalence/differential suites gate.

**Device-parallel mode** — pass a ``mesh`` to ``TimeSurfaceEngine`` and the
slot pool shards its leading axis over the mesh's data axes
(``distributed.sharding.slot_pool_sharding``).  Ingest routes each chunk to
the device owning its slot and scatters under ``shard_map`` with donated
state; the batched ``ts_decay``/STCF readouts run the same Pallas kernels
per shard.  The dirty-tile cache lives in the same pytree, so it shards
with the pool and the incremental refresh stays collective-free: each
shard counts its own dirty tiles and picks incremental-vs-dense locally.
Every hot-path op is purely local — zero cross-device traffic.
Pools not divisible by the device count are padded up
(``n_slots_padded``); the dead tail slots are never acquirable, stay
"never written", and read as all-zero surfaces.  Per-slot results are
bit-identical to the single-device engine at any device count: the math
per slot never changes, only where the slot lives.

**Elastic slot pools + live migration** — the pool is not fixed:
``grow()`` adds acquirable capacity in ``slot_bucket`` pad-ahead
increments (new rows are never-written state; each distinct padded size
is one *capacity bucket* that retraces the shape-keyed jit caches once
— the spec layer is pool-size-agnostic, so no hot spec recompiles when
a bucket is revisited), ``shrink()`` compacts live slots out of the
tail deterministically and releases it, and ``migrate(src, dst)``
moves one live session's entire per-slot state — surface, dirty-tile
cache row, counter plane, and the attach-epoch ``generation`` whose
value keys the analog-fidelity noise draws — onto a free slot,
re-binding its ``SensorSession`` in place.  On a sharded engine the
migration broadcasts the source rows with one ``lax.psum`` (cold
administrative path; the hot path stays collective-free), and both
sides are bitwise the single-device move, which the streaming replay
oracle gates.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core import edram
from repro.core import stcf as stcf_mod
from repro.core import time_surface as ts
from repro.events import aer
from repro.events import pipeline
from repro.events import synthetic as syn
from repro.hw import constants as C
from repro.kernels import ops
from repro.serve import fidelity as fidelity_mod
from repro.serve import spec as spec_mod
from repro.serve.api import SensorSession
from repro.serve.trace import span


@dataclasses.dataclass(frozen=True)
class TSEngineConfig:
    """Static engine configuration (part of every jit cache key)."""

    h: int = C.QVGA_H
    w: int = C.QVGA_W
    polarities: int = 1
    n_slots: int = 8                     # sensor pool size
    chunk_capacity: int = 2048           # events per ingest chunk (padded)
    mode: str = "edram"                  # "edram" | "ideal"
    tau: float = C.MEMORY_WINDOW_S       # ideal-TS decay constant
    tau_tw: float = C.MEMORY_WINDOW_S    # STCF correlation window
    cmem_f: float = C.ISC_CMEM_F
    stcf_radius: int = 3
    stcf_threshold: int = 2
    backend: Optional[str] = None        # kernels.ops backend selector
    block: Tuple[int, int] = (8, 128)    # ts_decay tile (= dirty-tile size)
    slot_bucket: Optional[int] = None    # elastic pad-ahead growth increment
    # (slots per ``grow()`` call; ``None`` = the initial ``n_slots``).
    # Capacity only ever changes in whole buckets, so the pool's padded
    # slot axis takes a small set of sizes — each size retraces the
    # shape-keyed jit caches once and every later visit to that bucket
    # reuses the compiled entries (the spec layer is pool-size-agnostic:
    # nothing in ``serve.spec`` depends on ``n_slots``).
    max_dirty_tiles: int = 0             # incremental-readout gather cap;
    # 0 = auto (a quarter of the pool's tiles, at least 16).  On a sharded
    # engine the cap applies per shard.  Overflow falls back to one dense
    # pass — correctness never depends on this knob.
    specs: Tuple[spec_mod.ReadoutSpec, ...] = ()
    # the ReadoutSpecs this engine intends to serve.  Purely declarative
    # for SAE-only products (any spec can be read at runtime); its one
    # structural effect is state sizing: a declared spec needing the
    # per-slot counter plane (``count(...)``) makes ``init_state``
    # materialize it — undeclared count reads fail fast instead of
    # silently serving zero counts.

    def __post_init__(self):
        assert self.mode in ("edram", "ideal"), self.mode
        assert self.slot_bucket is None or self.slot_bucket >= 1, (
            self.slot_bucket
        )
        ops.resolve_backend(self.backend)  # fail fast on typos
        for s in self.specs:
            assert isinstance(s, spec_mod.ReadoutSpec), s

    @property
    def needs_counts(self) -> bool:
        """Whether any declared spec requires the counter plane."""
        return any(spec_mod.needs_counts(s) for s in self.specs)

    def tile_counts(self) -> Tuple[int, int, int]:
        """(tiles_h, tiles_w, tiles_per_slot) for the dirty-tile cache."""
        th, tw, tpl = ops.tile_geometry(self.h, self.w, self.block)
        return th, tw, self.polarities * tpl

    def decay_params(self) -> edram.DecayParams:
        """Uniform decay params; ideal TS as a degenerate double-exp
        (one shared constructor, ``representations.edram_ideal_params``,
        so served and offline ideal reads can never drift)."""
        if self.mode == "ideal":
            from repro.core import representations

            return representations.edram_ideal_params(self.tau)
        return edram.decay_params_for_cmem(self.cmem_f)

    def v_tw(self) -> float:
        """Comparator threshold equivalent to the ``tau_tw`` window."""
        if self.mode == "ideal":
            return float(np.exp(-self.tau_tw / self.tau))
        return float(edram.v_tw_for_window(self.tau_tw, self.decay_params()))

    def stcf_config(self) -> stcf_mod.STCFConfig:
        return stcf_mod.STCFConfig(
            radius=self.stcf_radius, tau_tw=self.tau_tw,
            threshold=self.stcf_threshold,
            polarity_sensitive=self.polarities > 1,
        )


class ReadoutCache(NamedTuple):
    """Dirty-tile readout cache, one row per slot (shards with the pool).

    ``tiles`` holds the last readout in tiled layout — tile ``(p, ty, tx)``
    of slot ``s`` at flat index ``(p*TH + ty)*TW + tx`` — edge tiles padded
    exactly as the dense ``ts_decay`` pads (NEVER -> 0), so a tile patched
    incrementally is bit-identical to its dense counterpart.  A zeroed row
    is the correct readout of a never-written slot at *any* ``t_now``,
    which is what makes slot resets cache-coherent for free.
    """

    tiles: jax.Array   # (S, TP, bh, bw) float32 — tiled last dense readout
    dirty: jax.Array   # (S, TP) bool — tiles written since the cache fill


class EngineState(NamedTuple):
    """The full slot pool as one pytree (leading axis = slot).

    Liveness is host-side bookkeeping (the engine's free list); device
    state holds only what jitted computations read.  ``counts`` is the
    optional per-slot event-counter plane serving ``count(...)`` spec
    products; it materializes only when the engine config declares a
    spec needing it (``None`` otherwise — an empty pytree subtree, so
    every jit/shard_map entry handles both layouts).
    """

    surfaces: ts.SurfaceState   # sae (S, P, H, W), t_last (S,), n_events (S,)
    generation: jax.Array       # (S,) int32 — bumped on every acquire
    cache: ReadoutCache         # dirty-tile readout cache (see above)
    counts: Optional[jax.Array] = None  # (S, H, W) int32, polarity-merged


def init_state(cfg: TSEngineConfig, n_slots: Optional[int] = None) -> EngineState:
    """Fresh pool state; ``n_slots`` overrides the config for padded
    (device-divisible) pools in sharded mode."""
    s = cfg.n_slots if n_slots is None else n_slots
    p, h, w = cfg.polarities, cfg.h, cfg.w
    bh, bw = cfg.block
    _, _, tp = cfg.tile_counts()
    return EngineState(
        surfaces=ts.SurfaceState(
            sae=jnp.full((s, p, h, w), ts.NEVER, jnp.float32),
            t_last=jnp.zeros((s,), jnp.float32),
            n_events=jnp.zeros((s,), jnp.int32),
        ),
        generation=jnp.zeros((s,), jnp.int32),
        cache=ReadoutCache(
            tiles=jnp.zeros((s, tp, bh, bw), jnp.float32),
            dirty=jnp.zeros((s, tp), bool),
        ),
        counts=(jnp.zeros((s, h, w), jnp.int32)
                if cfg.needs_counts else None),
    )


# ----------------------------------------------------------------------------
# jit'd state transitions (pure; the engine class only does host bookkeeping)
# ----------------------------------------------------------------------------

def _scatter_chunks(
    state: EngineState,
    slot_ids: jax.Array,     # (B,) int32 — target slot per chunk
    ev: ts.EventBatch,       # (B, N) fields — one padded chunk per row
    polarities: int,
) -> EngineState:
    """The fused max-combine scatter body, shared by the single-device jit
    and the per-shard ``shard_map`` local step (slot ids are then local).

    Also marks the dirty-tile cache: every (slot, tile) a valid event
    lands in is flagged so a later incremental readout knows what to
    recompute.  Tile geometry is derived from the state's array shapes —
    no extra static arguments.

    Out-of-range coordinates are masked invalid up front: jnp's
    ``mode="drop"`` only drops *past-the-end* indices and silently wraps
    negative ones, which would scatter into the wrong column AND mark the
    wrong dirty tile (``-1 // bw`` floors), serving a stale cached tile.
    """
    sur = state.surfaces
    h, w = sur.sae.shape[-2:]
    pol = ev.p if polarities > 1 else jnp.zeros_like(ev.p)
    valid = (ev.valid & (ev.x >= 0) & (ev.x < w) & (ev.y >= 0)
             & (ev.y < h) & (pol >= 0) & (pol < sur.sae.shape[1]))
    t = jnp.where(valid, ev.t, ts.NEVER)
    sid = jnp.broadcast_to(slot_ids[:, None], ev.t.shape)
    sae = sur.sae.at[sid, pol, ev.y, ev.x].max(t, mode="drop")
    t_last = sur.t_last.at[slot_ids].max(
        t.max(axis=1, initial=ts.NEVER), mode="drop"
    )
    n_events = sur.n_events.at[slot_ids].add(
        valid.sum(axis=1).astype(jnp.int32), mode="drop"
    )
    bh, bw = state.cache.tiles.shape[-2:]
    th, tw, _ = ops.tile_geometry(h, w, (bh, bw))
    tid = (pol * th + ev.y // bh) * tw + ev.x // bw
    dirty = state.cache.dirty.at[sid, tid].max(valid, mode="drop")
    counts = state.counts
    if counts is not None:   # polarity-merged, like representations.event_count
        counts = counts.at[sid, ev.y, ev.x].add(
            valid.astype(jnp.int32), mode="drop"
        )
    return state._replace(
        surfaces=ts.SurfaceState(sae=sae, t_last=t_last, n_events=n_events),
        cache=state.cache._replace(dirty=dirty),
        counts=counts,
    )


@functools.partial(jax.jit, static_argnames=("polarities",))
def ingest_step(
    state: EngineState,
    slot_ids: jax.Array,     # (B,) int32 — target slot per chunk
    ev: ts.EventBatch,       # (B, N) fields — one padded chunk per row
    polarities: int = 1,
) -> EngineState:
    """Scatter B event chunks into their slots in one fused max-combine.

    Duplicate slot ids in one call are fine (max/add combine); padding
    events carry t=-inf and never win the max.  O(B*N) writes total.
    """
    return _scatter_chunks(state, slot_ids, ev, polarities)


@functools.partial(
    jax.jit, static_argnames=("polarities",), donate_argnums=(0,)
)
def ingest_step_donated(
    state: EngineState,
    slot_ids: jax.Array,     # (B,) int32 — ring upload
    ev: ts.EventBatch,       # (B, N) fields — ring upload
    polarities: int = 1,
) -> EngineState:
    """``ingest_step`` with the engine state donated.

    The device-ring ingest path (``TimeSurfaceEngine.push_staged``)
    immediately replaces ``self.state`` with the result, so the old
    state buffers — the full (n_slots, P, H, W) surface planes — are
    dead on return; donating them lets XLA scatter in place instead of
    holding two copies of the pool live per deadline (exactly what the
    sharded plan's shard_map ingest already does).  Same
    ``_scatter_chunks`` body — bitwise identical to ``ingest_step`` on
    equal inputs.
    """
    return _scatter_chunks(state, slot_ids, ev, polarities)


@functools.partial(
    jax.jit,
    static_argnames=("cfg_stcf", "mode", "intra_chunk"),
)
def ingest_support(
    state: EngineState,
    slot_ids: jax.Array,
    ev: ts.EventBatch,
    cfg_stcf: stcf_mod.STCFConfig,
    mode: str,
    params: edram.DecayParams,
    v_tw,
    intra_chunk: bool = True,
) -> jax.Array:
    """STCF support of each chunk's events vs its slot's pre-ingest SAE.

    Returns (B, N) int32.  Runs the same ``stcf_chunk_support`` the offline
    ``stcf_chunked`` path scans with, vmapped over the slot gather.
    """
    sae_b = state.surfaces.sae[slot_ids]          # (B, P, H, W)
    sup = jax.vmap(
        lambda s, c: stcf_mod.stcf_chunk_support(
            s, c, cfg_stcf, mode=mode, params=params, v_tw=v_tw,
            intra_chunk=intra_chunk,
        )
    )(sae_b, ev)
    return sup


@functools.partial(jax.jit, static_argnames=("bump_generation",))
def reset_slot(
    state: EngineState, slot: jax.Array, bump_generation: bool = True,
) -> EngineState:
    """Wipe one slot back to 'never written'; acquire also bumps its
    generation, release just wipes.  The slot's cache row resets to zeros
    (the readout of a never-written surface at any ``t_now``) with no
    dirty tiles, so resets keep the pool-wide cache epoch valid."""
    sur = state.surfaces
    gen = state.generation
    return EngineState(
        surfaces=ts.SurfaceState(
            sae=sur.sae.at[slot].set(ts.NEVER),
            t_last=sur.t_last.at[slot].set(0.0),
            n_events=sur.n_events.at[slot].set(0),
        ),
        generation=gen.at[slot].add(1) if bump_generation else gen,
        cache=ReadoutCache(
            tiles=state.cache.tiles.at[slot].set(0.0),
            dirty=state.cache.dirty.at[slot].set(False),
        ),
        counts=(None if state.counts is None
                else state.counts.at[slot].set(0)),
    )


@jax.jit
def migrate_slot(
    state: EngineState, src: jax.Array, dst: jax.Array,
) -> EngineState:
    """Move slot ``src``'s rows onto slot ``dst`` and wipe ``src``.

    Every per-slot leaf moves: the SAE plane, ``t_last``/``n_events``,
    the readout-cache row (the destination's cached tiles are then the
    source's last valid readout, so the pool-wide cache epoch stays
    coherent), the counter plane, and the slot ``generation`` — the
    analog-fidelity noise key is folded from the generation *value*,
    never the slot index, so moving the value moves the per-cell noise
    draws bitwise with it.  ``src`` is wiped exactly like
    ``reset_slot`` without a generation bump (its next acquire bumps
    from the carried value, deterministically).  ``src != dst`` is the
    caller's contract (``TimeSurfaceEngine.migrate`` enforces it).
    """
    sur = state.surfaces
    return EngineState(
        surfaces=ts.SurfaceState(
            sae=sur.sae.at[dst].set(sur.sae[src]).at[src].set(ts.NEVER),
            t_last=sur.t_last.at[dst].set(sur.t_last[src]).at[src].set(0.0),
            n_events=sur.n_events.at[dst].set(
                sur.n_events[src]).at[src].set(0),
        ),
        generation=state.generation.at[dst].set(state.generation[src]),
        cache=ReadoutCache(
            tiles=state.cache.tiles.at[dst].set(
                state.cache.tiles[src]).at[src].set(0.0),
            dirty=state.cache.dirty.at[dst].set(
                state.cache.dirty[src]).at[src].set(False),
        ),
        counts=(None if state.counts is None
                else state.counts.at[dst].set(
                    state.counts[src]).at[src].set(0)),
    )


@functools.partial(
    jax.jit, static_argnames=("spec", "cfg", "backend", "statics")
)
def read_spec_products(
    sae: jax.Array,                    # (S, P, H, W) pool SAE
    counts,                            # (S, H, W) int32 or None
    t_now,
    dynamic,                           # {name: DecayParams}, traced
    spec: spec_mod.ReadoutSpec,
    cfg: TSEngineConfig,
    backend: str,
    statics: Tuple[Tuple[str, float], ...] = (),
    head_params=None,                  # {head name: params}, traced
    noise_step=None,                   # traced int — analog noise key input
    generation=None,                   # (S,) int32 — analog noise key input
) -> Dict[str, jax.Array]:
    """One fused batched dispatch serving every product of ``spec`` —
    stage-0 surface products and the stage-1 heads that consume them,
    all in one program.

    ``spec`` (with ``cfg``/``backend``) is the jit cache key: the first
    read of a new spec traces once, every later read of an equal spec —
    from any session — reuses the compiled entry.  Stage-0 products are
    independent subgraphs over the shared pool state, each dispatching
    the same ``kernels.ops`` math its standalone predecessor ran, so the
    ``surface`` product stays bit-identical to a standalone ``ts_decay``;
    heads read their inputs through an ``optimization_barrier``, so
    inlining them cannot re-contract the stage-0 math and the fused
    logits equal a standalone head over the read surfaces (gated by the
    kernel-equivalence and engine-differential suites).  Head weights
    (``head_params``) are traced arguments resolved from the spec's
    static weights key by the engine — never baked constants.
    """
    # the plan is rebuilt from the static args rather than via
    # compile_spec: resolving comparator thresholds is host math, and
    # this body runs under trace — ``statics`` already carries them
    compiled = spec_mod.CompiledSpec(
        spec=spec, stage0=spec.stage0(), heads=spec.head_products(),
        statics=tuple(statics),
    )
    return spec_mod.read_compiled(sae, counts, t_now, dynamic, compiled,
                                  cfg, backend, head_params,
                                  noise_step=noise_step,
                                  generation=generation)


@functools.partial(jax.jit, static_argnames=("compiled", "cfg"))
def read_head_products(
    stage0_out: Dict[str, jax.Array],  # the shared stage-0 pool read
    head_params,                       # {head name: params}, traced
    compiled: spec_mod.CompiledSpec,
    cfg: TSEngineConfig,
) -> Dict[str, jax.Array]:
    """Stage-1-only dispatch: ``compiled``'s heads over an already-read
    stage-0 product dict — the second half of ``read_many``'s shared-
    stage-0 path.  Bitwise the fused in-dispatch heads: both trace the
    same ``apply_heads`` body, whose ``optimization_barrier`` pins the
    head subgraph to consume exactly the served stage-0 arrays."""
    return spec_mod.apply_heads(stage0_out, head_params, compiled, cfg)


def _read_refresh(
    state: EngineState,
    t_now,
    params,
    *,
    max_dirty: int,
    block: Tuple[int, int],
    backend: str,
    refresh_all: bool,
) -> Tuple[EngineState, jax.Array]:
    """Traceable dirty-tile cache refresh at ``t_now`` (pool surface out).

    The ``shard_map`` local step of the sharded fused path: runs
    ``ops.ts_fused_dirty_local`` — the inline form whose
    incremental-vs-dense choice is a shard-local ``lax.cond`` (no host
    sync, no collectives).  ``refresh_all`` (a trace-time constant — the
    plan compiles one dense and one incremental entry) forces the dense
    refill used when ``t_now`` moved or the cache is cold.  The
    single-device engine instead host-orchestrates ``ops.ts_fused_dirty``
    directly (see ``ingest_and_read``)."""
    s, p, h, w = state.surfaces.sae.shape
    tp = state.cache.dirty.shape[1]
    bh, bw = state.cache.tiles.shape[-2:]
    surface, tiles, dirty = ops.ts_fused_dirty_local(
        state.surfaces.sae.reshape(s * p, h, w),
        state.cache.tiles.reshape(s * tp, bh, bw),
        state.cache.dirty.reshape(s * tp),
        jnp.float32(t_now), params, max_dirty=max_dirty, block=block,
        backend=backend, force_dense=refresh_all,
    )
    cache = ReadoutCache(tiles=tiles.reshape(s, tp, bh, bw),
                         dirty=dirty.reshape(s, tp))
    return state._replace(cache=cache), surface.reshape(s, p, h, w)


# ----------------------------------------------------------------------------
# device-parallel plan: shard_map'd state transitions over the slot axis
# ----------------------------------------------------------------------------

class _ShardPlan:
    """Per-engine compiled plan for a slot pool sharded over a mesh.

    Every function here is ``shard_map`` over the mesh's data axes with the
    slot axis split, so the hot path (ingest scatter, batched ts_decay /
    STCF readout) is embarrassingly data-parallel: each device owns
    ``slots_per_shard`` slots and runs the exact single-device computation
    on them — no collectives anywhere in the lowered program.
    """

    def __init__(self, cfg: TSEngineConfig, mesh: Mesh):
        # deferred: distributed.sharding pulls the model stack, which the
        # single-device engine never needs
        from repro.distributed import sharding as shd

        self.mesh = mesh
        self.axes = shd.data_axes(mesh)
        self.n_shards = shd.slot_shard_count(mesh)
        self.n_slots_padded = shd.pad_pool(cfg.n_slots, mesh)
        self.slots_per_shard = self.n_slots_padded // self.n_shards
        self.sharding = shd.slot_pool_sharding(mesh)
        spec = shd.slot_pool_spec(mesh)
        rep = P()
        # comparator thresholds are *static* in kernels.ops (part of the
        # jit key; serve.spec resolves them per product), matching the
        # single-device path; decay params stay runtime arguments —
        # baking them in as shard_map closure constants lets XLA
        # constant-fold the transcendentals differently and costs
        # bit-identity with the unsharded engine.
        backend = ops.resolve_backend(cfg.backend)

        def smap(fn, in_specs, out_specs):
            return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False)

        def local_ingest(state, slot_ids, ev):
            # slot_ids are *local* (host routing already picked the shard)
            return _scatter_chunks(state, slot_ids, ev, cfg.polarities)

        self.ingest = jax.jit(
            smap(local_ingest, (spec, spec, spec), spec), donate_argnums=0,
        )

        def shard_offset(slots_per_shard):
            """First global slot id owned by this device (major-to-minor
            over the data axes, matching PartitionSpec((a1, a2)) order).
            ``slots_per_shard`` comes from the *traced* state's local
            block shape, so every shape-keyed trace is automatically
            correct for its capacity bucket (the elastic pool resizes
            the slot axis without touching these programs)."""
            gid = jnp.int32(0)
            for a in self.axes:
                gid = gid * mesh.shape[a] + lax.axis_index(a)
            return gid * slots_per_shard

        def local_reset(state, slot, bump):
            n_local = state.generation.shape[0]
            hit = shard_offset(n_local) + jnp.arange(n_local) == slot
            sur = state.surfaces
            return EngineState(
                surfaces=ts.SurfaceState(
                    sae=jnp.where(hit[:, None, None, None], ts.NEVER, sur.sae),
                    t_last=jnp.where(hit, 0.0, sur.t_last),
                    n_events=jnp.where(hit, 0, sur.n_events),
                ),
                generation=state.generation + hit.astype(jnp.int32)
                if bump else state.generation,
                cache=ReadoutCache(
                    tiles=jnp.where(hit[:, None, None, None], 0.0,
                                    state.cache.tiles),
                    dirty=jnp.where(hit[:, None], False, state.cache.dirty),
                ),
                counts=(None if state.counts is None
                        else jnp.where(hit[:, None, None], 0, state.counts)),
            )

        self.reset_acquire = jax.jit(smap(
            lambda st, s: local_reset(st, s, True), (spec, rep), spec,
        ), donate_argnums=0)
        self.reset_release = jax.jit(smap(
            lambda st, s: local_reset(st, s, False), (spec, rep), spec,
        ), donate_argnums=0)

        def local_migrate(state, src, dst):
            """Move global slot ``src`` onto global slot ``dst`` across
            shards: broadcast the source rows with a ``lax.psum`` over
            the data axes (exactly one shard contributes non-zero rows;
            -inf SAE entries survive the sum-with-zeros), write them at
            the destination's owner, wipe the source.  Collectives are
            fine here — migration is a cold administrative path, never
            the per-deadline hot loop."""
            n_local = state.generation.shape[0]
            idx = shard_offset(n_local) + jnp.arange(n_local)
            src_hit = idx == src
            dst_hit = idx == dst

            def bcast(arr):
                mask = src_hit.reshape((n_local,) + (1,) * (arr.ndim - 1))
                row = jnp.sum(
                    jnp.where(mask, arr, jnp.zeros_like(arr)), axis=0
                )
                return lax.psum(row, self.axes) if self.axes else row

            def move(arr, wipe):
                shaped = lambda m: m.reshape(
                    (n_local,) + (1,) * (arr.ndim - 1))
                row = bcast(arr.astype(jnp.int32)
                            if arr.dtype == bool else arr)
                if arr.dtype == bool:
                    row = row > 0
                out = jnp.where(shaped(dst_hit), row[None].astype(arr.dtype),
                                arr)
                return jnp.where(shaped(src_hit),
                                 jnp.asarray(wipe, arr.dtype), out)

            sur = state.surfaces
            return EngineState(
                surfaces=ts.SurfaceState(
                    sae=move(sur.sae, ts.NEVER),
                    t_last=move(sur.t_last, 0.0),
                    n_events=move(sur.n_events, 0),
                ),
                generation=jnp.where(
                    dst_hit, bcast(state.generation), state.generation),
                cache=ReadoutCache(
                    tiles=move(state.cache.tiles, 0.0),
                    dirty=move(state.cache.dirty, False),
                ),
                counts=(None if state.counts is None
                        else move(state.counts, 0)),
            )

        self.migrate = jax.jit(
            smap(local_migrate, (spec, rep, rep), spec), donate_argnums=0,
        )

        # spec readers compile lazily, one shard_map program per unique
        # ReadoutSpec (the sharded analogue of ``read_spec_products``'s
        # jit cache); the slot-leading product arrays all shard like the
        # pool, scalars/params replicate
        self._cfg = cfg
        self._smap = smap
        self._spec_p, self._rep_p = spec, rep
        self._backend = backend
        self._spec_readers: Dict[spec_mod.ReadoutSpec, object] = {}
        self._head_readers: Dict[spec_mod.ReadoutSpec, object] = {}

        # fused ingest->readout: scatter + dirty-tile refresh, all local.
        # The gather cap applies per shard (each shard counts only its own
        # dirty tiles) so the incremental-vs-dense choice needs no
        # collectives; either choice is bit-identical.  Derived from the
        # *traced* local block shape, so each capacity bucket's trace
        # carries its own cap (``self.max_dirty`` mirrors the current
        # bucket's value for telemetry).
        _, _, tp = cfg.tile_counts()
        self.max_dirty = cfg.max_dirty_tiles or max(
            16, self.slots_per_shard * tp // 4
        )

        def local_max_dirty(state):
            return cfg.max_dirty_tiles or max(
                16, state.generation.shape[0] * tp // 4
            )

        def local_ingest_read(refresh_all):
            def f(state, slot_ids, ev, t_now, params):
                state = _scatter_chunks(state, slot_ids, ev, cfg.polarities)
                return _read_refresh(
                    state, t_now, params, max_dirty=local_max_dirty(state),
                    block=cfg.block, backend=backend,
                    refresh_all=refresh_all,
                )
            return f

        io_specs = ((spec, spec, spec, rep, rep), (spec, spec))
        self.ingest_read_dense = jax.jit(
            smap(local_ingest_read(True), *io_specs), donate_argnums=0,
        )
        self.ingest_read_inc = jax.jit(
            smap(local_ingest_read(False), *io_specs), donate_argnums=0,
        )

        # pure cached reads (ingest_and_read with no payload): same
        # refresh, no scatter
        def local_refresh(refresh_all):
            def f(state, t_now, params):
                return _read_refresh(
                    state, t_now, params, max_dirty=local_max_dirty(state),
                    block=cfg.block, backend=backend,
                    refresh_all=refresh_all,
                )
            return f

        r_specs = ((spec, rep, rep), (spec, spec))
        self.refresh_dense = jax.jit(smap(local_refresh(True), *r_specs),
                                     donate_argnums=0)
        self.refresh_inc = jax.jit(smap(local_refresh(False), *r_specs),
                                   donate_argnums=0)

    def resize(self, n_slots_padded: int) -> None:
        """Track an elastic capacity change.  The compiled programs need
        nothing — every closure derives its local slot count (and the
        per-shard dirty-gather cap) from the traced state shapes, so a
        new bucket size simply retraces once and a revisited bucket hits
        the existing shape-keyed cache.  Only the *host* routing state
        (``route``/``_stage_sharded``'s ``divmod`` split) moves here."""
        assert n_slots_padded % self.n_shards == 0, (
            n_slots_padded, self.n_shards
        )
        self.n_slots_padded = n_slots_padded
        self.slots_per_shard = n_slots_padded // self.n_shards
        _, _, tp = self._cfg.tile_counts()
        self.max_dirty = self._cfg.max_dirty_tiles or max(
            16, self.slots_per_shard * tp // 4
        )

    def spec_reader(self, rspec: spec_mod.ReadoutSpec):
        """The compiled pool-wide reader for one ReadoutSpec (cached).

        Each product array leads with the slot axis — head logits
        ``(S, n_classes)`` exactly like surface planes ``(S, P, H, W)``
        — so the whole output dict shards like the pool; the staged spec
        body (stage-0 products, then heads behind the barrier) runs
        shard-local with zero collectives, same as every other hot-path
        op here.  Head weights replicate (they are per-model, not
        per-slot).  Two layouts per spec never coexist: whether the
        counter plane is materialized is fixed at engine construction.
        """
        fn = self._spec_readers.get(rspec)
        if fn is not None:
            return fn
        from repro.distributed import sharding as shd

        cfg, backend = self._cfg, self._backend
        p, rep = self._spec_p, self._rep_p
        out_specs = shd.slot_pool_out_specs(self.mesh, rspec.names)
        compiled = spec_mod.compile_spec(rspec, cfg)

        if fidelity_mod.spec_needs_noise(rspec):
            # analog-fidelity specs take the (noise_step, generation)
            # key inputs: the step index replicates, the per-slot attach
            # epochs shard with the pool, and the per-cell draws are
            # element-wise per slot — so each shard folds exactly the
            # keys the single-device program folds (sharding-invariant
            # noise, same rule as every other hot-path op here)
            def noisy_with_counts(sae, counts, t_now, dynamic,
                                  head_params, noise_step, generation):
                return spec_mod.read_compiled(
                    sae, counts, t_now, dynamic, compiled, cfg, backend,
                    head_params, noise_step=noise_step,
                    generation=generation,
                )

            def noisy_no_counts(sae, t_now, dynamic, head_params,
                                noise_step, generation):
                return spec_mod.read_compiled(
                    sae, None, t_now, dynamic, compiled, cfg, backend,
                    head_params, noise_step=noise_step,
                    generation=generation,
                )

            if spec_mod.needs_counts(rspec):
                fn = jax.jit(self._smap(
                    noisy_with_counts, (p, p, rep, rep, rep, rep, p),
                    out_specs,
                ))
            else:
                base = jax.jit(self._smap(
                    noisy_no_counts, (p, rep, rep, rep, rep, p), out_specs,
                ))
                fn = (lambda sae, counts, t_now, dynamic, head_params,
                      noise_step, generation:
                      base(sae, t_now, dynamic, head_params, noise_step,
                           generation))
            self._spec_readers[rspec] = fn
            return fn

        def local_with_counts(sae, counts, t_now, dynamic, head_params):
            return spec_mod.read_compiled(sae, counts, t_now, dynamic,
                                          compiled, cfg, backend,
                                          head_params)

        def local_no_counts(sae, t_now, dynamic, head_params):
            return spec_mod.read_compiled(sae, None, t_now, dynamic,
                                          compiled, cfg, backend,
                                          head_params)

        if spec_mod.needs_counts(rspec):
            fn = jax.jit(self._smap(local_with_counts,
                                    (p, p, rep, rep, rep), out_specs))
        else:
            base = jax.jit(self._smap(local_no_counts,
                                      (p, rep, rep, rep), out_specs))
            fn = (lambda sae, counts, t_now, dynamic, head_params:
                  base(sae, t_now, dynamic, head_params))
        self._spec_readers[rspec] = fn
        return fn

    def head_reader(self, compiled: spec_mod.CompiledSpec):
        """The compiled stage-1-only reader for one head-bearing spec
        (cached): ``apply_heads`` under ``shard_map`` over an
        already-read stage-0 product dict.  Inputs and head outputs all
        lead with the slot axis and every head op is per-slot, so the
        heads run shard-local; weights replicate.  The sharded leg of
        ``read_many``'s shared-stage-0 path."""
        fn = self._head_readers.get(compiled.spec)
        if fn is not None:
            return fn
        from repro.distributed import sharding as shd

        cfg = self._cfg
        in_specs = shd.slot_pool_out_specs(self.mesh, compiled.stage0.names)
        out_specs = shd.slot_pool_out_specs(
            self.mesh, tuple(n for n, _ in compiled.heads)
        )

        def local(stage0_out, head_params):
            return spec_mod.apply_heads(stage0_out, head_params,
                                        compiled, cfg)

        fn = jax.jit(self._smap(local, (in_specs, self._rep_p), out_specs))
        self._head_readers[compiled.spec] = fn
        return fn

    def place(self, tree):
        """Pin a slot-pool pytree to the plan's NamedSharding."""
        return jax.device_put(tree, self.sharding)

    def route(self, slot_ids: Sequence[int], chunks: Sequence["ts.EventBatch"]):
        """Per-slot -> per-device ingest routing.

        Groups chunk rows by the shard owning their slot, pads every shard
        to a common power-of-two row count with no-op chunks (all-invalid,
        local slot 0), and returns shard-major ``(local_slot_ids, ev)``
        device arrays laid out so shard_map's block split hands each device
        exactly the rows that target its slots.
        """
        per_shard: List[List[Tuple[int, ts.EventBatch]]] = [
            [] for _ in range(self.n_shards)
        ]
        for slot, chunk in zip(slot_ids, chunks):
            shard, local = divmod(slot, self.slots_per_shard)
            per_shard[shard].append((local, chunk))
        b_local = TimeSurfaceEngine._pad_batch(
            max(len(rows) for rows in per_shard)
        )
        empty = jax.tree_util.tree_map(jnp.zeros_like, chunks[0])
        sids: List[int] = []
        rows: List[ts.EventBatch] = []
        for shard_rows in per_shard:
            shard_rows = shard_rows + [(0, empty)] * (b_local - len(shard_rows))
            sids.extend(local for local, _ in shard_rows)
            rows.extend(chunk for _, chunk in shard_rows)
        ev = jax.tree_util.tree_map(lambda *fs: jnp.stack(fs), *rows)
        return (
            self.place(jnp.asarray(sids, jnp.int32)),
            self.place(ev),
        )


# ----------------------------------------------------------------------------
# device-resident ingest ring
# ----------------------------------------------------------------------------

#: one raw ingest part: (x, y, t, p) host arrays, equal length <= capacity
RawPart = Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]


class IngestRing:
    """Double-buffered host staging for device-resident ingest.

    ``TimeSurfaceEngine.push_staged`` fills one pre-allocated staging
    set — whole (B, cap) fields, one ``device_put`` per field — instead
    of building B little per-chunk ``EventBatch`` device arrays and
    ``jnp.stack``-ing them on the hot path.  ``depth`` staging sets
    alternate per padded batch size: with JAX async dispatch the upload
    for deadline k+1 starts while deadline k's scatter + spec read is
    still running on device (on GPU the latency-hiding scheduler
    overlaps the H2D copy with compute), and the set filled at step k is
    only rewritten at step k+depth, after its upload has been consumed
    by the donated scatter.

    The staging pad values (zero coordinates, ``valid=False``) need not
    match ``pipeline.to_event_batch``'s padding bit for bit: the scatter
    masks every invalid event to -inf before it can touch a surface bit,
    so ring-staged and host-staged ingest are bitwise identical — the
    replay-oracle digest gate holds on either path.

    Reuse needs an upload that copies.  Where ``device_put`` may alias
    the NumPy buffer instead (the CPU backend hands it to the
    computation zero-copy), a set rewritten ``depth`` pushes later can
    still be read by a queued scatter — two tier groups of one padded
    size reuse their sets at the very next deadline.  ``reuse=False``
    therefore hands out a fresh set on every acquire; the set then
    lives exactly as long as the arrays that alias it.  On the TPU the
    upload copies into device memory and sets are reused, with no
    extra host copy.
    """

    def __init__(self, capacity: int, depth: int = 2, reuse: bool = True):
        assert depth >= 2, depth
        self.capacity = capacity
        self.depth = depth
        self.reuse = reuse
        self._sets: Dict[int, List[dict]] = {}   # padded B -> staging sets
        self._turn: Dict[int, int] = {}

    def _alloc(self, b: int) -> dict:
        cap = self.capacity
        return {
            "sids": np.zeros(b, np.int32),
            "x": np.zeros((b, cap), np.int32),
            "y": np.zeros((b, cap), np.int32),
            "t": np.zeros((b, cap), np.float32),
            "p": np.zeros((b, cap), np.int32),
            "valid": np.zeros((b, cap), bool),
        }

    def acquire(self, b: int) -> dict:
        """The next staging set for padded batch size ``b``, zero-filled
        (pad rows must stay scatter no-ops)."""
        if not self.reuse:
            return self._alloc(b)
        sets = self._sets.get(b)
        if sets is None:
            sets = self._sets[b] = [self._alloc(b) for _ in range(self.depth)]
            self._turn[b] = 0
        i = self._turn[b]
        self._turn[b] = (i + 1) % self.depth
        buf = sets[i]
        for f in buf.values():
            f[:] = 0
        return buf

    @staticmethod
    def fill_row(buf: dict, row: int, slot: int, part: RawPart) -> None:
        """Stage one (slot, part) into row ``row`` of the staging set."""
        x, y, t, p = part
        n = len(x)
        buf["sids"][row] = slot
        if n:
            buf["x"][row, :n] = x
            buf["y"][row, :n] = y
            buf["t"][row, :n] = t
            buf["p"][row, :n] = p
            buf["valid"][row, :n] = True

    @staticmethod
    def upload(buf: dict, put=jax.device_put):
        """One async H2D transfer per field (6 total, any batch size).
        ``put`` defaults to a plain ``device_put``; the sharded engine
        passes ``_ShardPlan.place`` so the fields land pre-sharded."""
        return put(buf["sids"]), ts.EventBatch(
            x=put(buf["x"]), y=put(buf["y"]), t=put(buf["t"]),
            p=put(buf["p"]), valid=put(buf["valid"]),
        )


# ----------------------------------------------------------------------------
# the engine
# ----------------------------------------------------------------------------

#: an ingest item: (slot id, packed AER words | host EventStream | EventBatch)
IngestItem = Tuple[int, Union[np.ndarray, syn.EventStream, ts.EventBatch]]

#: specs behind the deprecated shims (module-level so every engine shares
#: one jit cache entry per shim, exactly like the pre-spec methods did)
_SURFACE_MASK_SPEC = spec_mod.ReadoutSpec(surface=spec_mod.Surface(),
                                          mask=spec_mod.Mask())
_STCF_SPEC = spec_mod.ReadoutSpec(stcf=spec_mod.Stcf())


class TimeSurfaceEngine:
    """Host-facing multi-sensor serving engine over the batched slot state.

    Typical use (sessions + declarative specs)::

        from repro.serve import spec as rs

        eng = TimeSurfaceEngine(TSEngineConfig(h=240, w=320, n_slots=8))
        cam = eng.attach()                     # SensorSession on a slot
        cam.push(packed_aer_words)
        spec = rs.ReadoutSpec(surface=rs.surface(), stcf=rs.stcf())
        out = cam.read(spec, t_now)            # {"surface": ..., "stcf": ...}
        cam.detach()

    Pool-level calls (``read`` / ``serve_step``) return pool-shaped
    products for all slots in one fused dispatch per unique spec.  With a
    ``mesh`` the pool shards over the mesh's data axes (see the module
    docstring): same API, same per-slot bits, ``n_slots_padded`` rows in
    pool-shaped outputs.  The pre-spec method names remain as deprecated
    shims (one ``DeprecationWarning`` each per engine), value-identical
    to the session/spec path they forward to.
    """

    def __init__(self, cfg: TSEngineConfig, mesh: Optional[Mesh] = None):
        self.cfg = cfg
        self._plan = _ShardPlan(cfg, mesh) if mesh is not None else None
        self.n_slots_padded = (
            self._plan.n_slots_padded if self._plan else cfg.n_slots
        )
        state = init_state(cfg, n_slots=self.n_slots_padded)
        self.state = self._plan.place(state) if self._plan else state
        #: acquirable slots right now (elastic: grows/shrinks in
        #: ``slot_bucket`` increments; ``cfg.n_slots`` stays the initial
        #: capacity).  Slots in [capacity, n_slots_padded) are the dead
        #: sharding-pad tail — never acquirable, always never-written.
        self.capacity = cfg.n_slots
        self._free: List[int] = list(range(cfg.n_slots))
        self._sessions: Dict[int, SensorSession] = {}
        self._params = cfg.decay_params()
        self._v_tw = cfg.v_tw()
        self._stcf_cfg = cfg.stcf_config()
        self._backend = ops.resolve_backend(cfg.backend)
        # dirty-tile cache epoch, spec-keyed: the (surface product,
        # t_now) the cache tiles were read under (None = cold).  Device
        # state tracks *which* tiles are stale; the host tracks *what*
        # the clean ones hold — a fused read whose surface product or
        # t_now differs from the epoch refills densely and takes the
        # cache over.
        self._cache_t: Optional[float] = None
        self._cache_surface: Optional[Tuple[str, spec_mod.Surface]] = None
        self._dynamic_cache: Dict[spec_mod.ReadoutSpec, tuple] = {}
        self._compiled_cache: Dict[spec_mod.ReadoutSpec,
                                   spec_mod.CompiledSpec] = {}
        # serve_step's spec minus its cached surface product, precomputed
        # per spec (the fused path is the per-burst hot loop)
        self._rest_cache: Dict[spec_mod.ReadoutSpec,
                               Optional[spec_mod.ReadoutSpec]] = {}
        self._warned: set = set()
        devices = mesh.devices.flat if mesh is not None else jax.devices()[:1]
        self._ring = IngestRing(
            cfg.chunk_capacity,
            reuse=all(d.platform != "cpu" for d in devices))
        _, _, tp = cfg.tile_counts()
        self._max_dirty = (
            self._plan.max_dirty if self._plan
            else cfg.max_dirty_tiles or max(16, self.n_slots_padded * tp // 4)
        )

    @property
    def mesh(self) -> Optional[Mesh]:
        return self._plan.mesh if self._plan else None

    # -- sessions ------------------------------------------------------------
    def attach(self, qos=None) -> SensorSession:
        """Claim a free slot (resetting its surface) and return the
        ``SensorSession`` owning it; raises ``RuntimeError`` when the
        pool is full.  ``qos`` optionally tags the session with a
        ``serve.stream.QoSClass`` — the engine itself is QoS-agnostic
        (scheduling lives in ``StreamRuntime``), the tag just rides the
        session for introspection and the streaming action log."""
        if not self._free:
            raise RuntimeError(
                f"no free sensor slots (pool capacity {self.capacity}; "
                "grow() adds a bucket, or let StreamRuntime's elastic "
                "policy do it)"
            )
        slot = self._free.pop(0)
        self.state = self._reset(slot, bump_generation=True)
        session = SensorSession(self, slot, qos=qos)
        self._sessions[slot] = session
        return session

    def _detach(self, slot: int) -> None:
        """Session teardown: wipe the slot and return it to the pool."""
        self._check_acquired(slot)
        self.state = self._reset(slot, bump_generation=False)
        self._sessions.pop(slot, None)
        self._free.append(slot)
        self._free.sort()

    def _reset(self, slot: int, bump_generation: bool) -> EngineState:
        if self._plan:
            fn = (self._plan.reset_acquire if bump_generation
                  else self._plan.reset_release)
            return fn(self.state, jnp.int32(slot))
        return reset_slot(self.state, jnp.int32(slot),
                          bump_generation=bump_generation)

    def _check_acquired(self, slot: int) -> None:
        if not 0 <= slot < self.capacity:
            raise ValueError(
                f"slot {slot} out of range [0, {self.capacity})"
            )
        if slot in self._free:
            raise ValueError(f"slot {slot} is not acquired")

    @property
    def n_live(self) -> int:
        return self.capacity - len(self._free)

    # -- elastic capacity + live migration ------------------------------------
    @property
    def slot_bucket(self) -> int:
        """The pad-ahead growth increment (``cfg.slot_bucket`` or the
        initial pool size)."""
        return self.cfg.slot_bucket or self.cfg.n_slots

    def _recompute_max_dirty(self) -> None:
        _, _, tp = self.cfg.tile_counts()
        self._max_dirty = (
            self._plan.max_dirty if self._plan
            else self.cfg.max_dirty_tiles
            or max(16, self.n_slots_padded * tp // 4)
        )

    def _resize_state(self, n_slots_padded: int) -> None:
        """Grow (tree-concat fresh never-written tail rows) or shrink
        (slice the tail off) every slot-pool leaf to ``n_slots_padded``
        rows, re-pinning the plan sharding.  Cold path: the shape change
        retraces each hot jit once per capacity bucket; revisited
        buckets hit the existing entries."""
        if n_slots_padded > self.n_slots_padded:
            tail = init_state(
                self.cfg, n_slots=n_slots_padded - self.n_slots_padded
            )
            state = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b], axis=0),
                self.state, tail,
            )
        elif n_slots_padded < self.n_slots_padded:
            state = jax.tree_util.tree_map(
                lambda a: a[:n_slots_padded], self.state
            )
        else:
            return
        self.state = self._plan.place(state) if self._plan else state

    def _padded_for(self, capacity: int) -> int:
        if self._plan is None:
            return capacity
        from repro.distributed import sharding as shd

        return shd.pad_pool(capacity, self._plan.mesh)

    def grow(self, capacity: Optional[int] = None) -> int:
        """Grow the pool to ``capacity`` acquirable slots (default: one
        ``slot_bucket`` more) without recompiling anything hot: new tail
        rows are never-written state, the padded slot axis moves to the
        new bucket's (mesh-divisible) size, and every compiled spec
        dispatch re-keys on the new shapes exactly like any other jit
        cache entry.  Returns the new capacity."""
        if capacity is None:
            capacity = self.capacity + self.slot_bucket
        if capacity <= self.capacity:
            raise ValueError(
                f"grow target {capacity} <= current capacity "
                f"{self.capacity} (use shrink())"
            )
        new_padded = self._padded_for(capacity)
        self._resize_state(new_padded)
        self._free.extend(range(self.capacity, capacity))
        self._free.sort()
        self.capacity = capacity
        self.n_slots_padded = new_padded
        if self._plan:
            self._plan.resize(new_padded)
        self._recompute_max_dirty()
        return self.capacity

    def shrink(self, capacity: int) -> List[Tuple[int, int]]:
        """Shrink the pool to ``capacity`` acquirable slots, compacting
        live slots out of the released tail first and then slicing the
        tail off every leaf.

        Compaction is deterministic — live tail slots in increasing
        order migrate into the lowest free head slots in increasing
        order — and returns the ``(src, dst)`` moves so callers
        (``StreamRuntime``) can re-key their own slot-indexed state and
        the replay oracle can assert it derived the identical moves.
        Raises when more than ``capacity`` slots are live."""
        if not 1 <= capacity < self.capacity:
            raise ValueError(
                f"shrink target {capacity} not in [1, {self.capacity})"
            )
        if self.n_live > capacity:
            raise RuntimeError(
                f"cannot shrink to {capacity}: {self.n_live} slots live"
            )
        live_tail = [s for s in range(capacity, self.capacity)
                     if s not in self._free]
        free_head = sorted(d for d in self._free if d < capacity)
        moves = list(zip(live_tail, free_head))
        for src, dst in moves:
            self._migrate_slot(src, dst)
        new_padded = self._padded_for(capacity)
        self._resize_state(new_padded)
        self._free = [d for d in self._free if d < capacity]
        self.capacity = capacity
        self.n_slots_padded = new_padded
        if self._plan:
            self._plan.resize(new_padded)
        self._recompute_max_dirty()
        return moves

    def _pick_migration_dst(self, src: int) -> int:
        """Deterministic destination policy: the lowest free slot on the
        least-loaded shard (live-slot count excluding ``src``, which is
        about to leave its shard); single-device pools take the lowest
        free slot.  Determinism is the whole contract — the action log
        records the actual (src, dst) pair, so the oracle replays the
        choice rather than re-deriving it."""
        if not self._free:
            raise RuntimeError("no free slot to migrate into")
        if self._plan is None:
            return self._free[0]
        sps = self._plan.slots_per_shard
        load: Dict[int, int] = {}
        for s in range(self.capacity):
            if s != src and s not in self._free:
                load[s // sps] = load.get(s // sps, 0) + 1
        return min(self._free, key=lambda d: (load.get(d // sps, 0), d))

    def _migrate_slot(self, src: int, dst: int) -> None:
        """Device-state move + host re-key for one live slot (shared by
        ``migrate`` and ``shrink`` compaction; bookkeeping only — the
        caller validates)."""
        if self._plan:
            self.state = self._plan.migrate(
                self.state, jnp.int32(src), jnp.int32(dst)
            )
        else:
            self.state = migrate_slot(
                self.state, jnp.int32(src), jnp.int32(dst)
            )
        self._free.remove(dst)
        session = self._sessions.pop(src, None)
        if session is not None:
            session._slot = dst
            self._sessions[dst] = session
        self._free.append(src)
        self._free.sort()

    def migrate(self, src: int, dst: Optional[int] = None) -> int:
        """Live-migrate the session on slot ``src`` to free slot ``dst``
        (default: ``_pick_migration_dst``).  The whole per-slot state
        moves — surface, caches, counts, and the attach-epoch
        ``generation`` whose *value* keys the analog noise draws, so an
        analog tier's per-cell noise migrates bitwise with its surface.
        The session handle re-binds in place (``session.slot`` returns
        the new slot); the old slot is wiped and returned to the free
        list.  Returns the destination slot."""
        self._check_acquired(src)
        if dst is None:
            dst = self._pick_migration_dst(src)
        if dst == src:
            raise ValueError(f"migration src == dst ({src})")
        if not 0 <= dst < self.capacity:
            raise ValueError(
                f"slot {dst} out of range [0, {self.capacity})"
            )
        if dst not in self._free:
            raise ValueError(f"destination slot {dst} is not free")
        self._migrate_slot(src, dst)
        return dst

    # -- ingest --------------------------------------------------------------
    def _as_chunks(self, item) -> List[ts.EventBatch]:
        """Normalize one ingest payload to fixed-capacity EventBatch chunks."""
        cap = self.cfg.chunk_capacity
        if isinstance(item, ts.EventBatch):
            assert item.x.shape[0] == cap, (
                f"EventBatch capacity {item.x.shape[0]} != engine chunk "
                f"capacity {cap}"
            )
            return [item]
        if isinstance(item, np.ndarray):  # packed 64-bit AER words
            item = aer.unpack(item.astype(np.uint64), self.cfg.h, self.cfg.w)
        assert isinstance(item, syn.EventStream), type(item)
        out = []
        for lo in range(0, max(item.n, 1), cap):
            sub = syn.EventStream(
                x=item.x[lo:lo + cap], y=item.y[lo:lo + cap],
                t=item.t[lo:lo + cap], p=item.p[lo:lo + cap],
                is_signal=item.is_signal[lo:lo + cap], h=self.cfg.h,
                w=self.cfg.w,
            )
            out.append(pipeline.to_event_batch(sub, cap))
        return out

    @staticmethod
    def _pad_batch(n: int) -> int:
        """Pad the ingest batch to a power of two: bounded jit retraces."""
        b = 1
        while b < n:
            b *= 2
        return b

    def _collect(self, items: Sequence[IngestItem]):
        """Normalize ingest items to (slot_ids, chunks, per-item spans).
        Items may target a slot id or a live ``SensorSession``."""
        slot_ids: List[int] = []
        chunks: List[ts.EventBatch] = []
        spans: List[Tuple[int, int]] = []
        for slot, payload in items:
            if isinstance(slot, SensorSession):
                slot._check()
                slot = slot.slot
            self._check_acquired(slot)
            cs = self._as_chunks(payload)
            spans.append((len(chunks), len(chunks) + len(cs)))
            chunks.extend(cs)
            slot_ids.extend([slot] * len(cs))
        return slot_ids, chunks, spans

    def _stack_chunks(self, slot_ids: List[int], chunks: List[ts.EventBatch]):
        """Pad the batch to a power of two and stack to (B, N) device arrays
        (pad rows are all-invalid chunks aimed at slot 0: scatter no-ops)."""
        b = self._pad_batch(len(chunks))
        pad = b - len(chunks)
        if pad:
            empty = jax.tree_util.tree_map(jnp.zeros_like, chunks[0])
            chunks = chunks + [empty] * pad
            slot_ids = slot_ids + [0] * pad
        ev = jax.tree_util.tree_map(lambda *fs: jnp.stack(fs), *chunks)
        return jnp.asarray(slot_ids, jnp.int32), ev

    def push(self, items: Sequence[IngestItem]) -> None:
        """Pool-level batched ingest: one fused scatter call for many
        sensors.  ``items`` pairs a ``SensorSession`` (or its slot id)
        with a payload; ``SensorSession.push`` is the single-sensor form.
        """
        self._ingest_items(items)

    def _ingest_items(self, items: Sequence[IngestItem]) -> None:
        """Scatter event payloads into their slots under one jit call
        (the body behind ``SensorSession.push``).

        ``items`` pairs a slot id with packed AER words (uint64), a host
        ``EventStream``, or a pre-padded ``EventBatch``.  Payloads longer
        than ``chunk_capacity`` are split host-side.  Every chunk fuses
        into one scatter call; on a sharded engine each chunk row is
        routed to the device owning its slot and scattered locally under
        ``shard_map`` (donated state, no collectives).
        """
        slot_ids, chunks, _ = self._collect(items)
        if not chunks:
            return
        if self._plan:
            sids, ev = self._plan.route(slot_ids, chunks)
            self.state = self._plan.ingest(self.state, sids, ev)
            return
        sids, ev = self._stack_chunks(slot_ids, chunks)
        self.state = ingest_step(
            self.state, sids, ev, polarities=self.cfg.polarities
        )

    def push_staged(self, items: Sequence[Tuple[int, RawPart]]) -> None:
        """Device-ring batched ingest: raw ``(slot | session, (x, y, t,
        p))`` host parts, each at most ``chunk_capacity`` events, staged
        into the engine's pre-allocated double-buffered host arrays and
        uploaded as whole (B, cap) fields.

        The streaming runtime's hot ingest path: versus ``push`` of the
        same parts it skips the per-part ``EventBatch`` construction and
        the B-way ``jnp.stack``, does one ``device_put`` per field, and
        (single device) feeds the donated ``ingest_step_donated`` entry
        — so the upload for the next deadline overlaps this deadline's
        in-flight scatter+read instead of serializing before it.  On a
        sharded engine the staging is shard-major (``_stage_sharded``)
        and feeds the plan's donated shard_map ingest.  Bitwise
        identical to ``push``: same scatter body, and the ring's staging
        pad values are masked to -inf before they can reach any surface
        bit (the replay-oracle digest gate covers both paths).
        """
        cap = self.cfg.chunk_capacity
        with span("serve.ingest", capacity=cap) as sp:
            rows: List[Tuple[int, RawPart]] = []
            n_events = 0
            for slot, part in items:
                if isinstance(slot, SensorSession):
                    slot._check()
                    slot = slot.slot
                self._check_acquired(slot)
                assert len(part[0]) <= cap, (
                    f"part of {len(part[0])} events exceeds chunk capacity "
                    f"{cap}; split parts host-side (see "
                    f"StreamRuntime._coalesce)"
                )
                rows.append((slot, part))
                n_events += len(part[0])
            if not rows:
                return
            with span("serve.stage"):
                if self._plan:
                    buf = self._stage_sharded(rows)
                else:
                    buf = self._ring.acquire(self._pad_batch(len(rows)))
                    for i, (slot, part) in enumerate(rows):
                        IngestRing.fill_row(buf, i, slot, part)
            sp.set_metadata(events=n_events, rows=len(rows),
                            padded_rows=len(buf["sids"]))
            with span("serve.upload"):
                sids, ev = IngestRing.upload(
                    buf, put=self._plan.place if self._plan else jax.device_put)
            if self._plan:
                self.state = self._plan.ingest(self.state, sids, ev)
            else:
                self.state = ingest_step_donated(
                    self.state, sids, ev, polarities=self.cfg.polarities
                )

    def _stage_sharded(self, rows: Sequence[Tuple[int, RawPart]]) -> dict:
        """Shard-major ring staging mirroring ``_ShardPlan.route``: rows
        group by the shard owning their slot (ids go local) and every
        shard pads to a common power-of-two row count; uploaded
        pre-sharded (``_ShardPlan.place``), shard_map's block split
        hands each device exactly the rows targeting its slots."""
        plan = self._plan
        per_shard: List[List[Tuple[int, RawPart]]] = [
            [] for _ in range(plan.n_shards)
        ]
        for slot, part in rows:
            shard, local = divmod(slot, plan.slots_per_shard)
            per_shard[shard].append((local, part))
        b_local = self._pad_batch(max(len(r) for r in per_shard))
        buf = self._ring.acquire(plan.n_shards * b_local)
        for shard, shard_rows in enumerate(per_shard):
            for j, (local, part) in enumerate(shard_rows):
                IngestRing.fill_row(buf, shard * b_local + j, local, part)
        return buf

    def _ingest_labeled(self, items: Sequence[IngestItem]) -> list:
        """Scatter payloads *and* label each event with its STCF support
        (the body behind ``SensorSession.push_labeled``).

        Chunks process sequentially — each chunk's support sees all
        earlier chunks' writes — so the labels are exactly those of the
        offline ``stcf_chunked`` scan with ``chunk=chunk_capacity``, at
        the cost of one jit call per chunk (on a sharded engine this
        labeling path runs through the global gather/scatter, not the
        data-parallel fast path).  Returns, per input item,
        ``(support, support >= threshold)`` over its valid events.
        """
        slot_ids, chunks, spans = self._collect(items)
        if not chunks:
            return []
        sups, valids = [], []
        for slot, chunk in zip(slot_ids, chunks):
            sid = jnp.asarray([slot], jnp.int32)
            ev1 = jax.tree_util.tree_map(lambda f: f[None], chunk)
            sups.append(ingest_support(
                self.state, sid, ev1, self._stcf_cfg, self.cfg.mode,
                self._params, jnp.float32(self._v_tw),
            ))
            valids.append(chunk.valid)
            self.state = ingest_step(
                self.state, sid, ev1, polarities=self.cfg.polarities
            )
        if self._plan:  # re-pin: the global scatter may drop the layout
            self.state = self._plan.place(self.state)
        sup_np = np.concatenate([np.asarray(s)[0] for s in sups])
        valid = np.concatenate([np.asarray(v) for v in valids])
        cap = self.cfg.chunk_capacity
        out = []
        for lo, hi in spans:
            s = sup_np[lo * cap:hi * cap]
            v = valid[lo * cap:hi * cap]
            out.append((s[v], s[v] >= self.cfg.stcf_threshold))
        return out

    # -- spec reads ----------------------------------------------------------
    def _check_spec(self, spec: spec_mod.ReadoutSpec) -> None:
        if not isinstance(spec, spec_mod.ReadoutSpec):
            raise TypeError(
                f"expected a ReadoutSpec, got {type(spec).__name__}; "
                "compose one with serve.spec (e.g. "
                "ReadoutSpec(surface=surface()))"
            )
        if spec_mod.needs_counts(spec) and self.state.counts is None:
            raise ValueError(
                "spec needs the counter plane (a count(...) product or "
                "analog_2d fidelity) but this engine has none; declare a "
                "counts-needing spec in TSEngineConfig.specs so "
                "init_state materializes it"
            )

    def _compiled(self, spec: spec_mod.ReadoutSpec) -> spec_mod.CompiledSpec:
        """The spec's staged plan under this engine's config (cached)."""
        plan = self._compiled_cache.get(spec)
        if plan is None:
            plan = spec_mod.compile_spec(spec, self.cfg)
            self._compiled_cache[spec] = plan
        return plan

    def _resolved(self, spec: spec_mod.ReadoutSpec):
        """Per-spec (traced decay params, static thresholds, traced head
        weights), host-resolved once per engine and cached.  Head
        weights resolve from each ``classify`` head's static key through
        ``serve.heads`` (registry / checkpoint / deterministic default)
        — the resolution is host work; the arrays enter every dispatch
        traced."""
        entry = self._dynamic_cache.get(spec)
        if entry is None:
            head_params = None
            classify_heads = [
                (name, h) for name, h in self._compiled(spec).heads
                if isinstance(h, spec_mod.Classify)
            ]
            if classify_heads:
                from repro.serve import heads as heads_mod

                head_params = {
                    name: heads_mod.resolve_head_params(h, self.cfg)
                    for name, h in classify_heads
                }
            entry = (spec_mod.resolve_dynamic(spec, self.cfg),
                     spec_mod.resolve_static(spec, self.cfg),
                     head_params)
            self._dynamic_cache[spec] = entry
        return entry

    def read(
        self,
        spec: spec_mod.ReadoutSpec = spec_mod.SURFACE_SPEC,
        t_now: float = 0.0,
        noise_step: int = 0,
    ) -> Dict[str, jax.Array]:
        """Read every product of ``spec`` over the whole pool at ``t_now``
        in **one fused batched dispatch** (the spec is the jit cache key;
        an equal spec never retraces) — stage-0 surface products and the
        stage-1 heads consuming them come out of the same program.
        Product arrays lead with the slot axis — ``n_slots_padded`` rows
        on a sharded engine; dead/free slots read as never-written (zero
        surfaces, zero counts, and whatever the heads make of zeros).

        The ``surface()`` product runs the same ``ts_decay`` math the
        offline ``time_surface.surface_read_kernel`` dispatches, so
        engine and offline readouts of equal SAE state stay bit-identical,
        composed or not, sharded or not; head products are bitwise the
        standalone head over the served stage-0 arrays (the
        ``optimization_barrier`` contract in ``serve.spec``).

        ``noise_step`` keys the analog-fidelity per-cell noise draws
        (with each slot's attach epoch) — the stream runtime passes its
        step index, the replay oracle replays the recorded one; specs
        without noise-drawing products ignore it entirely (the compiled
        program never takes the key inputs, so digital reads are
        byte-for-byte the pre-fidelity programs).
        """
        self._check_spec(spec)
        dynamic, statics, head_params = self._resolved(spec)
        t = jnp.float32(t_now)
        needs_noise = fidelity_mod.spec_needs_noise(spec)
        if self._plan:
            fn = self._plan.spec_reader(spec)
            if needs_noise:
                out = fn(self.state.surfaces.sae, self.state.counts, t,
                         dynamic, head_params, jnp.int32(noise_step),
                         self.state.generation)
            else:
                out = fn(self.state.surfaces.sae, self.state.counts, t,
                         dynamic, head_params)
        elif needs_noise:
            out = read_spec_products(
                self.state.surfaces.sae, self.state.counts, t, dynamic,
                spec=spec, cfg=self.cfg, backend=self._backend,
                statics=statics, head_params=head_params,
                noise_step=jnp.int32(noise_step),
                generation=self.state.generation,
            )
        else:
            out = read_spec_products(
                self.state.surfaces.sae, self.state.counts, t, dynamic,
                spec=spec, cfg=self.cfg, backend=self._backend,
                statics=statics, head_params=head_params,
            )
        return dict(out)

    def read_many(
        self,
        specs: Sequence[spec_mod.ReadoutSpec],
        t_now: float = 0.0,
        noise_step: int = 0,
    ) -> Dict[spec_mod.ReadoutSpec, Dict[str, jax.Array]]:
        """Serve several ``ReadoutSpec``s against the *same* pool state
        at ``t_now`` — the multi-spec step primitive behind QoS
        streaming, where sensors in one deadline step may carry
        different per-tier specs.

        Duplicate specs are deduped (order-preserving) so N sensors
        sharing a spec cost exactly one fused dispatch.  Specs that
        share a **stage-0 sub-spec** (tiers differing only in heads, or
        a head-bearing tier next to its plain-surface tier) share one
        stage-0 surface dispatch: the group's stage-0 plan is read once,
        and each member's heads dispatch over those arrays
        (``read_head_products`` single-device, ``_ShardPlan.head_reader``
        sharded).  Head outputs are bitwise the member's own fused
        ``read`` — both trace the same barriered ``apply_heads`` body
        over the same stage-0 bits — so sharing never shows in the
        digests.  Singleton groups run the identical compiled program a
        plain ``read`` runs.  Dispatches stay async — the caller syncs
        all specs' products with one ``jax.block_until_ready`` (the
        streaming pipeline's single host sync per deadline).
        """
        uniq = list(dict.fromkeys(specs))
        with span("serve.read", specs=len(uniq)):
            groups: Dict[spec_mod.ReadoutSpec,
                         List[spec_mod.ReadoutSpec]] = {}
            for sp in uniq:
                self._check_spec(sp)
                groups.setdefault(self._compiled(sp).stage0, []).append(sp)
            out: Dict[spec_mod.ReadoutSpec, Dict[str, jax.Array]] = {}
            for stage0, members in groups.items():
                if len(members) == 1:
                    out[members[0]] = self.read(members[0], t_now,
                                                noise_step=noise_step)
                    continue
                base = self.read(stage0, t_now,   # one shared stage-0 dispatch
                                 noise_step=noise_step)
                for sp in members:
                    compiled = self._compiled(sp)
                    if not compiled.has_heads:    # sp IS the stage-0 spec
                        out[sp] = dict(base)
                        continue
                    head_params = self._resolved(sp)[2]
                    inputs = {n: base[n] for n in compiled.stage0.names}
                    if self._plan:
                        heads_out = self._plan.head_reader(compiled)(
                            inputs, head_params
                        )
                    else:
                        heads_out = read_head_products(
                            inputs, head_params, compiled=compiled,
                            cfg=self.cfg)
                    merged = {**base, **heads_out}
                    out[sp] = {n: merged[n] for n in sp.names}
            return {sp: out[sp] for sp in uniq}

    def serve_step(
        self,
        items: Sequence[IngestItem],
        spec: spec_mod.ReadoutSpec = spec_mod.SURFACE_SPEC,
        t_now: float = 0.0,
        noise_step: int = 0,
    ) -> Dict[str, jax.Array]:
        """Fused scatter + spec read: ingest ``items`` and serve every
        product of ``spec`` at ``t_now`` (the body behind
        ``SensorSession.push_and_read``; an empty ``items`` list is a
        pure cached read).

        The spec's first surface product rides the **dirty-tile cache**:
        consecutive steps under one cache epoch — same ``t_now``, same
        surface product — re-read only the tiles this call's chunks
        (plus any interleaved plain pushes) touched; every clean tile
        comes from the cache filled by the previous step.  When the
        epoch moves (``t_now`` changed, a different surface product took
        the cache over, cold cache) or more than ``max_dirty_tiles``
        tiles are dirty, the step refills the cache with one dense pass
        — the *identical* compiled program a plain ``read`` runs, so
        fused and plain readouts are bit-identical (see
        ``ops.ts_fused_dirty``).  Non-surface products (and any second
        surface product) always read dense, post-scatter.

        On a sharded engine the scatter+refresh runs per shard under
        ``shard_map`` with donated state: the dirty mask, cache, and
        incremental-vs-dense choice are all shard-local (no collectives,
        no host sync).
        """
        self._check_spec(spec)
        dynamic, _, _ = self._resolved(spec)
        surface_products = spec.surface_products()
        if (not surface_products or self._compiled(spec).has_heads
                or fidelity_mod.spec_fidelity_mode(spec) != "ideal"):
            # nothing cacheable (no surface product), a head-bearing
            # spec (heads need every input dense and current, so the
            # single-surface tile cache buys nothing), or an
            # analog-fidelity spec (the cache holds *digital* tiles —
            # an analog read must go through the cell physics every
            # time): plain scatter, then the same fused staged read a
            # plain ``read`` runs
            self._ingest_items(items)
            return self.read(spec, t_now, noise_step=noise_step)

        slot_ids, chunks, _ = self._collect(items)
        name0, prod0 = surface_products[0]
        params0 = dynamic[name0]
        refresh_all = (
            self._cache_t is None or float(t_now) != self._cache_t
            or self._cache_surface != (name0, prod0)
        )
        if self._plan:
            if chunks:
                sids, ev = self._plan.route(slot_ids, chunks)
                fn = (self._plan.ingest_read_dense if refresh_all
                      else self._plan.ingest_read_inc)
                self.state, surface = fn(
                    self.state, sids, ev, jnp.float32(t_now), params0
                )
            else:   # pure cached read: refresh only, no scatter
                fn = (self._plan.refresh_dense if refresh_all
                      else self._plan.refresh_inc)
                self.state, surface = fn(
                    self.state, jnp.float32(t_now), params0
                )
        else:
            state = self.state
            if chunks:
                sids, ev = self._stack_chunks(slot_ids, chunks)
                state = ingest_step(state, sids, ev,
                                    polarities=self.cfg.polarities)
            s, p, h, w = state.surfaces.sae.shape
            tp = state.cache.dirty.shape[1]
            bh, bw = self.cfg.block
            surface, tiles, dirty = ops.ts_fused_dirty(
                state.surfaces.sae,
                state.cache.tiles.reshape(s * tp, bh, bw),
                state.cache.dirty.reshape(s * tp),
                jnp.float32(t_now), params0,
                max_dirty=self._max_dirty, block=self.cfg.block,
                backend=self._backend, force_dense=refresh_all,
            )
            self.state = state._replace(cache=ReadoutCache(
                tiles=tiles.reshape(s, tp, bh, bw),
                dirty=dirty.reshape(s, tp),
            ))
        self._cache_t = float(t_now)
        self._cache_surface = (name0, prod0)
        out = {name0: surface}
        if spec not in self._rest_cache:
            rest = {n: p for n, p in spec.products if n != name0}
            self._rest_cache[spec] = (
                spec_mod.ReadoutSpec(**rest) if rest else None
            )
        rest_spec = self._rest_cache[spec]
        if rest_spec is not None:
            out.update(self.read(rest_spec, t_now))
        return {name: out[name] for name in spec.names}

    # -- deprecated method-per-feature shims (one release of grace) ----------
    def _deprecated(self, name: str, use: str) -> None:
        if name in self._warned:
            return
        self._warned.add(name)
        warnings.warn(
            f"TimeSurfaceEngine.{name}() is deprecated; use {use} "
            "(see the serve.spec module docstring)",
            DeprecationWarning, stacklevel=3,
        )

    def acquire(self) -> int:
        """Deprecated: use ``attach()`` (returns a ``SensorSession``)."""
        self._deprecated("acquire", "attach()")
        return self.attach().slot

    def release(self, slot: int) -> None:
        """Deprecated: use ``SensorSession.detach()``."""
        self._deprecated("release", "SensorSession.detach()")
        self._check_acquired(slot)
        session = self._sessions.get(slot)
        if session is not None:
            session.detach()
        else:  # slot acquired before the session era — wipe directly
            self._detach(slot)

    def ingest(
        self,
        items: Sequence[IngestItem],
        with_support: bool = False,
    ):
        """Deprecated: use ``SensorSession.push`` / ``push_labeled`` (or
        the pool-level ``serve_step`` for multi-sensor steps)."""
        self._deprecated(
            "ingest", "SensorSession.push()/push_labeled()"
        )
        if with_support:
            return self._ingest_labeled(items)
        self._ingest_items(items)
        return None

    def ingest_and_read(self, items: Sequence[IngestItem], t_now) -> jax.Array:
        """Deprecated: use ``serve_step(items, SURFACE_SPEC, t_now)`` (or
        ``SensorSession.push_and_read``); this shim returns its
        ``surface`` product, unchanged from the pre-spec behavior."""
        self._deprecated(
            "ingest_and_read", "serve_step(items, spec, t_now)"
        )
        return self.serve_step(items, spec_mod.SURFACE_SPEC, t_now)["surface"]

    def readout(self, t_now) -> jax.Array:
        """Deprecated: use ``read(ReadoutSpec(surface=surface()), t_now)``
        — this shim returns that spec's ``surface`` product, bit-identical
        to the pre-spec readout."""
        self._deprecated("readout", 'read(spec, t_now)["surface"]')
        return self.read(spec_mod.SURFACE_SPEC, t_now)["surface"]

    def readout_with_mask(self, t_now):
        """Deprecated: use ``read`` with a composed
        ``ReadoutSpec(surface=surface(), mask=mask())``."""
        self._deprecated(
            "readout_with_mask",
            "read(ReadoutSpec(surface=surface(), mask=mask()), t_now)",
        )
        out = self.read(_SURFACE_MASK_SPEC, t_now)
        return out["surface"], out["mask"]

    def support_map(self, t_now) -> jax.Array:
        """Deprecated: use ``read`` with a ``stcf()`` product."""
        self._deprecated(
            "support_map", "read(ReadoutSpec(stcf=stcf()), t_now)"
        )
        return self.read(_STCF_SPEC, t_now)["stcf"]

    # -- telemetry ------------------------------------------------------------
    def stats(self) -> dict:
        s, n = self.state, self.capacity
        out = {
            "backend": self._backend,
            "capacity": self.capacity,
            "n_slots_padded": self.n_slots_padded,
            "slot_bucket": self.slot_bucket,
            "live": [i not in self._free for i in range(n)],
            "generation": np.asarray(s.generation)[:n].tolist(),
            "n_events": np.asarray(s.surfaces.n_events)[:n].tolist(),
            "t_last": np.asarray(s.surfaces.t_last)[:n].tolist(),
            "free_slots": list(self._free),
            "dirty_tiles": int(np.asarray(s.cache.dirty).sum()),
            "cache_t": self._cache_t,
            "max_dirty_tiles": self._max_dirty,
            "sessions": sorted(self._sessions),
            "counts_plane": s.counts is not None,
            "compiled_specs": len(self._dynamic_cache),
        }
        if self._plan:
            out["mesh"] = {
                "axes": list(self._plan.axes),
                "n_shards": self._plan.n_shards,
                "n_slots_padded": self.n_slots_padded,
                "slots_per_shard": self._plan.slots_per_shard,
            }
        return out
