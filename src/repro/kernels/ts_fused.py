"""Pallas TPU kernel: AER-chunk scatter for the fused ingest->readout path.

``chunk_scatter_pallas`` folds one padded event chunk into the SAE with a
single row-block pass: the chunk's few-KB coordinate stream rides along to
every block and is combined in with a max — the kernel form of the
paper's in-sensor write.  Padding events carry ``t = -inf`` and never
win; coordinates outside the surface never match the kernel's coordinate
grid, so they are dropped (note jnp's ``.at[].max(mode="drop")`` instead
*wraps* negative indices — ``kernels.ops.chunk_scatter`` masks
out-of-range events to ``-inf`` before either path so the backends
agree); max-combine keeps the result order-independent.  Because max
never rounds, the op is **bit-exact** against the XLA scatter on every
backend and in any surrounding program — the anchor of the fused path's
bit-identity gates.

**Why the decay readout is not in this kernel's epilogue:** bitwise
reproducibility.  The repo's bit-identity guarantees (engine vs offline,
fused vs unfused, incremental vs dense) all come from routing every decay
evaluation through the one jitted ``ops.ts_decay`` entry point as its own
dispatch — two differently-structured XLA programs that compute the same
transcendental expression can legally differ by an ULP (fusion and FMA
contraction are context-dependent; observed on CPU when the decay math is
inlined behind a scatter loop or a gather).  ``ops.ts_fused`` therefore
composes this scatter kernel with the *same compiled readout the unfused
path runs*, making fused == scatter-then-``ts_decay`` true by
construction; the dirty-tile variant (``ops.ts_fused_dirty``) dispatches
the same kernel over the gathered stack of touched tiles.

Polarity is folded into the row coordinate by the caller (``kernels.ops``
passes a ``(P*H, W)`` plane and ``gy = p*H + y``), keeping the kernel
two-dimensional and the row-block grid dense.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEVER_SENTINEL = -jnp.inf


def _scatter_kernel(n_events, sae_ref, ex_ref, ey_ref, et_ref, new_ref):
    bh, wp = new_ref.shape
    y0 = pl.program_id(0) * bh
    rows = y0 + lax.broadcasted_iota(jnp.int32, (bh, wp), 0)
    cols = lax.broadcasted_iota(jnp.int32, (bh, wp), 1)

    def body(k, acc):
        # one event per step, read as scalars out of SMEM
        gx, gy, tv = ex_ref[0, k], ey_ref[0, k], et_ref[0, k]
        hit = (rows == gy) & (cols == gx)
        return jnp.where(hit, jnp.maximum(acc, tv), acc)

    new_ref[...] = lax.fori_loop(0, n_events, body, sae_ref[...])


def chunk_scatter_pallas(
    sae: jax.Array,      # (R, W) float32 last-write times; R = P*H
    ex: jax.Array,       # (N,) int32 event columns
    ey: jax.Array,       # (N,) int32 event rows (polarity folded in)
    et: jax.Array,       # (N,) float32 event times; invalid pre-masked -inf
    block: Tuple[int, int] = (8, 128),
    interpret: bool = False,
) -> jax.Array:
    """Max-combine an event chunk into the SAE, one row-block pass."""
    r, w = sae.shape
    bh, bw = block
    ph, pw = (-r) % bh, (-w) % bw
    sae_p = jnp.pad(sae, ((0, ph), (0, pw)), constant_values=NEVER_SENTINEL)
    rp, wp = sae_p.shape
    n = ex.shape[0]

    tile = pl.BlockSpec((bh, wp), lambda i: (i, 0))
    # the whole (1, N) chunk in SMEM; (1, N) rather than (N,) so a vmapped
    # call's (1, 1, N) block keeps its last two dims equal to the array's
    ev_spec = pl.BlockSpec((1, n), lambda i: (0, 0),
                           memory_space=pltpu.SMEM)
    new = pl.pallas_call(
        functools.partial(_scatter_kernel, n),
        grid=(rp // bh,),
        in_specs=[tile, ev_spec, ev_spec, ev_spec],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rp, wp), jnp.float32),
        interpret=interpret,
    )(sae_p, ex.reshape(1, n), ey.reshape(1, n), et.reshape(1, n))
    return new[:r, :w]
