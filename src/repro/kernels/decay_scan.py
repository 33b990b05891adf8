"""Pallas TPU kernel: chunked input-driven exponential-decay recurrence.

    s_t = a_t * s_{t-1} + x_t        (elementwise over channels)

This is the paper's decay primitive in streaming form — the eDRAM array's
"voltage between reads" is exactly this recurrence on scattered event
energy — and it is also the diagonal inner loop of Mamba-2 SSD decode and
the [37]-style local-memory time surface.

Layout: (B, T, C).  Grid = (B, C/bc, T/bt) with T innermost (sequential);
the running state lives in a VMEM scratch carried across the T steps of
the grid.  Within a chunk the recurrence is evaluated with a log2(bt)-step
associative scan (numerically stable — no divisions by decaying
cumulative products).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _combine(carry_a, carry_x, a, x):
    """Compose decay segments: (a2,x2) o (a1,x1) = (a1*a2, a2*x1 + x2)."""
    return carry_a * a, carry_x * a + x


def _decay_kernel(bt, a_ref, x_ref, out_ref, final_ref, s_ref):
    t_idx = pl.program_id(2)

    @pl.when(t_idx == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    a = a_ref[0]          # (bt, bc)
    x = x_ref[0]

    # inclusive Hillis-Steele scan along the chunk (axis 0): shifted
    # static slices, which Mosaic lowers (lax.associative_scan's strided
    # slices it does not)
    aa, xx = a, x
    d = 1
    while d < bt:
        a_prev = jnp.concatenate([jnp.ones_like(aa[:d]), aa[:-d]], axis=0)
        x_prev = jnp.concatenate([jnp.zeros_like(xx[:d]), xx[:-d]], axis=0)
        aa, xx = _combine(a_prev, x_prev, aa, xx)
        d *= 2
    s0 = s_ref[...]       # (1, bc) running state entering this chunk
    states = aa * s0 + xx                 # (bt, bc)
    out_ref[0] = states.astype(out_ref.dtype)
    s_ref[...] = states[-1:].astype(s_ref.dtype)

    @pl.when(t_idx == pl.num_programs(2) - 1)
    def _fin():
        final_ref[0] = states[-1:].astype(final_ref.dtype)


def decay_scan_pallas(
    a: jax.Array,     # (B, T, C) decay factors in (0, 1]
    x: jax.Array,     # (B, T, C) inputs
    s0: jax.Array | None = None,   # (B, C) initial state (default zeros)
    block: Tuple[int, int] = (128, 128),   # (bt, bc)
    interpret: bool = False,
):
    """Returns (states (B, T, C), final_state (B, C))."""
    b, t, c = a.shape
    bt, bc = block
    pt, pc = (-t) % bt, (-c) % bc
    # pad T with identity steps (a=1, x=0); pad C arbitrarily (sliced off)
    a_p = jnp.pad(a.astype(jnp.float32), ((0, 0), (0, pt), (0, pc)),
                  constant_values=1.0)
    x_p = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pt), (0, pc)))
    if s0 is not None:
        # fold s0 into a leading identity-decay step: s_0 enters as x at t=-1
        a_p = jnp.concatenate(
            [jnp.ones((b, bt, c + pc), jnp.float32), a_p], axis=1
        )
        s0_p = jnp.pad(s0.astype(jnp.float32), ((0, 0), (0, pc)))
        x_lead = jnp.zeros((b, bt, c + pc), jnp.float32).at[:, -1].set(s0_p)
        x_p = jnp.concatenate([x_lead, x_p], axis=1)
    tp = a_p.shape[1]
    grid = (b, (c + pc) // bc, tp // bt)

    blk = pl.BlockSpec((1, bt, bc), lambda bi, ci, ti: (bi, ti, ci))
    out, final = pl.pallas_call(
        functools.partial(_decay_kernel, bt),
        grid=grid,
        in_specs=[blk, blk],
        # final state as (B, 1, C): a (1, 1, bc) block keeps its last two
        # dims (full 1, lane-aligned bc) within the TPU's block rule
        out_specs=[blk, pl.BlockSpec((1, 1, bc),
                                     lambda bi, ci, ti: (bi, 0, ci))],
        out_shape=[
            jax.ShapeDtypeStruct((b, tp, c + pc), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, c + pc), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bc), jnp.float32)],
        interpret=interpret,
    )(a_p, x_p)
    lead = bt if s0 is not None else 0
    return out[:, lead : lead + t, :c], final[:, 0, :c]
