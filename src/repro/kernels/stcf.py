"""Pallas TPU kernel: STCF support count (dense comparator + patch sum).

Given the ISC surface, computes for every pixel the number of cells in the
surrounding (2r+1)^2 patch whose voltage is above V_tw — the dense form of
the STCF denoiser's support count.  Fusing the decay evaluation, the
comparator, and the patch sum keeps the surface in VMEM for the whole
pipeline: HBM traffic is one float32 stream in, one int32 stream out.

Halo handling: the operand is padded by one full row-block on top/bottom
(zeros = "no support") and by r columns left/right; the kernel receives
three vertically-adjacent row blocks (prev/cur/next) via three input specs
with shifted index maps, so every (2r+1)-row window around the current
block is resident without overlapping BlockSpecs.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEVER_SENTINEL = -jnp.inf


def _support_kernel(r, include_self, fused, prev_ref, cur_ref,
                    next_ref, c_ref, out_ref):
    bh = out_ref.shape[0]
    wpad = prev_ref.shape[1]          # W + 2r
    rows = jnp.concatenate([prev_ref[...], cur_ref[...], next_ref[...]], axis=0)
    if fused:                         # fused: rows are SAE times, not a mask
        a1, tau1, a2, tau2, b, v_tw, t_now = (c_ref[0, i] for i in range(7))
        dt = t_now - rows
        v = a1 * jnp.exp(-dt / tau1) + a2 * jnp.exp(-dt / tau2) + b
        rows = jnp.where(jnp.isfinite(rows), v, 0.0)
        rows = (rows > v_tw).astype(jnp.float32)
    w = wpad - 2 * r
    acc = jnp.zeros((bh, w), jnp.float32)
    for dy in range(-r, r + 1):
        band = rows[bh + dy:2 * bh + dy]
        for dx in range(-r, r + 1):
            if include_self or not (dy == 0 and dx == 0):
                acc = acc + band[:, r + dx:r + dx + w]
    out_ref[...] = acc.astype(out_ref.dtype)


def stcf_support_pallas(
    surface: jax.Array,            # (H, W): bool/float mask, or SAE times if fused
    radius: int = 3,
    include_self: bool = False,
    fused_decay=None,              # None, or (DecayParams-scalars, v_tw, t_now)
    block_h: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Support count per pixel, (H, W) int32."""
    h, w = surface.shape
    r = radius
    bh = block_h
    assert r <= bh, "radius must fit within one row block"
    ph = (-h) % bh

    fused = fused_decay is not None
    if not fused:
        x = surface.astype(jnp.float32)
        fill = 0.0
        consts = jnp.zeros((1, 7), jnp.float32)
    else:
        params, v_tw, t_now = fused_decay
        assert jnp.ndim(params.tau1) == 0, "fused path uses uniform cell params"
        x = surface.astype(jnp.float32)
        fill = NEVER_SENTINEL       # padding cells never fired
        consts = jnp.stack(
            [jnp.float32(v) for v in (params.a1, params.tau1, params.a2,
                                      params.tau2, params.b, v_tw, t_now)]
        ).reshape(1, 7)

    # pad: one full row-block top & bottom; r columns each side; tail to bh.
    x = jnp.pad(x, ((bh, bh + ph), (r, r)), constant_values=fill)
    hp, wp = x.shape                  # (H + ph + 2bh, W + 2r)
    n_blocks = (hp - 2 * bh) // bh

    row = lambda off: pl.BlockSpec((bh, wp), lambda i: (i + off, 0))
    kern = functools.partial(_support_kernel, r, include_self, fused)
    out = pl.pallas_call(
        kern,
        grid=(n_blocks,),
        in_specs=[row(0), row(1), row(2), pl.BlockSpec((1, 7), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bh, wp - 2 * r), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((hp - 2 * bh, wp - 2 * r), jnp.int32),
        interpret=interpret,
    )(x, x, x, consts)
    return out[:h, :w]
