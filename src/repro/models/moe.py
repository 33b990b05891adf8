"""Mixture-of-Experts with expert parallelism (shard_map + capacity packing).

Sharding strategy (DESIGN.md §6):

  * tokens are sharded over the data axes and **replicated over "model"**
    (standard Megatron activation layout), experts live on "model".  Every
    model shard routes identically (same x, same router), selects the
    tokens destined to *its* experts with static-capacity packing, and the
    per-shard partial outputs are combined with one psum over "model" —
    the same collective a dense TP MLP needs.  No all_to_all is required
    because the expert axis is orthogonal to the token sharding.

  * E >= model_size  ("ep"): experts sharded over "model"
        weights (E, d, f) -> P("model", fsdp?, None), E_loc = E/M
  * E <  model_size  ("tp"): every expert's FFN is sharded over "model"
        weights (E, d, f) -> P(None, fsdp?, "model"), partial-f compute

Both paths produce identical math to the dense fallback (up to capacity
drops), which is what the single-device tests check.

Aux losses: switch-style load-balance loss and router z-loss, pmean'd over
the data axes.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.module import ParamDef


def moe_defs(cfg: ModelConfig) -> dict:
    d, e, fe = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    defs = {
        "router": ParamDef((d, e), ("embed", "experts_router")),
        "we_gate": ParamDef((e, d, fe), ("experts", "embed", "expert_mlp")),
        "we_up": ParamDef((e, d, fe), ("experts", "embed", "expert_mlp")),
        "we_down": ParamDef((e, fe, d), ("experts", "expert_mlp", "embed")),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * fe
        defs["shared"] = {
            "wi_gate": ParamDef((d, fs), ("embed", "mlp")),
            "wi_up": ParamDef((d, fs), ("embed", "mlp")),
            "wo": ParamDef((fs, d), ("mlp", "embed")),
        }
    return defs


def route(
    router_w: jax.Array, x_flat: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Top-k routing.  Returns (top_idx (T,k), top_w (T,k), aux dict)."""
    logits = jnp.einsum(
        "td,de->te", x_flat.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    # switch-style load balance: E * sum_e f_e * p_e
    e = cfg.n_experts
    counts = jnp.zeros((e,), jnp.float32).at[top_idx.reshape(-1)].add(1.0)
    frac = counts / jnp.maximum(counts.sum(), 1.0)
    mean_prob = probs.mean(axis=0)
    lb_loss = e * jnp.sum(frac * mean_prob)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return top_idx, top_w, {"lb_loss": lb_loss, "z_loss": z_loss}


def _expert_ffn(xe, wg, wu, wd):
    dt = xe.dtype
    h = jax.nn.gelu(
        jnp.einsum("td,df->tf", xe, wg.astype(dt)).astype(jnp.float32)
    ).astype(dt) * jnp.einsum("td,df->tf", xe, wu.astype(dt))
    return jnp.einsum("tf,fd->td", h, wd.astype(dt))


def _pack_compute_all(x_flat, top_idx, top_w, expert_ids, wg, wu, wd, cap):
    """Capacity-packed compute for a set of experts at once (vectorized).

    x_flat (T, d); expert_ids (E_loc,) global ids; wg/wu (E_loc, d, f);
    wd (E_loc, f, d).  Returns the weighted scatter-add combine (T, d) f32.
    """
    t, d = x_flat.shape
    e_loc = expert_ids.shape[0]
    # per-token gate weight for each local expert: (T, E_loc)
    gate = jnp.where(
        top_idx[:, None, :] == expert_ids[None, :, None],
        top_w[:, None, :], 0.0,
    ).sum(-1)
    sel = gate > 0
    order = jnp.argsort(~sel, axis=0, stable=True)    # selected tokens first
    idx = order[:cap].T                               # (E_loc, cap)
    gsel = jnp.take_along_axis(gate, idx.T, axis=0).T  # (E_loc, cap)
    dt = x_flat.dtype
    xe = x_flat[idx.reshape(-1)].reshape(e_loc, cap, d)
    h = jax.nn.gelu(
        jnp.einsum("ecd,edf->ecf", xe, wg.astype(dt)).astype(jnp.float32)
    ).astype(dt) * jnp.einsum("ecd,edf->ecf", xe, wu.astype(dt))
    y = jnp.einsum("ecf,efd->ecd", h, wd.astype(dt)).astype(jnp.float32)
    contrib = (y * gsel[..., None]).reshape(e_loc * cap, d)
    out = jnp.zeros((t, d), jnp.float32)
    return out.at[idx.reshape(-1)].add(contrib)


def capacity(n_tokens: int, cfg: ModelConfig, n_parts: int) -> int:
    """Static per-expert capacity, clamped to the local token count (tiny
    decode shards can have fewer tokens than the nominal capacity)."""
    c = math.ceil(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return int(min(n_tokens, max(8, c)))


# ----------------------------------------------------------------------------
# Dense fallback (single device; exact, no capacity drops) — test oracle
# ----------------------------------------------------------------------------

def moe_dense(params, x: jax.Array, cfg: ModelConfig):
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    top_idx, top_w, aux = route(params["router"], xf, cfg)
    y = jnp.zeros_like(xf, dtype=jnp.float32)
    for e in range(cfg.n_experts):
        w_e = jnp.where(top_idx == e, top_w, 0.0).sum(-1)  # (T,)
        ye = _expert_ffn(xf, params["we_gate"][e], params["we_up"][e],
                         params["we_down"][e])
        y = y + ye.astype(jnp.float32) * w_e[:, None]
    if cfg.n_shared_experts:
        from repro.models.layers import mlp_block
        y = y + mlp_block(params["shared"], x).reshape(-1, d).astype(jnp.float32)
    return y.reshape(b, s, d).astype(x.dtype), aux


# ----------------------------------------------------------------------------
# Sharded path
# ----------------------------------------------------------------------------

def moe_strategy(cfg: ModelConfig, model_size: int) -> str:
    if cfg.n_experts % model_size == 0:
        return "ep"
    if model_size % cfg.n_experts == 0 and cfg.d_ff_expert % model_size == 0:
        return "tp"
    raise ValueError(
        f"experts={cfg.n_experts} not compatible with model axis {model_size}"
    )


def expert_weight_specs(cfg: ModelConfig, model_size: int, fsdp_axis=None):
    """PartitionSpecs for (we_gate/we_up (E,d,f), we_down (E,f,d))."""
    if moe_strategy(cfg, model_size) == "ep":
        return P("model", fsdp_axis, None), P("model", None, fsdp_axis)
    return P(None, fsdp_axis, "model"), P(None, "model", fsdp_axis)


def moe_sharded(
    params, x: jax.Array, cfg: ModelConfig, mesh,
    data_axes: Tuple[str, ...] = ("data",),
    fsdp_axis: Optional[str] = None,
):
    """shard_map MoE: x (B, S, D) sharded over data_axes on dim 0."""
    m_size = mesh.shape["model"]
    strat = moe_strategy(cfg, m_size)
    e_loc = cfg.n_experts // m_size if strat == "ep" else cfg.n_experts
    up_spec, down_spec = expert_weight_specs(cfg, m_size, fsdp_axis)
    x_spec = P(data_axes, None, None)

    b, s, d = x.shape
    n_shards = 1
    for a in data_axes:
        n_shards *= mesh.shape[a]
    t_loc = (b // n_shards) * s if b >= n_shards else b * s
    cap = capacity(t_loc, cfg, m_size)

    def fn(router_w, wg, wu, wd, x_loc):
        # barrier at the manual level: stops XLA:CPU hoisting the bf16->f32
        # dot-input converts out of the layer loop as full-stack f32 copies
        wg, wu, wd = jax.lax.optimization_barrier((wg, wu, wd))
        xf = x_loc.reshape(-1, d)
        if fsdp_axis is not None:
            wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_axis, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_axis, axis=2, tiled=True)
        top_idx, top_w, aux = route(router_w, xf, cfg)
        mi = jax.lax.axis_index("model")
        if strat == "ep":
            expert_ids = mi * e_loc + jnp.arange(e_loc)
        else:  # "tp": every expert present (f-sharded); psum joins partials
            expert_ids = jnp.arange(cfg.n_experts)
        y = _pack_compute_all(xf, top_idx, top_w, expert_ids, wg, wu, wd, cap)
        y = jax.lax.psum(y, "model")
        aux = jax.tree_util.tree_map(
            lambda v: jax.lax.pmean(v, data_axes), aux
        )
        return y.reshape(x_loc.shape).astype(x_loc.dtype), aux

    shard = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, None), up_spec, up_spec, down_spec, x_spec),
        out_specs=(x_spec, {"lb_loss": P(), "z_loss": P()}),
        check_vma=False,
    )
    y, aux = shard(params["router"], params["we_gate"], params["we_up"],
                   params["we_down"], x)
    if cfg.n_shared_experts:
        from repro.models.layers import mlp_block
        y = y + mlp_block(params["shared"], x)
    return y, aux
