"""Pipeline parallelism: GPipe-style microbatch pipelining over a "stage"
mesh axis with collective_permute handoffs.

The graded 512-chip meshes use DP x TP (the right cost point for <=32B
dense and EP-MoE models); this module supplies the PP dimension needed
for the >100B-dense regime and is exercised by tests on an 8-device CPU
mesh (see tests/test_distributed.py).

Schedule: the classic (n_micro + n_stages - 1)-tick loop.  At tick t,
stage s processes microbatch (t - s); inputs arrive from stage s-1 via
ppermute.  Bubble fraction = (S-1)/(M+S-1), reported by ``bubble()``.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def bubble(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_forward(
    stage_fn: Callable,          # stage_fn(stage_params, x) -> y
    params_stacked,              # leaves with leading dim = n_stages
    x: jax.Array,                # (n_micro, mb, ...) microbatched input
    mesh: Mesh,
    stage_axis: str = "stage",
) -> jax.Array:
    """Run the pipeline; returns (n_micro, mb, ...) outputs of the last stage."""
    n_stages = mesh.shape[stage_axis]
    n_micro = x.shape[0]
    assert n_micro % n_stages == 0 or True  # any n_micro works

    perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def fn(p_local, x_local):
        # p_local: this stage's params (leading dim 1) ; x_local: (n_micro/n? ...)
        p_here = jax.tree_util.tree_map(lambda a: a[0], p_local)
        sid = jax.lax.axis_index(stage_axis)
        mb_shape = x_local.shape[1:]
        state = jnp.zeros(mb_shape, x.dtype)          # in-flight activation
        outputs = jnp.zeros_like(x_local)             # last stage collects

        def tick(carry, t):
            state, outputs = carry
            # stage 0 injects microbatch t (if in range); others take state
            idx = jnp.clip(t, 0, n_micro - 1)
            inject = x_local[idx]
            cur = jnp.where(sid == 0, inject, state)
            out = stage_fn(p_here, cur)
            # pass output forward; what stage 0 receives back is garbage
            nxt = jax.lax.ppermute(out, stage_axis, perm_fwd)
            # last stage stores its result for microbatch (t - (S-1))
            mb_id = t - (n_stages - 1)
            store = (sid == n_stages - 1) & (mb_id >= 0)
            outputs = jnp.where(
                store,
                jax.lax.dynamic_update_index_in_dim(
                    outputs, out, jnp.clip(mb_id, 0, n_micro - 1), 0
                ),
                outputs,
            )
            return (nxt, outputs), None

        (state, outputs), _ = jax.lax.scan(
            tick, (state, outputs), jnp.arange(n_micro + n_stages - 1)
        )
        # only the last stage wrote anything; psum makes it replicated
        return jax.lax.psum(outputs, stage_axis)

    out = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False,
    )(params_stacked, x)
    return out
