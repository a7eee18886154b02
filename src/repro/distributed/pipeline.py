"""Pipeline parallelism: GPipe-style microbatched stage execution via
shard_map + collective_permute.

The layer stack (L, ...) is split into ``n_stages`` contiguous stages along
a mesh axis; microbatches stream through: at global step t, stage s runs
microbatch t-s (bubble = n_stages-1 idle slots at each end — the standard
GPipe trade-off; 1F1B would halve activation memory but complicates the
schedule; noted as future work in DESIGN.md).

Backward comes for free through autodiff: the transpose of ppermute is the
reverse ppermute, so jax.grad of ``pipeline_apply`` yields the GPipe
backward schedule automatically.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def pipeline_apply(
    layer_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stacked_params: Any,
    x: jnp.ndarray,
    *,
    mesh,
    axis: str,
    n_microbatches: int,
):
    """Run x through L stacked layers, pipelined over mesh axis ``axis``.

    layer_fn(params_one_layer, h) -> h. stacked_params leaves have leading L
    divisible by the axis size. x: (B, ...) with B divisible by
    n_microbatches. Returns f(x) identical (up to dtype rounding) to the
    sequential loop.
    """
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    assert L % n_stages == 0, (L, n_stages)
    B = x.shape[0]
    assert B % n_microbatches == 0, (B, n_microbatches)
    mb = B // n_microbatches

    # stage-local params: (n_stages, L/n_stages, ...) sharded over axis
    def restack(p):
        return p.reshape((n_stages, L // n_stages) + p.shape[1:])

    sp = jax.tree.map(restack, stacked_params)
    mbs = x.reshape((n_microbatches, mb) + x.shape[1:])

    p_spec = jax.tree.map(lambda _: P(axis), sp)
    fwd = [(i, i + 1) for i in range(n_stages - 1)]

    def stage_body(params_local, mbs_local):
        """Inside shard_map: params_local (1, L/n, ...), mbs replicated."""
        params_local = jax.tree.map(lambda p: p[0], params_local)
        sid = jax.lax.axis_index(axis)
        T = n_microbatches + n_stages - 1
        state = jnp.zeros((mb,) + mbs_local.shape[2:], mbs_local.dtype)
        outs = jnp.zeros_like(mbs_local)

        def apply_stage(h):
            for i in range(L // n_stages):
                p_i = jax.tree.map(lambda p: p[i], params_local)
                h = layer_fn(p_i, h)
            return h

        def step(t, carry):
            state, outs = carry
            # stage 0 ingests microbatch t (clamped; masked out later)
            ingest = jax.lax.dynamic_index_in_dim(
                mbs_local, jnp.minimum(t, n_microbatches - 1), 0, keepdims=False
            )
            h = jnp.where(sid == 0, ingest, state)
            h = apply_stage(h)
            # collect on the last stage when a real microbatch exits
            out_idx = t - (n_stages - 1)
            valid = (sid == n_stages - 1) & (out_idx >= 0)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, h.astype(o.dtype), jnp.maximum(out_idx, 0), 0
                ),
                lambda o: o,
                outs,
            )
            # shift activations to the next stage
            state = jax.lax.ppermute(h, axis, fwd)
            return state, outs

        state, outs = jax.lax.fori_loop(0, T, step, (state, outs))
        # broadcast from the last stage (all other stages hold zeros)
        outs = jax.lax.psum(outs, axis)
        return outs

    fn = jax.shard_map(
        stage_body,
        mesh=mesh,
        in_specs=(p_spec, P()),
        out_specs=P(),
        check_vma=False,
    )
    out = fn(sp, mbs)
    return out.reshape((B,) + x.shape[1:])


def sequential_reference(layer_fn, stacked_params, x):
    """Oracle: plain scan over the layer stack."""
    def body(h, p):
        return layer_fn(p, h), None

    h, _ = jax.lax.scan(body, x, stacked_params)
    return h
