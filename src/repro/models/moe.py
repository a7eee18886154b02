"""Mixture-of-Experts layers.

Four execution paths, one parameterization:

  * ``moe_forward_dropless`` — exact top-k MoE for serving (DeepSeekMoE
    too: shared experts, unrenormalised weights). The path follows the
    static token count: with no more routed (token, expert) pairs than
    experts (a decode step), only the routed experts' weights are gathered
    and computed; otherwise tokens are sorted by expert into grouped
    matmuls. No token is dropped on either path.

  * ``moe_forward_grouped`` — exact dropless top-k MoE: sort tokens by expert
    and run grouped matmuls via ``jax.lax.ragged_dot``. Single-device
    semantics; serves as the numerical oracle for the other two paths.
  * ``moe_forward_dense`` — GShard-style capacity-factor dispatch with
    one-hot einsums. Fully auto-partitioned by pjit (no shard_map); robust
    baseline, but dispatch FLOPs scale with group_size * E * capacity (this
    is the classic GShard overhead — measured in the roofline table, and the
    motivation for the EP path).
  * ``moe_forward_ep`` — expert parallelism: experts sharded over the
    ``ep_axis`` mesh axis; tokens routed to their expert's shard with
    ``all_to_all`` inside ``shard_map``; local grouped matmul via ragged_dot
    (TPU Megablox analogue). Capacity-based (static shapes, TPU-friendly).

All paths return ``(y, aux_loss)`` where aux_loss is the standard
load-balancing loss E * sum_e(f_e * p_e).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import Params, dense_init, dtype_of, mlp_forward, mlp_init, split_keys


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def moe_init(cfg, key) -> Params:
    e = cfg.moe
    d = cfg.d_model
    dt = dtype_of(cfg.param_dtype)
    ks = split_keys(key, 4)
    p: Params = {
        "router": dense_init(ks[0], (d, e.num_experts), jnp.float32, scale=0.02),
        "w_up": dense_init(ks[2], (e.num_experts, d, e.d_ff_expert), dt),
        "w_down": dense_init(ks[3], (e.num_experts, e.d_ff_expert, d), dt),
    }
    if cfg.mlp_act == "swiglu":
        p["w_gate"] = dense_init(ks[1], (e.num_experts, d, e.d_ff_expert), dt)
    if e.num_shared_experts:
        p["shared"] = mlp_init(cfg, jax.random.fold_in(key, 5), d,
                               e.num_shared_experts * e.d_ff_expert)
    return p


def _routing(cfg, p: Params, x2d: jnp.ndarray):
    """x2d: (T, D) -> (probs (T,E) f32, topk_w (T,K), topk_idx (T,K), aux)."""
    e = cfg.moe
    logits = x2d.astype(jnp.float32) @ p["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, e.experts_per_token)
    if e.norm_topk_prob:
        topk_w = topk_w / jnp.maximum(jnp.sum(topk_w, axis=-1, keepdims=True), 1e-9)
    # load-balancing aux loss
    T = x2d.shape[0]
    onehot = jax.nn.one_hot(topk_idx, e.num_experts, dtype=jnp.float32)  # (T,K,E)
    f = jnp.sum(onehot, axis=(0, 1)) / (T * e.experts_per_token)  # fraction routed
    pbar = jnp.mean(probs, axis=0)
    aux = e.num_experts * jnp.sum(f * pbar) * e.aux_loss_weight
    return probs, topk_w, topk_idx, aux


def _expert_ffn(cfg, p: Params, h: jnp.ndarray, group_sizes: jnp.ndarray):
    """Grouped FFN via ragged_dot. h: (M, D) sorted by expert; returns (M, D)."""
    if cfg.mlp_act == "swiglu":
        g = jax.lax.ragged_dot(h, p["w_gate"], group_sizes)
        u = jax.lax.ragged_dot(h, p["w_up"], group_sizes)
        a = jax.nn.silu(g) * u
    else:
        u = jax.lax.ragged_dot(h, p["w_up"], group_sizes)
        a = jax.nn.relu(u) ** 2 if cfg.mlp_act == "squared_relu" else jax.nn.gelu(u)
    return jax.lax.ragged_dot(a, p["w_down"], group_sizes)


# ---------------------------------------------------------------------------
# Exact grouped path (oracle)
# ---------------------------------------------------------------------------


def moe_forward_grouped(cfg, p: Params, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dropless: every routed (token, expert) pair is computed."""
    B, S, D = x.shape
    x2d = x.reshape(B * S, D)
    _, topk_w, topk_idx, aux = _routing(cfg, p, x2d)
    y2d = _routed_grouped(cfg, p, x2d, topk_w, topk_idx, None)
    return y2d.reshape(B, S, D).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Dropless serving path (routed gather at decode, grouped otherwise)
# ---------------------------------------------------------------------------


EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _routed_gather(cfg, p: Params, x2d, topk_w, topk_idx, layer):
    """Only the routed experts' weights are read: a loop over the routed
    (token, expert) pairs slices each pair's expert out of the weights in
    place (a ``gather`` would have XLA copy the whole stack first)."""
    T, K = topk_idx.shape

    def expert(name, e):
        w = p[name]
        if layer is None:
            return jax.lax.dynamic_index_in_dim(w, e, keepdims=False)
        start = (layer, e) + (0,) * (w.ndim - 2)
        return jax.lax.dynamic_slice(w, start, (1, 1) + w.shape[2:]).reshape(w.shape[2:])

    def pair(y, tew):
        t, e, g = tew
        xt = jax.lax.dynamic_index_in_dim(x2d, t)  # (1, D)
        if cfg.mlp_act == "swiglu":
            a = jax.nn.silu(xt @ expert("w_gate", e)) * (xt @ expert("w_up", e))
        else:
            u = xt @ expert("w_up", e)
            a = jax.nn.relu(u) ** 2 if cfg.mlp_act == "squared_relu" else jax.nn.gelu(u)
        o = jnp.dot(a, expert("w_down", e), preferred_element_type=jnp.float32)
        yt = jax.lax.dynamic_index_in_dim(y, t) + g * o
        return jax.lax.dynamic_update_index_in_dim(y, yt, t, 0), None

    pairs = (jnp.repeat(jnp.arange(T), K), topk_idx.reshape(-1), topk_w.reshape(-1))
    y, _ = jax.lax.scan(pair, jnp.zeros((T, x2d.shape[1]), jnp.float32), pairs)
    return y


def _routed_grouped(cfg, p: Params, x2d, topk_w, topk_idx, layer):
    """Every routed pair, sorted by expert into grouped matmuls."""
    e = cfg.moe
    if layer is not None:
        p = {**p, **{n: jax.lax.dynamic_index_in_dim(p[n], layer, keepdims=False)
                     for n in EXPERT_WEIGHTS if n in p}}
    T, K = topk_idx.shape
    flat_expert = topk_idx.reshape(-1)
    order = jnp.argsort(flat_expert)  # stable
    sorted_tokens = jnp.repeat(jnp.arange(T), K)[order]
    group_sizes = jnp.bincount(flat_expert, length=e.num_experts).astype(jnp.int32)
    out = _expert_ffn(cfg, p, x2d[sorted_tokens], group_sizes)
    contrib = out.astype(jnp.float32) * topk_w.reshape(-1)[order][:, None]
    return jnp.zeros((T, x2d.shape[1]), jnp.float32).at[sorted_tokens].add(contrib)


def moe_forward_dropless(cfg, p: Params, x: jnp.ndarray, *, layer=None):
    """Exact top-k MoE plus shared experts. Returns (y, aux, counts): counts
    (E,) int32 is the routed (token, expert) pairs per expert.

    With ``layer``, the expert weights in ``p`` are a whole stack
    (L_moe, E, ...) and this is its MoE layer ``layer``: a layer loop then
    passes the stack whole, and the routed gather reads the routed experts
    straight from it instead of a copy of the layer's 64."""
    e = cfg.moe
    B, S, D = x.shape
    T = B * S
    x2d = x.reshape(T, D)
    with jax.named_scope("moe.route"):
        _, topk_w, topk_idx, aux = _routing(cfg, p, x2d)
        counts = jnp.bincount(topk_idx.reshape(-1), length=e.num_experts).astype(jnp.int32)
    with jax.named_scope("moe.experts"):
        if T * e.experts_per_token <= e.num_experts:
            y2d = _routed_gather(cfg, p, x2d, topk_w, topk_idx, layer)
        else:
            y2d = _routed_grouped(cfg, p, x2d, topk_w, topk_idx, layer)
    if e.num_shared_experts:
        with jax.named_scope("moe.shared"):
            y2d = y2d + mlp_forward(cfg, p["shared"], x2d)
    return y2d.reshape(B, S, D).astype(x.dtype), aux, counts


# ---------------------------------------------------------------------------
# GShard dense dispatch (capacity-based, pjit-auto-partitioned)
# ---------------------------------------------------------------------------


def moe_forward_dense(
    cfg, p: Params, x: jnp.ndarray, *, capacity: Optional[int] = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    e = cfg.moe
    B, S, D = x.shape
    K = e.experts_per_token
    G = B  # one dispatch group per batch row (keeps dispatch local under DP)
    Tg = S
    x3d = x.reshape(G, Tg, D)
    x2d = x.reshape(G * Tg, D)
    _, topk_w, topk_idx, aux = _routing(cfg, p, x2d)
    topk_w = topk_w.reshape(G, Tg, K)
    topk_idx = topk_idx.reshape(G, Tg, K)

    if capacity is None:
        capacity = int(Tg * K / e.num_experts * e.capacity_factor) + 1
    C = capacity

    # position of each (token, k) within its expert queue, per group
    onehot = jax.nn.one_hot(topk_idx, e.num_experts, dtype=jnp.int32)  # (G,Tg,K,E)
    flat = onehot.reshape(G, Tg * K, e.num_experts)
    pos = jnp.cumsum(flat, axis=1) - flat  # (G, Tg*K, E) position in queue
    pos = jnp.sum(pos * flat, axis=-1).reshape(G, Tg, K)  # (G,Tg,K)
    keep = pos < C

    # dispatch/combine tensors: (G, Tg, E, C)
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, C), C + 1, dtype=jnp.float32)[..., :C]
    disp = jnp.einsum("gtke,gtkc->gtec", onehot.astype(jnp.float32), pos_oh)
    comb = jnp.einsum("gtke,gtkc,gtk->gtec", onehot.astype(jnp.float32), pos_oh, topk_w)

    xe = jnp.einsum("gtec,gtd->gecd", disp.astype(x.dtype), x3d)  # (G,E,C,D)
    if cfg.mlp_act == "swiglu":
        gg = jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])
        uu = jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
        a = jax.nn.silu(gg) * uu
    else:
        uu = jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
        a = jax.nn.relu(uu) ** 2 if cfg.mlp_act == "squared_relu" else jax.nn.gelu(uu)
    ye = jnp.einsum("gecf,efd->gecd", a, p["w_down"])  # (G,E,C,D)
    y = jnp.einsum("gtec,gecd->gtd", comb.astype(ye.dtype), ye)
    return y.reshape(B, S, D).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Expert-parallel path (shard_map + all_to_all + ragged_dot)
# ---------------------------------------------------------------------------


def moe_forward_ep(
    cfg,
    p: Params,
    x: jnp.ndarray,
    *,
    mesh,
    ep_axis: str = "model",
    dp_axes: Tuple[str, ...] = ("data",),
    capacity: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Experts sharded over ``ep_axis``; tokens sharded over ``dp_axes``.

    Per-device: route local tokens, bucket them by destination expert-shard
    (capacity-limited), all_to_all across ep_axis, run local experts via
    ragged_dot, all_to_all back, combine.
    """
    e = cfg.moe
    B, S, D = x.shape
    n_ep = 1
    for ax, sz in zip(mesh.axis_names, mesh.devices.shape):
        if ax == ep_axis:
            n_ep = sz
    assert e.num_experts % n_ep == 0, (e.num_experts, n_ep)
    e_loc = e.num_experts // n_ep

    K = e.experts_per_token

    E = e.num_experts
    a2a_dt = {"auto": None, "bfloat16": jnp.bfloat16,
              "float8_e4m3fn": jnp.float8_e4m3fn,
              "float32": jnp.float32}[e.a2a_dtype]

    def local_fn(p_loc, x_loc):
        """x_loc: (B_loc, S, D); expert weights p_loc sharded: (e_loc, D, F).

        Per-EXPERT capacity buckets (not per-shard): the expert compute is a
        batched matmul einsum('ecd,edf->ecf') — static shapes, MXU-friendly
        (Megablox-equivalent), and FLOP-exact in the HLO (the CPU lowering of
        ragged_dot dense-expands over experts, inflating accounting 24x).

        Perf knobs: dispatch payloads cross the ICI in ``a2a_dtype`` (fp8
        halves bytes, DeepSeek-V3-style); ``dispatch_chunks`` splits the
        token stream to bound the transient buffer footprint.
        """
        Bl, Sl, Dl = x_loc.shape
        T_all = Bl * Sl
        x2d_all = x_loc.reshape(T_all, D)
        n_chunks = max(1, e.dispatch_chunks)
        assert T_all % n_chunks == 0, (T_all, n_chunks)
        ys = []
        aux_out = None
        for ci in range(n_chunks):
            y, aux = _dispatch_block(
                p_loc, x2d_all[ci * (T_all // n_chunks):(ci + 1) * (T_all // n_chunks)]
            )
            ys.append(y)
            aux_out = aux
        y2d = jnp.concatenate(ys, axis=0) if n_chunks > 1 else ys[0]
        return y2d.reshape(Bl, Sl, D).astype(x_loc.dtype), aux_out

    def _dispatch_block(p_loc, x2d):
        T = x2d.shape[0]
        _, topk_w, topk_idx, aux = _routing(cfg, {**p_loc, "router": p_loc["router"]}, x2d)
        aux = jax.lax.pmean(aux, ep_axis)
        for ax in dp_axes:
            aux = jax.lax.pmean(aux, ax)

        if capacity is None:
            cap = int(T * K / E * e.capacity_factor) + 1
        else:
            cap = capacity
        flat_e = topk_idx.reshape(-1)  # (T*K,) global expert ids
        flat_t = jnp.repeat(jnp.arange(T), K)
        flat_w = topk_w.reshape(-1)
        # position within expert via sort-rank (O(M log M), no M*E one-hot)
        M0 = T * K
        order = jnp.argsort(flat_e)  # stable
        sorted_e = flat_e[order]
        idx = jnp.arange(M0)
        first_of_group = jnp.searchsorted(sorted_e, sorted_e, side="left")
        pos_sorted = idx - first_of_group
        pos = jnp.zeros((M0,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
        keep = pos < cap
        slot = jnp.where(keep, flat_e * cap + pos, E * cap)  # overflow -> dropped

        # send buffer: one bucket per (global expert, capacity slot); payload
        # crosses the ICI in a2a_dtype. fp8 uses per-token scales
        # (DeepSeek-V3-style quantized dispatch: +4 bytes/row of scale vs
        # 2x fewer payload bytes).
        dt_wire = a2a_dt if a2a_dt is not None else x2d.dtype
        fp8 = dt_wire == jnp.float8_e4m3fn

        def quant(rows):
            if not fp8:
                return rows.astype(dt_wire), None
            scale = jnp.max(jnp.abs(rows.astype(jnp.float32)), -1, keepdims=True)
            scale = jnp.maximum(scale, 1e-6) / 240.0
            return (rows / scale).astype(a2a_dt), scale[:, 0]

        def dequant(rows, scale, dt):
            if not fp8:
                return rows.astype(dt)
            return (rows.astype(jnp.float32) * scale[:, None]).astype(dt)

        payload, pscale = quant(x2d[flat_t])
        send = jnp.zeros((E * cap + 1, D), dt_wire).at[slot].set(payload)
        send = send[: E * cap].reshape(n_ep, e_loc * cap, D)
        if fp8:
            sscale = jnp.zeros((E * cap + 1,), jnp.float32).at[slot].set(pscale)
            sscale = sscale[: E * cap].reshape(n_ep, e_loc * cap)

        # all_to_all over the EP axis: device p receives every shard's buckets
        # for ITS experts: (n_ep src, e_loc*cap, D)
        recv = jax.lax.all_to_all(send, ep_axis, split_axis=0, concat_axis=0, tiled=True)
        if fp8:
            rscale = jax.lax.all_to_all(sscale, ep_axis, split_axis=0,
                                        concat_axis=0, tiled=True)
            recv = dequant(recv.reshape(-1, D), rscale.reshape(-1), x2d.dtype)
            recv = recv.reshape(n_ep, e_loc * cap, D)
        xe = recv.astype(x2d.dtype).reshape(n_ep, e_loc, cap, D).transpose(1, 0, 2, 3)
        xe = xe.reshape(e_loc, n_ep * cap, D)  # (E_loc, C', D)

        # batched expert FFN on the MXU
        if cfg.mlp_act == "swiglu":
            g = jnp.einsum("ecd,edf->ecf", xe, p_loc["w_gate"])
            uu = jnp.einsum("ecd,edf->ecf", xe, p_loc["w_up"])
            a = jax.nn.silu(g) * uu
        else:
            uu = jnp.einsum("ecd,edf->ecf", xe, p_loc["w_up"])
            a = jax.nn.relu(uu) ** 2 if cfg.mlp_act == "squared_relu" else jax.nn.gelu(uu)
        ye = jnp.einsum("ecf,efd->ecd", a, p_loc["w_down"])  # (E_loc, C', D)

        # route back to the source shards (same quantized payload scheme)
        yq, yscale = quant(ye.reshape(-1, D))
        back = yq.reshape(e_loc, n_ep, cap, D).transpose(1, 0, 2, 3)
        back = back.reshape(n_ep, e_loc * cap, D)
        back = jax.lax.all_to_all(back, ep_axis, split_axis=0, concat_axis=0, tiled=True)
        if fp8:
            bscale = yscale.reshape(e_loc, n_ep, cap).transpose(1, 0, 2)
            bscale = jax.lax.all_to_all(bscale.reshape(n_ep, e_loc * cap), ep_axis,
                                        split_axis=0, concat_axis=0, tiled=True)
            back_rows = dequant(back.reshape(E * cap, D), bscale.reshape(-1),
                                x2d.dtype)
        else:
            back_rows = back.reshape(E * cap, D).astype(x2d.dtype)
        back2d = jnp.concatenate(
            [back_rows, jnp.zeros((1, D), x2d.dtype)], axis=0
        )
        gathered = back2d[slot]  # (T*K, D); dropped slots read the zero row
        contrib = gathered * flat_w[:, None].astype(x2d.dtype)
        y2d = jnp.zeros((T, D), contrib.dtype).at[flat_t].add(contrib)
        return y2d, aux

    # replicate router over ep; shard experts over ep
    pspec_params = {
        "router": P(),
        "w_up": P(ep_axis, None, None),
        "w_down": P(ep_axis, None, None),
    }
    if "w_gate" in p:
        pspec_params["w_gate"] = P(ep_axis, None, None)
    batch_spec = P(dp_axes if len(dp_axes) > 1 else dp_axes[0], None, None)

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(pspec_params, batch_spec),
        out_specs=(batch_spec, P()),
        check_vma=False,
    )
    return fn({k: v for k, v in p.items()}, x)


def moe_forward(cfg, p: Params, x: jnp.ndarray, parallel_ctx=None):
    """Dispatch on cfg.moe.mode (+ availability of a mesh)."""
    mode = cfg.moe.mode
    if mode == "ep" and parallel_ctx is not None and parallel_ctx.mesh is not None:
        return moe_forward_ep(
            cfg,
            p,
            x,
            mesh=parallel_ctx.mesh,
            ep_axis=parallel_ctx.ep_axis,
            dp_axes=parallel_ctx.dp_axes,
        )
    if mode == "ep":
        # no mesh (smoke tests): exact grouped path, same math minus collectives
        return moe_forward_grouped(cfg, p, x)
    return moe_forward_dense(cfg, p, x)
