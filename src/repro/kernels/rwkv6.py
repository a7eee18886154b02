"""Pallas TPU kernel for the RWKV6 (wkv6) chunked recurrence.

TPU adaptation of the CUDA wkv6 kernel (which uses warp shuffles over the
head dim): grid = (B, H, n_chunks) with the chunk axis sequential
("arbitrary"); the (N, N) fp32 state lives in VMEM scratch across chunk
steps, intra-chunk work is (C, N) x (N, C) matmuls on the MXU, and the
decay factorization matches models/rwkv.py::wkv6_chunked exactly:

    y = (r * exp(la_prev)) @ S + tril_strict((r e^{la_prev}) (k e^{-la})^T) V
        + (sum_n r u k) * v
    S' = diag(e^{la_C}) S + (k e^{la_C - la})^T V

Chunk C=64, N=64: state 16 KiB + 4 chunk tensors 64 KiB — trivially VMEM
resident; the kernel is compute-bound on the (C,C)x(C,N) matmuls.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, st_out_ref, state_scr,
                 *, chunk, n_chunks):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[0, 0].astype(jnp.float32)  # (C, N)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)  # log-decay, negative
    u = u_ref[0].astype(jnp.float32)  # (N,)

    la = jnp.cumsum(w, axis=0)  # (C, N) inclusive
    la_prev = la - w
    la_end = la[-1:]  # (1, N)

    q_t = r * jnp.exp(la_prev)
    k_t = k * jnp.exp(-la)
    scores = jax.lax.dot_general(
        q_t, k_t, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (C, C)
    rows = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(cols < rows, scores, 0.0)  # strictly lower
    y_intra = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    diag_c = jnp.sum(r * u[None] * k, axis=-1, keepdims=True)  # (C, 1)
    y_diag = diag_c * v
    state = state_scr[...]
    y_inter = jax.lax.dot_general(
        q_t, state, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[0, 0] = (y_intra + y_diag + y_inter).astype(o_ref.dtype)

    k_dec = k * jnp.exp(la_end - la)  # (C, N)
    state_scr[...] = jnp.exp(la_end[0])[:, None] * state + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        st_out_ref[0, 0] = state_scr[...]


def wkv6(
    r: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    w_log: jnp.ndarray,
    u: jnp.ndarray,
    *,
    chunk: int = 64,
    interpret: bool = False,
):
    """r,k,v,w_log: (B, H, S, N); u: (H, N).
    Returns (y (B,H,S,N), final state (B,H,N,N))."""
    B, H, S, N = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk

    kernel = functools.partial(_wkv6_kernel, chunk=chunk, n_chunks=n_chunks)
    y, state = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, N), lambda b, h, ic: (h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, N, N), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, N), r.dtype),
            jax.ShapeDtypeStruct((B, H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, w_log, u)
    return y, state
