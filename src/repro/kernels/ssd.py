"""Pallas TPU kernel for Mamba2 SSD chunked scan.

Same factorization as models/mamba.py::ssd_chunked: grid = (B, H, n_chunks),
chunk axis sequential, (P, Ns) fp32 state in VMEM scratch. B/C projections
are shared across heads (n_groups=1) so their blocks are indexed by (b, ic)
only — fetched once per head iteration from the same HBM region (backed by
Pallas's block revisiting; on TPU the pipeline keeps them VMEM-resident).

Intra-chunk: scores = (C @ B^T) * exp(la_i - la_j) masked to j<=i, then
scores @ (dt*x) on the MXU; inter-chunk via state matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, D_ref, o_ref, st_out_ref,
                state_scr, *, chunk, n_chunks):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)  # (C, P)
    dt = dt_ref[0, 0].astype(jnp.float32)  # (C,)
    A_log = A_ref[0]  # ()
    Bc = B_ref[0].astype(jnp.float32)  # (C, Ns)
    Cc = C_ref[0].astype(jnp.float32)  # (C, Ns)
    D = D_ref[0]  # ()

    dlog = dt * (-jnp.exp(A_log))  # (C,) log decay
    la = jnp.cumsum(dlog)  # inclusive
    la_end = la[-1]

    dec = jnp.exp(la[:, None] - la[None, :])  # (Ci, Cj)
    cb = jax.lax.dot_general(
        Cc, Bc, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Ci, Cj)
    rows = jax.lax.broadcasted_iota(jnp.int32, cb.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, cb.shape, 1)
    scores = jnp.where(cols <= rows, cb * dec, 0.0)
    dtx = x * dt[:, None]  # (C, P)
    y_intra = jax.lax.dot_general(
        scores, dtx, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    state = state_scr[...]  # (P, Ns)
    y_inter = jnp.exp(la)[:, None] * jax.lax.dot_general(
        Cc, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (C, P)
    o_ref[0, 0] = (y_intra + y_inter + D * x).astype(o_ref.dtype)

    k_dec = dtx * jnp.exp(la_end - la)[:, None]  # (C, P)
    state_scr[...] = jnp.exp(la_end) * state + jax.lax.dot_general(
        k_dec, Bc, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ic == n_chunks - 1)
    def _emit():
        st_out_ref[0, 0] = state_scr[...]


def ssd(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    A_log: jnp.ndarray,
    B_: jnp.ndarray,
    C_: jnp.ndarray,
    D: jnp.ndarray,
    *,
    chunk: int = 128,
    interpret: bool = False,
):
    """x: (B, H, S, P); dt: (B, H, S); A_log, D: (H,); B_/C_: (B, S, Ns).
    Returns (y (B,H,S,P), state (B,H,P,Ns))."""
    Bb, H, S, P = x.shape
    Ns = B_.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks)
    y, state = pl.pallas_call(
        kernel,
        grid=(Bb, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, h, ic: (b, h, ic)),
            pl.BlockSpec((1,), lambda b, h, ic: (h,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, Ns), lambda b, h, ic: (b, ic, 0)),
            pl.BlockSpec((1, chunk, Ns), lambda b, h, ic: (b, ic, 0)),
            pl.BlockSpec((1,), lambda b, h, ic: (h,), memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, P, Ns), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((Bb, H, P, Ns), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, Ns), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt, A_log, B_, C_, D)
    return y, state
