"""Pallas blocked cosine-similarity top-k (the plan-cache lookup kernel).

One device call answers a whole batch of fuzzy lookups: ``queries`` (Q, D)
against a ``bank`` (N, D) of L2-normalized rows -> top-k scores and row
indices per query. This replaces the O(N*D) host numpy scan the paper's
prototype runs per request (Table 5's scaling cliff) with an MXU matmul
whose N dimension is streamed block-by-block.

Tiling: grid = (n_q_blocks, n_n_blocks) with the N axis ``arbitrary`` so a
running top-k can live in VMEM scratch. Each step computes a (bq, bn) score
tile on the MXU, masks the N-padding tail, merges it with the carried
(bq, k) best-so-far and re-selects top-k in k rounds of row max, lowest
row attaining it, and mask — a merge whose cost is O(k * bq * bn) on the
VPU, small next to the matmul. Ties resolve to the lowest bank row.

On CPU (this container) the kernel runs with ``interpret=True``; on TPU the
same call sites compile to Mosaic. D must be a multiple of 128 (lane width);
the bank embedding dim 384 = 3*128 satisfies this.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NO_ROW = 2**31 - 1  # larger than any bank row: loses every min


def _topk_kernel(q_ref, b_ref, s_out, i_out, s_scr, i_scr, *, block_n, n_total,
                 n_blocks, k):
    jn = pl.program_id(1)

    @pl.when(jn == 0)
    def _init():
        s_scr[...] = jnp.full_like(s_scr, NEG_INF)
        i_scr[...] = jnp.full_like(i_scr, -1)

    q = q_ref[...].astype(jnp.float32)  # (bq, D)
    b = b_ref[...].astype(jnp.float32)  # (bn, D)
    # full f32 passes: the device backend must give the same answers as the
    # exact brute and dense backends (the cost of the extra MXU passes is
    # not measured)
    s = jax.lax.dot_general(
        q, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # (bq, bn)
    pos = jn * block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos >= n_total, NEG_INF, s)

    # k-way merge of the carried best-so-far with this tile, in k rounds of
    # (row max -> lowest bank row attaining it -> mask that row out). Only
    # max/min reductions and selects: Mosaic lowers no sort or top_k.
    # Carried rows come from earlier tiles, so "lowest row among ties" is
    # the same tie rule as a stable sort over the whole bank.
    c_s, c_i = s_scr[...], i_scr[...]  # (bq, k)
    col = jax.lax.broadcasted_iota(jnp.int32, c_s.shape, 1)
    top_s = jnp.full_like(c_s, NEG_INF)
    top_i = jnp.full_like(c_i, -1)
    for r in range(k):
        m = jnp.maximum(jnp.max(c_s, axis=1, keepdims=True),
                        jnp.max(s, axis=1, keepdims=True))  # (bq, 1)
        row = jnp.minimum(
            jnp.min(jnp.where(c_s == m, c_i, _NO_ROW), axis=1, keepdims=True),
            jnp.min(jnp.where(s == m, pos, _NO_ROW), axis=1, keepdims=True),
        )
        top_s = jnp.where(col == r, m, top_s)
        top_i = jnp.where(col == r, row, top_i)
        c_s = jnp.where(c_i == row, NEG_INF, c_s)
        s = jnp.where(pos == row, NEG_INF, s)
    s_scr[...] = top_s
    i_scr[...] = top_i

    @pl.when(jn == n_blocks - 1)
    def _finalize():
        s_out[...] = s_scr[...]
        i_out[...] = jnp.where(s_scr[...] <= NEG_INF / 2, -1, i_scr[...])


def topk_cosine(
    queries: jnp.ndarray,
    bank: jnp.ndarray,
    k: int,
    *,
    block_q: int = 128,
    block_n: int = 1024,
    interpret: bool = False,
):
    """queries (Q, D), bank (N, D), both L2-normalized rows.

    Returns (scores (Q, k) f32, indices (Q, k) i32); indices are -1 (scores
    NEG_INF) past the end when N < k. Q, N need not be block multiples —
    padding is handled here; D must be a lane multiple.
    """
    Q, D = queries.shape
    N = bank.shape[0]
    assert bank.shape[1] == D, (queries.shape, bank.shape)
    assert k >= 1
    if Q == 0 or N == 0:  # degenerate: empty batch or empty bank
        return (
            jnp.full((Q, k), NEG_INF, jnp.float32),
            jnp.full((Q, k), -1, jnp.int32),
        )
    block_q = max(8, min(block_q, max(8, Q)))
    block_n = max(k, min(block_n, max(128, N)))

    q_pad = (-Q) % block_q
    n_pad = (-N) % block_n
    qp = jnp.pad(queries.astype(jnp.float32), ((0, q_pad), (0, 0)))
    bp = jnp.pad(bank.astype(jnp.float32), ((0, n_pad), (0, 0)))
    n_blocks = bp.shape[0] // block_n

    kernel = functools.partial(
        _topk_kernel, block_n=block_n, n_total=N, n_blocks=n_blocks, k=k
    )
    scores, idx = pl.pallas_call(
        kernel,
        grid=(qp.shape[0] // block_q, n_blocks),
        in_specs=[
            pl.BlockSpec((block_q, D), lambda iq, jn: (iq, 0)),
            pl.BlockSpec((block_n, D), lambda iq, jn: (jn, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda iq, jn: (iq, 0)),
            pl.BlockSpec((block_q, k), lambda iq, jn: (iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qp.shape[0], k), jnp.float32),
            jax.ShapeDtypeStruct((qp.shape[0], k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, bp)
    return scores[:Q], idx[:Q]
