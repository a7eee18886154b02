"""Pallas TPU flash attention (causal, GQA).

Tiling: grid = (B, Hq, n_q, n_k) with the kv axis innermost ("arbitrary"
semantics — sequential per q block). Per (b, h, iq): stream K/V blocks
through VMEM, fp32 online-softmax accumulators live in VMEM scratch and the
output block is written once on the last kv step. GQA is handled in the
index map (kv head = q head // group), so K/V blocks are fetched once per
q-head without materializing the repeat.

Block sizes default to (128, 128) (MXU-aligned: head_dim 64/80/128 are lane
multiples); for long-context prefill block_k 512 amortizes HBM->VMEM
latency. VMEM footprint per step: q(1*bq*hd) + k,v(2*bk*hd) + acc(bq*hd)
fp32 ~ 128*128*4*4B = 256 KiB at defaults — well under the 16 MiB/core
budget, leaving room for double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, block_q, block_k, causal, n_k
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        # whole block strictly above the diagonal contributes nothing
        run = (ik * block_k) <= (iq * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0, 0, :, :].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        hd = q.shape[-1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) / jnp.sqrt(float(hd))  # (bq, bk)
        if causal:
            rows = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols > rows, NEG_INF, s)
        m_old = m_scr[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finalize():
        o_ref[0, 0, :, :] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """q: (B, Hq, S, hd); k, v: (B, Hkv, S, hd) -> (B, Hq, S, hd)."""
    B, Hq, S, hd = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    n_q, n_k = S // block_q, S // block_k

    grid = (B, Hq, n_q, n_k)
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, causal=causal, n_k=n_k
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
