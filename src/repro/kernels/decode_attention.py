"""Pallas TPU decode attention (one query token vs a long KV cache).

This kernel is memory-bound (arithmetic intensity ~1 FLOP/byte streaming
K/V), so the tiling targets HBM->VMEM streaming, not the MXU: grid =
(B, Hkv, n_k) with all G q-heads of a kv-group processed together per block
(the (G, bk) score tile keeps the VPU busy while K/V stream). Valid-length
masking uses a per-sequence ``lengths`` vector in SMEM — mixed-length
batches mask each row to its own valid count (the historical scalar
``length`` masked every row to one shared length, silently wrong for any
batch whose sequences differ).

VMEM per step: k,v blocks 2*bk*hd*2B (bf16) + q (G*hd) + acc (G*hd) fp32;
bk=512, hd=128: ~260 KiB — sized so ~8 outstanding copies double-buffer the
HBM stream.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                   block_k, n_k):
    ib = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[ib]

    @pl.when(ik * block_k < length)
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32)  # (G, hd)
        k = k_ref[0, 0, :, :].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        hd = q.shape[-1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) / jnp.sqrt(float(hd))  # (G, bk)
        pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos >= length, NEG_INF, s)
        m_old = m_scr[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finalize():
        o_ref[0, 0, :, :] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


def decode_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    length: jnp.ndarray,
    *,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """q: (B, Hq, hd); k, v: (B, Hkv, M, hd); length: () or (B,) int32 valid
    KV counts (a scalar broadcasts to the whole batch) -> (B, Hq, hd)."""
    B, Hq, hd = q.shape
    Hkv, M = k.shape[1], k.shape[2]
    G = Hq // Hkv
    block_k = min(block_k, M)
    assert M % block_k == 0, (M, block_k)
    n_k = M // block_k
    qg = q.reshape(B, Hkv, G, hd)
    lengths = jnp.broadcast_to(
        jnp.asarray(length, jnp.int32).reshape(-1), (B,)
    )

    kernel = functools.partial(_decode_kernel, block_k=block_k, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # per-sequence lengths
            pl.BlockSpec((1, 1, G, hd), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths, qg, k, v)
    return out.reshape(B, Hq, hd)
