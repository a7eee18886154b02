"""Pallas TPU paged decode attention: one query token vs K/V gathered
through a page table.

The KV cache here is not a per-sequence slab but a shared page pool
(``serving/kv_cache.py``): pages of ``page_size`` tokens live at arbitrary
pool rows, and each sequence names its pages through a ``(B, P)`` page
table. The kernel streams K/V one page per grid step, with the page row
resolved *before* the DMA via scalar prefetch
(``pltpu.PrefetchScalarGridSpec``) — the page table and per-sequence
lengths are SMEM-resident, and the BlockSpec index map reads
``page_table[b, ip]`` to aim each HBM->VMEM copy at the right pool row.
Entries past a sequence's last page are ``-1``; the index map clamps them
to row 0 and the length mask (plus a ``pl.when`` skip) discards the block.

Each K/V block is a whole page, all heads: ``(page_size, Hkv, hd)``. Its
last two dims are the slab's own, which Mosaic's block rule (last two
block dims divisible by 8 and 128, or whole) accepts; a one-head slice
``(page_size, 1, hd)`` of the token-major pool is refused. The kernel then
walks the heads of the page in VMEM.

The accumulation is the same block-sequential online softmax as
``decode_attention.py``, head by head — with ``page_size == block_k`` and
in-order pages, the two kernels perform bit-identical arithmetic, which is
exactly what ``tests/test_kv_cache.py`` pins (paged-vs-dense bitwise
parity).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page_size, n_pages, n_kv):
    ib = pl.program_id(0)
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[ib]

    @pl.when(ip * page_size < length)
    def _compute():
        # convert the whole page once, then slice heads: a convert fused
        # into each head's slice changes XLA's rounding in interpret mode
        # and breaks bitwise parity with the dense kernel
        k_page = k_ref[0].astype(jnp.float32)  # (page_size, Hkv, hd)
        v_page = v_ref[0].astype(jnp.float32)
        for h in range(n_kv):
            q = q_ref[0, h, :, :].astype(jnp.float32)  # (G, hd)
            k = k_page[:, h, :]  # (page_size, hd)
            v = v_page[:, h, :]
            hd = q.shape[-1]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) / jnp.sqrt(float(hd))  # (G, page_size)
            pos = ip * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos >= length, NEG_INF, s)
            m_old = m_scr[h]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            m_scr[h] = m_new

    @pl.when(ip == n_pages - 1)
    def _finalize():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


def paged_decode_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """q: (B, Hq, hd); k_pages, v_pages: (N, page_size, Hkv, hd) pool slabs;
    page_table: (B, P) int32 pool rows, -1 past a sequence's last page;
    lengths: (B,) or () int32 valid token counts -> (B, Hq, hd)."""
    B, Hq, hd = q.shape
    N, page_size, Hkv, _ = k_pages.shape
    P = page_table.shape[1]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, hd)
    table = jnp.asarray(page_table, jnp.int32)
    lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1), (B,))

    kernel = functools.partial(
        _paged_kernel, page_size=page_size, n_pages=P, n_kv=Hkv
    )
    # index maps receive the scalar-prefetch refs after the grid indices;
    # invalid (-1) table entries clamp to pool row 0 — the DMA lands
    # somewhere legal, and the length mask discards the whole block
    page = lambda b, ip, pt, ln: (jnp.maximum(pt[b, ip], 0), 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page table + lengths
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, hd), lambda b, ip, pt, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, page_size, Hkv, hd), page),
            pl.BlockSpec((1, page_size, Hkv, hd), page),
        ],
        out_specs=pl.BlockSpec(
            (1, Hkv, G, hd), lambda b, ip, pt, ln: (b, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(table, lens, qg, k_pages, v_pages)
    return out.reshape(B, Hq, hd)
