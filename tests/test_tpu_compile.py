"""The main-path Pallas kernels compile for a TPU v5e, without a chip.

Each test lowers one kernel at published widths and compiles it for a
described ``v5e:2x2`` topology (one chip of it). Interpret mode, which
every other kernel test uses, accepts block shapes and primitives that
Mosaic refuses; this file is where such a kernel fails on a CPU-only
machine. Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.index import DIM
from repro.kernels import decode_attention, flash_attention, paged_attention
from repro.kernels import similarity

# (Hq, Hkv, hd) of the two tier models the one-chip deployment serves
WIDTHS = {
    name: (registry.get(name).num_heads, registry.get(name).num_kv_heads,
           registry.get(name).head_dim)
    for name in ("olmo-1b", "qwen2.5-3b")
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a chip-targeted executable written to the persistent cache cannot be
    # read back on the CPU; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel is in
    return compiled


@pytest.mark.parametrize("model", sorted(WIDTHS))
def test_flash_attention_compiles(model, one_chip):
    Hq, Hkv, hd = WIDTHS[model]
    B, S = 1, 1024
    _compile(
        lambda q, k, v: flash_attention.flash_attention(q, k, v),
        one_chip,
        ((B, Hq, S, hd), jnp.bfloat16),
        ((B, Hkv, S, hd), jnp.bfloat16),
        ((B, Hkv, S, hd), jnp.bfloat16),
    )


@pytest.mark.parametrize("model", sorted(WIDTHS))
def test_decode_attention_compiles(model, one_chip):
    Hq, Hkv, hd = WIDTHS[model]
    B, M = 4, 2048
    _compile(
        lambda q, k, v, n: decode_attention.decode_attention(
            q, k, v, n, block_k=512),
        one_chip,
        ((B, Hq, hd), jnp.bfloat16),
        ((B, Hkv, M, hd), jnp.bfloat16),
        ((B, Hkv, M, hd), jnp.bfloat16),
        ((B,), jnp.int32),
    )


@pytest.mark.parametrize("model", sorted(WIDTHS))
def test_paged_attention_compiles(model, one_chip):
    Hq, Hkv, hd = WIDTHS[model]
    B, n_pages, page_size, P = 4, 256, 16, 8
    _compile(
        paged_attention.paged_decode_attention,
        one_chip,
        ((B, Hq, hd), jnp.bfloat16),
        ((n_pages, page_size, Hkv, hd), jnp.bfloat16),
        ((n_pages, page_size, Hkv, hd), jnp.bfloat16),
        ((B, P), jnp.int32),
        ((B,), jnp.int32),
    )


def test_topk_cosine_compiles_at_deployment_bank(one_chip):
    Q, N, k = 128, 65_536, 4
    compiled = _compile(
        lambda q, b: similarity.topk_cosine(q, b, k),
        one_chip,
        ((Q, DIM), jnp.float32),
        ((N, DIM), jnp.float32),
    )
    # the bank streams through VMEM block by block; the program holds
    # little beyond its arguments
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < N * DIM * 4
