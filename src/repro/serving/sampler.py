"""Token sampling: greedy / temperature / top-k."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("temperature", "top_k"))
def sample_token(logits, temperature: float = 0.0, key=None, top_k: int = 0):
    """logits: (B, V) -> (B, 1) int32, on the device: nothing is copied to
    the host, so the token can feed the next decode step directly. One
    program per (logits shape, dtype, ``temperature``, ``top_k``)."""
    lg = logits.astype(jnp.float32)
    if temperature <= 0.0:
        tok = jnp.argmax(lg, axis=-1)
    else:
        lg = lg / temperature
        if top_k:
            kth = jnp.sort(lg, axis=-1)[:, -top_k][:, None]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        key = key if key is not None else jax.random.PRNGKey(0)
        tok = jax.random.categorical(key, lg, axis=-1)
    return tok[:, None].astype(jnp.int32)
