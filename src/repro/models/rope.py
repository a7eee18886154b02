"""Rotary position embeddings: standard RoPE, Qwen2-VL M-RoPE, and
DeepSeek-V2's YaRN over interleaved pairs.

Positions are explicit everywhere (no hidden state), so prefill, decode and
chunked execution all share the same code path.
"""

from __future__ import annotations

import math
from typing import Tuple

import numpy as np

import jax.numpy as jnp


def rope_angles(positions: jnp.ndarray, head_dim: int, theta: float) -> Tuple:
    """positions: (...,) int32 -> cos/sin of shape positions.shape + (head_dim/2,)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, H, hd); cos/sin: (B, S, half) -> broadcast over heads.

    Uses the 'split-half' convention (x = [x1, x2]) matching Llama/Qwen.
    """
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    out1 = x1 * c - x2 * s
    out2 = x2 * c + x1 * s
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def mrope_sections(head_dim: int) -> Tuple[int, int, int]:
    """Qwen2-VL splits the half-dims into (temporal, height, width) sections.

    For hd=128 (half=64) the reference split is (16, 24, 24); we generalize
    to (half/4, 3*half/8, 3*half/8).
    """
    half = head_dim // 2
    t = half // 4
    h = (half - t) // 2
    w = half - t - h
    return t, h, w


def mrope_angles(positions_3d: jnp.ndarray, head_dim: int, theta: float) -> Tuple:
    """positions_3d: (3, B, S) [temporal, height, width] -> (cos, sin) (B,S,half).

    Each frequency band takes its angle from the section's position stream.
    """
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    # angles per stream: (3, B, S, half)
    ang = positions_3d.astype(jnp.float32)[..., None] * freqs
    t, h, w = mrope_sections(head_dim)
    sec = jnp.concatenate(
        [
            ang[0, ..., :t],
            ang[1, ..., t : t + h],
            ang[2, ..., t + h :],
        ],
        axis=-1,
    )  # (B, S, half)
    return jnp.cos(sec), jnp.sin(sec)


def positions_for_rope(cfg, positions: jnp.ndarray, head_dim: int):
    """Dispatch rope/mrope/none. positions: (B,S) int32 or (3,B,S) for mrope.

    Returns (cos, sin) or (None, None) for rope_type == 'none'.
    """
    if cfg.rope_type == "none":
        return None, None
    if cfg.rope_type == "mrope":
        if positions.ndim == 2:  # text-only: replicate across the 3 streams
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        return mrope_angles(positions, head_dim, cfg.rope_theta)
    return rope_angles(positions, head_dim, cfg.rope_theta)


# ---------------------------------------------------------------------------
# YaRN (DeepSeek-V2's ``rope_scaling`` of type "yarn")
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def _correction_dim(rotations: float, dim: int, theta: float, max_pos: int) -> float:
    """The pair index whose wavelength fits ``rotations`` turns in ``max_pos``."""
    return dim * math.log(max_pos / (rotations * 2 * math.pi)) / (2 * math.log(theta))


def yarn_inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    """Per pair frequency: pairs below the fast correction index keep the
    base frequency, pairs above the slow one are divided by ``factor``, and
    a linear ramp blends the two in between."""
    half = dim // 2
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    low = max(math.floor(_correction_dim(yarn.beta_fast, dim, theta,
                                         yarn.original_max_position_embeddings)), 0)
    high = min(math.ceil(_correction_dim(yarn.beta_slow, dim, theta,
                                         yarn.original_max_position_embeddings)), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp  # share of the unscaled frequency
    return (base / yarn.factor * (1.0 - keep) + base * keep).astype(np.float32)


def yarn_angles(positions: jnp.ndarray, dim: int, theta: float, yarn) -> Tuple:
    """positions (...,) -> cos/sin (..., dim/2), scaled by YaRN's mscale
    ratio (1 where ``mscale`` equals ``mscale_all_dim``)."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        yarn_inv_freq(dim, theta, yarn))
    m = yarn_mscale(yarn.factor, yarn.mscale) / yarn_mscale(yarn.factor, yarn.mscale_all_dim)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if m != 1.0:
        cos, sin = cos * m, sin * m
    return cos, sin


def yarn_softmax_scale(head_dim: int, yarn) -> float:
    """DeepSeek-V2's attention scale: head_dim**-0.5 times mscale_all_dim's
    YaRN factor squared."""
    scale = head_dim ** -0.5
    if yarn is not None and yarn.mscale_all_dim:
        scale *= yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2
    return scale


def apply_rope_pairs(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotation of interleaved pairs (x[2i], x[2i+1]) by angle i.
    cos/sin broadcast against x's leading dims with a last axis of dim/2."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)
