"""Serving engine: jitted prefill + decode with donated KV buffers.

One Engine instance = one model deployment (a planner tier or the actor
pool). The engine exposes:

  * ``generate(tokens, max_new)`` — batched greedy/temperature generation
  * ``prefill_with_prefix(template_id, suffix)`` — suffix-only prefill
    against a template prefix held in the paged KV pool
    (``serving/kv_cache.py``): a plan-cache hit re-serves a known prefix,
    so only the adaptation prompt pays prefill compute
  * ``measured_rates()`` — tokens/s observed, fed into the APC cost model so
    control-plane latency numbers come from the actual data plane

An engine computes where its parameters live: on the default device, for
every served path today. ``mesh=`` threads a ``ParallelCtx`` through the
model code, but no caller passes one, so a model must fit one device
(``configs/apc_minion.ONE_CHIP`` does on a TPU v5e; ``DEFAULT`` does not).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShardingProfile
from repro.distributed import sharding as shd
from repro.models import lm
from repro.obs import trace_span
from repro.obs.names import SPAN_ENGINE_GENERATE
from repro.serving.kv_cache import CachePoint, KVPrefixCache, PagePoolExhausted
from repro.serving.sampler import sample_token

# families whose cache is pure KV (no recurrent state): the only ones a
# stored prefix can be re-entered into mid-stream
_PREFIX_FAMILIES = ("dense", "moe", "vlm")


@dataclass
class EngineStats:
    prefill_tokens: int = 0
    decode_tokens: int = 0
    prefix_tokens_reused: int = 0
    prefill_s: float = 0.0
    decode_s: float = 0.0

    def rates(self) -> Dict[str, float]:
        return {
            "prefill": self.prefill_tokens / self.prefill_s if self.prefill_s else 0.0,
            "decode": self.decode_tokens / self.decode_s if self.decode_s else 0.0,
        }


class Engine:
    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        *,
        mesh=None,
        profile: Optional[ShardingProfile] = None,
        max_len: int = 512,
        donate_cache: bool = True,
        kv_prefix: Optional[KVPrefixCache] = None,
    ):
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        self.max_len = max_len
        self.stats = EngineStats()
        self.kv_prefix = kv_prefix if cfg.family in _PREFIX_FAMILIES else None
        ctx = None
        if mesh is not None:
            profile = profile or ShardingProfile()
            ctx = lm.ParallelCtx(
                mesh=mesh,
                dp_axes=shd.dp_axes_for_mesh(mesh),
                tp_axis=profile.tp_axis,
                ep_axis=profile.ep_axis,
            )
        self.ctx = ctx

        def prefill_fn(params, batch):
            logits, cache = lm.prefill(cfg, params, batch, ctx, cache_len=max_len)
            return logits[:, -1], cache

        def decode_fn(params, cache, tokens):
            logits, cache = lm.decode_step(cfg, params, cache, tokens, ctx)
            return logits[:, -1], cache

        def extend_fn(params, prefix_k, prefix_v, batch, *, prefix_len):
            logits, cache = lm.prefill_extend(
                cfg, params, batch, prefix_k, prefix_v, prefix_len, ctx,
                cache_len=max_len,
            )
            return logits[:, -1], cache

        donate = (1,) if donate_cache else ()
        self._prefill = jax.jit(prefill_fn)
        self._decode = jax.jit(decode_fn, donate_argnums=donate)
        self._extend = jax.jit(extend_fn, static_argnames=("prefix_len",))

    # ------------------------------------------------------------------

    def prefill(self, tokens: np.ndarray, *,
                n_valid: Optional[int] = None) -> Tuple[np.ndarray, Any]:
        """tokens: (B, S) int32 -> (last logits (B, V), cache).

        ``n_valid`` is the number of REAL tokens in the batch; without it
        every element counts, padding included — callers that right-pad
        ragged prompts should pass the true count or the prefill tokens/s
        rate (and the APC cost model downstream of it) reads high.
        """
        t0 = time.perf_counter()
        logits, cache = self._prefill(self.params, {"tokens": jnp.asarray(tokens)})
        logits.block_until_ready()
        self.stats.prefill_s += time.perf_counter() - t0
        self.stats.prefill_tokens += int(tokens.size if n_valid is None else n_valid)
        return np.asarray(logits), cache

    def decode(self, cache: Any, tokens: np.ndarray, *,
               active: Optional[int] = None) -> Tuple[np.ndarray, Any]:
        """One decode step. ``active`` counts the rows still generating;
        finished (post-EOS) rows ride along in the dense batch but must
        not inflate the decode tokens/s rate."""
        t0 = time.perf_counter()
        logits, cache = self._decode(self.params, cache, jnp.asarray(tokens))
        logits.block_until_ready()
        self.stats.decode_s += time.perf_counter() - t0
        self.stats.decode_tokens += int(
            tokens.shape[0] if active is None else active
        )
        return np.asarray(logits), cache

    # -- paged KV prefix path ------------------------------------------

    def register_prefix(self, template_id: str, cache: Any,
                        prefix_len: int) -> bool:
        """Distill the first ``prefix_len`` cached positions into the page
        pool under ``template_id`` (batch row 0 — the template prefix is
        identical across rows by construction). Call right after the full
        prefill that built ``cache``, before decode donates its buffers."""
        if self.kv_prefix is None or "kv_k" not in cache:
            return False
        k = cache["kv_k"][:, 0]  # (L, M, Hkv, hd)
        v = cache["kv_v"][:, 0]
        try:
            self.kv_prefix.put(template_id, k, v, length=prefix_len)
        except PagePoolExhausted:
            # registration is best-effort: the full prefill already
            # served this request; a pool too small (or a still-leased
            # stale entry) just means the next hit pays prefill again
            return False
        return True

    def prefill_with_prefix(
        self, template_id: str, suffix_tokens: np.ndarray,
        *, n_valid: Optional[int] = None,
        expected_len: Optional[int] = None,
    ) -> Optional[Tuple[np.ndarray, Any]]:
        """Prefill only the adaptation suffix; the template prefix K/V is
        gathered from the page pool. Returns None when the prefix isn't
        cached (caller falls back to a full prefill + register_prefix).

        ``expected_len`` is the prefix length the caller split the prompt
        at (the cache point). The pooled prefix MUST be exactly that long
        — the extend kernel derives RoPE positions and the attention mask
        from it — so a mismatched entry (stale registration, re-tokenized
        template) is treated as a miss, never served.
        """
        if self.kv_prefix is None:
            return None
        lease = self.kv_prefix.acquire(template_id)
        if lease is None:
            return None
        if expected_len is not None and lease.length != expected_len:
            # wrong-length prefix: serving it would silently shift every
            # suffix position; fall back so the caller re-registers
            self.kv_prefix.release_lease(lease)
            return None
        try:
            B, S = suffix_tokens.shape
            pk, pv, plen = self.kv_prefix.gather(lease, batch=B)
            t0 = time.perf_counter()
            logits, cache = self._extend(
                self.params, pk, pv,
                {"tokens": jnp.asarray(suffix_tokens)}, prefix_len=plen,
            )
            logits.block_until_ready()
            self.stats.prefill_s += time.perf_counter() - t0
            self.stats.prefill_tokens += int(
                suffix_tokens.size if n_valid is None else n_valid
            )
            self.stats.prefix_tokens_reused += B * plen
            return np.asarray(logits), cache
        finally:
            self.kv_prefix.release_lease(lease)

    # ------------------------------------------------------------------

    def generate(
        self,
        tokens: np.ndarray,
        max_new: int = 32,
        *,
        temperature: float = 0.0,
        seed: int = 0,
        eos_id: Optional[int] = None,
        pad_id: int = 0,
        prompt_lengths: Optional[np.ndarray] = None,
        cache_point: Optional[CachePoint] = None,
    ) -> np.ndarray:
        """Batched generation. Returns (B, <=max_new) generated ids.

        Rows that hit ``eos_id`` emit ``pad_id`` from the next step on and
        stop counting toward decode throughput. ``prompt_lengths`` ((B,)
        valid prompt token counts) keeps right-padding out of the prefill
        rate. ``cache_point`` routes the prefill through the paged KV
        prefix cache: suffix-only prefill on a pool hit, full prefill +
        prefix registration on a pool miss.
        """
        B, S = tokens.shape
        if S + max_new > self.max_len:
            raise ValueError(
                f"prompt ({S}) + max_new ({max_new}) exceeds the engine's "
                f"KV capacity (max_len={self.max_len}); decode would write "
                f"past the cache"
            )
        n_valid = None if prompt_lengths is None else int(np.sum(prompt_lengths))
        with trace_span(SPAN_ENGINE_GENERATE, batch=B, prompt_len=S,
                        max_new=max_new) as sp:
            res = None
            if cache_point is not None and self.kv_prefix is not None:
                suffix = tokens[:, cache_point.prefix_len:]
                n_suf = (None if n_valid is None
                         else n_valid - B * cache_point.prefix_len)
                res = self.prefill_with_prefix(
                    cache_point.template_id, suffix, n_valid=n_suf,
                    expected_len=cache_point.prefix_len,
                )
            if res is None:
                res = self.prefill(tokens, n_valid=n_valid)
                if cache_point is not None and self.kv_prefix is not None:
                    self.register_prefix(
                        cache_point.template_id, res[1], cache_point.prefix_len
                    )
            logits, cache = res
            out = []
            key = jax.random.PRNGKey(seed)
            tok = sample_token(logits, temperature, key)
            done = np.zeros((B,), bool)
            for i in range(max_new):
                if eos_id is not None and done.any():
                    # finished rows keep a slot in the dense batch but must
                    # emit padding, not whatever the sampler drew for them
                    tok = np.where(done[:, None], pad_id, tok).astype(tok.dtype)
                out.append(tok)
                if eos_id is not None:
                    done |= tok[:, 0] == eos_id
                    if done.all():
                        break
                if i + 1 == max_new:
                    break  # the last token is emitted; skip the wasted decode
                logits, cache = self.decode(
                    cache, tok, active=int(B - done.sum())
                )
                key, sub = jax.random.split(key)
                tok = sample_token(logits, temperature, sub)
            sp.set(new_tokens=len(out))
            return np.concatenate(out, axis=1)

    def measured_rates(self) -> Dict[str, float]:
        r = self.stats.rates()
        r["rtt"] = 0.0  # local serving: no API round-trip
        return r
