"""Serving engine: jitted prefill + decode with donated KV buffers.

One Engine instance = one model deployment (a planner tier or the actor
pool). The engine exposes:

  * ``generate(tokens, max_new)`` — batched greedy/temperature generation
  * ``prefill_with_prefix(template_id, suffix)`` — suffix-only prefill
    against a template prefix held in the paged KV pool
    (``serving/kv_cache.py``): a plan-cache hit re-serves a known prefix,
    so only the adaptation prompt pays prefill compute

``generate`` keeps each sampled token on the device, where it feeds the
next decode step, so the host does not wait for the device between steps.
Without ``eos_id`` a call makes three blocking reads: the prefill's logits,
the first decode step's logits and, after the last step, every token at
once. The first step is awaited so that a profiler can tie the decode
program's device time to the call: the TPU runtime records a run's
completion on the host only where the host awaits the run's output before
another program takes it in. With ``eos_id`` the host also reads each
token as it is drawn, to pad finished rows and count the rows still
active. The counter ``engine.host_syncs`` (in ``Engine.obs``) counts the
engine's blocking reads.

A latent-attention MoE model keeps, in its cache, the routed
(token, expert) pairs per MoE layer and expert, summed on the device by
prefill and every decode step; ``generate`` reads them with the tokens in
its final fetch (a separate read only with ``eos_id``) into
``EngineStats.expert_tokens``, the counter ``moe.expert_tokens`` and the
``expert_tokens`` attribute of ``engine.generate``.

``EngineStats`` sums the host-clock seconds of prefill (up to its logits
being ready on the device) and of each call's decode phase (from the first
step's dispatch to the tokens being on the host), and the tokens they
processed. The APC cost model does not read them: ``CostLedger`` keeps its
own rates.

Under a tracer, ``generate`` records ``engine.generate`` (``arch`` is the
model's name) and beneath it ``engine.prefill`` (``S``, ``path`` =
``full`` | ``prefix``) over the interval ``prefill_s`` sums, one
``engine.decode`` per step and one ``engine.sample`` per token. The two
per-step spans cover the host's dispatch of the step and of the sampler
(with the PRNG split, and the token's read and the EOS mask where
``eos_id`` is given; the first step's span runs on to its logits being
ready); they lie inside the interval ``decode_s`` sums.

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
from repro.obs import MetricsRegistry, trace_span
from repro.obs.names import (
    ENGINE_HOST_SYNCS,
    MOE_EXPERT_TOKENS,
    SPAN_ENGINE_DECODE,
    SPAN_ENGINE_GENERATE,
    SPAN_ENGINE_PREFILL,
    SPAN_ENGINE_SAMPLE,
)
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
    # routed (token, expert) pairs, (MoE layers, experts); None without them
    expert_tokens: Optional[np.ndarray] = None


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
        obs: Optional[MetricsRegistry] = None,
        obs_labels: Optional[Dict[str, str]] = None,
    ):
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        self.max_len = max_len
        self.stats = EngineStats()
        self.obs = obs if obs is not None else MetricsRegistry()
        self._host_syncs = self.obs.counter(ENGINE_HOST_SYNCS, **(obs_labels or {}))
        routed = lm.expert_tokens_shape(cfg)
        self._expert_counters = None if routed is None else [
            [self.obs.counter(MOE_EXPERT_TOKENS, **(obs_labels or {}),
                              layer=str(l), expert=str(e)) for e in range(routed[1])]
            for l in range(routed[0])]
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
        ragged prompts should pass the true count or the prefill
        tokens/s rate read from ``EngineStats`` reads high.
        """
        logits, cache = self._prefill_on_device(tokens, n_valid)
        return np.asarray(logits), cache

    def _prefill_on_device(self, tokens: np.ndarray,
                           n_valid: Optional[int]) -> Tuple[Any, Any]:
        with trace_span(SPAN_ENGINE_PREFILL, S=int(tokens.shape[1]), path="full"):
            t0 = time.perf_counter()
            logits, cache = self._prefill(self.params, {"tokens": jnp.asarray(tokens)})
            self._wait(logits)
            self.stats.prefill_s += time.perf_counter() - t0
        self.stats.prefill_tokens += int(tokens.size if n_valid is None else n_valid)
        return logits, cache

    def decode(self, cache: Any, tokens: np.ndarray, *,
               active: Optional[int] = None) -> Tuple[np.ndarray, Any]:
        """One decode step, its logits read to the host. ``active`` counts
        the rows still generating; finished (post-EOS) rows ride along in
        the dense batch but must not inflate the decode tokens/s rate."""
        with trace_span(SPAN_ENGINE_DECODE):
            t0 = time.perf_counter()
            logits, cache = self._decode(self.params, cache, jnp.asarray(tokens))
            logits = self._read(logits)
            self.stats.decode_s += time.perf_counter() - t0
        self.stats.decode_tokens += int(
            tokens.shape[0] if active is None else active
        )
        return logits, cache

    def _wait(self, x: Any) -> None:
        """Block until ``x`` is computed: one of the engine's host syncs."""
        self._host_syncs.inc()
        jax.block_until_ready(x)

    def _read(self, x: Any) -> Any:
        """``x`` (an array or a list of them) copied to the host: one of the
        engine's host syncs."""
        self._host_syncs.inc()
        return jax.device_get(x)

    # -- paged KV prefix path ------------------------------------------

    def register_prefix(self, template_id: str, cache: Any,
                        prefix_len: int) -> bool:
        """Distill the first ``prefix_len`` cached positions into the page
        pool under ``template_id`` (batch row 0 — the template prefix is
        identical across rows by construction). Call right after the full
        prefill that built ``cache``, before decode donates its buffers."""
        self._refuse_latent_prefix()
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
        self._refuse_latent_prefix()
        res = self._prefill_with_prefix_on_device(
            template_id, suffix_tokens, n_valid, expected_len)
        return None if res is None else (np.asarray(res[0]), res[1])

    def _refuse_latent_prefix(self) -> None:
        """The page pool holds per-head K/V; a latent cache has no place in
        it yet, so prefix reuse is refused rather than skipped."""
        if self.cfg.mla is not None:
            raise NotImplementedError(
                f"prefix reuse of a latent (MLA) cache is not implemented: "
                f"{self.cfg.name} caches kv_latent, the page pool holds kv_k/kv_v")

    def _prefill_with_prefix_on_device(
        self, template_id: str, suffix_tokens: np.ndarray,
        n_valid: Optional[int], expected_len: Optional[int],
    ) -> Optional[Tuple[Any, Any]]:
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
            with trace_span(SPAN_ENGINE_PREFILL, S=S, path="prefix"):
                t0 = time.perf_counter()
                logits, cache = self._extend(
                    self.params, pk, pv,
                    {"tokens": jnp.asarray(suffix_tokens)}, prefix_len=plen,
                )
                self._wait(logits)
                self.stats.prefill_s += time.perf_counter() - t0
            self.stats.prefill_tokens += int(
                suffix_tokens.size if n_valid is None else n_valid
            )
            self.stats.prefix_tokens_reused += B * plen
            return logits, cache
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
        if cache_point is not None:
            self._refuse_latent_prefix()
        n_valid = None if prompt_lengths is None else int(np.sum(prompt_lengths))
        with trace_span(SPAN_ENGINE_GENERATE, arch=self.cfg.name, batch=B,
                        prompt_len=S, max_new=max_new) as sp:
            res = None
            if cache_point is not None and self.kv_prefix is not None:
                suffix = tokens[:, cache_point.prefix_len:]
                n_suf = (None if n_valid is None
                         else n_valid - B * cache_point.prefix_len)
                res = self._prefill_with_prefix_on_device(
                    cache_point.template_id, suffix, n_suf,
                    cache_point.prefix_len,
                )
            if res is None:
                res = self._prefill_on_device(tokens, n_valid)
                if cache_point is not None and self.kv_prefix is not None:
                    self.register_prefix(
                        cache_point.template_id, res[1], cache_point.prefix_len
                    )
            logits, cache = res
            out = []
            # greedy draws ignore the key: only a temperature needs the
            # split sequence
            key = sub = jax.random.PRNGKey(seed) if temperature > 0.0 else None
            done = np.zeros((B,), bool)
            t0 = None
            for i in range(max_new):
                with trace_span(SPAN_ENGINE_SAMPLE):
                    if i and key is not None:
                        key, sub = jax.random.split(key)
                    tok = sample_token(logits, temperature, sub)
                    if eos_id is not None:
                        tok = self._read(tok)
                        if done.any():
                            # finished rows keep a slot in the dense batch
                            # but must emit padding, not whatever the
                            # sampler drew for them
                            tok = np.where(done[:, None], pad_id, tok).astype(tok.dtype)
                        done |= tok[:, 0] == eos_id
                out.append(tok)
                if eos_id is not None and done.all():
                    break
                if i + 1 == max_new:
                    break  # the last token is emitted; skip the wasted decode
                with trace_span(SPAN_ENGINE_DECODE):
                    if t0 is None:
                        t0 = time.perf_counter()
                    # the token goes straight back in: on the device unless
                    # the host read it for the EOS mask
                    logits, cache = self._decode(self.params, cache, tok)
                    if i == 0:
                        # one round trip a call, so the runtime records the
                        # first step's completion (see the module docstring)
                        self._wait(logits)
                self.stats.decode_tokens += int(B - done.sum())
            routed = cache.get(lm.EXPERT_TOKENS)
            if routed is not None:
                # the routed pairs ride the final fetch of the tokens
                got, routed = self._read((out if eos_id is None else [], routed))
                if eos_id is None:
                    out = got
                self._count_experts(routed, sp)
            elif eos_id is None:
                out = self._read(out)
            if t0 is not None:
                self.stats.decode_s += time.perf_counter() - t0
            sp.set(new_tokens=len(out))
            return np.concatenate(out, axis=1)

    def _count_experts(self, routed: np.ndarray, span) -> None:
        """Adds one call's routed pairs (MoE layers, experts) to the stats,
        the counters and the call's span."""
        routed = np.asarray(routed, np.int64)
        st = self.stats
        st.expert_tokens = routed if st.expert_tokens is None else st.expert_tokens + routed
        for row, counters in zip(routed.tolist(), self._expert_counters):
            for n, c in zip(row, counters):
                c.inc(n)
        span.set(expert_tokens=routed.tolist())
