"""Paged KV prefix cache keyed by plan template id.

The APC insight is that a plan-cache hit re-serves a *known prefix*: the
cached plan template is rendered verbatim ahead of the per-request
adaptation prompt. The serving engine therefore re-prefills the same
template tokens on every hit. This module keeps that prefix's KV around —
vLLM-style — in a shared refcounted page pool so a hit prefills only the
adaptation suffix:

  * :class:`PagePool` — per-layer K/V slabs of ``(num_pages, page_size,
    Hkv, hd)`` pages with refcounts and a free list. Device writes are
    donated jit scatters (the ``DeviceBank`` idiom) so slab updates don't
    double the pool's footprint.
  * :class:`KVPrefixCache` — template-id -> page-list map with LRU
    eviction on pool exhaustion, copy-on-write suffix extension
    (:meth:`KVPrefixCache.extend` shares full pages with the parent and
    copies only the partial tail page), and lease-based pinning so a
    prefix can't be evicted out from under an in-flight prefill.
  * :class:`CachePoint` / :func:`plan_cache_point` — the single cache
    point discipline: exactly one prefix/suffix split per request, placed
    after the template and before the adaptation prompt. Anything
    volatile ahead of the split would fork the KV and defeat sharing.

Lifecycle is tied to the plan cache: ``TwoTierRouter`` registers
:meth:`KVPrefixCache.release` as a ``PlanCache`` eviction listener, so a
template's pages are freed exactly when the template leaves the plan
cache — no second eviction policy to tune, no leaked pages.

Thread-safety: ``KVPrefixCache`` owns the lock; ``PagePool`` is not
independently thread-safe and must only be mutated by its owning cache
(or a single-threaded test). Recency is a monotonic integer sequence, not
wall-clock time, so eviction order is deterministic under repro.sim.
"""

from __future__ import annotations

import functools
import threading
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.obs import MetricsRegistry
from repro.obs import names as _names


class PagePoolExhausted(RuntimeError):
    """Raised when an allocation cannot be satisfied even after evicting
    every idle (lease-free) prefix."""


def _donated(fn, *args):
    """Call a donating jit'd helper with the CPU donation notice silenced
    (CPU jax cannot honor donation and warns per call; see index/device.py)."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        return fn(*args)


@functools.partial(jax.jit, donate_argnums=(0,))
def _slab_write(slab, rows, data):
    """slab (L, N, ps, Hkv, hd); rows (n,) i32; data (L, n, ps, Hkv, hd)."""
    return slab.at[:, rows].set(data.astype(slab.dtype))


@jax.jit
def _slab_gather(slab, rows):
    """slab (L, N, ps, Hkv, hd); rows (n,) i32 -> (L, n, ps, Hkv, hd)."""
    return jnp.take(slab, rows, axis=1)


class PagePool:
    """Refcounted per-layer K/V page slabs shared by every cached prefix.

    One pool row = one page of ``page_size`` tokens across all layers.
    Refcounts make copy-on-write sharing safe: a row is recycled onto the
    free list only when its last owner (prefix entry or lease) releases
    it. NOT independently thread-safe — the owning :class:`KVPrefixCache`
    serializes access under its lock.
    """

    def __init__(
        self,
        num_layers: int,
        num_pages: int,
        page_size: int,
        num_kv_heads: int,
        head_dim: int,
        dtype=jnp.bfloat16,
    ):
        shape = (num_layers, num_pages, page_size, num_kv_heads, head_dim)
        self._k = jnp.zeros(shape, dtype)
        self._v = jnp.zeros(shape, dtype)
        self.num_pages = num_pages
        self.page_size = page_size
        self.refcount = np.zeros((num_pages,), np.int32)
        # pop() from the tail allocates low rows first (stable test order)
        self._free: List[int] = list(range(num_pages - 1, -1, -1))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> List[int]:
        """Take ``n`` free rows at refcount 1."""
        if len(self._free) < n:
            raise PagePoolExhausted(
                f"need {n} pages, {len(self._free)} free of {self.num_pages}"
            )
        rows = [self._free.pop() for _ in range(n)]
        for r in rows:
            self.refcount[r] = 1
        return rows

    def retain(self, rows: Sequence[int]) -> None:
        for r in rows:
            self.refcount[r] += 1

    def release(self, rows: Sequence[int]) -> None:
        for r in rows:
            self.refcount[r] -= 1
            assert self.refcount[r] >= 0, f"page {r} over-released"
            if self.refcount[r] == 0:
                self._free.append(r)

    def write(self, rows: Sequence[int], k_data, v_data) -> None:
        """Scatter page data into the slabs (donated: no transient copy)."""
        idx = jnp.asarray(list(rows), jnp.int32)
        self._k = _donated(_slab_write, self._k, idx, k_data)
        self._v = _donated(_slab_write, self._v, idx, v_data)

    def gather(self, rows: Sequence[int]):
        """-> (k, v) each (L, n, page_size, Hkv, hd)."""
        idx = jnp.asarray(list(rows), jnp.int32)
        return _slab_gather(self._k, idx), _slab_gather(self._v, idx)

    def kernel_view(self, layer: int):
        """The (N, page_size, Hkv, hd) slabs one layer of
        ``kernels.paged_attention`` streams through its page table."""
        return self._k[layer], self._v[layer]


def pool_for_config(cfg, *, num_pages: int = 64,
                    page_size: int = 16) -> PagePool:
    """Size a pool to a model config: the per-head K/V geometry and dtype
    of its cache, as ``lm.init_cache`` lays it out."""
    kv = lm.abstract_cache(cfg, 1, 1)["kv_k"]
    layers, _, _, heads, head_dim = kv.shape
    return PagePool(layers, num_pages, page_size, heads, head_dim, dtype=kv.dtype)


@dataclass(frozen=True)
class PrefixLease:
    """A pinned view of one prefix: holds its own refcount on every page,
    so the prefix stays gatherable even if the entry is evicted mid-use."""

    template_id: str
    pages: Tuple[int, ...]
    length: int


@dataclass
class _Prefix:
    pages: List[int]
    length: int
    last_used: int
    leases: int = 0


class KVPrefixCache:
    """template_id -> prefix pages, with plan-cache-coupled lifecycle.

    ``put`` chops a prefix's per-layer K/V into pool pages; ``acquire`` +
    ``gather`` re-materialize it for a suffix-only prefill; ``extend``
    derives a child prefix copy-on-write; ``release`` (the plan-cache
    eviction listener) frees the pages when the template is evicted.

    Owns the lock for itself AND its pool: every pool mutation happens
    under ``self._lock``.
    """

    def __init__(
        self,
        pool: PagePool,
        *,
        obs: Optional[MetricsRegistry] = None,
        obs_labels: Optional[Dict[str, str]] = None,
    ):
        self.pool = pool
        self._lock = threading.Lock()
        self._entries: Dict[str, _Prefix] = {}
        self._seq = 0  # monotonic recency counter (deterministic LRU)
        self.obs = obs if obs is not None else MetricsRegistry()
        labels = dict(obs_labels or {})
        self._pages_hit = self.obs.counter(_names.KV_PAGES_HIT, **labels)
        self._pages_built = self.obs.counter(_names.KV_PAGES_BUILT, **labels)
        self._tokens_prefetched = self.obs.counter(
            _names.KV_TOKENS_PREFETCHED, **labels
        )
        self._prefix_evictions = self.obs.counter(
            _names.KV_PREFIX_EVICTIONS, **labels
        )

    # -- internals (call with the lock held) -------------------------------

    def _release_locked(self, template_id: str) -> None:
        entry = self._entries.pop(template_id)
        self.pool.release(entry.pages)
        self._prefix_evictions.inc()

    def _alloc_locked(self, n: int) -> List[int]:
        """Allocate ``n`` pages, LRU-evicting idle prefixes to make room."""
        if n > self.pool.num_pages:
            # unsatisfiable even by evicting everything: refuse up front
            # rather than destroy the whole cache before failing anyway
            raise PagePoolExhausted(
                f"need {n} pages but the pool holds only "
                f"{self.pool.num_pages} total"
            )
        while self.pool.free_pages < n:
            victim = None
            for tid, e in self._entries.items():
                if e.leases:
                    continue
                if victim is None or e.last_used < self._entries[victim].last_used:
                    victim = tid
            if victim is None:
                leased = sum(1 for e in self._entries.values() if e.leases)
                raise PagePoolExhausted(
                    f"need {n} pages, {self.pool.free_pages} free of "
                    f"{self.pool.num_pages}; no evictable prefix left "
                    f"({leased} leased, remaining pages pinned by "
                    f"outstanding leases or COW shares)"
                )
            self._release_locked(victim)
        return self.pool.alloc(n)

    def _paginate(self, k_prefix, v_prefix, length: int, n_pages: int):
        """(L, S, Hkv, hd) arrays -> (L, n_pages, ps, Hkv, hd) page data."""
        ps = self.pool.page_size
        L, _, H, hd = k_prefix.shape
        pad = n_pages * ps - length

        def chop(x):
            x = x[:, :length]
            if pad:
                x = jnp.concatenate(
                    [x, jnp.zeros((L, pad, H, hd), x.dtype)], axis=1
                )
            return x.reshape(L, n_pages, ps, H, hd)

        return chop(k_prefix), chop(v_prefix)

    # -- public API --------------------------------------------------------

    def put(self, template_id: str, k_prefix, v_prefix, *,
            length: Optional[int] = None) -> int:
        """Store a template prefix. k/v: (L, S, Hkv, hd) post-RoPE cache
        rows; ``length`` valid tokens (default S). Returns pages used."""
        S = int(k_prefix.shape[1])
        length = S if length is None else int(length)
        assert 0 < length <= S, (length, S)
        ps = self.pool.page_size
        n = -(-length // ps)
        with self._lock:
            if template_id in self._entries:
                if self._entries[template_id].leases:
                    raise PagePoolExhausted(
                        f"prefix {template_id!r} is leased; cannot replace"
                    )
                self._release_locked(template_id)
            rows = self._alloc_locked(n)
            kp, vp = self._paginate(k_prefix, v_prefix, length, n)
            self.pool.write(rows, kp, vp)
            self._seq += 1
            self._entries[template_id] = _Prefix(rows, length, self._seq)
            self._pages_built.inc(n)
        return n

    def acquire(self, template_id: str) -> Optional[PrefixLease]:
        """Pin a prefix for use; None on miss. Pair with release_lease."""
        with self._lock:
            entry = self._entries.get(template_id)
            if entry is None:
                return None
            entry.leases += 1
            self._seq += 1
            entry.last_used = self._seq
            self.pool.retain(entry.pages)
            self._pages_hit.inc(len(entry.pages))
            return PrefixLease(template_id, tuple(entry.pages), entry.length)

    def gather(self, lease: PrefixLease, *, batch: int = 1):
        """-> (k, v, length): (L, B, Sp, Hkv, hd) dense prefix views
        (Sp = pages * page_size >= length; positions past length are the
        zero padding the extend mask discards)."""
        with self._lock:
            kg, vg = self.pool.gather(lease.pages)
            self._tokens_prefetched.inc(batch * lease.length)
        L, n, ps, H, hd = kg.shape
        k = jnp.broadcast_to(kg.reshape(L, 1, n * ps, H, hd),
                             (L, batch, n * ps, H, hd))
        v = jnp.broadcast_to(vg.reshape(L, 1, n * ps, H, hd),
                             (L, batch, n * ps, H, hd))
        return k, v, lease.length

    def release_lease(self, lease: PrefixLease) -> None:
        with self._lock:
            self.pool.release(lease.pages)
            entry = self._entries.get(lease.template_id)
            if entry is not None and entry.leases > 0:
                entry.leases -= 1

    def page_table(self, leases: Sequence[PrefixLease]):
        """Batch leases into the paged-attention calling convention:
        -> (page_table (B, P) i32 with -1 past each prefix's last page,
        lengths (B,) i32)."""
        P = max(len(l.pages) for l in leases)
        table = np.full((len(leases), P), -1, np.int32)
        for i, l in enumerate(leases):
            table[i, : len(l.pages)] = l.pages
        lengths = np.asarray([l.length for l in leases], np.int32)
        return jnp.asarray(table), jnp.asarray(lengths)

    def extend(self, parent_id: str, child_id: str, k_suffix, v_suffix,
               *, length: Optional[int] = None) -> int:
        """Copy-on-write suffix extension: the child shares every FULL
        parent page (refcount bump, no copy) and copies only the parent's
        partial tail page before appending the suffix K/V.

        k/v_suffix: (L, S, Hkv, hd); ``length`` valid suffix tokens
        (default S). Returns the number of NEW pages written."""
        S = int(k_suffix.shape[1])
        length = S if length is None else int(length)
        assert 0 < length <= S, (length, S)
        ps = self.pool.page_size
        with self._lock:
            parent = self._entries.get(parent_id)
            if parent is None:
                raise KeyError(f"unknown parent prefix {parent_id!r}")
            # Pin the parent for the duration: with leases == 0 it would be
            # a legal victim for _alloc_locked's LRU sweep, whose eviction
            # would free the parent's pages and let the child's new rows be
            # carved out of them — retain(shared) below would then re-pin
            # freed/overwritten rows and the child would silently hold
            # corrupted KV. (This also makes a child_id == parent_id
            # replace fail loudly instead of freeing the pages mid-read.)
            parent.leases += 1
            try:
                if child_id in self._entries:
                    if self._entries[child_id].leases:
                        raise PagePoolExhausted(
                            f"prefix {child_id!r} is leased; cannot replace"
                        )
                    self._release_locked(child_id)
                n_full, tail = divmod(parent.length, ps)
                new_len = parent.length + length
                n_new = -(-new_len // ps) - n_full
                shared = list(parent.pages[:n_full])
                rows = self._alloc_locked(n_new)
                # tail-page data precedes the suffix in the first new page
                if tail:
                    tk, tv = self.pool.gather(parent.pages[n_full : n_full + 1])
                    tk, tv = tk[:, 0, :tail], tv[:, 0, :tail]  # (L, tail, H, hd)
                    k_data = jnp.concatenate([tk.astype(k_suffix.dtype),
                                              k_suffix[:, :length]], axis=1)
                    v_data = jnp.concatenate([tv.astype(v_suffix.dtype),
                                              v_suffix[:, :length]], axis=1)
                else:
                    k_data, v_data = k_suffix[:, :length], v_suffix[:, :length]
                kp, vp = self._paginate(k_data, v_data, tail + length, n_new)
                self.pool.write(rows, kp, vp)
                self.pool.retain(shared)
                self._seq += 1
                self._entries[child_id] = _Prefix(shared + rows, new_len,
                                                  self._seq)
                self._pages_built.inc(n_new)
            finally:
                parent.leases -= 1
        return n_new

    def release(self, template_id: str) -> bool:
        """Free a prefix's pages (refcount-decrement; COW children and
        outstanding leases keep shared rows alive). This is the plan-cache
        eviction listener: wired via ``PlanCache.add_evict_listener``, it
        runs for every hot-tier delete, so unknown ids are a no-op."""
        with self._lock:
            if template_id not in self._entries:
                return False
            self._release_locked(template_id)
            return True

    def clear(self) -> None:
        with self._lock:
            for tid in list(self._entries):
                self._release_locked(tid)

    def __contains__(self, template_id: str) -> bool:
        with self._lock:
            return template_id in self._entries

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def length_of(self, template_id: str) -> Optional[int]:
        with self._lock:
            entry = self._entries.get(template_id)
            return None if entry is None else entry.length


# ---------------------------------------------------------------------------
# The single cache point
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CachePoint:
    """One prefix/suffix split for a request batch: the first
    ``prefix_len`` prompt columns are the plan template (shared KV, keyed
    ``template_id``); everything after is the per-request adaptation
    prompt (fresh prefill). Exactly one cache point per request — a
    second split, or anything volatile ahead of this one, would fork the
    shared prefix and defeat caching."""

    template_id: str
    prefix_len: int


def plan_cache_point(template_id: str, template_tokens,
                     prompt_tokens) -> Optional[CachePoint]:
    """Place the single cache point after the template and before the
    adaptation prompt. Returns None when the placement is unsafe: the
    prompt doesn't literally start with the template tokens (on every
    batch row), or there is no adaptation suffix left to prefill."""
    t = np.asarray(template_tokens).reshape(-1)
    p = np.atleast_2d(np.asarray(prompt_tokens))
    if t.size == 0 or t.size >= p.shape[1]:
        return None
    if not np.array_equal(p[:, : t.size],
                          np.broadcast_to(t, (p.shape[0], t.size))):
        return None
    return CachePoint(template_id=template_id, prefix_len=int(t.size))


__all__ = [
    "CachePoint",
    "KVPrefixCache",
    "PagePool",
    "PagePoolExhausted",
    "PrefixLease",
    "plan_cache_point",
    "pool_for_config",
]
