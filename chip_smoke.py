#!/usr/bin/env python3
"""Bring-up check: the plan-cache serving path on one TPU chip.

    python chip_smoke.py

Runs in one process (a chip belongs to one process at a time), one function
per phase:

  a. device gate — the default device must be a TPU. JAX falls back to the
     CPU in silence when the TPU backend fails to start, and the CPU
     branches of ``kernels/ops.py`` (Pallas interpret mode, the dense XLA
     top-k) key on the default backend, so nothing else runs before this.
  b. serving — ``repro.launch.serve.serve`` on the ``ONE_CHIP`` deployment
     at published widths (olmo-1b: small planner and keyword extractor;
     qwen2.5-3b: actor and large planner), financebench tasks, fuzzy
     plan-cache lookups on the device-resident index. Checks: at least one
     plan-cache hit and one miss, every engine served tokens, the index
     answered on its device backend, every parameter leaf lives on the
     chip, and a second (warm) pass repeats the first pass's counts.
  c. numerics — olmo-1b's last-token prefill logits, bf16 on the chip,
     against the same seeded weights in float32 on the CPU backend at
     ``highest`` matmul precision: relative L2 error within REL_L2_BOUND.
  d. index — top-k over a 65,536 x 384 bank through
     ``SimilarityIndex(backend="device")`` (the Pallas kernel on the chip)
     equals the ``brute`` backend's rows.

Each phase prints one ``phase {...}`` JSON line: its check values, set-up,
cold and warm wall seconds, compilations (count, seconds, persistent-cache
hits) and the device's peak bytes in use. The last line of the output is
``{"ok": true, "device": {...}}``. Any failed check raises, so the exit
code is non-zero and that line is never printed.

Times here include compilation and host work; they show that the path
runs, and are not speed results.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402

from repro.configs import registry  # noqa: E402
from repro.configs.apc_minion import ONE_CHIP  # noqa: E402
from repro.index import DIM, SimilarityIndex  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.serve import build_engines, serve  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.obs import InMemoryExporter, Tracer, use_tracer  # noqa: E402
from repro.obs.names import SPAN_ENGINE_GENERATE, SPAN_INDEX_TOPK  # noqa: E402
from repro.serving.engine import Engine  # noqa: E402

# financebench (seed 0) first repeats a task keyword at task 13: 16 tasks
# give plan-cache hits as well as misses
SERVE_TASKS = 16
NUMERICS_SEQ = 64
INDEX_ROWS = 65_536
INDEX_QUERIES = 64
INDEX_K = 4
# bf16 keeps 8 significant bits: each rounding of a weight or activation is
# off by up to 2^-8 relative (about 2.3e-3 RMS), and the roundings of a
# forward add up roughly in quadrature. This phase run on the CPU backend
# (bf16 vs f32) with olmo-1b cut in width gives 1.3e-2 at 16 layers and
# d_model 512, and 1.7e-2 at d_model 1024: about x1.3 per doubling, so
# about 2.3e-2 at the published 2048. 5e-2 leaves twice that, while a wrong
# layout, mask or kernel gives errors of order 1.
REL_L2_BOUND = 5e-2

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Counts XLA compilations in this process (persistent-cache hits
    included, which take the same path) and the seconds they took."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.compiles += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self):
        return {"compiles": self.compiles, "compile_s": self.seconds,
                "cache_hits": self.cache_hits}


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _require(ok, what):
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _placed_on(tree, device):
    return all(leaf.devices() == {device} for leaf in jax.tree.leaves(tree))


# -- phases -------------------------------------------------------------------


def device_gate():
    """(a) Refuse to run anywhere but on a TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu" or jax.default_backend() != "tpu":
        raise SystemExit(
            f"chip_smoke: the default device is {dev.platform!r}, not a TPU; "
            f"nothing was run"
        )
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def phase_serve(*, full=True, n=SERVE_TASKS):
    """(b) The served path: build the engines, serve ``n`` tasks twice."""
    dev = jax.devices()[0]

    def build():
        engines = build_engines(ONE_CHIP, full=full)
        jax.block_until_ready([e.params for e in engines.values()])
        return engines

    engines, setup_s = _timed(build)
    for role, eng in engines.items():
        _require(_placed_on(eng.params, dev), f"{role} params are not all on {dev}")
    run = partial(serve, ONE_CHIP, env="financebench", n=n, engines=engines,
                  log=lambda _: None)
    spans = InMemoryExporter()
    with use_tracer(Tracer(exporters=[spans])):
        cold, cold_s = _timed(run)
    warm, warm_s = _timed(run)
    topk = [s for s in spans.spans if s["name"] == SPAN_INDEX_TOPK]
    generate = [s for s in spans.spans if s["name"] == SPAN_ENGINE_GENERATE]
    _require(cold.hits >= 1 and cold.misses >= 1, f"hits and misses: {cold}")
    _require(cold.tokens and all(t > 0 for t in cold.tokens.values()),
             f"an engine served no tokens: {cold}")
    _require(topk and all(s["attrs"]["backend"] == "device" for s in topk),
             "the plan cache did not search the device index")
    _require((warm.hits, warm.misses, warm.tokens)
             == (cold.hits, cold.misses, cold.tokens),
             f"warm pass differs: {cold} vs {warm}")
    return {"setup_s": setup_s, "cold_s": cold_s, "warm_s": warm_s,
            "tasks": cold.tasks, "hits": cold.hits, "misses": cold.misses,
            "accuracy": cold.accuracy, "tokens": cold.tokens,
            "index_topk_calls": len(topk), "engine_calls": len(generate),
            "widths": {e.cfg.name: [e.cfg.num_layers, e.cfg.d_model,
                                    e.cfg.num_heads, e.cfg.num_kv_heads]
                       for e in engines.values()}}


def phase_numerics(cfg, *, seq_len=NUMERICS_SEQ, seed=0):
    """(c) Served prefill in ``cfg``'s dtype on the default device vs the
    same weights in float32 on the CPU backend."""
    cpu = jax.devices("cpu")[0]
    cfg32 = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
    tokens = np.random.RandomState(seed).randint(
        1, cfg.vocab_size, (1, seq_len)).astype(np.int32)

    def reference():
        with jax.default_device(cpu), jax.default_matmul_precision("highest"):
            p32 = jax.jit(partial(lm.init_params, cfg32))(jax.random.PRNGKey(seed))
            logits, _ = Engine(cfg32, p32, max_len=seq_len).prefill(tokens)
        return p32, logits

    (p32, ref), ref_s = _timed(reference)
    dtypes = lm.abstract_params(cfg)
    params = jax.device_put(
        jax.tree.map(lambda a, x: x.astype(a.dtype), dtypes, p32),
        jax.devices()[0],
    )
    del p32
    eng = Engine(cfg, params, max_len=seq_len)
    (got, _), cold_s = _timed(lambda: eng.prefill(tokens))
    (again, _), warm_s = _timed(lambda: eng.prefill(tokens))
    got = np.asarray(got, np.float32)
    _require(got.shape == ref.shape == (1, cfg.vocab_size),
             f"logits shapes {got.shape} vs {ref.shape}")
    _require(np.isfinite(got).all(), "non-finite logits")
    _require(np.array_equal(got, np.asarray(again, np.float32)),
             "a repeated prefill gave other logits")
    rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    _require(rel <= REL_L2_BOUND, f"logits rel L2 {rel} > {REL_L2_BOUND}")
    return {"reference_s": ref_s, "cold_s": cold_s, "warm_s": warm_s,
            "model": cfg.name, "seq_len": seq_len, "rel_l2": rel,
            "bound": REL_L2_BOUND,
            "argmax_equal": bool(got.argmax() == ref.argmax())}


def phase_index(*, n_rows=INDEX_ROWS, n_queries=INDEX_QUERIES, k=INDEX_K,
                seed=0):
    """(d) Device-resident top-k at deployment size vs the exact host scan."""

    def unit(m):
        return (m / np.linalg.norm(m, axis=1, keepdims=True)).astype(np.float32)

    def build():
        rng = np.random.RandomState(seed)
        bank = unit(rng.randn(n_rows, DIM))
        # each query mixes k rows, made orthonormal, with falling weights:
        # its top-k is those rows in that order, cosine gaps near 0.05 —
        # far above f32 rounding, so a disagreement is a fault, not a tie
        picks = rng.choice(n_rows, (n_queries, k), replace=False)
        basis = np.linalg.qr(bank[picks].transpose(0, 2, 1))[0]
        bank[picks] = basis.transpose(0, 2, 1)
        weights = np.linspace(1.0, 0.7, k)
        queries = unit(np.einsum("j,qjd->qd", weights, bank[picks])
                       + 0.005 * rng.randn(n_queries, DIM))
        keys = [f"plan-{i}" for i in range(n_rows)]
        device = SimilarityIndex(backend="device", initial_capacity=n_rows)
        brute = SimilarityIndex(backend="brute", initial_capacity=n_rows)
        device.add_batch(keys, bank)
        brute.add_batch(keys, bank)
        return device, brute, queries, picks

    (device, brute, queries, picks), setup_s = _timed(build)
    (s_dev, i_dev), cold_s = _timed(lambda: device.topk(queries, k=k))
    (s_dev2, i_dev2), warm_s = _timed(lambda: device.topk(queries, k=k))
    s_ref, i_ref = brute.topk(queries, k=k)
    _require(np.array_equal(i_dev, i_ref), "device top-k rows differ from brute")
    _require(np.array_equal(i_dev2, i_dev), "a repeated top-k gave other rows")
    _require(np.array_equal(i_ref, picks), "brute top-k missed the mixed rows")
    return {"setup_s": setup_s, "cold_s": cold_s, "warm_s": warm_s,
            "rows": n_rows, "dim": DIM, "queries": n_queries, "k": k,
            "rows_equal": True,
            "max_score_diff": float(np.abs(s_dev - s_ref).max()),
            "min_rank_gap": float(np.diff(-s_ref, axis=1).min())}


# -----------------------------------------------------------------------------


def main():
    device = device_gate()
    print(f"device {json.dumps(device)}", flush=True)
    print(f"compile_cache {compile_cache.enable()}", flush=True)
    log = CompileLog()
    chip = jax.devices()[0]
    phases = (
        ("serve", phase_serve),
        ("numerics", partial(phase_numerics, registry.get("olmo-1b"))),
        ("index", phase_index),
    )
    for name, phase in phases:
        before = log.snapshot()
        out, wall = _timed(phase)
        after = log.snapshot()
        out.update(
            wall_s=wall,
            compiles=after["compiles"] - before["compiles"],
            compile_s=after["compile_s"] - before["compile_s"],
            cache_hits=after["cache_hits"] - before["cache_hits"],
            peak_bytes_in_use=(chip.memory_stats() or {}).get("peak_bytes_in_use"),
        )
        print(f"phase {json.dumps({'name': name, **out}, sort_keys=True)}",
              flush=True)
    print(f"compile_total {json.dumps(log.snapshot())}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
