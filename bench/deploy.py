"""Assemble the system under test from a configuration file.

Everything here drives the program through its public pieces: the model
configs of ``repro.configs.registry`` (widths replaced by the file's),
``repro.serving.engine.Engine``, ``repro.serving.jax_backend.JaxBackend``,
``repro.core.agent_loop.PlanActAgent`` and its plan cache. Roles that share
an architecture share one engine, as ``repro.launch.serve.build_engines``
builds them. The weights are the benchmark's own: made on the device from
the run seed in one jitted call, in the program's parameter layout, so the
reference can read the same arrays without taking anything the program made.
"""

from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

CONFIG_DIR = Path(__file__).resolve().parent / "configs"

# configuration-file key -> repro ModelConfig field
_FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "norm": "norm_type",
    "attention_bias": "qkv_bias",
    "qk_norm": "qk_norm",
    "tie_word_embeddings": "tie_embeddings",
    "activation": "mlp_act",
    "dtype": "dtype",
}


def load(name: str, directory: Path = CONFIG_DIR) -> dict:
    return json.loads((Path(directory) / f"{name}.json").read_text())


def model_config(spec: dict):
    """The program's ModelConfig for one model of the file, at its widths."""
    from repro.configs import registry

    base = registry.get(spec["arch"])
    return dataclasses.replace(
        base, param_dtype=spec["dtype"],
        **{field: spec[key] for key, field in _FIELDS.items()},
    )


def seed_key(seed: int):
    """A PRNG key from a seed of any size (run seeds may exceed 32 bits)."""
    import jax

    key = jax.random.key(seed & 0xFFFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_params(cfg, seed: int, std: float):
    """Random weights in the program's layout, on the device, one jitted call.

    Matrices are uniform with standard deviation ``std``; norm scales are
    uniform in [0.9, 1.1] and biases uniform with ``std``, so a reference
    that dropped either would disagree with the program.
    """
    import jax
    import jax.numpy as jnp

    from repro.models import lm

    shapes = lm.abstract_params(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, sd) in enumerate(leaves):
            name = jax.tree_util.keystr(path)
            u = jax.random.uniform(jax.random.fold_in(key, i), sd.shape, jnp.float32,
                                   -1.0, 1.0)
            if name.endswith("scale']"):
                v = 1.0 + 0.1 * u
            else:
                v = (std * 3.0 ** 0.5) * u
            out.append(v.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed))


@dataclasses.dataclass
class Call:
    """One ``Engine.generate`` call: what the engine was given and served."""

    arch: str
    prompt: np.ndarray  # (S,) int32
    out: np.ndarray  # (n,) int32


def recording_engine_class():
    from repro.serving.engine import Engine

    class RecordingEngine(Engine):
        """Engine whose ``generate`` calls are recorded for the check.

        ``annotate`` wraps each call in a profiler annotation carrying its
        architecture, prompt length and host clock, so a trace can be
        placed on the host's clock and its programs assigned to engines.
        """

        def __init__(self, *a, arch: str, log: List[Call], **kw):
            super().__init__(*a, **kw)
            self.arch = arch
            self.log = log
            self.recording = False
            self.annotate = False

        def generate(self, tokens, max_new=32, **kw):
            if self.annotate:
                import jax

                with jax.profiler.TraceAnnotation(
                    "bench.generate", arch=self.arch, S=int(tokens.shape[1]),
                    n=int(max_new), t_ns=int(time.perf_counter() * 1e9),
                ):
                    out = super().generate(tokens, max_new, **kw)
            else:
                out = super().generate(tokens, max_new, **kw)
            if self.recording:
                self.log.append(Call(self.arch, np.array(tokens[0]), np.array(out[0])))
            return out

    return RecordingEngine


def timed_backend_class():
    from repro.serving.jax_backend import JaxBackend

    class TimedJaxBackend(JaxBackend):
        """JaxBackend that times each role call around ``super()``.

        ``turns`` gets (role, seconds) per call while ``recording``;
        ``annotate`` wraps each call in a profiler annotation; ``after_turn``
        runs after each call (the traced run stops its profiler there).
        """

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.turns: List[Tuple[str, float]] = []
            self.recording = False
            self.annotate = False
            self.after_turn: Optional[Callable[[], None]] = None

        def _timed(self, role, fn, *a, **kw):
            t0 = time.perf_counter()
            if self.annotate:
                import jax

                with jax.profiler.TraceAnnotation(f"bench.{role}", t_ns=int(t0 * 1e9)):
                    r = fn(*a, **kw)
            else:
                r = fn(*a, **kw)
            t1 = time.perf_counter()
            if self.recording:
                self.turns.append((role, t1 - t0))
            if self.after_turn is not None:
                self.after_turn()
            return r

        def extract_keyword(self, task):
            return self._timed("extract_keyword", super().extract_keyword, task)

        def plan(self, task, responses, **kw):
            return self._timed("plan", super().plan, task, responses, **kw)

        def adapt(self, task, template, responses, **kw):
            return self._timed("adapt", super().adapt, task, template, responses, **kw)

        def act(self, task, plan):
            return self._timed("act", super().act, task, plan)

    return TimedJaxBackend


class ShapeProbe:
    """Stands in for an engine and records the prompt lengths it is given."""

    def __init__(self, arch: str, max_len: int, seen: set):
        self.arch, self.max_len, self.seen = arch, max_len, seen

    def generate(self, tokens, max_new=32, **kw):
        self.seen.add((self.arch, int(tokens.shape[1])))
        return np.zeros((tokens.shape[0], max_new), np.int32)


def agent_config(doc: dict):
    from repro.core.agent_loop import AgentConfig

    pc = doc["plan_cache"]
    return AgentConfig(
        method="apc", max_iterations=pc["max_iterations"],
        cache_capacity=pc["capacity"], fuzzy=pc["fuzzy"],
        fuzzy_threshold=pc["fuzzy_threshold"], index_backend=pc["index_backend"],
    )


def ledger():
    from repro.configs.apc_minion import APCDeployment
    from repro.core.cost_model import CostLedger

    return CostLedger(pricing_map=dict(APCDeployment().pricing))


def prewarmed_cache(doc: dict, traffic: dict, prewarm_tasks, *, index_backend=None):
    """A plan cache filled through ``PlanActAgent.prewarm`` on the simulated
    backend, which runs no chip work; the same pool gives the same keys."""
    from repro.core.agent_loop import PlanActAgent
    from repro.core.backends import SimulatedBackend

    cfg = agent_config(doc)
    if index_backend is not None:
        cfg.index_backend = index_backend
    agent = PlanActAgent(SimulatedBackend(seed=traffic["semantic_seed"]), ledger(), cfg)
    agent.prewarm(prewarm_tasks)
    return agent.cache


def build_engines(doc: dict, params: Dict[str, object], log: List[Call]):
    """role -> RecordingEngine over the given weights (one per architecture)."""
    Engine = recording_engine_class()
    built, engines = {}, {}
    for role, arch in doc["roles"].items():
        if arch not in built:
            built[arch] = Engine(model_config(doc["models"][arch]), params[arch],
                                 max_len=doc["max_len"], arch=arch, log=log)
        engines[role] = built[arch]
    return engines, built


def agent(doc: dict, traffic: dict, engines, cache, *, max_exec: int):
    from repro.core.agent_loop import PlanActAgent

    backend = timed_backend_class()(engines, max_exec_tokens=max_exec,
                                    seed=traffic["semantic_seed"])
    return PlanActAgent(backend, ledger(), agent_config(doc), cache=cache), backend


def prompt_shapes(doc: dict, traffic: dict, prewarm_tasks, stream) -> set:
    """(arch, prompt length) of every engine call the stream makes, from a
    pass of the stream through a throwaway agent whose engines only record
    shapes. It has its own plan cache, prewarmed the same way and on the
    same index backend, so it meets the same hits and misses and compiles
    the index programs the window will use."""
    seen: set = set()
    probes, engines = {}, {}
    for role, arch in doc["roles"].items():
        probes.setdefault(arch, ShapeProbe(arch, doc["max_len"], seen))
        engines[role] = probes[arch]
    cache = prewarmed_cache(doc, traffic, prewarm_tasks)
    a, _ = agent(doc, traffic, engines, cache, max_exec=doc["max_exec_tokens"])
    for t in stream:
        a.run_task(_fresh(t))
    return seen


def _fresh(task):
    """A copy of a task with an empty workspace (a run writes into it)."""
    from repro.envs.base import Workspace

    return dataclasses.replace(task, workspace=Workspace())
