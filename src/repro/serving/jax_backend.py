"""JaxBackend: the APC control plane driving real JAX model engines.

Semantics (which plan/keyword/answer is produced) come from the simulated
behavioral layer — random-weight models emit no usable text — while every
control-plane LM call is *executed* on the data plane with a token count
matching the call (prefill prompt tokens, decode output tokens). This is the
standard synthetic-workload methodology: real compute, synthetic content.
The cost model keeps its own rates; the engines' ``EngineStats`` are read
by whoever measures them. Under a tracer each executed call records
``backend.call`` (``role``, ``prompt_len``, ``max_new``) around the
engine's ``engine.generate``.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from repro.core.backends import SimulatedBackend
from repro.data.tokenizer import HashTokenizer
from repro.envs.base import Task
from repro.obs import trace_span
from repro.obs.names import SPAN_BACKEND_CALL
from repro.serving.engine import Engine


class JaxBackend(SimulatedBackend):
    """SimulatedBackend + real data-plane execution per role."""

    def __init__(self, engines: Dict[str, Engine], *, max_exec_tokens: int = 32, **kw):
        super().__init__(**kw)
        self.engines = engines
        self.tok = HashTokenizer()
        self.max_exec = max_exec_tokens

    def _exec(self, role: str, prompt_text: str, out_tokens: int) -> None:
        eng = self.engines.get(role)
        if eng is None:
            return
        ids = self.tok.encode(prompt_text)[: eng.max_len - self.max_exec - 8]
        if not ids:
            ids = [1]
        arr = np.asarray([ids], np.int32)
        max_new = min(out_tokens, self.max_exec)
        with trace_span(SPAN_BACKEND_CALL, role=role, prompt_len=len(ids),
                        max_new=max_new):
            eng.generate(arr, max_new=max_new)

    # -- overridden role calls (same returns, + real execution) ----------

    def extract_keyword(self, task: Task):
        kw, i, o = super().extract_keyword(task)
        self._exec("keyword_extractor", task.query, o)
        return kw, i, o

    def plan(self, task: Task, responses, *, large: bool, round_idx: int):
        msg, i, o = super().plan(task, responses, large=large, round_idx=round_idx)
        role = "large_planner" if large else "small_planner"
        self._exec(role, task.query + " " + str(responses)[-512:], o)
        return msg, i, o

    def adapt(self, task: Task, template, responses, *, round_idx: int,
              full_history: bool = False):
        msg, i, o = super().adapt(
            task, template, responses, round_idx=round_idx, full_history=full_history
        )
        self._exec("small_planner", task.query, o)
        return msg, i, o

    def act(self, task: Task, plan):
        resp, i, o = super().act(task, plan)
        self._exec("actor", plan.text, o)
        return resp, i, o
