"""The Plan-Act agent loop: Algorithms 1-3 from the paper.

``PlanActAgent`` owns one serving deployment (backends + plan store +
ledger) and the two inner loops every method composes:

* ``_loop_scratch`` — plan from scratch on the large/small planner
  (Algorithm 3's replan path and both no-cache baselines);
* ``_loop_adapt``   — adapt a cached template with the small planner
  (Algorithm 2).

WHICH loop runs, and how the plan store is consulted, is a method
strategy: ``run_task`` dispatches to a class registered in
:mod:`repro.memory.registry` (``@register_method``) and implemented in
:mod:`repro.core.methods` — apc, the paper's baselines, and any
out-of-tree method a scenario registers. There is no per-method branching
here.

Under a tracer each task is one ``agent.task`` span (``task``, ``method``,
``hit``), the root of its lookup and role-call spans.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from repro.core.backends import SimulatedBackend
from repro.core.cache import PlanCache
from repro.core.cost_model import CostLedger
from repro.core.journal import StepJournal
from repro.core.template import ExecutionLog, PlanTemplate, make_template
from repro.envs.base import Task
from repro.obs import compile_counter, trace_span
from repro.obs.names import SPAN_AGENT_TASK


@dataclass
class RunRecord:
    task_id: str
    method: str
    correct: bool
    hit: bool
    keyword: str
    iterations: int
    answer: Optional[float]
    cost: float
    latency_s: float
    cache_lookup_s: float = 0.0
    cache_gen_s: float = 0.0
    speculated: bool = False
    spec_outcome: str = ""  # "" | commit | patch | rollback
    compiles: int = 0  # XLA compiles the process made while the task ran


@dataclass
class AgentConfig:
    method: str = "apc"
    max_iterations: int = 10
    cache_capacity: int = 100
    fuzzy: bool = False
    fuzzy_threshold: float = 0.8
    semantic_threshold: float = 0.85
    index_backend: str = "auto"  # repro.index backend for fuzzy/semantic search
    eviction: str = "lru"  # repro.memory eviction policy (lru | lfu | cost)
    async_cachegen: bool = False  # beyond-paper: don't block on cache writes
    seed: int = 0


class PlanActAgent:
    """One agent serving deployment: backends + cache + ledger."""

    def __init__(
        self,
        backend: SimulatedBackend,
        ledger: CostLedger,
        config: AgentConfig,
        cache: Optional[PlanCache] = None,
    ):
        self.be = backend
        self.ledger = ledger
        self.cfg = config
        # NB: `cache is not None` — an empty PlanCache is falsy
        self.cache_external = cache is not None
        self.cache: Optional[PlanCache] = cache
        self._compiles = compile_counter()
        # registry dispatch: the method strategy may SUPPLY self.cache in
        # its setup() (cascade builds an exact->fuzzy->semantic store), so
        # the default store is built only if neither the caller nor the
        # strategy provided one — no throwaway construction.
        from repro.core.methods import make_method

        self._method = make_method(config.method, self)
        if self.cache is None:
            self.cache = PlanCache(
                capacity=config.cache_capacity,
                fuzzy=config.fuzzy,
                fuzzy_threshold=config.fuzzy_threshold,
                index_backend=config.index_backend,
                eviction=config.eviction,
            )

    # ==================================================================
    # Cache pre-warming (paper §4.5: "pre-populating the cache with
    # offline samples before deployment" mitigates cold start)
    # ==================================================================

    def prewarm(self, tasks: List[Task]) -> int:
        """Run offline samples through the miss path to populate templates.
        Costs accrue to the ledger (offline budget); returns #inserted."""
        inserted = 0
        for task in tasks:
            kw, ki, ko = self.be.extract_keyword(task)
            self.ledger.record("keyword_extractor", ki, ko)
            if kw in self.cache:
                continue
            answer, _, log, _ = self._loop_scratch(task, large=True)
            if answer is not None and log.final_answer is not None:
                gi, go = self.be.cachegen_tokens(log.raw_tokens())
                self.ledger.record("cache_generator", gi, go)
                miss = self.be.generalization_misses(task)
                self.cache.insert(kw, make_template(log, kw, task.slots,
                                                    miss_slots=miss))
                inserted += 1
        return inserted

    # ==================================================================
    # Algorithm 1: end-to-end (registry dispatch, no method branching)
    # ==================================================================

    def run_task(self, task: Task) -> RunRecord:
        n0 = self._compiles.total
        with trace_span(SPAN_AGENT_TASK, task=task.id,
                        method=self.cfg.method) as sp:
            rec = self._method.run(task)
            sp.set(hit=rec.hit)
        rec.compiles = self._compiles.total - n0
        return rec

    # ==================================================================
    # inner loops (shared by every method strategy)
    # ==================================================================

    def _record_act_effects(
        self, task: Task, journal: StepJournal, round_idx: int,
        resp: Dict[str, Any],
    ) -> None:
        """Journal one actor round's env writes (reversible workspace
        puts). With a caller-owned journal the step stays open until the
        verifier commits/patches/rolls back; the default loops commit
        per step, so the journal is the single env-mutation path either
        way (the ``journal-discipline`` checker pins this)."""
        step = journal.begin_step(f"round-{round_idx}")
        ws = task.workspace
        for name in sorted(resp.get("values", {})):
            step.applied(ws.write(f"r{round_idx}/{name}", resp["values"][name]))

    def _loop_scratch(
        self, task: Task, *, large: bool,
        journal: Optional[StepJournal] = None,
        responses: Optional[List[Dict[str, Any]]] = None,
        start_round: int = 0,
    ) -> Tuple[Optional[float], int, ExecutionLog, float]:
        """Plan from scratch. ``responses``/``start_round`` let the
        speculative patch path re-enter mid-task: the verified planner
        continues from the committed prefix's retrievals instead of
        round 0."""
        role = "large_planner" if large else "small_planner"
        log = ExecutionLog(task_query=task.query)
        responses = list(responses or [])
        own_journal = journal is None
        journal = journal if journal is not None else StepJournal()
        lat = 0.0
        answer = None
        iters = 0
        for it in range(start_round, self.cfg.max_iterations):
            iters += 1
            msg, pi, po = self.be.plan(task, responses, large=large, round_idx=it)
            lat += self.ledger.record(role, pi, po)
            if msg.kind == "answer":
                log.final_answer = {"answer_text": msg.text, "op": msg.op}
                answer = msg.op.get("value")
                break
            resp, ai, ao = self.be.act(task, msg)
            lat += self.ledger.record("actor", ai, ao)
            responses.append(resp)
            log.append({"message": msg.text, "op": msg.op}, resp)
            self._record_act_effects(task, journal, it, resp)
            if own_journal:
                journal.commit()  # non-speculative: finalize per step
        return answer, iters or self.cfg.max_iterations, log, lat

    def _loop_adapt(
        self, task: Task, template: PlanTemplate, *, full_history: bool,
        journal: Optional[StepJournal] = None,
    ) -> Tuple[Optional[float], int, float]:
        responses: List[Dict[str, Any]] = []
        lat = 0.0
        n_rounds = max(1, template.n_rounds())
        own_journal = journal is None
        journal = journal if journal is not None else StepJournal()
        for it in range(self.cfg.max_iterations):
            msg, pi, po = self.be.adapt(
                task, template, responses, round_idx=it, full_history=full_history
            )
            lat += self.ledger.record("small_planner", pi, po)
            if msg.kind == "answer":
                return msg.op.get("value"), it + 1, lat
            resp, ai, ao = self.be.act(task, msg)
            lat += self.ledger.record("actor", ai, ao)
            responses.append(resp)
            self._record_act_effects(task, journal, it, resp)
            if own_journal:
                journal.commit()
            if it + 1 >= n_rounds and it + 1 < self.cfg.max_iterations:
                continue  # next adapt() call emits the answer
        return None, self.cfg.max_iterations, lat
