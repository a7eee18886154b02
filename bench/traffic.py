"""The one traffic generator: turns a mix's data file into agent tasks.

A mix is a JSON file under ``bench/traffic/`` holding its intents (keyword,
query template, retrieval rounds, final expression, paraphrase keywords),
its slot entities and value ranges, and how tasks are drawn from them:

* ``"kind": "hot"`` — a hot set of intents. The pool is ``blocks`` blocks
  of ``block`` tasks; every block holds each intent as often as a Zipf law
  of exponent ``zipf_s`` over the intents' ranks apportions (largest
  remainder), and ``prewarm_per_intent`` extra tasks per intent admit the
  templates in set-up.
* ``"kind": "unique"`` — one task per intent, none prewarmed: every task
  brings a keyword new to the plan cache.

The pool (slots, document values, task ids) is fixed by ``pool_seed``, so
every run seed serves the same set of tasks, of the same sizes. The run's
``--seed`` only orders them: blocks in a seeded order and, inside each
block, an intent's tasks in a seeded order over that intent's places. The
places are the same in every block and for every seed: each prefix of a
block holds every intent as near its share as it can. Intents differ in
rounds, so a window that closes inside a block does the same work under
every seed (a unique mix is one block, its tasks in a seeded order).

Tasks are the program's own ``repro.envs.base.Task``; the generator copies
the environments' arithmetic for slots, contexts and ground truth so that
the program cannot move the yardstick.
"""

from __future__ import annotations

import hashlib
import json
import math
import random
from pathlib import Path
from typing import Dict, List, Tuple

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    doc = json.loads((Path(directory) / f"{name}.json").read_text())
    doc["name"] = name
    return doc


def _rng(*parts) -> random.Random:
    h = hashlib.blake2b("|".join(str(p) for p in parts).encode(), digest_size=8)
    return random.Random(int.from_bytes(h.digest(), "little"))


def zipf_counts(n_items: int, total: int, s: float) -> List[int]:
    """Largest-remainder apportionment of ``total`` over ranks 1/r^s."""
    w = [1.0 / (r + 1) ** s for r in range(n_items)]
    quota = [total * x / sum(w) for x in w]
    counts = [int(math.floor(q)) for q in quota]
    order = sorted(range(n_items), key=lambda i: (-(quota[i] - counts[i]), i))
    for i in order[: total - sum(counts)]:
        counts[i] += 1
    return counts


def _compute(expr: str, bindings: Dict[str, float]):
    env = {k: float(v) for k, v in bindings.items()}
    env.update({"abs": abs, "min": min, "max": max, "sqrt": math.sqrt})
    try:
        return float(eval(expr, {"__builtins__": {}}, env))  # noqa: S307 fixed data
    except Exception:
        return None


def _task(doc: dict, intent, task_id: str, rng: random.Random):
    from repro.envs.base import Task

    fields = intent.all_fields
    lo, hi = doc["value_range"]
    for _ in range(6):  # redraw degenerate documents (division by ~0)
        context: Dict[str, float] = {f: round(rng.uniform(lo, hi), 2) for f in fields}
        distractors = []
        for j in range(doc["n_distractor_fields"]):
            name = f"{doc['name']}_aux_metric_{rng.randint(0, 999)}_{j}"
            context[name] = round(rng.uniform(lo, hi), 2)
            distractors.append(name)
        gt = _compute(intent.expr, {"abcdefghij"[i]: context[f] for i, f in enumerate(fields)})
        if gt is not None and math.isfinite(gt) and abs(gt) <= 1e12:
            break
    slots = {k: rng.choice(v) for k, v in doc["entities"].items()}
    return Task(
        id=task_id, env=doc["name"], query=intent.query_template.format(**slots),
        intent=intent, slots=slots, context=context, distractors=distractors,
        gt_answer=gt, context_tokens=rng.randint(*doc["context_tokens_range"]),
    )


def intents(doc: dict):
    from repro.envs.base import IntentSpec

    return [
        IntentSpec(
            id=f"{doc['name']}-{i}", keyword=it["keyword"], query_template=it["query"],
            rounds=[list(r) for r in it["rounds"]], expr=it["expr"],
            paraphrase_keywords=tuple(it["paraphrases"]),
        )
        for i, it in enumerate(doc["intents"])
    ]


def spread_order(counts: List[int]) -> List[int]:
    """Intent index per place of a block: each place goes to the intent
    furthest below its share of the places so far (ties to the lower index)."""
    total, placed, order = sum(counts), [0] * len(counts), []
    for p in range(total):
        i = max(range(len(counts)), key=lambda j: ((p + 1) * counts[j] / total - placed[j], -j))
        placed[i] += 1
        order.append(i)
    return order


def generate(doc: dict, seed: int) -> Tuple[list, list]:
    """(prewarm tasks, ordered task stream) for one run seed."""
    specs = intents(doc)
    name, pool_seed = doc["name"], doc["pool_seed"]
    if doc["kind"] == "hot":
        counts = zipf_counts(len(specs), doc["block"], doc["zipf_s"])
        prewarm = [
            _task(doc, it, f"{name}-warm-{i}-{j}", _rng(name, pool_seed, "warm", i, j))
            for i, it in enumerate(specs) for j in range(doc["prewarm_per_intent"])
        ]
        blocks = []
        for b in range(doc["blocks"]):
            picks = [it for it, c in zip(specs, counts) for _ in range(c)]
            blocks.append([
                _task(doc, it, f"{name}-{b}-{j}", _rng(name, pool_seed, b, j))
                for j, it in enumerate(picks)
            ])
    elif doc["kind"] == "unique":
        prewarm = []
        blocks = [[
            _task(doc, it, f"{name}-{j}", _rng(name, pool_seed, j))
            for j, it in enumerate(specs)
        ]]
    else:
        raise ValueError(f"unknown traffic kind {doc['kind']!r}")
    order = random.Random(seed)
    order.shuffle(blocks)
    stream = []
    for blk in blocks:
        if doc["kind"] == "unique":
            blk = list(blk)
            order.shuffle(blk)
            stream.extend(blk)
            continue
        by_intent = [[t for t in blk if t.intent is it] for it in specs]
        for tasks in by_intent:
            order.shuffle(tasks)
        stream.extend(by_intent[i].pop() for i in spread_order(counts))
    return prewarm, stream

