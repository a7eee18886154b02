"""What decides ``correct``: the timed path's outputs against plain references.

* Engines: a sample, drawn from the seed, of the ``generate`` calls the
  window finished, the longest among them. The reference runs once over
  each prompt with its served tokens, and the number compared is the widest
  gap by which a served token's reference logit lies below the reference's
  best at that position (greedy decoding serves the best token, so a sound
  engine's gaps are rounding). One number per architecture.
* Plan cache: every lookup of the window against a plain exact-then-fuzzy
  matcher (cosine over hashed n-grams, copied from the program's
  ``repro/index/bank.py``) over the keys the cache held, and every
  admission against the rule "a miss that produced an answer is admitted".
* Replay: the window's tasks on the simulated backend with the exact
  ``brute`` index: each task's hit, keyword and answer must repeat.
"""

from __future__ import annotations

import dataclasses
import hashlib
import random
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

BUCKETS = (128, 256, 512, 1024, 2048, 4096)


# -- engines ----------------------------------------------------------------


def sample_calls(calls, arch: str, seed: int, target_tokens: int, max_rows: int,
                 min_rows: int = 3):
    """The longest call of ``arch``, then others in a seeded order, until
    ``target_tokens`` served tokens and ``min_rows`` calls, or ``max_rows``."""
    mine = [c for c in calls if c.arch == arch]
    if not mine:
        return []
    longest = max(range(len(mine)), key=lambda i: (len(mine[i].out), -i))
    rest = [c for i, c in enumerate(mine) if i != longest]
    random.Random(seed ^ 0x5EED).shuffle(rest)
    picked = [mine[longest]]
    for c in rest:
        enough = sum(len(p.out) for p in picked) >= target_tokens and len(picked) >= min_rows
        if enough or len(picked) >= max_rows:
            break
        picked.append(c)
    return picked


def _row(call, T):
    """Tokens fed to the reference and, per position, the served token it
    predicts (position S-1+j predicts served token j)."""
    S, n = len(call.prompt), len(call.out)
    seq = np.concatenate([call.prompt, call.out[:-1]]).astype(np.int32)
    tokens = np.zeros((1, T), np.int32)
    tokens[0, : len(seq)] = seq
    served = np.zeros((1, T), np.int32)
    served[0, S - 1 : S - 1 + n] = call.out
    mask = np.zeros((1, T), bool)
    mask[0, S - 1 : S - 1 + n] = True
    return tokens, served, mask


def bucket(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"sequence of {n} tokens is longer than any reference bucket")


def logit_gaps(ref, spec: dict, params, calls, *, control: bool = False) -> Dict[str, float]:
    """Widest gap of the served tokens (``served``) and, with ``control``,
    of the tokens the fp8 control puts first at the same positions
    (``control``), each against the float32 reference's best logit."""
    out = {"served": 0.0, "tokens": 0}
    if control:
        out["control"] = 0.0
    for call in calls:
        T = bucket(len(call.prompt) + len(call.out) - 1)
        tokens, served, mask = _row(call, T)
        alt = np.asarray(ref.control_argmax(spec, params, tokens)) if control else served
        best, at_served, at_alt = (np.asarray(a) for a in ref.reference(spec, params, tokens, served, alt))
        out["served"] = max(out["served"], float(np.max((best - at_served)[mask])))
        out["tokens"] += int(mask.sum())
        if control:
            out["control"] = max(out["control"], float(np.max((best - at_alt)[mask])))
    return out


# -- plan cache -------------------------------------------------------------

DIM = 384


def _grams(text: str) -> List[str]:
    words = re.findall(r"[a-z0-9]+", text.lower())
    grams = list(words)
    for w in words:
        grams += [w[i : i + 3] for i in range(len(w) - 2)]
    grams += [a + "_" + b for a, b in zip(words, words[1:])]
    return grams


def embed(text: str) -> np.ndarray:
    """Hashed character n-gram embedding, L2-normalised (zero stays zero)."""
    v = np.zeros(DIM, np.float64)
    for g in _grams(text):
        h = int.from_bytes(hashlib.blake2b(g.encode(), digest_size=8).digest(), "little")
        v[h % DIM] += 1.0 if (h >> 62) & 1 else -1.0
    n = np.linalg.norm(v)
    return v / n if n > 0 else v


@dataclasses.dataclass
class Lookup:
    keyword: str
    matched: Optional[str]  # the stored key served, None on a miss
    answered: bool  # the task produced an answer
    admitted: bool  # the keyword was in the cache after the task


def cache_mismatches(keys: Sequence[str], lookups: Sequence[Lookup], threshold: float,
                     fuzzy: bool, margin: float = 1e-4) -> Tuple[int, List[str]]:
    """Replays the lookups on a plain matcher; returns (mismatches, notes).

    A fuzzy score within ``margin`` of the threshold, or of the runner-up,
    is a tie that float32 on the device may break either way: both answers
    count as right there."""
    held = list(keys)
    vecs = [embed(k) for k in held]
    bad, notes = 0, []
    for lk in lookups:
        allowed = set()
        if lk.keyword in held:
            allowed = {lk.keyword}
        elif fuzzy and held:
            s = np.array([v @ embed(lk.keyword) for v in vecs])
            order = np.argsort(-s, kind="stable")
            top = float(s[order[0]])
            if top >= threshold + margin:
                allowed = {held[order[0]]}
                if len(order) > 1 and top - s[order[1]] < margin:
                    allowed.add(held[order[1]])
            elif top > threshold - margin:
                allowed = {None, held[order[0]]}
        if not allowed:
            allowed = {None}
        if lk.matched not in allowed:
            bad += 1
            notes.append(f"lookup {lk.keyword!r}: served {lk.matched!r}, expected {sorted(map(str, allowed))}")
        should_admit = lk.matched is None and lk.answered
        if should_admit and lk.keyword not in held:
            held.append(lk.keyword)
            vecs.append(embed(lk.keyword))
        if lk.admitted != (lk.keyword in held):
            bad += 1
            notes.append(f"admission of {lk.keyword!r}: cache holds it={lk.admitted}, expected {lk.keyword in held}")
    return bad, notes
