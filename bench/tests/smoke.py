"""Smoke-size cells for the CPU tests: the real files, cut in width and
depth, shorter outputs and a shorter traffic pool."""

from __future__ import annotations

import copy
from pathlib import Path

from bench import harness

SMALL = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
         "head_dim": 16, "intermediate_size": 128, "vocab_size": 50304}


def loaded(cell: str, *, root=harness.ROOT, max_exec: int = 6, blocks: int = 2,
           weight_std: float = 0.05) -> dict:
    d = copy.deepcopy(harness.load_cell(cell, Path(root)))
    doc = d["config"]
    for m in doc["models"].values():
        kv = max(1, SMALL["num_attention_heads"] * m["num_key_value_heads"]
                 // m["num_attention_heads"])
        m.update(SMALL, num_key_value_heads=kv, dtype="float32")
    doc["max_len"] = 512
    doc["max_exec_tokens"] = max_exec
    doc["weight_std"] = weight_std
    if d["traffic"]["kind"] == "hot":
        d["traffic"]["blocks"] = blocks
    return d
