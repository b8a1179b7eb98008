"""A cell at smoke size for the CPU tests: a configuration of
``BENCHMARK.json`` at its architecture module's smoke widths, short
requests, a short window."""
from __future__ import annotations

import copy
import json
import os

import arch

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _json(path):
    with open(path) as f:
        return json.load(f)


BENCHMARK = _json(os.path.join(ROOT, "BENCHMARK.json"))
# the configurations that tests "of every configuration" run
CONFIGS = [c["name"] for c in BENCHMARK["configs"]]


def full(name: str) -> dict:
    """A configuration of ``BENCHMARK.json`` as its file states it."""
    c = {c["name"]: c for c in BENCHMARK["configs"]}[name]
    return _json(os.path.join(ROOT, c["file"]))


def conf(name: str = CONFIGS[0], tie: bool = True) -> dict:
    """``name`` at smoke size, with a small engine."""
    c = arch.module(full(name)).smoke(full(name))
    c["model"]["tie_embeddings"] = tie
    c["engine"].update(max_batch=4, max_len=64, num_blocks=16,
                       prefill_chunk=8)
    return c


def mix() -> dict:
    m = copy.deepcopy(_json(os.path.join(BENCH, "mixes", "chat.json")))
    m["prompt_tokens"].update(median=12, min=4, max=40)
    m["output_tokens"].update(median=6, min=2, max=16)
    m.update(lead_in_s=0.5, tail_s=1.0, greedy_share=0.5, burst=2)
    return m


def spec(rate: float = 4.0, limit: float = 1e-3, config: dict = None) -> dict:
    """The smoke cell on ``config`` (the first configuration at smoke size
    where none is given)."""
    c = config or conf()
    return {
        "cell": {"name": c["name"] + ".chat", "config": c["name"],
                 "traffic": "chat", "chips": 1},
        "conf": c, "mix": mix(),
        "cellfile": {"rate_per_s": rate,
                     "check": {"requests": 3, "greedy_gap_limit": limit,
                               "topk_gap_limit": limit}},
        "end_to_end": [{"name": n, "unit": u} for n, u in (
            ("ttft_p90_ms", "ms"), ("itl_p50_ms", "ms"), ("itl_p95_ms", "ms"),
            ("tokens_per_s", "tokens/s"), ("setup_s", "s"))],
        "per_layer": [],
    }
