"""A cell at smoke size for the CPU tests: the repo's smoke widths of
gpt2 (2 layers, d64, 4 heads, vocab 512), short requests, a short window."""
from __future__ import annotations

import copy
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(name):
    with open(os.path.join(BENCH, name)) as f:
        return json.load(f)


def conf(tie: bool = True) -> dict:
    c = _json("configs/gpt2-small.json")
    c["name"] = "gpt2-smoke"
    c["model"].update(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                      d_ff=128, vocab_size=512, layers_per_segment=1,
                      tie_embeddings=tie, max_seq_len=256)
    c["engine"].update(max_batch=4, max_len=64, num_blocks=16,
                       prefill_chunk=8)
    return c


def mix() -> dict:
    m = copy.deepcopy(_json("mixes/chat.json"))
    m["prompt_tokens"].update(median=12, min=4, max=40)
    m["output_tokens"].update(median=6, min=2, max=16)
    m.update(lead_in_s=0.5, tail_s=1.0, greedy_share=0.5, burst=2)
    return m


def spec(rate: float = 4.0, limit: float = 1e-3) -> dict:
    return {
        "cell": {"name": "gpt2-smoke.chat", "config": "gpt2-smoke",
                 "traffic": "chat", "chips": 1},
        "conf": conf(), "mix": mix(),
        "cellfile": {"rate_per_s": rate,
                     "check": {"requests": 3, "greedy_gap_limit": limit,
                               "topk_gap_limit": limit}},
        "end_to_end": [{"name": n, "unit": u} for n, u in (
            ("ttft_p90_ms", "ms"), ("itl_p50_ms", "ms"), ("itl_p95_ms", "ms"),
            ("tokens_per_s", "tokens/s"), ("setup_s", "s"))],
        "per_layer": [],
    }
