"""The warm set at the chat cell's engine settings holds every step shape
the chip served in a lead-in or window at 3.5-4.0 requests a second, and
every feed-fixup shape."""
import json
import os
import types

import warm
from repro.serving.engine import ElasticEngine
from repro.serving.kv_cache import PagedKVCache
from repro.configs import get_config

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (token width, table width, sample rows, top-k) recorded on a v5e
SEEN_ON_THE_CHIP = [
    (16, 64, 16, True), (32, 64, 32, True), (64, 4, 4, False),
    (64, 8, 4, False), (64, 16, 4, False), (64, 64, 16, True),
    (64, 64, 32, True), (96, 16, 4, False), (96, 16, 4, True),
    (96, 32, 4, True), (96, 32, 8, True), (96, 32, 16, True),
    (96, 64, 8, True), (96, 64, 16, True), (96, 64, 32, True)]


def _engine_and_cache():
    with open(os.path.join(BENCH, "configs", "gpt2-small.json")) as f:
        ec = json.load(f)["engine"]
    engine = types.SimpleNamespace(
        max_batch=ec["max_batch"], prefill_chunk=ec["prefill_chunk"],
        _mixed_budget=ec["max_batch"] + ec["prefill_chunk"],
        _bucket_rows=ElasticEngine._bucket_rows)
    engine._bucket_tokens = lambda used: ElasticEngine._bucket_tokens(
        engine, used)
    cache = PagedKVCache(get_config("gpt2-small", smoke=True),
                         max_batch=ec["max_batch"], max_len=ec["max_len"],
                         block_size=ec["block_size"], num_blocks=4)
    return engine, cache, ec


def test_the_warm_set_holds_what_the_chip_served_and_every_fixup():
    engine, cache, ec = _engine_and_cache()
    step, fixup = warm.shapes(engine, cache)
    assert set(SEEN_ON_THE_CHIP) <= set(step)
    assert len(step) == len(set(step)) < 238
    widths = {w for w, *_ in step}
    rows = {r for _, _, r, _ in step}
    assert set(fixup) == {(w, f, r) for w in widths for r in rows
                          for f in range(1, min(w, r, ec["max_batch"]) + 1)}
