"""The warm set at the chat cell's engine settings holds every step shape
the chip served in a lead-in or window, and every feed-fixup shape; a
whole run at smoke size compiles nothing after the warm-up."""
import re
import types

import run
import smoke
import state
import warm
from repro.serving.engine import ElasticEngine
from repro.serving.kv_cache import PagedKVCache

# (token width, table width, sample rows) recorded on a v5e, of the shapes
# the engine still dispatches: since every step passes tables
# ``padded_max_blocks`` (64) wide and a top-k array, the narrower tables
# and the top-k-off programs recorded with them are gone
SEEN_ON_THE_CHIP = [
    (16, 64, 16), (32, 64, 32), (64, 64, 16), (64, 64, 32), (96, 64, 8),
    (96, 64, 16), (96, 64, 32)]


def _engine_and_cache():
    # the chat cell, the first of BENCHMARK.json, where these were recorded
    name = smoke.BENCHMARK["workloads"][0]["config"]
    ec = smoke.full(name)["engine"]
    engine = types.SimpleNamespace(
        max_batch=ec["max_batch"], prefill_chunk=ec["prefill_chunk"],
        _mixed_budget=ec["max_batch"] + ec["prefill_chunk"],
        _bucket_rows=ElasticEngine._bucket_rows)
    engine._bucket_tokens = lambda used: ElasticEngine._bucket_tokens(
        engine, used)
    cache = PagedKVCache(state.model_config(smoke.conf(name)),
                         max_batch=ec["max_batch"], max_len=ec["max_len"],
                         block_size=ec["block_size"], num_blocks=4)
    return engine, cache, ec


def test_the_warm_set_holds_what_the_chip_served_and_every_fixup():
    engine, cache, ec = _engine_and_cache()
    step, fixup = warm.shapes(engine, cache)
    assert set(SEEN_ON_THE_CHIP) <= set(step)
    assert len(step) == len(set(step)) < 238
    assert {t for _, t, _ in step} == {cache.padded_max_blocks}
    widths = {w for w, *_ in step}
    rows = {r for _, _, r in step}
    assert set(fixup) == {(w, f, r) for w in widths for r in rows
                          for f in range(1, min(w, r, ec["max_batch"]) + 1)}


def test_a_whole_run_compiles_nothing_after_the_warm_up():
    lines = []
    res = run.run_cell(smoke.spec(), 2 ** 33 + 11, 1.0, False,
                       require_chip=False, use_pallas=False,
                       log=lines.append)
    assert res["correct"] is True
    window = [s for s in lines if s.startswith("# window:")]
    assert len(window) == 1
    m = re.search(r"compiles in the lead-in (\d+), in the window (\d+)",
                  window[0])
    assert m and (int(m.group(1)), int(m.group(2))) == (0, 0)
