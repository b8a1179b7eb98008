"""Prefix-caching suite: refcounted allocator semantics, copy-on-write
content preservation, the prefix index lifecycle, and the serving-path
bugfix regressions that ride along.

Covers:

  * ``BudgetRouter.route`` off-by-one — a row even 1 param over budget is
    infeasible (the old integer ``+ 1`` slack admitted it on fine tables);
  * incremental fragmentation parity — ``fragmentation()`` from the run
    tracker must equal the sorted-scan reference after any op sequence;
  * one table width — every step dispatches the pow2-padded table, even
    when ``max_blocks_per_seq`` is not pow2 and every live sequence is short;
  * allocator refcount rules — no block recycled at refcount > 0, warm-tier
    FIFO eviction through the hook, ``take`` resurrection;
  * COW block copies are bit-exact on device and never disturb the sharer;
  * probe/register semantics — full-block-only hits, the one-token-short
    cap, insert-if-absent, miss after eviction;
  * engine-level token identity — cache on vs off must be bit-identical
    across chunk sizes, mid-prefill preemption, and spec decoding, with
    real hits on shared-prefix workloads and zero hits on disjoint ones.
"""
import numpy as np
import jax
import pytest

from repro.configs import get_config
from repro.serving import (BlockAllocator, CacheOOM, ElasticEngine,
                           PagedKVCache, Request, SpecConfig)
from repro.serving.scheduler import BudgetRouter

CFG_TINY = get_config("gpt2-small", smoke=True)
BLOCK = 8


# --------------------------------------------- BudgetRouter off-by-one fix

def test_budget_router_rejects_one_param_over():
    """Adjacent rows 1 param apart: requesting exactly the smaller row's
    fraction must route to it, never to the row 1 param over budget."""
    router = BudgetRouter(np.array([999_999, 1_000_000], np.int64))
    assert router.route(999_999 / 1_000_000) == 0
    assert router.route(1.0) == 1
    assert router.route(0.1) == 0            # below every row: smallest


def test_budget_router_fraction_roundtrip():
    """Every row's own cost fraction must route back to that row (the float
    tolerance exists for exactly this round trip, nothing more)."""
    costs = np.array([3_210_001, 3_210_002, 7_654_321, 12_345_678], np.int64)
    router = BudgetRouter(costs)
    for row, c in enumerate(costs):
        assert router.route(c / float(costs[-1])) == row
        if row + 1 < len(costs):
            # epsilon under the NEXT row's fraction still lands here
            assert router.route((costs[row + 1] - 1) / float(costs[-1])) == row


# -------------------------------------- incremental fragmentation parity

def test_fragmentation_incremental_parity_walk():
    """fragmentation() (run tracker) vs fragmentation_exact() (full sort)
    after every op of a mixed alloc/incref/decref/take walk."""
    rng = np.random.default_rng(0)
    a = BlockAllocator(64)
    live = []                                # blocks with our refs, one entry per ref
    for _ in range(2000):
        op = rng.integers(0, 4)
        if op == 0 and a.free_count:
            (b,) = a.alloc(1)
            live.append(b)
            if rng.integers(0, 3) == 0:
                a.set_cached(b, True)        # some blocks enter the warm tier
        elif op == 1 and live:
            b = live.pop(int(rng.integers(0, len(live))))
            a.decref(b)
        elif op == 2 and live:
            b = live[int(rng.integers(0, len(live)))]
            a.incref(b)
            live.append(b)
        elif op == 3 and a.cached_free_count:
            warm = [b for b in range(1, a.num_blocks)
                    if a.refcount(b) == 0 and a._is_cached[b]]
            b = warm[int(rng.integers(0, len(warm)))]
            a.take(b)                        # resurrect a specific interior id
            live.append(b)
        assert abs(a.fragmentation() - a.fragmentation_exact()) < 1e-12
    for b in live:
        a.decref(b)
    assert a.free_count == a.num_blocks - 1
    assert a.fragmentation() == a.fragmentation_exact() == 0.0


# -------------------------------------------------- one table width

def test_active_max_blocks_pow2_closure_non_pow2_cap():
    """max_len/block_size = 6 blocks (not pow2): the one table width the
    steps dispatch is the pow2 ceiling, 8, whatever the live lengths, never
    the raw 6; its extra columns are null blocks."""
    cache = PagedKVCache(CFG_TINY, max_batch=1, max_len=44, block_size=8)
    assert cache.max_blocks_per_seq == 6
    assert cache.padded_max_blocks == 8
    cache.open_slot(0)
    widths = set()
    while cache.slots[0].num_tokens < 44:
        cache.extend_slot(0, min(8, 44 - cache.slots[0].num_tokens))
        widths.add(cache.host_tables().shape[1])
    assert widths == {8}
    t = cache.host_tables()
    assert t.shape == (1, 8)
    assert t[:, :6].all()                    # a full table
    assert not t[:, 6:].any()                # padded columns are null blocks


@pytest.mark.parametrize("spec", [None, SpecConfig(draft_rank=0.7, spec_len=3)],
                         ids=["plain", "spec"])
def test_every_step_dispatches_one_table_width(smoke_state, monkeypatch, spec):
    """Short and long live sequences alike get the padded table width: a
    narrower width would be one more program per token width and row
    bucket, compiled mid-serve the first time a near-empty server met it."""
    widths = []
    host_tables = PagedKVCache.host_tables

    def record(self, **kw):
        t = host_tables(self, **kw)
        widths.append(t.shape[1])
        return t

    monkeypatch.setattr(PagedKVCache, "host_tables", record)
    eng = _mk_engine(smoke_state, prefill_chunk=BLOCK, spec=spec)
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(0, CFG_TINY.vocab_size, n)
                    .astype(np.int32), max_new_tokens=4, budget=1.0)
            for n in (3, 40, 5)]
    eng.generate(reqs, mode="continuous")
    assert widths and set(widths) == {64 // BLOCK}


# ------------------------------------------------ allocator refcount rules

def test_no_block_recycled_at_positive_refcount():
    a = BlockAllocator(4)                    # 3 usable
    xs = a.alloc(3)
    a.incref(xs[0])
    a.free(xs)                               # xs[0] keeps one ref
    assert a.refcount(xs[0]) == 1
    assert a.free_count == 2
    assert xs[0] not in a.alloc(2)           # never handed out while held
    a.decref(xs[0])
    assert a.alloc(1) == [xs[0]]             # now it can come back
    assert a.free_count == 0
    with pytest.raises(CacheOOM):
        a.alloc(1)


def test_incref_of_free_block_asserts():
    a = BlockAllocator(4)
    (b,) = a.alloc(1)
    a.decref(b)
    with pytest.raises(AssertionError, match="incref of free block"):
        a.incref(b)


def test_decref_below_zero_is_a_double_free():
    a = BlockAllocator(4)
    (b,) = a.alloc(1)
    a.decref(b)
    with pytest.raises(AssertionError, match="double free"):
        a.decref(b)


def test_warm_tier_fifo_eviction_and_take():
    evicted = []
    a = BlockAllocator(5, evict_hook=evicted.append)
    xs = a.alloc(4)
    a.set_cached(xs[0], True)
    a.set_cached(xs[1], True)
    a.free(xs)
    assert a.cached_free_count == 2
    # plain tier drains first (LIFO), warm blocks survive
    got = a.alloc(2)
    assert set(got) == {xs[2], xs[3]} and not evicted
    # then the OLDEST warm block is recycled through the hook
    assert a.alloc(1) == [xs[0]]
    assert evicted == [xs[0]]
    # a specific warm block can be resurrected without the hook firing
    a.take(xs[1])
    assert a.refcount(xs[1]) == 1 and evicted == [xs[0]]
    with pytest.raises(AssertionError):
        a.take(xs[1])                        # live blocks cannot be taken


def test_uncache_moves_warm_block_to_plain_tier():
    evicted = []
    a = BlockAllocator(3, evict_hook=evicted.append)
    xs = a.alloc(2)
    a.set_cached(xs[0], True)
    a.free(xs)
    a.uncache(xs[0])
    assert a.cached_free_count == 0
    a.alloc(2)                               # reuses both without eviction
    assert not evicted


# ----------------------------------------------------- COW device content

def _paint_blocks(cache, blocks):
    """Stamp every (k, v) pool entry of each block with its own id so a
    bitwise copy is detectable and in-place divergence is visible."""
    for si in range(len(cache.pools)):
        for name in ("k", "v"):
            p = cache.pools[si][name]
            for b in blocks:
                p = p.at[:, b].set(float(b))
            cache.pools[si][name] = p


def test_cow_copy_is_bit_exact_and_sharer_untouched():
    cache = PagedKVCache(CFG_TINY, max_batch=2, max_len=8, block_size=2,
                         prefix_cache=True)
    toks = np.arange(4, dtype=np.int32)
    cache.open_slot(0)
    cache.extend_slot(0, 4)
    src_blocks = list(cache.slots[0].blocks)
    _paint_blocks(cache, src_blocks)
    assert cache.register_prefix(0, toks, 4) == 2

    probe = np.concatenate([toks, [99]]).astype(np.int32)
    cache.open_slot(1)
    assert cache.probe_prefix(1, probe) == 4         # both full blocks hit
    assert cache.slots[1].blocks == src_blocks
    assert all(cache.allocator.refcount(b) == 2 for b in src_blocks)

    # rewind slot 1 into the shared second block, then write: must COW
    cache.truncate_slot(1, 3)
    assert cache.token_append_needs_block(1)
    old = cache.slots[1].blocks[1]
    cache.append_token(1)
    new = cache.slots[1].blocks[1]
    assert new != old
    assert cache.stats.cow_copies == 1
    # refcounts split; the canonical block stays indexed (content unchanged)
    assert cache.allocator.refcount(old) == 1
    assert cache.allocator.refcount(new) == 1
    assert old in cache._block_key and new not in cache._block_key
    # slot 0 is untouched: same blocks, same table, same device bytes
    assert cache.slots[0].blocks == src_blocks
    assert list(cache._tables[0, :2]) == src_blocks
    for si in range(len(cache.pools)):
        for name in ("k", "v"):
            pool = np.asarray(cache.pools[si][name])
            np.testing.assert_array_equal(pool[:, old],
                                          np.full_like(pool[:, old],
                                                       float(old)))
            # the private copy is bit-exact at copy time
            np.testing.assert_array_equal(pool[:, new], pool[:, old])


# ------------------------------------------- probe / register semantics

def test_probe_hits_are_full_blocks_capped_one_token_short():
    cache = PagedKVCache(CFG_TINY, max_batch=2, max_len=8, block_size=2,
                         prefix_cache=True)
    toks = np.arange(4, dtype=np.int32)
    cache.open_slot(0)
    cache.extend_slot(0, 4)
    cache.register_prefix(0, toks, 4)
    # identical prompt: the cap leaves the last token (and its block) out so
    # the finishing chunk still has a position to produce the first sample
    cache.open_slot(1)
    assert cache.probe_prefix(1, toks) == 2
    assert cache.stats.hits == 1 and cache.stats.hit_tokens == 2
    # registering the shared block again is a no-op (insert-if-absent)
    assert cache.register_prefix(1, toks, 2) == 0
    cache.free_slot(1)
    # a 3-token probe matching one full block hits exactly that block
    cache.open_slot(1)
    assert cache.probe_prefix(1, toks[:3]) == 2


def test_probe_misses_after_pressure_evicts_warm_blocks():
    cache = PagedKVCache(CFG_TINY, max_batch=2, max_len=8, block_size=2,
                         num_blocks=4, prefix_cache=True)
    toks = np.arange(4, dtype=np.int32)
    cache.open_slot(0)
    cache.extend_slot(0, 4)
    cache.register_prefix(0, toks, 4)
    cache.free_slot(0)                       # blocks retire to the warm tier
    assert cache.cached_blocks == 2
    cache.allocate_slot(0, 8)                # whole pool: evicts both
    assert cache.cached_blocks == 0
    assert cache.stats.evictions == 2
    cache.free_slot(0)
    cache.open_slot(0)
    assert cache.probe_prefix(0, np.concatenate([toks, [9]]).astype(np.int32)) == 0
    assert cache.stats.misses == 1


def test_prefix_cache_off_probe_and_register_are_noops():
    cache = PagedKVCache(CFG_TINY, max_batch=2, max_len=8, block_size=2,
                         prefix_cache=False)
    toks = np.arange(4, dtype=np.int32)
    cache.open_slot(0)
    cache.extend_slot(0, 4)
    assert cache.register_prefix(0, toks, 4) == 0
    cache.open_slot(1)
    assert cache.probe_prefix(1, toks) == 0
    assert cache.cached_blocks == 0
    assert cache.stats.hits == cache.stats.misses == 0


# ------------------------------------------ engine-level token identity

@pytest.fixture(scope="module")
def smoke_state():
    from repro.data import make_source
    from repro.launch.train import build_flexrank_state
    from repro.models import common as cm
    from repro.models import transformer as tfm
    cfg = get_config("gpt2-small", smoke=True)
    source = make_source(cfg.vocab_size, 64, 4, seed=0)
    dense = cm.instantiate(tfm.model_spec(cfg), jax.random.PRNGKey(0))
    params_fact, table, infos = build_flexrank_state(cfg, dense, source)
    return cfg, params_fact, table, infos


def _mk_engine(state, **kw):
    cfg, params_fact, table, infos = state
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", BLOCK)
    return ElasticEngine(cfg, params_fact, table, infos, **kw)


def _shared_prefix_requests(cfg, n=5, shared=24, seed=11):
    """n requests sharing a `shared`-token system prompt + unique tails;
    with max_batch=2 the later admissions probe a populated index."""
    rng = np.random.default_rng(seed)
    head = rng.integers(0, cfg.vocab_size, shared).astype(np.int32)
    reqs = []
    for i in range(n):
        tail = rng.integers(0, cfg.vocab_size, 4 + i % 3).astype(np.int32)
        reqs.append(Request(prompt=np.concatenate([head, tail]),
                            max_new_tokens=4, budget=1.0))
    return reqs


@pytest.mark.parametrize("chunk", [4, BLOCK])
@pytest.mark.parametrize("spec", [None, SpecConfig(draft_rank=0.7, spec_len=3)],
                         ids=["plain", "spec"])
def test_prefix_cache_token_identity_matrix(smoke_state, chunk, spec):
    """Cache on vs off must be bit-identical across chunk sizes and spec
    decoding, and the shared-prefix workload must actually hit."""
    cfg = smoke_state[0]
    reqs = _shared_prefix_requests(cfg)
    off = _mk_engine(smoke_state, prefill_chunk=chunk, spec=spec,
                     prefix_cache=False)
    base = [r.tokens for r in off.generate(reqs, mode="continuous")]
    on = _mk_engine(smoke_state, prefill_chunk=chunk, spec=spec,
                    prefix_cache=True)
    res = on.generate(reqs, mode="continuous")
    for a, r in zip(base, res):
        np.testing.assert_array_equal(a, r.tokens)
    s = on.last_metrics.summary()
    assert s["prefix_hits"] >= 1
    assert s["prefix_hit_tokens"] >= s["prefix_hits"] * BLOCK
    assert off.last_metrics.summary()["prefix_hits"] == 0


def test_prefix_cache_identity_under_mid_prefill_preemption(smoke_state):
    """Tight pool forces mid-prefill preemption; the recomputed victim may
    re-hit its own registered blocks and must still stream exact tokens."""
    cfg = smoke_state[0]
    rng = np.random.default_rng(5)
    head = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    reqs = [Request(prompt=np.concatenate(
                [head, rng.integers(0, cfg.vocab_size, 4).astype(np.int32)]),
                    max_new_tokens=6, budget=1.0) for _ in range(2)]
    kw = dict(max_len=32, block_size=4, num_blocks=5, prefill_chunk=4)
    off = _mk_engine(smoke_state, prefix_cache=False, **kw)
    base = [r.tokens for r in off.generate(reqs, mode="continuous")]
    on = _mk_engine(smoke_state, prefix_cache=True, **kw)
    res = on.generate(reqs, mode="continuous")
    assert on.last_metrics.preemptions >= 1
    for a, r in zip(base, res):
        np.testing.assert_array_equal(a, r.tokens)


def test_prefix_cache_zero_hit_workload_is_transparent(smoke_state):
    """Disjoint prompts: the cache must stay out of the way — zero hits,
    identical streams (the throughput-overhead bound lives in the bench)."""
    cfg = smoke_state[0]
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, 9 + i).astype(np.int32),
                    max_new_tokens=4, budget=1.0) for i in range(4)]
    off = _mk_engine(smoke_state, prefill_chunk=4, prefix_cache=False)
    base = [r.tokens for r in off.generate(reqs, mode="continuous")]
    on = _mk_engine(smoke_state, prefill_chunk=4, prefix_cache=True)
    res = on.generate(reqs, mode="continuous")
    for a, r in zip(base, res):
        np.testing.assert_array_equal(a, r.tokens)
    s = on.last_metrics.summary()
    assert s["prefix_hits"] == 0
