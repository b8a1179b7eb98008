"""Serving subsystem tests: paged KV cache invariants, paged-attention
kernel vs oracle, scheduler routing, continuous batching join/preempt, and
end-to-end token identity with the seed greedy path."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.serving import (BlockAllocator, BudgetRouter, CacheOOM,
                           ElasticEngine, PagedKVCache, Request, Scheduler)

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------- allocator

def test_allocator_alloc_free_reuse():
    a = BlockAllocator(8)                    # 7 usable, block 0 reserved
    xs = a.alloc(3)
    assert len(set(xs)) == 3 and 0 not in xs
    assert a.free_count == 4
    ys = a.alloc(4)
    assert not set(xs) & set(ys)
    with pytest.raises(CacheOOM):
        a.alloc(1)
    a.free(xs)
    assert a.free_count == 3
    zs = a.alloc(3)
    assert set(zs) == set(xs)                # LIFO reuse


def test_allocator_double_free_asserts():
    a = BlockAllocator(4)
    xs = a.alloc(1)
    a.free(xs)
    with pytest.raises(AssertionError):
        a.free(xs)


# ----------------------------------------------------------- paged kv cache

def _cache(max_batch=2, max_len=32, block_size=4, num_blocks=None):
    cfg = get_config("gpt2-small", smoke=True)
    return PagedKVCache(cfg, max_batch=max_batch, max_len=max_len,
                        block_size=block_size, num_blocks=num_blocks)


def test_cache_allocate_append_free_invariants():
    c = _cache()
    st = c.allocate_slot(0, 6)               # 6 tokens -> 2 blocks of 4
    assert len(st.blocks) == 2 and st.num_tokens == 6
    tbl = np.asarray(c.device_tables())
    assert list(tbl[0, :2]) == st.blocks and not tbl[0, 2:].any()
    c.append_token(0)                        # 7th token: same block
    c.append_token(0)                        # 8th token: same block
    assert len(st.blocks) == 2
    c.append_token(0)                        # 9th token: new block
    assert len(st.blocks) == 3 and st.num_tokens == 9
    used_before = c.allocator.free_count
    c.free_slot(0)
    assert c.allocator.free_count == used_before + 3
    assert not np.asarray(c.device_tables()).any()


def test_cache_max_len_guard():
    c = _cache(max_len=8)
    with pytest.raises(CacheOOM):
        c.allocate_slot(0, 9)
    c.allocate_slot(0, 8)
    with pytest.raises(CacheOOM):
        c.append_token(0)


def test_cache_scatter_roundtrip():
    """write_prefill + decode-step scatter land tokens at (block, offset)."""
    c = _cache(block_size=4)
    st = c.allocate_slot(0, 8)
    cfg = c.cfg
    hd = cfg.resolved_head_dim
    count = cfg.segments[0].count
    vals = RNG.standard_normal((count, 1, 8, cfg.num_kv_heads, hd)).astype(np.float32)
    seg_caches = [{"k": jnp.asarray(vals), "v": jnp.asarray(vals) * 2.0}
                  for _ in cfg.segments]
    c.write_prefill(0, seg_caches)
    pool_k = np.asarray(c.pools[0]["k"])     # (count, NB, BS, H * D)
    assert pool_k.shape[2:] == (4, cfg.num_kv_heads * hd)
    for t in range(8):
        blk, off = st.blocks[t // 4], t % 4
        np.testing.assert_array_equal(pool_k[:, blk, off],
                                      vals[:, 0, t].reshape(count, -1))


# ------------------------------------------------------- paged attn kernel

# pool rows of Hkv * D lanes: 64, 64, 8, and 144 (a head straddles the
# 128-lane boundary)
@pytest.mark.parametrize("b,hq,hkv,d,bs,mb", [(2, 4, 4, 16, 4, 3),
                                              (3, 8, 2, 32, 8, 4),
                                              (1, 2, 1, 8, 16, 2),
                                              (2, 6, 3, 48, 8, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_kernel_vs_ref(b, hq, hkv, d, bs, mb, dtype):
    nb = b * mb + 1
    q = jnp.asarray(RNG.standard_normal((b, hq, d)), dtype)
    kp = jnp.asarray(RNG.standard_normal((nb, bs, hkv * d)), dtype)
    vp = jnp.asarray(RNG.standard_normal((nb, bs, hkv * d)), dtype)
    tables = 1 + RNG.permutation(b * mb).reshape(b, mb).astype(np.int32)
    lens = RNG.integers(1, mb * bs + 1, size=b).astype(np.int32)
    y_ref = ops.paged_attention_forward(q, kp, vp, jnp.asarray(tables),
                                        jnp.asarray(lens), use_pallas=False)
    y_ker = ops.paged_attention_forward(q, kp, vp, jnp.asarray(tables),
                                        jnp.asarray(lens),
                                        use_pallas="interpret")
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    err = float(jnp.abs(y_ref.astype(jnp.float32)
                        - y_ker.astype(jnp.float32)).max())
    assert err < tol, err


def test_paged_attention_softcap_and_ignores_dead_blocks():
    b, hq, hkv, d, bs, mb = 2, 4, 2, 16, 4, 3
    nb = b * mb + 1
    q = jnp.asarray(RNG.standard_normal((b, hq, d)).astype(np.float32))
    kp = jnp.asarray(RNG.standard_normal((nb, bs, hkv * d)).astype(np.float32))
    vp = jnp.asarray(RNG.standard_normal((nb, bs, hkv * d)).astype(np.float32))
    tables = 1 + RNG.permutation(b * mb).reshape(b, mb).astype(np.int32)
    lens = np.asarray([5, 8], np.int32)     # block 2 dead for both
    y1 = ops.paged_attention_forward(q, kp, vp, jnp.asarray(tables),
                                     jnp.asarray(lens), softcap=20.0,
                                     use_pallas="interpret")
    # scribbling blocks past each context length must not change the output
    kp2 = kp.at[np.asarray(tables[:, 2])].set(99.0)
    vp2 = vp.at[np.asarray(tables[:, 2])].set(-99.0)
    y2 = ops.paged_attention_forward(q, kp2, vp2, jnp.asarray(tables),
                                     jnp.asarray(lens), softcap=20.0,
                                     use_pallas="interpret")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-6)


def test_paged_ref_matches_contiguous_attention():
    """Paged oracle == dense attention over the linearized cache."""
    import math
    b, hq, hkv, d, bs, mb = 2, 8, 4, 16, 4, 4
    nb = b * mb + 1
    kp = RNG.standard_normal((nb, bs, hkv * d)).astype(np.float32)
    vp = RNG.standard_normal((nb, bs, hkv * d)).astype(np.float32)
    q = RNG.standard_normal((b, hq, d)).astype(np.float32)
    tables = 1 + RNG.permutation(b * mb).reshape(b, mb).astype(np.int32)
    lens = np.asarray([7, 13], np.int32)
    out = np.asarray(ref.paged_attention_ref(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(lens)))
    for i in range(b):
        k = kp[tables[i]].reshape(-1, hkv, d)[: lens[i]]
        v = vp[tables[i]].reshape(-1, hkv, d)[: lens[i]]
        g = hq // hkv
        qi = q[i].reshape(hkv, g, d) / math.sqrt(d)
        logits = np.einsum("hgd,thd->hgt", qi, k)
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        expect = np.einsum("hgt,thd->hgd", p, v).reshape(hq, d)
        np.testing.assert_allclose(out[i], expect, atol=1e-5)


# ---------------------------------------------------------------- scheduler

def test_budget_router_matches_seed_bruteforce():
    cost = np.asarray([40, 55, 70, 85, 100], np.int64)
    r = BudgetRouter(cost)
    for budget in (0.05, 0.4, 0.55, 0.72, 0.99, 1.0):
        # relative float tolerance only — the old integer ``+ 1`` slack
        # admitted rows 1 param over budget (see tests/test_prefix_cache.py)
        feasible = [k for k, c in enumerate(cost)
                    if c <= budget * cost[-1] * (1.0 + 1e-9)]
        assert r.route(budget) == (feasible[-1] if feasible else 0), budget
    assert r.route(0.0) == 0                 # infeasible -> smallest submodel


def test_scheduler_fifo_and_preempt_requeue():
    sched = Scheduler(BudgetRouter(np.asarray([50, 100])))
    a = sched.submit(Request(prompt=np.zeros(4, np.int32), budget=1.0))
    b = sched.submit(Request(prompt=np.zeros(4, np.int32), budget=0.5))
    c = sched.submit(Request(prompt=np.zeros(4, np.int32), budget=1.0))
    assert (a.row, b.row, c.row) == (1, 0, 1)
    assert sched.next_row() == 1             # oldest waiting request wins
    got = sched.pop(1)
    assert got is a
    got.generated.extend([7, 8])
    sched.requeue_front(got)
    assert got.generated == []               # recompute semantics
    assert sched.pop(1) is a and sched.pop(1) is c
    assert Scheduler.pick_victim([a, c]) is c  # youngest-first


# ------------------------------------------------------------- end-to-end

@pytest.fixture(scope="module")
def smoke_engine():
    from repro.data import make_source
    from repro.launch.train import build_flexrank_state
    from repro.models import common as cm
    from repro.models import transformer as tfm
    cfg = get_config("gpt2-small", smoke=True)
    source = make_source(cfg.vocab_size, 64, 4, seed=0)
    dense = cm.instantiate(tfm.model_spec(cfg), jax.random.PRNGKey(0))
    params_fact, table, infos = build_flexrank_state(cfg, dense, source)
    return cfg, params_fact, table, infos


def _mk_engine(smoke_engine, **kw):
    cfg, params_fact, table, infos = smoke_engine
    return ElasticEngine(cfg, params_fact, table, infos, **kw)


def _mixed_requests(cfg, spec):
    rng = np.random.default_rng(7)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, pl).astype(np.int32),
                    max_new_tokens=mn, budget=b) for pl, mn, b in spec]


def test_cost_table_precomputed_and_routing(smoke_engine):
    eng = _mk_engine(smoke_engine, max_batch=2, max_len=32)
    assert eng._cost_table.ndim == 1
    assert np.all(np.diff(eng._cost_table) >= 0)
    assert eng._budget_row(1.0) == len(eng._cost_table) - 1
    assert eng._budget_row(0.01) == 0


def test_continuous_token_identical_to_seed_greedy(smoke_engine):
    """Continuous batching (mid-decode joins included: 5 requests, 2 slots)
    must reproduce the seed greedy path token-for-token."""
    eng = _mk_engine(smoke_engine, max_batch=2, max_len=64, block_size=8)
    cfg = eng.cfg
    reqs = _mixed_requests(cfg, [(5, 6, 0.4), (9, 3, 0.4), (7, 10, 1.0),
                                 (4, 2, 0.4), (21, 9, 0.7)])
    res = eng.generate(reqs, mode="continuous")
    m = eng.last_metrics.summary()
    assert m["requests"] == 5 and m["generated_tokens"] == 6 + 3 + 10 + 2 + 9
    assert 0.0 < m["cache_occupancy_peak"] <= 1.0
    for i, rq in enumerate(reqs):
        ref_toks = eng.generate_drain([rq])[0].tokens   # seed path, batch=1
        assert len(res[i].tokens) == len(rq.prompt) + rq.max_new_tokens
        np.testing.assert_array_equal(res[i].tokens, ref_toks)


def test_drained_rows_free_their_pools(smoke_engine):
    """Each budget row's KV pools are freed when the row drains, so serving
    row after row never holds two rows' pools at once (a dropped cache
    lives on until the cyclic GC: its allocator's evict hook points back
    at it)."""
    eng = _mk_engine(smoke_engine, max_batch=2, max_len=32, block_size=4,
                     prefill_chunk=8)
    reqs = _mixed_requests(eng.cfg, [(5, 3, 0.4), (6, 3, 1.0), (4, 2, 0.4)])
    eng.generate(reqs, mode="continuous")
    nb = eng.max_batch * 32 // 4 + 1        # the pool's blocks, null included
    pools = [a for a in jax.live_arrays() if a.ndim >= 4
             and a.shape[1:3] == (nb, 4)]
    assert not pools, [a.shape for a in pools]


def test_budget_mapping_preserved(smoke_engine):
    eng = _mk_engine(smoke_engine, max_batch=2, max_len=32, block_size=4)
    cfg = eng.cfg
    reqs = _mixed_requests(cfg, [(4, 2, 0.4), (4, 2, 1.0)])
    res = eng.generate(reqs)
    assert res[1].deployed_params > res[0].deployed_params
    assert res[1].budget_row > res[0].budget_row


def test_preemption_recompute_preserves_tokens(smoke_engine):
    """Force cache pressure: two growing sequences, pool too small for both.
    The victim is preempted, recomputed, and still yields exact tokens."""
    eng = _mk_engine(smoke_engine, max_batch=2, max_len=32, block_size=4,
                     num_blocks=4)
    cfg = eng.cfg
    reqs = _mixed_requests(cfg, [(4, 11, 1.0), (4, 11, 1.0)])
    res = eng.generate(reqs, mode="continuous")
    assert eng.last_metrics.preemptions >= 1
    for i, rq in enumerate(reqs):
        np.testing.assert_array_equal(res[i].tokens,
                                      eng.generate_drain([rq])[0].tokens)


def test_single_request_oom_raises(smoke_engine):
    eng = _mk_engine(smoke_engine, max_batch=1, max_len=32, block_size=4,
                     num_blocks=2)
    cfg = eng.cfg
    (rq,) = _mixed_requests(cfg, [(4, 20, 1.0)])    # needs 6 blocks, pool has 2
    with pytest.raises(CacheOOM):
        eng.generate([rq], mode="continuous")


def test_paged_pallas_engine_matches_ref_path(smoke_engine):
    eng_ref = _mk_engine(smoke_engine, max_batch=2, max_len=32, block_size=4)
    eng_ker = _mk_engine(smoke_engine, max_batch=2, max_len=32, block_size=4,
                         use_pallas="interpret")
    cfg = eng_ref.cfg
    reqs = _mixed_requests(cfg, [(5, 4, 1.0), (8, 6, 1.0)])
    r1 = eng_ref.generate(reqs, mode="continuous")
    r2 = eng_ker.generate(reqs, mode="continuous")
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_zero_new_tokens_matches_drain_and_bad_mode_rejected(smoke_engine):
    eng = _mk_engine(smoke_engine, max_batch=2, max_len=32, block_size=4)
    cfg = eng.cfg
    reqs = _mixed_requests(cfg, [(5, 0, 1.0), (4, 3, 1.0)])
    res = eng.generate(reqs, mode="continuous")
    assert len(res[0].tokens) == 5               # prompt only, like drain
    np.testing.assert_array_equal(res[0].tokens, reqs[0].prompt)
    assert len(res[1].tokens) == 7
    with pytest.raises(ValueError, match="unknown mode"):
        eng.generate(reqs, mode="continous")     # typo must not fall through


def test_preemption_metrics_count_only_delivered_tokens(smoke_engine):
    eng = _mk_engine(smoke_engine, max_batch=2, max_len=32, block_size=4,
                     num_blocks=4)
    cfg = eng.cfg
    reqs = _mixed_requests(cfg, [(4, 11, 1.0), (4, 11, 1.0)])
    eng.generate(reqs, mode="continuous")
    m = eng.last_metrics.summary()
    assert m["preemptions"] >= 1
    assert m["generated_tokens"] == 22           # discarded work not counted


def test_drain_path_single_pass_prefill_matches_seed_semantics(smoke_engine):
    """The upgraded drain path keeps the seed's exact output contract
    (including padded-prompt slicing for mixed-length batches)."""
    eng = _mk_engine(smoke_engine, max_batch=4, max_len=48, block_size=8)
    cfg = eng.cfg
    reqs = _mixed_requests(cfg, [(6, 4, 1.0), (9, 4, 1.0)])
    res = eng.generate_drain(reqs)
    for r, rq in zip(res, reqs):
        assert len(r.tokens) == len(rq.prompt) + rq.max_new_tokens
    # longest prompt in the batch has no padding: must equal its solo run
    np.testing.assert_array_equal(res[1].tokens,
                                  eng.generate_drain([reqs[1]])[0].tokens)
