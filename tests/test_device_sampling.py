"""Device-resident sampling pipeline: draw equivalence vs the host oracle,
fused-accept exactness, and engine-level identity/replay contracts.

Layers:

  * sampler unit level — ``keyed_uniform`` determinism/decorrelation (the
    fold_in port of the host's (seed, req_id, purpose, position) keying),
    the float32 device warp vs the float64 host ``SamplerState.probs``,
    and bitwise host/device agreement of the inverse-CDF draw *given the
    same uniform* (the generators differ; the deterministic map must not);
  * draw-equivalence — the seeded chi-squared/TV harness of
    ``tests/test_stochastic_spec.py`` pointed at device draws: tokens
    sampled with keyed device uniforms must be distributed exactly as the
    host sampler's warped distribution says;
  * fused-accept unit level — ``device_accept`` commits tokens exactly
    distributed as the target rows (first token + bonus token), accepts
    everything when q == p, and degenerates to the keyed ``DRAW_TARGET``
    draw at k = 0 (bitwise match with the fused sampler's own draw — the
    verify-only fallback's cross-engine identity);
  * engine level — greedy bit-identity between ``device_sampling`` on/off
    (the REPRO_DEVICE_SAMPLING env knob flips the same default the CI
    sampling matrix drives), stochastic cross-engine identity on the
    device path (drain / continuous / chunked share the keyed draws),
    device-vs-host distributional equivalence on a tiny vocab, and replay
    determinism under forced mid-round preemption of the device spec path.
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import FlexRankConfig, ModelConfig, Segment
from repro.kernels import ops, ref
from repro.serving import (ElasticEngine, Request, SamplingParams,
                           SpecConfig)
from repro.serving import device_sampling as DS
from repro.serving.sampling import (DRAW_ACCEPT, DRAW_DRAFT, DRAW_TARGET,
                                    SamplerState, sample_from)

TINY_CFG = ModelConfig(
    name="devsamp-tiny", family="dense", num_layers=2, d_model=64,
    num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=32,
    segments=(Segment("attn", 1), Segment("attn", 1)),
    rope_base=10000.0,
    flexrank=FlexRankConfig(enabled=True, budgets=(0.35, 0.6, 1.0)),
)


@pytest.fixture(scope="module")
def tiny_state():
    from repro.data import make_source
    from repro.launch.train import build_flexrank_state
    from repro.models import common as cm
    from repro.models import transformer as tfm
    source = make_source(TINY_CFG.vocab_size, 64, 4, seed=0)
    dense = cm.instantiate(tfm.model_spec(TINY_CFG), jax.random.PRNGKey(0))
    params_fact, table, infos = build_flexrank_state(TINY_CFG, dense, source)
    return TINY_CFG, params_fact, table, infos


def _mk_engine(state, **kw):
    cfg, params_fact, table, infos = state
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", 8)
    return ElasticEngine(cfg, params_fact, table, infos, **kw)


# ------------------------------------------------------- sampler unit level

def test_keyed_uniform_deterministic_and_decorrelated():
    u = DS.keyed_uniform(jnp.asarray([5]), jnp.asarray([3]),
                         jnp.asarray([DRAW_ACCEPT]), jnp.asarray([17]))
    again = DS.keyed_uniform(jnp.asarray([5]), jnp.asarray([3]),
                             jnp.asarray([DRAW_ACCEPT]), jnp.asarray([17]))
    assert 0.0 <= float(u[0]) < 1.0
    assert float(u[0]) == float(again[0])       # pure function of the key
    for other in ((5, 3, DRAW_DRAFT, 17), (5, 3, DRAW_ACCEPT, 18),
                  (5, 4, DRAW_ACCEPT, 17), (6, 3, DRAW_ACCEPT, 17)):
        v = DS.keyed_uniform(*[jnp.asarray([x]) for x in other])
        assert float(u[0]) != float(v[0]), other


def test_device_warp_matches_host_probs():
    """The float32 device warp must agree with the float64 host
    ``SamplerState.probs`` to float precision, top-k ties included."""
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((6, 64)).astype(np.float32) * 3
    cases = [(0.0, 0), (0.7, 0), (1.0, 8), (0.3, 3), (2.5, 64), (1.0, 1)]
    temps = np.asarray([t for t, _ in cases], np.float32)
    topks = np.asarray([k for _, k in cases], np.int32)
    z = logits / np.maximum(temps, 1e-30)[:, None]
    thr = ref.topk_threshold_ref(jnp.asarray(z), jnp.asarray(topks))
    dev = np.asarray(ref.warp_probs_ref(jnp.asarray(logits),
                                        jnp.asarray(temps), thr))
    for i, (t, k) in enumerate(cases):
        params = (SamplingParams(temperature=t, top_k=k, seed=0)
                  if t > 0 else None)
        host = SamplerState(params, 0).probs(logits[i].astype(np.float64))
        np.testing.assert_allclose(dev[i], host, atol=1e-5)


def test_device_sample_given_u_matches_host_bitwise():
    """With the SAME uniform, the device inverse-CDF draw must pick the
    same token as the host ``sample_from`` — the generators differ, the
    deterministic (probs, u) -> token map must not."""
    rng = np.random.default_rng(1)
    logits = rng.standard_normal((40, 96)).astype(np.float32)
    temps = np.full(40, 0.8, np.float32)
    topks = np.full(40, 13, np.int32)
    u = rng.random(40).astype(np.float32)
    toks = np.asarray(ops.topk_mask_sample_forward(
        jnp.asarray(logits), jnp.asarray(temps), jnp.asarray(topks),
        jnp.asarray(u)))
    for i in range(40):
        s = SamplerState(SamplingParams(temperature=0.8, top_k=13, seed=0),
                         0)
        assert int(toks[i]) == sample_from(s.probs(logits[i]), float(u[i]))


def test_greedy_rows_are_raw_argmax():
    rng = np.random.default_rng(2)
    logits = rng.standard_normal((9, 50)).astype(np.float32)
    toks = np.asarray(ops.topk_mask_sample_forward(
        jnp.asarray(logits), jnp.zeros(9, jnp.float32), None,
        jnp.asarray(rng.random(9), jnp.float32)))
    np.testing.assert_array_equal(toks, np.argmax(logits, axis=-1))


def test_all_greedy_batch_shares_the_sampling_program():
    """A batch whose sampled rows are all greedy packs the same operand
    tree as one with top-k rows, so the engine dispatches one step program
    per shape instead of compiling a second where a batch first samples
    only greedy rows."""
    greedy = [(SamplerState(None, 0), DRAW_TARGET, 5)]
    topk = greedy + [(SamplerState(SamplingParams(temperature=0.8, top_k=40,
                                                  seed=3), 1),
                      DRAW_TARGET, 9)]
    a = ElasticEngine._pack_sampling(greedy, 8)
    b = ElasticEngine._pack_sampling(topk, 8)
    assert (jax.tree.structure(a) == jax.tree.structure(b))
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(a)] == \
        [(x.shape, x.dtype) for x in jax.tree.leaves(b)]
    np.testing.assert_array_equal(np.asarray(a["top_k"]), 0)


# ----------------------------------------------- draw-equivalence (seeded)

def test_device_draws_match_host_distribution():
    """Chi-squared + TV: tokens drawn with keyed device uniforms over one
    logits row must be distributed exactly as the host sampler's warped
    distribution of that row (the device-vs-host draw-equivalence half of
    the pipeline's contract)."""
    rng = np.random.default_rng(3)
    v, n = 8, 6000
    logits = rng.standard_normal(v).astype(np.float32) * 2
    host = SamplerState(SamplingParams(temperature=0.9, top_k=6, seed=0), 0)
    p = host.probs(logits)

    rows = jnp.asarray(np.tile(logits, (n, 1)))
    sampling = {
        "temperature": jnp.full((n,), 0.9, jnp.float32),
        "top_k": jnp.full((n,), 6, jnp.int32),
        "seed": jnp.arange(n, dtype=jnp.int32),
        "req_id": jnp.zeros(n, jnp.int32),
        "purpose": jnp.full((n,), DRAW_TARGET, jnp.int32),
        "position": jnp.full((n,), 11, jnp.int32),
    }
    toks = np.asarray(DS.sample_rows(rows, sampling))
    counts = np.bincount(toks, minlength=v).astype(np.float64)
    freq = counts / n
    tv = 0.5 * np.abs(freq - p).sum()
    assert tv < 0.03, (tv, freq, p)
    live = p > 0
    chi2 = float((((counts - n * p) ** 2)[live] / (n * p)[live]).sum())
    assert chi2 < 27.9, chi2                    # chi2(df<=5) p ~ 1e-4
    assert counts[~live].sum() == 0             # top-k support respected


# ------------------------------------------------ fused-accept unit level

def _device_round(seed, committed, q_rows, p_rows, k):
    """One synthetic device round: proposals drawn from q with keyed
    DRAW_DRAFT uniforms (exactly the device draft phase), then the fused
    accept against log-p target rows."""
    jj = jnp.arange(k, dtype=jnp.int32)
    u_d = DS.keyed_uniform(jnp.full((k,), seed, jnp.int32),
                           jnp.zeros((k,), jnp.int32),
                           jnp.full((k,), DRAW_DRAFT, jnp.int32),
                           committed + jj)
    drafts = ref.sample_cdf_ref(jnp.asarray(q_rows), u_d)
    with np.errstate(divide="ignore"):
        rows = jnp.asarray(np.log(p_rows), jnp.float32)[None]
    accept = {"k": jnp.asarray([k], jnp.int32), "drafts": drafts[None],
              "committed": jnp.asarray([committed], jnp.int32),
              "temperature": jnp.asarray([1.0], jnp.float32),
              "seed": jnp.asarray([seed], jnp.int32),
              "req_id": jnp.asarray([0], jnp.int32),
              "q": jnp.asarray(q_rows, jnp.float32)[None]}
    commit, m = DS.device_accept(rows, accept)
    return np.asarray(commit[0]), int(m[0])


def test_device_accept_first_token_exact():
    rng = np.random.default_rng(0)
    v, k, n = 6, 3, 4000
    q_rows = rng.dirichlet(np.ones(v) * 0.8, size=k)
    p_rows = rng.dirichlet(np.ones(v) * 0.8, size=k + 1)
    counts = np.zeros(v)
    mlens = np.zeros(k + 1, np.int64)

    @jax.jit
    def _device_round_traced(seed):
        jj = jnp.arange(k, dtype=jnp.int32)
        u_d = DS.keyed_uniform(jnp.full((k,), seed, jnp.int32),
                               jnp.zeros((k,), jnp.int32),
                               jnp.full((k,), DRAW_DRAFT, jnp.int32),
                               11 + jj)
        drafts = ref.sample_cdf_ref(jnp.asarray(q_rows, jnp.float32), u_d)
        rows = jnp.asarray(np.log(p_rows), jnp.float32)[None]
        accept = {"k": jnp.asarray([k], jnp.int32), "drafts": drafts[None],
                  "committed": jnp.asarray([11], jnp.int32),
                  "temperature": jnp.asarray([1.0], jnp.float32),
                  "seed": seed[None], "req_id": jnp.asarray([0], jnp.int32),
                  "q": jnp.asarray(q_rows, jnp.float32)[None]}
        commit, m = DS.device_accept(rows, accept)
        return commit[0], m[0]

    for t in range(n):
        commit, m = _device_round_traced(jnp.asarray(t, jnp.int32))
        counts[int(commit[0])] += 1
        mlens[int(m)] += 1
    freq = counts / n
    tv = 0.5 * np.abs(freq - p_rows[0]).sum()
    assert tv < 0.04, (tv, freq, p_rows[0])
    chi2 = float((((counts - n * p_rows[0]) ** 2) / (n * p_rows[0])).sum())
    assert chi2 < 25.7, chi2                    # chi2(df=5) p ~ 1e-4
    # mismatched q/p must actually reject sometimes AND accept sometimes
    assert mlens[0] > 0 and mlens[1:].sum() > 0


def test_device_accept_identical_distributions_accept_all():
    rng = np.random.default_rng(2)
    v, k = 8, 4
    rows = rng.dirichlet(np.ones(v), size=k + 1)
    for seed in range(100):
        commit, m = _device_round(seed, 0, rows[:k].astype(np.float32),
                                  rows, k)
        assert m == k and int(commit[k]) >= 0


def test_device_accept_k0_is_keyed_target_draw():
    """A k = 0 device round must commit bitwise the token the fused
    sampler would draw at (DRAW_TARGET, committed) — the verify-only
    fallback's identity with the non-speculative device engine."""
    rng = np.random.default_rng(5)
    v = 16
    logits = rng.standard_normal(v).astype(np.float32)
    k_cap = 3                                    # padded round shape
    accept = {"k": jnp.asarray([0], jnp.int32),
              "drafts": jnp.zeros((1, k_cap), jnp.int32),
              "committed": jnp.asarray([9], jnp.int32),
              "temperature": jnp.asarray([1.1], jnp.float32),
              "seed": jnp.asarray([4], jnp.int32),
              "req_id": jnp.asarray([2], jnp.int32),
              "q": jnp.zeros((1, k_cap, v), jnp.float32)}
    rows = jnp.asarray(np.tile(logits, (k_cap + 1, 1)))[None]
    commit, m = DS.device_accept(rows, accept)
    assert int(m[0]) == 0
    sampling = {"temperature": jnp.asarray([1.1], jnp.float32),
                "top_k": None,
                "seed": jnp.asarray([4], jnp.int32),
                "req_id": jnp.asarray([2], jnp.int32),
                "purpose": jnp.asarray([DRAW_TARGET], jnp.int32),
                "position": jnp.asarray([9], jnp.int32)}
    expect = DS.sample_rows(jnp.asarray(logits)[None], sampling)
    assert int(commit[0, 0]) == int(expect[0])


# ------------------------------------------------------------ engine level

def _greedy_requests(cfg, seed=7):
    rng = np.random.default_rng(seed)
    spec = [(7, 4, 1.0), (8, 3, 0.4), (9, 5, 1.0), (17, 2, 0.7),
            (4, 1, 1.0), (12, 9, 0.4)]
    return [Request(prompt=rng.integers(0, cfg.vocab_size, pl)
                    .astype(np.int32), max_new_tokens=mn, budget=b)
            for pl, mn, b in spec]


@pytest.mark.parametrize("chunk", [4, 16])
def test_greedy_identity_device_vs_host(tiny_state, chunk):
    """Greedy decoding is bit-identical with device sampling on and off —
    the sample-position gather + in-jit argmax must not change a single
    token vs the host argmax over the same gathered rows."""
    cfg = tiny_state[0]
    reqs = _greedy_requests(cfg)
    dev = _mk_engine(tiny_state, prefill_chunk=chunk,
                     device_sampling=True).generate(reqs, mode="continuous")
    host = _mk_engine(tiny_state, prefill_chunk=chunk,
                      device_sampling=False).generate(reqs,
                                                      mode="continuous")
    for a, b in zip(dev, host):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_env_knob_flips_engine_default(tiny_state, monkeypatch):
    monkeypatch.setenv("REPRO_DEVICE_SAMPLING", "0")
    assert _mk_engine(tiny_state).device_sampling is False
    monkeypatch.setenv("REPRO_DEVICE_SAMPLING", "1")
    assert _mk_engine(tiny_state).device_sampling is True
    monkeypatch.delenv("REPRO_DEVICE_SAMPLING")
    assert _mk_engine(tiny_state).device_sampling is True  # default on


def test_stochastic_device_stream_identical_across_engines(tiny_state):
    """On the device path every engine draws the same keyed
    (seed, req_id, DRAW_TARGET, position) uniforms, so a sampled request
    decodes identical tokens through drain, continuous, and chunked
    serving — the device analogue of the host sequential-stream identity."""
    cfg = tiny_state[0]
    rng = np.random.default_rng(11)
    sp = SamplingParams(temperature=0.9, top_k=8, seed=2)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, 8)
                    .astype(np.int32), max_new_tokens=mn, budget=1.0,
                    sampling=sp) for mn in (5, 4, 6)]
    drain = _mk_engine(tiny_state, device_sampling=True).generate_drain(reqs)
    cont = _mk_engine(tiny_state, device_sampling=True).generate(
        reqs, mode="continuous")
    chunked = _mk_engine(tiny_state, prefill_chunk=4,
                         device_sampling=True).generate(reqs,
                                                        mode="continuous")
    for i in range(len(reqs)):
        np.testing.assert_array_equal(cont[i].tokens, drain[i].tokens)
        np.testing.assert_array_equal(chunked[i].tokens, drain[i].tokens)


def test_engine_distribution_device_matches_host(tiny_state):
    """Two-sample TV on a tiny vocab: first-token frequencies from the
    device-sampling engine vs the host-sampling engine. Both are exact
    samplers of the same warped distributions (different uniform
    generators), so the pooled frequencies must agree within noise."""
    cfg = tiny_state[0]
    dev = _mk_engine(tiny_state, prefill_chunk=16, device_sampling=True)
    host = _mk_engine(tiny_state, prefill_chunk=16, device_sampling=False)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
    rounds, per = 12, 16
    firsts = {0: [], 1: []}
    for r in range(rounds):
        reqs = [Request(prompt=prompt.copy(), max_new_tokens=2, budget=1.0,
                        sampling=SamplingParams(temperature=0.8, seed=r))
                for _ in range(per)]
        for side, eng in enumerate((dev, host)):
            for res, rq in zip(eng.generate(reqs, mode="continuous"), reqs):
                firsts[side].append(int(res.tokens[len(rq.prompt)]))
    v = cfg.vocab_size
    f0 = np.bincount(firsts[0], minlength=v) / len(firsts[0])
    f1 = np.bincount(firsts[1], minlength=v) / len(firsts[1])
    tv = 0.5 * np.abs(f0 - f1).sum()
    assert tv < 0.15, tv


def test_device_spec_replay_under_mid_round_preemption(tiny_state):
    """Forced preemption drops in-flight device drafts mid-round; keyed
    device draws make the whole run a deterministic function of the
    workload — two identical runs agree bitwise, preemptions included."""

    def run():
        eng = _mk_engine(tiny_state, max_batch=2, max_len=32, block_size=4,
                         num_blocks=9, device_sampling=True,
                         spec=SpecConfig(draft_rank=0.7, spec_len=3,
                                         gap_chunk=8))
        rng = np.random.default_rng(5)
        reqs = [Request(prompt=rng.integers(0, TINY_CFG.vocab_size, 12)
                        .astype(np.int32), max_new_tokens=6, budget=1.0,
                        sampling=SamplingParams(temperature=0.8, seed=7))
                for _ in range(2)]
        res = eng.generate(reqs, mode="continuous")
        return res, eng.last_metrics

    r1, m1 = run()
    r2, m2 = run()
    assert m1.preemptions >= 1
    assert m1.preemptions == m2.preemptions
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_iteration_timing_breakdown_recorded(tiny_state):
    eng = _mk_engine(tiny_state, prefill_chunk=8)
    eng.generate(_greedy_requests(tiny_state[0]), mode="continuous")
    s = eng.last_metrics.summary()
    assert len(eng.last_metrics.timing_log) == s["mixed_iterations"]
    assert s["dispatch_ms_mean"] > 0.0
    assert s["dispatch_s_total"] > 0.0 and s["host_s_total"] >= 0.0
