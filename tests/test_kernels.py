"""Per-kernel shape/dtype sweeps: pallas (interpret=True) vs ref.py oracle."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, paged_attention, ref

RNG = np.random.default_rng(0)


def _arr(*s, dtype=np.float32):
    return jnp.asarray(RNG.standard_normal(s).astype(dtype))


TOLS = {jnp.float32: 2e-4, jnp.bfloat16: 6e-2}


# ------------------------------------------------------------- gar_matmul

@pytest.mark.parametrize("t,n,m,r", [(64, 32, 48, 16), (100, 96, 80, 40),
                                     (33, 17, 29, 7), (256, 128, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gar_matmul_sweep(t, n, m, r, dtype):
    x = _arr(t, n).astype(dtype)
    v = _arr(n, r).astype(dtype)
    u = _arr(m - r, r).astype(dtype)
    perm_inv = jnp.asarray(RNG.permutation(m).astype(np.int32))
    y_ref = ops.gar_forward(x, v, u, perm_inv, use_pallas=False)
    y_ker = ops.gar_forward(x, v, u, perm_inv, use_pallas="interpret",
                            bt=32, br=8)
    scale = float(jnp.abs(y_ref.astype(jnp.float32)).max()) + 1e-6
    err = float(jnp.abs(y_ref.astype(jnp.float32) - y_ker.astype(jnp.float32)).max())
    assert err / scale < TOLS[dtype], (err, scale)


def test_gar_matches_dense_reconstruction():
    """GAR kernel output == dense W_r matmul (paper §3.5 exactness)."""
    from repro.core.gar import gar_transform
    u_full = _arr(40, 24)
    v_full = _arr(32, 24)
    g = gar_transform(u_full, v_full, 12)
    x = _arr(16, 32)
    w_r = np.asarray(u_full)[:, :12] @ np.asarray(v_full)[:, :12].T
    y = ops.gar_forward(x, g.v_tilde, g.u_hat, jnp.argsort(g.perm),
                        use_pallas="interpret", bt=16, br=4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x) @ w_r.T,
                               rtol=1e-3, atol=1e-3)


# --------------------------------------------------------- lowrank_matmul

@pytest.mark.parametrize("t,n,m,r", [(64, 32, 48, 16), (70, 64, 96, 48)])
@pytest.mark.parametrize("rank", [None, 1, 5, "full"])
def test_lowrank_matmul_sweep(t, n, m, r, rank):
    x, v, u = _arr(t, n), _arr(n, r), _arr(m, r)
    rk = r if rank == "full" else rank
    y_ref = ops.lowrank_forward(x, v, u, rk, use_pallas=False)
    y_ker = ops.lowrank_forward(x, v, u, rk, use_pallas="interpret", bt=16, br=16)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=1e-3, atol=1e-3)


def test_lowrank_mask_traced_rank():
    """rank as a traced scalar (the consolidation-training path)."""
    x, v, u = _arr(32, 16), _arr(16, 8), _arr(24, 8)

    @jax.jit
    def f(rank):
        return ops.lowrank_forward(x, v, u, rank, use_pallas="interpret",
                                   bt=16, br=8)

    for rk in (1, 3, 8):
        np.testing.assert_allclose(
            np.asarray(f(jnp.asarray(rk))),
            np.asarray(ops.lowrank_forward(x, v, u, rk, use_pallas=False)),
            rtol=1e-3, atol=1e-3)


# -------------------------------------------------------- paged attention

# head counts, head dims, and block sizes deliberately include values that
# are NOT multiples of the TPU (8, 128) tile — interpret mode must stay
# exact there so the ops.py padding contract is the only tiling assumption.
# REPRO_PREFILL_CHUNK (the CI chunk matrix knob) adds one more block size.
PAGED_GEOMS = [
    # (hq, hkv, d,  bs, mb)
    (4, 4, 16, 4, 3),          # MHA, tile-aligned head dim
    (8, 2, 32, 8, 4),          # GQA 4:1
    (5, 5, 24, 3, 4),          # head count/dim off the (8, 128) tile
    (6, 3, 20, 5, 2),          # GQA with odd block size
    (2, 1, 8, 16, 2),          # tiny MQA, wide blocks
    (12, 4, 40, 7, 3),         # GQA 3:1, non-multiple everything
]
_env_bs = os.environ.get("REPRO_PREFILL_CHUNK")
if _env_bs:
    PAGED_GEOMS.append((4, 2, 16, max(1, int(_env_bs) % 32), 3))


def _paged_pools(b, hkv, d, bs, mb, dtype):
    nb = b * mb + 1
    # lane-dense pools, as ``PagedKVCache`` allocates them
    kp = jnp.asarray(RNG.standard_normal((nb, bs, hkv * d)), dtype)
    vp = jnp.asarray(RNG.standard_normal((nb, bs, hkv * d)), dtype)
    tables = 1 + RNG.permutation(b * mb).reshape(b, mb).astype(np.int32)
    return kp, vp, jnp.asarray(tables)


@pytest.mark.parametrize("hq,hkv,d,bs,mb", PAGED_GEOMS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_kernel_parity_sweep(hq, hkv, d, bs, mb, dtype):
    b = 3
    kp, vp, tables = _paged_pools(b, hkv, d, bs, mb, dtype)
    q = jnp.asarray(RNG.standard_normal((b, hq, d)), dtype)
    lens = jnp.asarray(RNG.integers(1, mb * bs + 1, size=b).astype(np.int32))
    y_ref = ops.paged_attention_forward(q, kp, vp, tables, lens,
                                        use_pallas=False)
    y_ker = ops.paged_attention_forward(q, kp, vp, tables, lens,
                                        use_pallas="interpret")
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    err = float(jnp.abs(y_ref.astype(jnp.float32)
                        - y_ker.astype(jnp.float32)).max())
    assert err < tol, (err, (hq, hkv, d, bs, mb))


@pytest.mark.parametrize("hq,hkv,d,bs,mb", PAGED_GEOMS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_prefill_kernel_parity_sweep(hq, hkv, d, bs, mb, dtype):
    """Chunked-prefill variant: flat token batch mixing chunk runs and
    decode singletons across slots, per-token contexts."""
    b, t = 3, 10
    kp, vp, tables = _paged_pools(b, hkv, d, bs, mb, dtype)
    q = jnp.asarray(RNG.standard_normal((t, hq, d)), dtype)
    sid = jnp.asarray(RNG.integers(0, b, size=t).astype(np.int32))
    lens = jnp.asarray(RNG.integers(1, mb * bs + 1, size=t).astype(np.int32))
    y_ref = ops.paged_prefill_attention_forward(q, kp, vp, tables, sid, lens,
                                                use_pallas=False)
    y_ker = ops.paged_prefill_attention_forward(q, kp, vp, tables, sid, lens,
                                                use_pallas="interpret")
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    err = float(jnp.abs(y_ref.astype(jnp.float32)
                        - y_ker.astype(jnp.float32)).max())
    assert err < tol, (err, (hq, hkv, d, bs, mb))


@pytest.mark.parametrize("lens,mb,want", [([0, 0], 8, 1), ([1, 5], 8, 2),
                                           ([4, 17], 8, 5), ([3, 99], 8, 8)])
def test_live_blocks_bounds_the_block_axis(lens, mb, want):
    """The kernels' block axis walks the longest context's blocks: at least
    one, at most the table's width."""
    got = paged_attention._live_blocks(jnp.asarray(lens, jnp.int32), 4, mb)
    assert int(got) == want


@pytest.mark.parametrize("prefill", [False, True])
def test_paged_kernels_wide_table_is_free(prefill):
    """Null columns past every live context change nothing: the kernel
    output with a table four times wider is bitwise the narrow one's."""
    b, hq, hkv, d, bs, mb = 3, 8, 4, 16, 4, 5
    kp, vp, tables = _paged_pools(b, hkv, d, bs, mb, jnp.float32)
    wide = jnp.pad(tables, ((0, 0), (0, 3 * mb)))
    if prefill:
        q = jnp.asarray(RNG.standard_normal((6, hq, d)), jnp.float32)
        sid = jnp.asarray([0, 0, 1, 2, 2, 2], jnp.int32)
        lens = jnp.asarray([3, 4, 20, 9, 10, 11], jnp.int32)
        run = lambda t: paged_attention.paged_prefill_attention(
            q, kp, vp, t, sid, lens, interpret=True)
    else:
        q = jnp.asarray(RNG.standard_normal((b, hq, d)), jnp.float32)
        lens = jnp.asarray([3, 20, 11], jnp.int32)
        run = lambda t: paged_attention.paged_attention(
            q, kp, vp, t, lens, interpret=True)
    np.testing.assert_array_equal(np.asarray(run(tables)),
                                  np.asarray(run(wide)))


def test_paged_prefill_reduces_to_decode_and_respects_window():
    """slot_ids == arange(B) makes the prefill oracle the decode oracle;
    sliding-window masking matches between the two."""
    b, hq, hkv, d, bs, mb = 2, 8, 4, 16, 4, 4
    kp, vp, tables = _paged_pools(b, hkv, d, bs, mb, jnp.float32)
    q = jnp.asarray(RNG.standard_normal((b, hq, d)).astype(np.float32))
    lens = jnp.asarray(np.asarray([7, 13], np.int32))
    sid = jnp.arange(b, dtype=jnp.int32)
    for window in (None, 5):
        y_dec = ops.paged_attention_forward(q, kp, vp, tables, lens,
                                            window=window, use_pallas=False)
        y_pre = ops.paged_prefill_attention_forward(q, kp, vp, tables, sid,
                                                    lens, window=window,
                                                    use_pallas=False)
        np.testing.assert_array_equal(np.asarray(y_dec), np.asarray(y_pre))


@pytest.mark.parametrize("prefill", [False, True])
@pytest.mark.parametrize("mode", [True, "interpret"])
def test_paged_kernels_refuse_window(prefill, mode):
    """A kernel asked for with a sliding window raises instead of quietly
    running the oracle: windowed layers choose the oracle at the caller."""
    b, hq, hkv, d, bs, mb = 2, 4, 2, 8, 4, 2
    kp, vp, tables = _paged_pools(b, hkv, d, bs, mb, jnp.float32)
    q = jnp.zeros((b, hq, d), jnp.float32)
    lens = jnp.asarray(np.asarray([3, 5], np.int32))
    with pytest.raises(ValueError, match="sliding window"):
        if prefill:
            ops.paged_prefill_attention_forward(
                q, kp, vp, tables, jnp.arange(b, dtype=jnp.int32), lens,
                window=3, use_pallas=mode)
        else:
            ops.paged_attention_forward(q, kp, vp, tables, lens, window=3,
                                        use_pallas=mode)


@pytest.mark.parametrize("hq,hkv,d,bs,mb", [(5, 5, 24, 3, 4),
                                            (12, 4, 40, 7, 3)])
def test_paged_verify_runs_parity_nontile_shapes(hq, hkv, d, bs, mb):
    """Speculative-verify layout through the chunked-prefill kernel: each
    sequence contributes a run of k+1 tokens at the TAIL of its context
    (positions L-1..L+k-1, strictly ascending per-token context lengths) —
    the shape ``paged_verify_step`` dispatches. Head counts / head dims /
    block sizes sit off the TPU (8, 128) tile, so interpret mode must stay
    exact with only the ops.py padding contract in between."""
    b, k = 3, 3
    kp, vp, tables = _paged_pools(b, hkv, d, bs, mb, jnp.float32)
    run = k + 1
    q = jnp.asarray(RNG.standard_normal((b * run, hq, d)).astype(np.float32))
    sid = jnp.asarray(np.repeat(np.arange(b, dtype=np.int32), run))
    lens = []
    for _ in range(b):
        first = int(RNG.integers(1, mb * bs - run + 1))
        lens.extend(range(first, first + run))
    lens = jnp.asarray(np.asarray(lens, np.int32))
    y_ref = ops.paged_prefill_attention_forward(q, kp, vp, tables, sid, lens,
                                                use_pallas=False)
    y_ker = ops.paged_prefill_attention_forward(q, kp, vp, tables, sid, lens,
                                                use_pallas="interpret")
    err = float(jnp.abs(y_ref - y_ker).max())
    assert err < 2e-5, (err, (hq, hkv, d, bs, mb))


def test_paged_prefill_intra_chunk_causality():
    """A chunk's tokens see strictly growing contexts: writing garbage past
    each token's context must not change its output (causality within the
    chunk is enforced purely by per-token context lengths)."""
    b, hq, hkv, d, bs, mb = 1, 4, 2, 16, 4, 3
    kp, vp, tables = _paged_pools(b, hkv, d, bs, mb, jnp.float32)
    t = 6                                     # chunk: positions 3..8
    q = jnp.asarray(RNG.standard_normal((t, hq, d)).astype(np.float32))
    sid = jnp.zeros(t, jnp.int32)
    lens = jnp.asarray(np.arange(4, 10, dtype=np.int32))   # pos + 1
    y1 = ops.paged_prefill_attention_forward(q, kp, vp, tables, sid, lens,
                                             use_pallas="interpret")
    # scribble the last block (tokens 8..11) — only the final token (context
    # 9) may see its first slot; nothing before position 8 changes
    blk = int(np.asarray(tables)[0, 2])
    kp2 = kp.at[blk, 1:].set(99.0)
    vp2 = vp.at[blk, 1:].set(-99.0)
    y2 = ops.paged_prefill_attention_forward(q, kp2, vp2, tables, sid, lens,
                                             use_pallas="interpret")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-6)


# --------------------------------------------------------- topk_mask_sample

@pytest.mark.parametrize("s,v,bv", [(6, 300, 2048), (9, 515, 128),
                                    (3, 64, 16), (12, 1000, 256)])
def test_sampling_kernel_parity_sweep(s, v, bv):
    """Fused warp+sample kernel vs the jnp oracle: identical tokens (the
    draws are discrete — a seeded sweep that never lands a uniform on a
    float boundary must agree exactly) and identical warped probs. Vocab
    sizes straddle the V-block so the two-pass streaming CDF crosses block
    boundaries."""
    from repro.kernels.sampling import topk_mask_sample
    rng = np.random.default_rng(s * 1000 + v)
    logits = jnp.asarray(rng.standard_normal((s, v)).astype(np.float32) * 3)
    temps = jnp.asarray(
        np.where(rng.random(s) < 0.3, 0.0,
                 rng.uniform(0.2, 2.5, s)).astype(np.float32))
    topks = jnp.asarray(
        np.where(rng.random(s) < 0.5, 0,
                 rng.integers(1, v + 1, s)).astype(np.int32))
    u = jnp.asarray(rng.random(s).astype(np.float32))
    z = logits / jnp.maximum(temps, 1e-30)[:, None]
    thr = ref.topk_threshold_ref(z, topks)
    t_ref, p_ref = ref.topk_mask_sample_ref(logits, temps, thr, u)
    t_ker, p_ker = topk_mask_sample(logits, temps, thr, u, bv=bv,
                                    return_probs=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(t_ref), np.asarray(t_ker))
    assert float(jnp.abs(p_ref - p_ker).max()) < 1e-5
    t_only = topk_mask_sample(logits, temps, thr, u, bv=bv, interpret=True)
    np.testing.assert_array_equal(np.asarray(t_ker), np.asarray(t_only))


@pytest.mark.parametrize("v", [1, 7, 513])
def test_topk_threshold_matches_sort(v):
    """The bisection cutoff is bitwise the sort-based oracle's: ties at the
    cutoff, negative and -inf entries, k of 0, 1, V and beyond V."""
    rng = np.random.default_rng(v)
    s = 12
    z = np.round(rng.standard_normal((s, v)) * 4, 1).astype(np.float32)
    z[0] = 0.0
    z[1, : (v + 1) // 2] = -np.inf
    z[2] = -np.abs(z[2])
    topks = np.asarray([1, 1, 2, 0, v, v + 5] + list(
        rng.integers(0, v + 1, s - 6)), np.int32)
    got = ops.topk_threshold(jnp.asarray(z), jnp.asarray(topks))
    want = ref.topk_threshold_ref(jnp.asarray(z), jnp.asarray(topks))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_topk_threshold_untruncated_batch_is_minus_inf():
    """With no row truncating, the run-time branch skips the bisection and
    gives the oracle's cutoff, -inf on every row."""
    z = jnp.asarray(np.random.default_rng(0).standard_normal((5, 33)),
                    jnp.float32)
    topks = jnp.zeros(5, jnp.int32)
    got = ops.topk_threshold(z, topks)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.topk_threshold_ref(z, topks)))
    assert np.isneginf(np.asarray(got)).all()


def test_sampling_dispatch_matches_host_oracle():
    """ops dispatch end to end (threshold sort included) against the host
    sampler's float64 warp: same uniform -> same token, kernel and oracle
    paths alike."""
    from repro.serving.sampling import SamplerState, SamplingParams, \
        sample_from
    rng = np.random.default_rng(7)
    s, v = 10, 123
    logits = rng.standard_normal((s, v)).astype(np.float32)
    temps = np.asarray([0.0, 0.5, 1.0, 1.5, 0.0, 0.8, 2.0, 0.4, 1.0, 0.9],
                       np.float32)
    topks = np.asarray([0, 4, 0, 9, 3, 1, 50, 0, 123, 7], np.int32)
    u = rng.random(s).astype(np.float32)
    for mode in (False, "interpret"):
        toks = np.asarray(ops.topk_mask_sample_forward(
            jnp.asarray(logits), jnp.asarray(temps), jnp.asarray(topks),
            jnp.asarray(u), use_pallas=mode))
        for i in range(s):
            if temps[i] <= 0:
                assert toks[i] == int(np.argmax(logits[i]))
                continue
            host = SamplerState(SamplingParams(
                temperature=float(temps[i]), top_k=int(topks[i]), seed=0), 0)
            expect = sample_from(host.probs(logits[i]), float(u[i]))
            assert toks[i] == expect, (mode, i)


# ------------------------------------------------------------------ wkv6

@pytest.mark.parametrize("b,s,h,n,chunk", [(2, 50, 3, 8, 16), (1, 64, 2, 16, 64),
                                           (2, 33, 1, 4, 8)])
def test_wkv6_sweep(b, s, h, n, chunk):
    r = _arr(b, s, h, n)
    k = _arr(b, s, h, n)
    v = _arr(b, s, h, n)
    w = jnp.asarray(np.exp(-np.exp(RNG.standard_normal((b, s, h, n)))).astype(np.float32))
    u = _arr(h, n)
    y_ref = ops.wkv6_forward(r, k, v, w, u, use_pallas=False)
    y_ker = ops.wkv6_forward(r, k, v, w, u, chunk=chunk, use_pallas="interpret")
    scale = float(jnp.abs(y_ref).max()) + 1e-6
    assert float(jnp.abs(y_ref - y_ker).max()) / scale < 1e-4


def test_wkv6_model_chunked_matches_sequential():
    from repro.models.rwkv import wkv_chunked
    b, s, h, n = 2, 40, 2, 8
    r, k, v = _arr(b, s, h, n), _arr(b, s, h, n), _arr(b, s, h, n)
    w = jnp.asarray(np.exp(-np.exp(RNG.standard_normal((b, s, h, n)))).astype(np.float32))
    u = _arr(h, n)
    y_seq = ops.wkv6_forward(r, k, v, w, u, use_pallas=False)
    y_chk, _ = wkv_chunked(r, k, v, w, u, chunk=10)
    np.testing.assert_allclose(np.asarray(y_chk), np.asarray(y_seq),
                               rtol=1e-3, atol=1e-3)


# ------------------------------------------------------------------- ssd

@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [(2, 60, 4, 16, 2, 8, 20),
                                               (1, 48, 2, 8, 1, 16, 16),
                                               (2, 37, 3, 8, 3, 4, 8)])
def test_ssd_sweep(b, s, h, p, g, n, chunk):
    x = _arr(b, s, h, p)
    dt = jnp.asarray(np.abs(RNG.standard_normal((b, s, h))).astype(np.float32) * 0.5)
    a = jnp.asarray(-np.abs(RNG.standard_normal(h)).astype(np.float32))
    bb = _arr(b, s, g, n)
    cc = _arr(b, s, g, n)
    y_ref = ops.ssd_forward(x, dt, a, bb, cc, use_pallas=False)
    y_ker = ops.ssd_forward(x, dt, a, bb, cc, chunk=chunk, use_pallas="interpret")
    scale = float(jnp.abs(y_ref).max()) + 1e-6
    assert float(jnp.abs(y_ref - y_ker).max()) / scale < 1e-4


def test_ssd_model_chunked_matches_sequential():
    from repro.models.ssm import ssd_chunked
    b, s, h, p, g, n = 2, 36, 2, 8, 1, 4
    x = _arr(b, s, h, p)
    dt = jnp.asarray(np.abs(RNG.standard_normal((b, s, h))).astype(np.float32) * 0.5)
    a = jnp.asarray(-np.abs(RNG.standard_normal(h)).astype(np.float32))
    bb, cc = _arr(b, s, g, n), _arr(b, s, g, n)
    y_seq = ops.ssd_forward(x, dt, a, bb, cc, use_pallas=False)
    y_chk, _ = ssd_chunked(x, dt, a, bb, cc, chunk=12)
    np.testing.assert_allclose(np.asarray(y_chk), np.asarray(y_seq),
                               rtol=1e-3, atol=1e-3)


def test_ssd_state_carry_matches_split_run():
    """Running 2 halves with carried state == one run (decode correctness)."""
    from repro.models.ssm import ssd_chunked
    b, s, h, p, g, n = 1, 32, 2, 4, 1, 4
    x = _arr(b, s, h, p)
    dt = jnp.asarray(np.abs(RNG.standard_normal((b, s, h))).astype(np.float32) * 0.3)
    a = jnp.asarray(-np.abs(RNG.standard_normal(h)).astype(np.float32))
    bb, cc = _arr(b, s, g, n), _arr(b, s, g, n)
    y_full, st_full = ssd_chunked(x, dt, a, bb, cc, chunk=8)
    y1, st1 = ssd_chunked(x[:, :16], dt[:, :16], a, bb[:, :16], cc[:, :16], chunk=8)
    y2, st2 = ssd_chunked(x[:, 16:], dt[:, 16:], a, bb[:, 16:], cc[:, 16:],
                          chunk=8, initial_state=st1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(st2), np.asarray(st_full),
                               rtol=1e-3, atol=1e-3)
