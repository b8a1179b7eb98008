"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gar_matmul_ref(x, v_tilde, u_hat):
    """(z, tail) for z = x@v_tilde, tail = z@u_hat^T."""
    z = x @ v_tilde
    return z, z @ u_hat.T


def lowrank_matmul_ref(x, v, u, rank=None):
    z = x @ v
    if rank is not None:
        mask = (jnp.arange(z.shape[-1]) < rank).astype(z.dtype)
        z = z * mask
    return z @ u.T


def wkv6_ref(r, k, v, w, u):
    """Sequential WKV6 recurrence. r/k/v/w: (BH, S, N); u: (BH, N)."""
    bh, s, n = r.shape

    def step(state, xs):
        r_t, k_t, v_t, w_t = xs  # (BH, N)
        kv = k_t[:, :, None] * v_t[:, None, :]           # (BH, N, N)
        y = jnp.einsum("bn,bnm->bm", r_t, state + u[:, :, None] * kv)
        state = state * w_t[:, :, None] + kv
        return state, y

    init = jnp.zeros((bh, n, n), jnp.float32)
    xs = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0) for t in (r, k, v, w))
    _, ys = jax.lax.scan(step, init, xs)
    return jnp.moveaxis(ys, 0, 1).astype(r.dtype)


def paged_attention_ref(q, k_pool, v_pool, block_tables, context_lens, *,
                        softcap=0.0, window=None):
    """Decode attention over a block-paged KV cache (gather + plain softmax).

    q: (B, Hq, D) — one query token per sequence, pre-RoPE'd.
    k_pool/v_pool: (NB, BS, Hkv * D) — global block pools, lane-dense (kv
    head h is lanes [h*D, (h+1)*D) of a token's row).
    block_tables: (B, MB) int32 — per-sequence block ids (0 = null block).
    context_lens: (B,) int32 — valid tokens per sequence (incl. current).

    Numerics deliberately mirror ``models.attention.chunked_attend`` (q
    pre-scaled, fp32 logits, -1e30 mask) so the paged engine stays
    token-identical to the contiguous decode path.
    """
    import math
    b, hq, d = q.shape
    nb, bs, row = k_pool.shape
    hkv = row // d
    mb = block_tables.shape[1]
    g = hq // hkv
    k = jnp.take(k_pool, block_tables, axis=0).reshape(b, mb * bs, hkv, d)
    v = jnp.take(v_pool, block_tables, axis=0).reshape(b, mb * bs, hkv, d)
    qg = (q * (1.0 / math.sqrt(d))).reshape(b, hkv, g, d)
    logits = jnp.einsum("bhgd,bthd->bhgt", qg, k).astype(jnp.float32)
    if softcap and softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    k_pos = jnp.arange(mb * bs, dtype=jnp.int32)[None, :]        # (1, T)
    valid = k_pos < context_lens[:, None]
    if window is not None:
        valid &= k_pos >= (context_lens[:, None] - window)
    logits = jnp.where(valid[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgt,bthd->bhgd", probs, v)
    return out.reshape(b, hq, d).astype(q.dtype)


def paged_prefill_attention_ref(q, k_pool, v_pool, block_tables, slot_ids,
                                context_lens, *, softcap=0.0, window=None):
    """Chunked-prefill attention over a block-paged KV cache.

    Generalizes ``paged_attention_ref`` from one-query-per-sequence to a flat
    token batch: query ``t`` belongs to batch slot ``slot_ids[t]`` and attends
    over the first ``context_lens[t]`` keys of that slot's block table (its
    own K/V must already be scattered into the pool, so intra-chunk causality
    is expressed purely through per-token context lengths).

    q: (T, Hq, D) — flat chunk/decode tokens, pre-RoPE'd.
    block_tables: (B, MB) int32 — per-slot block ids (0 = null block).
    slot_ids: (T,) int32 — row of ``block_tables`` for each token (point pad
    tokens at a row of null blocks).
    context_lens: (T,) int32 — ``position + 1`` of each token in its sequence.
    """
    per_token_tables = jnp.take(block_tables, slot_ids, axis=0)   # (T, MB)
    return paged_attention_ref(q, k_pool, v_pool, per_token_tables,
                               context_lens, softcap=softcap, window=window)


def topk_threshold_ref(z, top_k):
    """Per-row top-k cutoff on already-temperature-scaled logits.

    z: (S, V); top_k: (S,) int32 — 0 means no truncation. Returns (S,)
    thresholds: the k-th largest value of each row (rows keep every entry
    ``>= threshold``, so exact ties at the cutoff survive — matching the
    host sampler's ``np.partition`` rule), or -inf where ``top_k == 0``.
    """
    v = z.shape[-1]
    srt = jnp.sort(z, axis=-1)[:, ::-1]                     # descending
    k = jnp.clip(top_k, 1, v) - 1
    thr = jnp.take_along_axis(srt, k[:, None], axis=-1)[:, 0]
    return jnp.where(top_k > 0, thr, -jnp.inf)


def warp_probs_ref(logits, temperature, threshold):
    """Warped categorical per row: temperature scaling then threshold mask.

    logits: (S, V); temperature: (S,) with <= 0 meaning greedy (one-hot
    argmax, the zero-temperature limit); threshold: (S,) top-k cutoff on the
    *scaled* logits (-inf = no truncation). Returns (S, V) normalized
    probabilities — the device mirror of
    ``serving.sampling.SamplerState.probs`` (float32 instead of the host
    oracle's float64).
    """
    v = logits.shape[-1]
    t = jnp.maximum(temperature, 1e-30)[:, None]
    z = logits.astype(jnp.float32) / t
    z = jnp.where(z >= threshold[:, None], z, -jnp.inf)
    z = z - jnp.max(z, axis=-1, keepdims=True)
    p = jnp.exp(z)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    one_hot = (jnp.argmax(logits, axis=-1)[:, None]
               == jnp.arange(v)[None, :]).astype(jnp.float32)
    return jnp.where(temperature[:, None] > 0, p, one_hot)


def sample_cdf_ref(weights, u, block: int = 1024):
    """Inverse-CDF sample per row from non-negative (possibly unnormalized)
    weights with one uniform each — the device mirror of
    ``serving.sampling.sample_from`` (same ``searchsorted(side="right")``
    boundary rule: the token index is the count of CDF entries <= u * total,
    clamped to the last token). weights: (S, V); u: (S,). Returns (S,) int32.

    Two-level CDF: per-block sums locate the crossing block, then one small
    within-block scan resolves the index — a full-vocab ``cumsum`` lowers
    to a serial scan on CPU/TPU and dominated the fused sampler's cost at
    128k vocab. The blocked prefix (carry of block sums + within-block
    cumsum) is exactly the Pallas kernel's streaming structure, so kernel
    and oracle keep token-level parity.
    """
    s, v = weights.shape
    bv = min(block, v)
    pad = (-v) % bv
    w = weights.astype(jnp.float32)
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))     # zero weight: never crossed
    nb = w.shape[1] // bv
    blocks = w.reshape(s, nb, bv)
    bs = jnp.sum(blocks, axis=-1)              # (S, NB) block sums
    cum = jnp.cumsum(bs, axis=-1)              # tiny: NB entries per row
    target = u.astype(jnp.float32) * cum[:, -1]
    b = jnp.sum((cum <= target[:, None]).astype(jnp.int32), axis=-1)
    b = jnp.minimum(b, nb - 1)
    carry = jnp.where(b > 0,
                      jnp.take_along_axis(cum, jnp.maximum(b - 1, 0)[:, None],
                                          axis=-1)[:, 0], 0.0)
    inner = jnp.take_along_axis(blocks, b[:, None, None], axis=1)[:, 0]
    cs = carry[:, None] + jnp.cumsum(inner, axis=-1)   # (S, BV): one block
    idx = b * bv + jnp.sum((cs <= target[:, None]).astype(jnp.int32),
                           axis=-1)
    return jnp.minimum(idx, v - 1)


def topk_mask_sample_ref(logits, temperature, threshold, u,
                         return_probs: bool = True):
    """Fused warp + sample oracle: per row, temperature/top-k warp the
    logits and draw one token by inverse CDF with uniform ``u`` (greedy rows
    — ``temperature <= 0`` — take the raw argmax and ignore ``u``).

    logits: (S, V); temperature/u: (S,); threshold: (S,) or None (no row
    truncates — skips the masking pass entirely). Returns ``(tokens (S,)
    int32, probs)`` where ``probs`` is the warped (S, V) distribution each
    row actually sampled from (one-hot for greedy rows) — the draft phase
    of speculative decoding keeps it as ``q`` for the accept test — or
    None when ``return_probs`` is unset (the serving hot path: the draw
    samples the unnormalized exponentials directly, skipping the
    normalization and one-hot passes).
    """
    t = jnp.maximum(temperature, 1e-30)[:, None]
    z = logits.astype(jnp.float32) / t
    if threshold is not None:
        z = jnp.where(z >= threshold[:, None], z, -jnp.inf)
    e = jnp.exp(z - jnp.max(z, axis=-1, keepdims=True))
    sampled = sample_cdf_ref(e, u)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    tokens = jnp.where(temperature > 0, sampled, greedy)
    if not return_probs:
        return tokens, None
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    one_hot = (greedy[:, None]
               == jnp.arange(logits.shape[-1])[None, :]).astype(jnp.float32)
    return tokens, jnp.where(temperature[:, None] > 0, p, one_hot)


def ssd_ref(x, dt, a, b, c):
    """Sequential SSD recurrence. x: (BH,S,P); dt: (BH,S); a: (BH,); b/c: (BH,S,N)."""
    bh, s, p = x.shape
    n = b.shape[-1]

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs                          # (BH,P),(BH,),(BH,N)
        decay = jnp.exp(dt_t * a)                         # (BH,)
        state = state * decay[:, None, None] + jnp.einsum(
            "bn,bp->bnp", b_t, x_t * dt_t[:, None])
        y = jnp.einsum("bn,bnp->bp", c_t, state)
        return state, y

    init = jnp.zeros((bh, n, p), jnp.float32)
    xs = (jnp.moveaxis(x.astype(jnp.float32), 1, 0),
          jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
          jnp.moveaxis(b.astype(jnp.float32), 1, 0),
          jnp.moveaxis(c.astype(jnp.float32), 1, 0))
    _, ys = jax.lax.scan(step, init, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)
