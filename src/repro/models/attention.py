"""GQA self-attention (+RoPE, sliding window, logit softcap), cross-attention,
and FFN blocks — spec/apply pairs consumable by segment scans.

Memory discipline: training/prefill attention is *query-chunked* (exact, not
approximate): logits are materialized per (B, Hkv, G, Qc, T) chunk only, so
32k-token prefill never allocates an S x S score matrix. Decode attends one
query position against a (possibly sequence-sharded) KV cache.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common as cm
from repro.models.common import ParamSpec, linear

Array = jax.Array

Q_CHUNK = 1024  # query chunk for exact chunked attention


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def attn_spec(cfg: ModelConfig, *, cross: bool = False, kv_dim: Optional[int] = None) -> Dict:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    kvd = kv_dim or d
    return {
        "q": {"w": ParamSpec((d, cfg.num_heads * hd), (cm.EMBED, cm.HEADS))},
        "k": {"w": ParamSpec((kvd, cfg.num_kv_heads * hd), (cm.EMBED, cm.KV_HEADS))},
        "v": {"w": ParamSpec((kvd, cfg.num_kv_heads * hd), (cm.EMBED, cm.KV_HEADS))},
        "o": {"w": ParamSpec((cfg.num_heads * hd, d), (cm.HEADS, cm.EMBED))},
        "q_norm": ParamSpec((hd,), (None,), "zeros"),
        "k_norm": ParamSpec((hd,), (None,), "zeros"),
    }


def ffn_spec(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "gate": {"w": ParamSpec((d, f), (cm.EMBED, cm.MLP))},
        "up": {"w": ParamSpec((d, f), (cm.EMBED, cm.MLP))},
        "down": {"w": ParamSpec((f, d), (cm.MLP, cm.EMBED))},
    }


def block_norms_spec(cfg: ModelConfig, names: Tuple[str, ...]) -> Dict:
    return {n: ParamSpec((cfg.d_model,), (None,), "zeros") for n in names}


# ---------------------------------------------------------------------------
# chunked exact attention
# ---------------------------------------------------------------------------

def _softcap(logits: Array, cap: float) -> Array:
    if cap and cap > 0.0:
        return cap * jnp.tanh(logits / cap)
    return logits


def chunked_attend(q: Array, k: Array, v: Array, *, q_positions: Array,
                   k_positions: Array, window: Array | int, softcap: float = 0.0,
                   causal: bool = True) -> Array:
    """Exact attention, scanned over query chunks.

    q: (B, S, Hq, D); k/v: (B, T, Hkv, D). positions: (S,) / (T,) int32.
    ``window``: scalar (may be traced) — lookback horizon; pass T for global.
    """
    b, s, hq, dh = q.shape
    t = k.shape[1]
    hkv = k.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(dh)
    qc = min(Q_CHUNK, s)
    n_chunks = max(s // qc, 1)
    assert s % qc == 0 or n_chunks == 1, (s, qc)
    qc = s // n_chunks

    q = (q * scale).reshape(b, n_chunks, qc, hkv, g, dh)
    q_pos = q_positions.reshape(n_chunks, qc)

    def one_chunk(carry, xs):
        q_i, pos_i = xs  # (b, qc, hkv, g, dh), (qc,)
        logits = jnp.einsum("bqhgd,bthd->bhgqt", q_i, k).astype(jnp.float32)
        logits = _softcap(logits, softcap)
        delta = pos_i[:, None] - k_positions[None, :]
        valid = delta < window
        if causal:
            valid &= delta >= 0
        logits = jnp.where(valid[None, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhgqt,bthd->bqhgd", probs, v)
        return carry, out

    _, outs = jax.lax.scan(one_chunk, None,
                           (jnp.moveaxis(q, 1, 0), q_pos))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s, hq, dh)
    return out


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------

def _split_heads(x: Array, n: int) -> Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n, -1)


def project_qkv(p: Dict, x: Array, cfg: ModelConfig, *,
                ranks: Dict[str, Array], positions: Array,
                rope: bool = True) -> Tuple[Array, Array, Array]:
    """Self-attention q/k/v projection + head norms + RoPE.

    Shared by the contiguous (``attn_apply``) and paged
    (``paged_attn_apply``) decode paths — they must stay numerically
    identical for the serving engine's token-identity guarantee.
    """
    q = _split_heads(linear(p["q"], x, rank=ranks.get("q"), tap="q"), cfg.num_heads)
    q = cm.rms_norm(q, p["q_norm"], eps=cfg.norm_eps)
    k = _split_heads(linear(p["k"], x, rank=ranks.get("k"), tap="k"), cfg.num_kv_heads)
    v = _split_heads(linear(p["v"], x, rank=ranks.get("v"), tap="v"), cfg.num_kv_heads)
    k = cm.rms_norm(k, p["k_norm"], eps=cfg.norm_eps)
    if rope:
        q = cm.rope(q, positions, base=cfg.rope_base)
        k = cm.rope(k, positions, base=cfg.rope_base)
    return q, k, v


def attn_apply(
    p: Dict,
    x: Array,
    cfg: ModelConfig,
    *,
    positions: Array,
    window: Array | int,
    ranks: Optional[Dict[str, Array]] = None,
    cache: Optional[Dict[str, Array]] = None,
    kv_source: Optional[Array] = None,
    static_kv: Optional[Tuple[Array, Array]] = None,
    causal: bool = True,
    use_rope: bool = True,
) -> Tuple[Array, Optional[Dict[str, Array]]]:
    """Self- or cross-attention.

    ``cache`` (decode): {'k': (B, T, Hkv, D), 'v': ..., 'idx': ()} — returns
    the updated cache. ``kv_source`` (cross-attn): encoder/vision embeddings.
    ``static_kv``: precomputed cross-attention (k, v) — skips the K/V
    projections entirely (vision/enc-dec decode; EXPERIMENTS.md §Perf D).
    ``ranks``: FlexRank nested rank per projection name (traced scalars).
    """
    r = ranks or {}
    hd = cfg.resolved_head_dim

    if kv_source is None and static_kv is None:
        q, k, v = project_qkv(p, x, cfg, ranks=r, positions=positions,
                              rope=use_rope)
    else:
        q = _split_heads(linear(p["q"], x, rank=r.get("q"), tap="q"), cfg.num_heads)
        q = cm.rms_norm(q, p["q_norm"], eps=cfg.norm_eps)
        if static_kv is not None:
            k, v = static_kv
        else:
            k = _split_heads(linear(p["k"], kv_source, rank=r.get("k"), tap="k"), cfg.num_kv_heads)
            v = _split_heads(linear(p["v"], kv_source, rank=r.get("v"), tap="v"), cfg.num_kv_heads)
            k = cm.rms_norm(k, p["k_norm"], eps=cfg.norm_eps)
        if use_rope and kv_source is None:
            q = cm.rope(q, positions, base=cfg.rope_base)
            k = cm.rope(k, positions, base=cfg.rope_base)

    new_cache = None
    if cache is not None:
        # decode: x is (B, 1, D); scatter kv at cache['idx'].
        idx = cache["idx"]
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), idx, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), idx, axis=1)
        new_cache = {"k": ck, "v": cv, "idx": idx + x.shape[1]}
        t = ck.shape[1]
        k_positions = jnp.arange(t)
        out = chunked_attend(q, ck, cv, q_positions=positions,
                             k_positions=k_positions, window=window,
                             softcap=cfg.attn_logit_softcap, causal=causal)
    else:
        k_positions = (positions if kv_source is None
                       else jnp.arange(kv_source.shape[1]))
        out = chunked_attend(q, k, v, q_positions=positions,
                             k_positions=k_positions, window=window,
                             softcap=cfg.attn_logit_softcap,
                             causal=causal and kv_source is None)

    b, s = x.shape[:2]
    out = out.reshape(b, s, cfg.num_heads * hd)
    y = linear(p["o"], out, rank=r.get("o"), tap="o")
    return y, new_cache


def paged_attn_apply(
    p: Dict,
    x: Array,
    cfg: ModelConfig,
    *,
    positions: Array,
    block_tables: Array,
    k_pool: Array,
    v_pool: Array,
    window: Optional[Array | int] = None,
    ranks: Optional[Dict[str, Array]] = None,
    use_pallas=False,
) -> Tuple[Array, Array, Array]:
    """Decode self-attention over a block-paged KV cache.

    x: (B, 1, d) — one token per sequence, each at its *own* position
    (continuous batching: sequences in the batch are at different lengths).
    ``positions``: (B,) int32 — 0-based index of the current token; its K/V is
    scattered into (block_tables[b, pos // BS], pos % BS) before attending
    over the ``pos + 1`` valid keys. Pools are lane-dense (NB, BS, Hkv * D)
    (see ``serving.kv_cache``). Returns (y, k_pool, v_pool).
    """
    r = ranks or {}
    hd = cfg.resolved_head_dim
    bsz = x.shape[0]
    bs = k_pool.shape[1]

    q, k, v = project_qkv(p, x, cfg, ranks=r, positions=positions[:, None])

    blk = jnp.take_along_axis(block_tables, (positions // bs)[:, None], axis=1)[:, 0]
    off = positions % bs
    # lane-dense pool rows: (B, Hkv, D) -> (B, Hkv * D)
    k_pool = k_pool.at[blk, off].set(
        k[:, 0].reshape(bsz, -1).astype(k_pool.dtype))
    v_pool = v_pool.at[blk, off].set(
        v[:, 0].reshape(bsz, -1).astype(v_pool.dtype))

    from repro.kernels import ops
    out = ops.paged_attention_forward(
        q[:, 0], k_pool, v_pool, block_tables, positions + 1,
        softcap=cfg.attn_logit_softcap, window=window,
        use_pallas=use_pallas if window is None else False)
    out = out.reshape(bsz, 1, cfg.num_heads * hd)
    y = linear(p["o"], out, rank=r.get("o"), tap="o")
    return y, k_pool, v_pool


def paged_prefill_attn_apply(
    p: Dict,
    x: Array,
    cfg: ModelConfig,
    *,
    slot_ids: Array,
    positions: Array,
    block_tables: Array,
    k_pool: Array,
    v_pool: Array,
    window: Optional[Array | int] = None,
    ranks: Optional[Dict[str, Array]] = None,
    use_pallas=False,
) -> Tuple[Array, Array, Array]:
    """Mixed chunked-prefill/decode self-attention over a block-paged cache.

    x: (1, T, d) — a *flat token batch*: each token t belongs to batch slot
    ``slot_ids[t]`` and sits at ``positions[t]`` in that slot's sequence.
    Prefill chunks appear as runs of consecutive positions of one slot;
    decode tokens are singleton runs. Every token's K/V is scattered into
    (block_tables[slot, pos // BS], pos % BS) *before* attention, so queries
    see their own chunk's earlier keys through the pool and intra-chunk
    causality reduces to the per-token context length ``pos + 1``.

    Pad tokens must point ``slot_ids`` at a block-table row made of null
    blocks (the engine appends one) so their writes and reads never touch a
    live sequence. Returns (y, k_pool, v_pool).
    """
    r = ranks or {}
    hd = cfg.resolved_head_dim
    t = x.shape[1]
    bs = k_pool.shape[1]

    q, k, v = project_qkv(p, x, cfg, ranks=r, positions=positions[None, :])

    blk = block_tables[slot_ids, positions // bs]                   # (T,)
    off = positions % bs
    # distinct (slot, pos) pairs -> distinct (blk, off) targets; pads all
    # write identical values to the null block, so duplicates are benign.
    # The pool's rows are lane-dense: (T, Hkv, D) -> (T, Hkv * D)
    k_pool = k_pool.at[blk, off].set(k[0].reshape(t, -1).astype(k_pool.dtype))
    v_pool = v_pool.at[blk, off].set(v[0].reshape(t, -1).astype(v_pool.dtype))

    from repro.kernels import ops
    out = ops.paged_prefill_attention_forward(
        q[0], k_pool, v_pool, block_tables, slot_ids, positions + 1,
        softcap=cfg.attn_logit_softcap, window=window,
        use_pallas=use_pallas if window is None else False)
    out = out.reshape(1, t, cfg.num_heads * hd)
    y = linear(p["o"], out, rank=r.get("o"), tap="o")
    return y, k_pool, v_pool


def ffn_apply(p: Dict, x: Array, *, ranks: Optional[Dict[str, Array]] = None) -> Array:
    r = ranks or {}
    gate = linear(p["gate"], x, rank=r.get("gate"), tap="gate")
    up = linear(p["up"], x, rank=r.get("up"), tap="up")
    return linear(p["down"], cm.swiglu(gate, up), rank=r.get("down"), tap="down")


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, *, dtype=jnp.bfloat16,
                  num_instances: int = 1) -> Dict[str, "jax.ShapeDtypeStruct"]:
    """Shape skeleton for one attention cache (stacked over instances)."""
    hd = cfg.resolved_head_dim
    shape = (num_instances, batch, max_len, cfg.num_kv_heads, hd)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "idx": jnp.zeros((num_instances,), jnp.int32),
    }


def compute_cross_kv(p: Dict, cfg: ModelConfig, kv_source: Array,
                     *, ranks: Optional[Dict[str, Array]] = None):
    """Precompute cross-attention (k, v) once per request (decode fast path)."""
    r = ranks or {}
    k = _split_heads(linear(p["k"], kv_source, rank=r.get("k")), cfg.num_kv_heads)
    v = _split_heads(linear(p["v"], kv_source, rank=r.get("v")), cfg.num_kv_heads)
    k = cm.rms_norm(k, p["k_norm"], eps=cfg.norm_eps)
    return k, v
