"""Pallas paged attention: decode and chunked-prefill variants over a
block-paged KV cache whose blocks live at non-contiguous pool slots.

``paged_attention`` (decode): grid (B, MB), one query token per sequence. The
per-sequence block table is a *scalar-prefetch* operand, so the BlockSpec
index map DMAs exactly the K/V blocks the sequence owns — gathering from the
pool without ever materializing a contiguous (B, T) cache. The MB axis is
sequential per sequence; softmax runs in streaming (flash) form with running
(max, denom, acc) scratch carried across blocks, and blocks past
``context_len`` are skipped entirely (their DMA still targets a valid pool
slot — the shared null block 0 — so the index map stays in bounds). The MB
axis is bounded at run time by the longest context in the call (a dynamic
grid bound), so a table wider than every live sequence costs no grid steps:
the serving steps pass one table width whatever the sequences' lengths.

``paged_prefill_attention`` (mixed chunked-prefill/decode iterations): grid
(T, MB), bounded the same way, over a *flat token batch* — several tokens
may belong to the same sequence (a prefill chunk) while others are single
decode tokens of other sequences. A third scalar-prefetch operand,
``slot_ids``, maps each token to its block-table row; per-token
``context_lens`` (= position + 1) express intra-chunk causality, because the
chunk's own K/V is scattered into the pool before the kernel runs.

Layout: the pools are lane-dense, (NB, BS, Hkv * D): a token's K (or V)
for every kv head is one row, kv head h at lanes [h*D, (h+1)*D), which the
body takes as a static lane slice. The q block is (1, Hq, D) and each K/V
block (1, BS, Hkv * D); their last two dims span the array axes, which
Mosaic accepts at any size. A (NB, BS, Hkv, D) pool cost a relayout copy of
the whole pool into and out of every layer (docs/kernels.md).
``tests/test_tpu_compile.py`` compiles both kernels for a v5e and checks the
compiled step for pool copies; tests validate numerics via interpret mode
against ``ref.paged_attention_ref``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_body(ctx, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                bs: int, softcap: float, groups: int):
    """Shared streaming-softmax block step for both paged kernels: the grid
    row (a batch slot for decode, a flat token for chunked prefill) has
    already resolved its K/V block and ``ctx`` valid keys."""
    j = pl.program_id(1)
    mb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bs < ctx)
    def _block():
        q = q_ref[0].astype(jnp.float32)                 # (Hq, D)
        hq, d = q.shape
        hkv = hq // groups
        # kv head h is lanes [h*D, (h+1)*D) of each (BS, Hkv*D) row
        k, v = (jnp.stack([r[0, :, h * d:(h + 1) * d].astype(jnp.float32)
                           for h in range(hkv)])     # (Hkv, BS, D)
                for r in (k_ref, v_ref))
        qg = (q * (1.0 / math.sqrt(d))).reshape(hkv, groups, d)
        # (Hkv, G, BS) logits via per-kv-head batched contraction
        logits = jax.lax.dot_general(
            qg, k,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        if softcap and softcap > 0.0:
            logits = softcap * jnp.tanh(logits / softcap)
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
        logits = jnp.where(k_pos < ctx, logits, NEG_INF)
        logits = logits.reshape(hq, bs)

        m_prev, l_prev = m_ref[...], l_ref[...]          # (Hq, 1)
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)                      # (Hq, BS)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(
            p.reshape(hkv, groups, bs), v,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)          # (Hkv, G, D)
        acc_ref[...] = acc_ref[...] * alpha + pv.reshape(hq, d)

    @pl.when(j == mb - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _groups(hq: int, d: int, row: int) -> int:
    """Query heads per kv head, from q's (Hq, D) and a pool row of Hkv * D."""
    hkv = row // d
    assert hkv * d == row and hq % hkv == 0, (hq, d, row)
    return hq // hkv


def _live_blocks(context_lens, bs: int, mb: int):
    """The grid's block axis, set at run time: the blocks of the longest
    context in the call, at least one and at most the table's width."""
    n = (jnp.max(context_lens.astype(jnp.int32)) + bs - 1) // bs
    return jnp.clip(n, 1, mb)


def _kernel(block_tables_ref, context_lens_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, bs: int, softcap: float, groups: int):
    ctx = context_lens_ref[pl.program_id(0)]
    _flash_body(ctx, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                bs=bs, softcap=softcap, groups=groups)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array, context_lens: jax.Array, *,
                    softcap: float = 0.0, interpret: bool = False) -> jax.Array:
    """Paged decode attention: one query token per batch slot.

    Contract (see docs/kernels.md for the full operand walkthrough):

    * ``q``: (B, Hq, D) — the decode batch's current tokens.
    * ``k_pool`` / ``v_pool``: (NB, BS, Hkv * D) — global block pools,
      lane-dense (kv head h is lanes ``[h*D, (h+1)*D)`` of a token's row);
      a sequence's K/V lives at the (non-contiguous) blocks its table
      names. Hq must be a multiple of Hkv (grouped-query heads).
    * ``block_tables``: (B, MB) int32 — scalar-prefetch operand; entry
      ``[i, j]`` is the pool slot of sequence ``i``'s ``j``-th block.
      Unused entries must point at a valid pool slot (the shared null
      block 0) so every grid step's DMA stays in bounds.
    * ``context_lens``: (B,) int32 — keys visible to each query; blocks at
      or past the length are skipped (their values never enter the
      softmax), so stale data in reused blocks is harmless.
    * ``softcap`` > 0 applies ``softcap * tanh(logits / softcap)``.

    Grid is (B, n), n the blocks of the longest context (at most MB),
    innermost and sequential per sequence: streaming (flash) softmax over
    blocks with float32 running (max, denom, acc) scratch. Returns
    (B, Hq, D) in ``q``'s dtype. Prefer calling through
    ``ops.paged_attention_forward`` — it owns the ref/Pallas/interpret
    dispatch."""
    b, hq, d = q.shape
    _, bs, row = k_pool.shape
    mb = block_tables.shape[1]
    groups = _groups(hq, d, row)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, _live_blocks(context_lens, bs, mb)),
        in_specs=[
            pl.BlockSpec((1, hq, d), lambda i, j, bt, cl: (i, 0, 0)),
            pl.BlockSpec((1, bs, row), lambda i, j, bt, cl: (bt[i, j], 0, 0)),
            pl.BlockSpec((1, bs, row), lambda i, j, bt, cl: (bt[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hq, d), lambda i, j, bt, cl: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, softcap=softcap, groups=groups),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      q, k_pool, v_pool)


def _prefill_kernel(slot_ids_ref, block_tables_ref, context_lens_ref,
                    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                    bs: int, softcap: float, groups: int):
    """Grid's first axis is a flat token index instead of a batch slot; the
    block table row was resolved through ``slot_ids`` by the index maps, so
    the body only needs the per-token context length."""
    ctx = context_lens_ref[pl.program_id(0)]
    _flash_body(ctx, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                bs=bs, softcap=softcap, groups=groups)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_prefill_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, block_tables: jax.Array,
                            slot_ids: jax.Array, context_lens: jax.Array, *,
                            softcap: float = 0.0,
                            interpret: bool = False) -> jax.Array:
    """Flat-token paged attention for mixed prefill/decode iterations and
    speculative verify runs.

    Contract (see docs/kernels.md):

    * ``q``: (T, Hq, D) — ONE flat token batch: decode tokens, prompt
      chunks, draft-warmup feeds, and k+1-token verify runs all mix here;
      consecutive tokens of one run belong to the same sequence.
    * ``slot_ids``: (T,) int32 — third scalar-prefetch operand mapping
      each token to its block-table ROW. Pad tokens must point at an
      appended row of null blocks, never at a live sequence.
    * ``block_tables``: (B + null_rows, MB) int32 — as in
      ``paged_attention``, plus the pad rows.
    * ``context_lens``: (T,) int32 — per TOKEN, ``position + 1``: the
      token's own causal horizon. Intra-chunk causality works because the
      caller scatters the whole chunk's K/V into the pool *before* this
      kernel runs; token ``i`` of a chunk then sees exactly its prefix.
    * ``softcap`` as in ``paged_attention``.

    Grid is (T, n), n as in ``paged_attention``; the block-table row is
    resolved through ``slot_ids`` inside the BlockSpec index maps, so the
    body is the same streaming-softmax step as the decode kernel
    (``_flash_body``).
    Returns (T, Hq, D). Prefer ``ops.paged_prefill_attention_forward``
    for dispatch."""
    t, hq, d = q.shape
    _, bs, row = k_pool.shape
    mb = block_tables.shape[1]
    groups = _groups(hq, d, row)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(t, _live_blocks(context_lens, bs, mb)),
        in_specs=[
            pl.BlockSpec((1, hq, d), lambda i, j, sid, bt, cl: (i, 0, 0)),
            pl.BlockSpec((1, bs, row),
                         lambda i, j, sid, bt, cl: (bt[sid[i], j], 0, 0)),
            pl.BlockSpec((1, bs, row),
                         lambda i, j, sid, bt, cl: (bt[sid[i], j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hq, d), lambda i, j, sid, bt, cl: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_prefill_kernel, bs=bs, softcap=softcap,
                          groups=groups),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, hq, d), q.dtype),
        interpret=interpret,
    )(slot_ids.astype(jnp.int32), block_tables.astype(jnp.int32),
      context_lens.astype(jnp.int32), q, k_pool, v_pool)
