"""Pallas fused sampling kernel: temperature/top-k warp + categorical draw.

``topk_mask_sample`` is the device-resident warp step of the serving
sampling pipeline: given a batch of gathered logits rows (one per sample
position of a mixed serving iteration), per-row sampler knobs, and one
keyed uniform per row, it emits the sampled token ids without ever
materializing the warped probability tensor in HBM (unless the caller asks
for it — the speculative draft phase keeps the warped distribution ``q``
for the accept test).

Grid is (S_pad / 8, 2, NBV): blocks of eight rows outermost, then a
two-pass sweep over vocab blocks, innermost sequential —

  * **pass 0** accumulates the flash-style running ``(max, denom)`` of the
    masked, temperature-scaled logits (the softmax normalizer) plus the raw
    argmax for greedy rows (``temperature <= 0``);
  * **pass 1** re-streams the same blocks, forms the unnormalized
    exponentials, and counts CDF entries ``<= u * denom`` — the count IS
    the inverse-CDF sample (same ``searchsorted(side="right")`` boundary
    rule as ``ref.sample_cdf_ref`` and the host
    ``serving.sampling.sample_from``). The in-block prefix sum runs one
    128-lane chunk at a time as a matmul with an upper-triangular ones
    matrix (Mosaic has no ``cumsum``), plus a running total carried in
    scratch.

The top-k cutoff arrives as a per-row *threshold* on the scaled logits
(-inf = no truncation), computed by the ``ops.py`` wrapper
(``ops.topk_threshold``) — ranking needs global context, the warp + draw
does not, so only the latter lives in the kernel's streaming form.

TPU tiling: every block's last two dims are multiples of (8, 128) or span
the whole axis. The wrapper pads the rows to a multiple of 8 (pad rows are
greedy and sliced off), the vocabulary with -1e30 logits to a multiple of
the vocab block, and takes the per-row knobs as ``(S_pad, 1)`` columns.
Tokens come back lane-dense as an ``(S_pad, 128)`` int32 slab read at
column 0. Tests validate via interpret mode against
``ref.topk_mask_sample_ref``; ``tests/test_tpu_compile.py`` compiles the
kernel for a v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
ROWS = 8        # sublane tile: sample rows per block
LANES = 128     # lane tile: width of the token slab and of one CDF chunk


def _sample_kernel(temp_ref, thr_ref, u_ref, logits_ref, tok_ref, *rest,
                   bv: int, chunk: int, v: int, return_probs: bool):
    if return_probs:
        probs_ref, m_ref, l_ref, best_ref, bidx_ref, cum_ref, cnt_ref = rest
    else:
        m_ref, l_ref, best_ref, bidx_ref, cum_ref, cnt_ref = rest
    pass_ = pl.program_id(1)
    j = pl.program_id(2)
    nbv = pl.num_programs(2)
    temp = temp_ref[...]                                     # (R, 1)
    thr = thr_ref[...]
    u = u_ref[...]

    @pl.when((pass_ == 0) & (j == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        best_ref[...] = jnp.full_like(best_ref, NEG_INF)
        bidx_ref[...] = jnp.zeros_like(bidx_ref)
        cum_ref[...] = jnp.zeros_like(cum_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    x = logits_ref[...].astype(jnp.float32)                  # (R, bv)
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    # warp: temperature scale + threshold mask (pads carry NEG_INF already)
    z = x / jnp.maximum(temp, 1e-30)
    zz = jnp.where(z >= thr, z, NEG_INF)

    @pl.when(pass_ == 0)
    def _normalizer():
        # greedy running argmax: first column holding the block max, and a
        # strict > across blocks, so the first occurrence wins
        bm = jnp.max(x, axis=1, keepdims=True)
        first = jnp.min(jnp.where(x == bm, col.astype(jnp.float32),
                                  float(2 ** 30)), axis=1, keepdims=True)
        better = bm > best_ref[...]
        bidx_ref[...] = jnp.where(better, first.astype(jnp.int32),
                                  bidx_ref[...])
        best_ref[...] = jnp.maximum(best_ref[...], bm)
        # flash (max, denom) for the warped softmax
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(zz, axis=1, keepdims=True))
        l_ref[...] = (l_ref[...] * jnp.exp(m_prev - m_new)
                      + jnp.sum(jnp.exp(zz - m_new), axis=1, keepdims=True))
        m_ref[...] = m_new

    @pl.when(pass_ == 1)
    def _draw():
        e = jnp.exp(zz - m_ref[...])                         # (R, bv)
        target = u * l_ref[...]
        tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
               <= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
               ).astype(jnp.float32)
        cum, cnt = cum_ref[...], cnt_ref[...]
        for c in range(bv // chunk):
            ec = e[:, c * chunk:(c + 1) * chunk]
            cs = cum + jax.lax.dot(ec, tri,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
            cnt = cnt + jnp.sum((cs <= target).astype(jnp.int32), axis=1,
                                keepdims=True)
            cum = cum + jnp.sum(ec, axis=1, keepdims=True)
        cum_ref[...], cnt_ref[...] = cum, cnt
        if return_probs:
            one_hot = (col == bidx_ref[...]).astype(jnp.float32)
            probs_ref[...] = jnp.where(temp > 0.0, e / l_ref[...], one_hot)

        @pl.when(j == nbv - 1)
        def _emit():
            drawn = jnp.minimum(cnt, v - 1)     # never a padded column
            tok = jnp.where(temp > 0.0, drawn, bidx_ref[...])
            tok_ref[...] = jnp.broadcast_to(tok, tok_ref.shape)


@functools.partial(jax.jit, static_argnames=("bv", "return_probs",
                                             "interpret"))
def topk_mask_sample(logits: jax.Array, temperature: jax.Array,
                     threshold: jax.Array, u: jax.Array, *, bv: int = 2048,
                     return_probs: bool = False,
                     interpret: bool = False):
    """Fused warp + categorical draw over gathered logits rows.

    Contract (see docs/kernels.md):

    * ``logits``: (S, V) float — one row per sample position (decode slots,
      finishing prefill chunks, draft emissions of a serving iteration).
    * ``temperature``: (S,) float32 — ``<= 0`` means greedy: the row's
      token is the raw argmax and ``u`` is ignored.
    * ``threshold``: (S,) float32 — top-k cutoff on the *scaled* logits
      (row keeps entries ``>= threshold``); -inf disables truncation. The
      ``ops.topk_mask_sample_forward`` wrapper derives it from per-row
      ``top_k`` (``ops.topk_threshold``).
    * ``u``: (S,) float32 in [0, 1) — one keyed uniform per row
      (``serving.device_sampling.keyed_uniform``).

    Returns ``tokens (S,) int32``, plus ``probs (S, V) float32`` (the
    warped distribution actually sampled from; one-hot for greedy rows)
    when ``return_probs`` — the speculative draft phase keeps it as ``q``.

    ``bv`` is the vocab block, capped at V rounded up to 128 lanes. On a
    TPU it must be a multiple of 128; interpret mode takes any width (a
    block narrower than 128 lanes is one CDF chunk).
    """
    s, v = logits.shape
    bv = min(bv, -(-v // LANES) * LANES)
    chunk = LANES if bv % LANES == 0 else bv
    s_pad = -(-s // ROWS) * ROWS
    logits = jnp.pad(logits, ((0, s_pad - s), (0, (-v) % bv)),
                     constant_values=NEG_INF)
    nbv = logits.shape[1] // bv

    def column(a):
        # pad rows: greedy (temperature 0), no cutoff, u = 0; sliced off
        return jnp.pad(a.astype(jnp.float32), (0, s_pad - s))[:, None]

    row_spec = pl.BlockSpec((ROWS, 1), lambda i, p, j: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((s_pad, LANES), jnp.int32)]
    out_specs = [pl.BlockSpec((ROWS, LANES), lambda i, p, j: (i, 0))]
    if return_probs:
        out_shape.append(jax.ShapeDtypeStruct(logits.shape, jnp.float32))
        # pass 0 parks on block 0 so only pass 1's blocks are written back
        out_specs.append(pl.BlockSpec((ROWS, bv), lambda i, p, j: (i, j * p)))
    out = pl.pallas_call(
        functools.partial(_sample_kernel, bv=bv, chunk=chunk, v=v,
                          return_probs=return_probs),
        grid=(s_pad // ROWS, 2, nbv),
        in_specs=[row_spec, row_spec, row_spec,
                  pl.BlockSpec((ROWS, bv), lambda i, p, j: (i, j))],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((ROWS, 1), jnp.float32),     # running max
            pltpu.VMEM((ROWS, 1), jnp.float32),     # running denom
            pltpu.VMEM((ROWS, 1), jnp.float32),     # greedy best value
            pltpu.VMEM((ROWS, 1), jnp.int32),       # greedy best index
            pltpu.VMEM((ROWS, 1), jnp.float32),     # CDF carry across blocks
            pltpu.VMEM((ROWS, 1), jnp.int32),       # entries <= target so far
        ],
        interpret=interpret,
    )(column(temperature), column(threshold), column(u), logits)
    tokens = out[0][:s, 0]
    if return_probs:
        return tokens, out[1][:s, :v]
    return tokens
