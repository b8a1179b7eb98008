"""jit'd public wrappers around the Pallas kernels: padding to tile-aligned
shapes, (B, S, ...) <-> kernel layout reshapes, output permutation for GAR.

``use_pallas`` dispatch: True -> the real kernels (what the serving engine
picks on a TPU), 'interpret' -> the same kernels through the Pallas
interpreter (CPU validation, tests only), False -> the pure-jnp oracle
(identical numerics guaranteed by tests/test_kernels.py sweeps). The
wrappers never swap an oracle in for a kernel the caller asked for: a
request the kernel cannot serve raises.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.gar_matmul import gar_matmul
from repro.kernels.lowrank_matmul import lowrank_matmul
from repro.kernels.mamba2_ssd import ssd
from repro.kernels.paged_attention import (paged_attention,
                                           paged_prefill_attention)
from repro.kernels.rwkv6_wkv import wkv6
from repro.kernels.sampling import topk_mask_sample


def _mode(use_pallas):
    if use_pallas == "interpret":
        return True, True
    return bool(use_pallas), False


def _pad_to(x, multiple, axis):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x, size
    width = [(0, 0)] * x.ndim
    width[axis] = (0, pad)
    return jnp.pad(x, width), size


def _no_window(window):
    if window is not None:
        raise ValueError("the Pallas paged-attention kernels have no "
                         "sliding window; serve windowed layers with "
                         "use_pallas=False")


def gar_forward(x: jax.Array, v_tilde: jax.Array, u_hat: jax.Array,
                perm_inv: jax.Array, *, use_pallas=False,
                bt: int = 256, br: int = 256) -> jax.Array:
    """Full GAR linear: y = P^{-1} [z ; z @ u_hat^T], x: (..., n)."""
    lead = x.shape[:-1]
    n = x.shape[-1]
    xf = x.reshape(-1, n)
    run, interp = _mode(use_pallas)
    if u_hat.shape[0] == 0:
        # degenerate full-rank GAR: the identity block IS the whole output
        y = jnp.take(xf @ v_tilde.astype(x.dtype), perm_inv, axis=-1)
        return y.reshape(*lead, -1)
    if run:
        xf_p, t0 = _pad_to(xf, bt, 0)
        v_p, r0 = _pad_to(v_tilde, br, 1)
        u_p, _ = _pad_to(u_hat, br, 1)
        z, tail = gar_matmul(xf_p, v_p, u_p, bt=bt, br=min(br, v_p.shape[1]),
                             interpret=interp)
        z, tail = z[:t0, :r0], tail[:t0]
    else:
        z, tail = ref.gar_matmul_ref(xf, v_tilde, u_hat)
    y = jnp.concatenate([z.astype(x.dtype), tail.astype(x.dtype)], axis=-1)
    y = jnp.take(y, perm_inv, axis=-1)
    return y.reshape(*lead, -1)


def lowrank_forward(x: jax.Array, v: jax.Array, u: jax.Array,
                    rank=None, *, use_pallas=False,
                    bt: int = 256, br: int = 256) -> jax.Array:
    """Masked low-rank linear (training path). x: (..., n) -> (..., m)."""
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    run, interp = _mode(use_pallas)
    if run:
        xf_p, t0 = _pad_to(xf, bt, 0)
        v_p, _ = _pad_to(v, br, 1)
        u_p, _ = _pad_to(u, br, 1)
        y = lowrank_matmul(xf_p, v_p, u_p, rank if rank is not None else v.shape[1],
                           bt=bt, br=min(br, v_p.shape[1]), interpret=interp)
        y = y[:t0]
    else:
        y = ref.lowrank_matmul_ref(xf, v, u, rank)
    return y.astype(x.dtype).reshape(*lead, -1)


def paged_attention_forward(q, k_pool, v_pool, block_tables, context_lens, *,
                            softcap: float = 0.0, window=None,
                            use_pallas=False):
    """Paged decode attention. q: (B, Hq, D); pools: (NB, BS, Hkv * D);
    block_tables: (B, MB); context_lens: (B,). Returns (B, Hq, D).

    ``window`` (sliding-window lookback) is only supported on the oracle
    path: callers serving a windowed layer pass ``use_pallas=False``.
    """
    run, interp = _mode(use_pallas)
    if run:
        _no_window(window)
        return paged_attention(q, k_pool, v_pool,
                               jnp.asarray(block_tables, jnp.int32),
                               jnp.asarray(context_lens, jnp.int32),
                               softcap=softcap, interpret=interp)
    return ref.paged_attention_ref(q, k_pool, v_pool, block_tables,
                                   context_lens, softcap=softcap,
                                   window=window)


def paged_prefill_attention_forward(q, k_pool, v_pool, block_tables, slot_ids,
                                    context_lens, *, softcap: float = 0.0,
                                    window=None, use_pallas=False):
    """Chunked-prefill paged attention over a flat token batch (mixed
    prefill/decode iterations). q: (T, Hq, D); pools: (NB, BS, Hkv * D);
    block_tables: (B, MB); slot_ids/context_lens: (T,). Returns (T, Hq, D).

    ``window`` (sliding-window lookback) is only supported on the oracle
    path: callers serving a windowed layer pass ``use_pallas=False``.
    """
    run, interp = _mode(use_pallas)
    if run:
        _no_window(window)
        return paged_prefill_attention(q, k_pool, v_pool,
                                       jnp.asarray(block_tables, jnp.int32),
                                       jnp.asarray(slot_ids, jnp.int32),
                                       jnp.asarray(context_lens, jnp.int32),
                                       softcap=softcap, interpret=interp)
    return ref.paged_prefill_attention_ref(q, k_pool, v_pool, block_tables,
                                           slot_ids, context_lens,
                                           softcap=softcap, window=window)


def topk_threshold(z, top_k):
    """Per-row top-k cutoff, bitwise ``ref.topk_threshold_ref`` without
    its sort: a full-vocab sort costs the TPU compiler about 17 s per
    program (gpt2-small vocab, v5e), and every sampled step shape is a
    program. Instead the k-th largest value is built bit by bit: on an
    order-preserving uint32 image of the float32 values, the largest key
    ``t`` with ``count(key >= t) >= k`` is found in 32 compare-and-count
    passes over the row, and is the k-th largest key itself.

    z: (S, V) float32; top_k: (S,) int32, 0 = no truncation (-inf). When
    no row truncates, a run-time branch skips the passes: one program
    serves batches with and without top-k rows."""
    z = z.astype(jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)

    def bisect(z, top_k):
        bits = jax.lax.bitcast_convert_type(z, jnp.uint32)
        sign = jnp.uint32(0x80000000)
        key = jnp.where(bits >= sign, ~bits, bits | sign)  # monotone in z
        k = jnp.clip(top_k, 1, z.shape[-1])

        def set_bit(i, t):
            cand = t | (sign >> i.astype(jnp.uint32))
            enough = jnp.sum(key >= cand[:, None], axis=-1) >= k
            return jnp.where(enough, cand, t)

        t = jax.lax.fori_loop(0, 32, set_bit,
                              jnp.zeros(z.shape[:1], jnp.uint32))
        thr = jax.lax.bitcast_convert_type(
            jnp.where(t >= sign, t ^ sign, ~t), jnp.float32)
        return jnp.where(top_k > 0, thr, -jnp.inf)

    def untruncated(z, top_k):
        return jnp.full(z.shape[:1], -jnp.inf, jnp.float32)

    return jax.lax.cond(jnp.any(top_k > 0), bisect, untruncated, z, top_k)


def topk_mask_sample_forward(logits, temperature, top_k, u, *,
                             return_probs: bool = False, use_pallas=False):
    """Fused temperature/top-k warp + one categorical draw per logits row
    (the device sampling pipeline's warp step).

    logits: (S, V); temperature: (S,) — ``<= 0`` means greedy argmax;
    top_k: (S,) int32 (0 = no truncation; ``None`` is all zeros); u: (S,)
    keyed uniforms in [0, 1).
    Returns ``tokens (S,) int32`` (plus the warped ``probs (S, V)`` when
    ``return_probs`` — the speculative draft phase keeps it as ``q``).

    The per-row top-k *threshold* (k-th largest scaled logit) needs global
    ranking, so it is computed here (``topk_threshold``) and handed to the
    kernel / oracle as a cutoff value; the streaming warp + inverse-CDF
    draw is what the Pallas kernel fuses.
    """
    temperature = jnp.asarray(temperature, jnp.float32)
    u = jnp.asarray(u, jnp.float32)
    if top_k is None:
        top_k = jnp.zeros(logits.shape[:1], jnp.int32)
    z = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-30)[:, None]
    threshold = topk_threshold(z, top_k)
    run, interp = _mode(use_pallas)
    if run:
        return topk_mask_sample(logits, temperature, threshold, u,
                                return_probs=return_probs,
                                interpret=interp)
    tokens, probs = ref.topk_mask_sample_ref(logits, temperature, threshold,
                                             u, return_probs=return_probs)
    return (tokens, probs) if return_probs else tokens


def wkv6_forward(r, k, v, w, u, *, chunk: int = 64, use_pallas=False):
    """(B, S, H, N) layout wrapper. u: (H, N)."""
    b, s, h, n = r.shape
    flat = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s, n)
    rf, kf, vf, wf = flat(r), flat(k), flat(v), flat(w)
    uf = jnp.tile(u, (b, 1))
    run, interp = _mode(use_pallas)
    if run:
        rf_p, s0 = _pad_to(rf, chunk, 1)
        kf_p, _ = _pad_to(kf, chunk, 1)
        vf_p, _ = _pad_to(vf, chunk, 1)
        # pad decays with 1.0 (= no-op steps) to keep the recurrence exact
        wf_p = jnp.pad(wf, ((0, 0), (0, rf_p.shape[1] - s0), (0, 0)),
                       constant_values=1.0)
        y = wkv6(rf_p, kf_p, vf_p, wf_p, uf, chunk=chunk, interpret=interp)[:, :s0]
    else:
        y = ref.wkv6_ref(rf, kf, vf, wf, uf)
    return y.reshape(b, h, s, n).transpose(0, 2, 1, 3)


def ssd_forward(x, dt, a, b, c, *, chunk: int = 128, use_pallas=False):
    """(B, S, H, P) layout wrapper. dt: (B,S,H); a: (H,); b/c: (B,S,G,N)."""
    bb, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep = h // g
    xf = x.transpose(0, 2, 1, 3).reshape(bb * h, s, p)
    dtf = dt.transpose(0, 2, 1).reshape(bb * h, s)
    bf = jnp.repeat(b, rep, axis=2).transpose(0, 2, 1, 3).reshape(bb * h, s, n)
    cf = jnp.repeat(c, rep, axis=2).transpose(0, 2, 1, 3).reshape(bb * h, s, n)
    af = jnp.tile(a, (bb,))
    run, interp = _mode(use_pallas)
    if run:
        xp, s0 = _pad_to(xf, chunk, 1)
        dtp, _ = _pad_to(dtf, chunk, 1)
        bp, _ = _pad_to(bf, chunk, 1)
        cp, _ = _pad_to(cf, chunk, 1)
        y = ssd(xp, dtp, af, bp, cp, chunk=chunk, interpret=interp)[:, :s0]
    else:
        y = ref.ssd_ref(xf, dtf, af, bf, cf)
    return y.reshape(bb, h, s, p).transpose(0, 2, 1, 3)
