"""Device-resident sampling pipeline: keyed draws, fused token emission,
and speculative acceptance inside the jitted serving steps.

The host sampler (``serving.sampling``) draws every stochastic uniform as a
pure function of ``(seed, req_id, purpose, position)``; this module ports
that discipline onto JAX's counter-based PRNG — ``keyed_uniform`` folds the
same four integers into a threefry key with ``jax.random.fold_in`` — and
fuses the whole token-emission path into the serving forwards:

  * ``paged_sample_step`` — one mixed serving iteration that returns
    **int32 token ids only**: the LM head runs over the gathered sample
    positions (``caches['sample_ids']``), the warped temperature/top-k
    draw happens in-jit (``ops.topk_mask_sample_forward`` — Pallas kernel
    or jnp oracle), and the host receives one small integer transfer per
    iteration instead of a ``[T, vocab]`` logits tensor.
  * ``paged_verify_accept_step`` — one speculative draft/verify round's
    target forward with Leviathan accept/resample (``device_accept``)
    fused in: the round returns ``(accepted_len, commit tokens)`` per
    sequence plus the finishing prefill chunks' first tokens, instead of
    two full logits tensors.

Determinism contract: device draws are keyed exactly like the host
sampler's stream-split draws, so rollback and preemption-recompute replay
bit-identical device tokens; greedy rows reduce to the raw argmax and stay
bit-identical to the host engines. The *uniforms* themselves come from a
different generator than the host's (threefry vs numpy Philox), so
stochastic tokens agree with the host sampler in distribution, not
bitwise — ``tests/test_device_sampling.py`` pins both halves of that
contract (chi-squared/TV equivalence, and bitwise identity given the same
uniform).

Distribution warps (``ref.warp_probs_ref``) run in float32 on device where
the host oracle uses float64; the Leviathan identity ``min(p, q) + (1 -
sum min(p, q)) * residual = p`` holds for the float32-rounded
distributions the device actually samples from, so exactness is preserved
against the device target sampler (which uses the same float32 warp).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.models import transformer as tfm
from repro.serving.sampling import (DRAW_ACCEPT, DRAW_RESIDUAL, DRAW_TARGET)


def keyed_uniform(seed: jax.Array, req_id: jax.Array, purpose: jax.Array,
                  position: jax.Array) -> jax.Array:
    """One uniform in [0, 1) per row as a pure function of
    ``(seed, req_id, purpose, position)`` — the device port of
    ``serving.sampling.SamplerState.uniform``. All inputs are int32 arrays
    of one shape; the key is built by folding each component into a
    threefry key, so draws for different purposes/positions are mutually
    independent and immune to stream drift by construction (rollback and
    recompute re-derive the same uniform at the same key)."""

    def one(s, r, p, q):
        key = jax.random.PRNGKey(s)
        for part in (r, p, q):
            key = jax.random.fold_in(key, part)
        return jax.random.uniform(key)

    flat = [jnp.asarray(a, jnp.int32).reshape(-1)
            for a in (seed, req_id, purpose, position)]
    return jax.vmap(one)(*flat).reshape(jnp.shape(seed))


def sample_rows(logits: jax.Array, sampling: Dict, *, use_pallas=False,
                return_probs: bool = False):
    """Draw one token per gathered logits row with the row's keyed uniform.

    ``sampling``: {'temperature' (S,), 'top_k' (S,) int32 or None,
    'seed'/'req_id'/'purpose'/'position' (S,) int32}. Greedy rows
    (temperature <= 0) take the raw argmax. Returns (S,) int32 tokens
    (plus the warped (S, V) probs when ``return_probs``)."""
    u = keyed_uniform(sampling["seed"], sampling["req_id"],
                      sampling["purpose"], sampling["position"])
    return ops.topk_mask_sample_forward(
        logits, sampling["temperature"], sampling.get("top_k"), u,
        return_probs=return_probs, use_pallas=use_pallas)


def paged_sample_step(params, cfg, caches: Dict, tokens, sampling: Dict, *,
                      ranks=None, use_pallas=False,
                      return_probs: bool = False):
    """One fused mixed serving iteration: forward + gathered LM head +
    in-jit sampling. ``caches`` must carry ``sample_ids`` (the flat-token
    indices whose next-token distributions are actually read — decode
    slots and finishing prefill chunks), aligned row-for-row with the
    ``sampling`` arrays. Returns ``(tokens (S,) int32, new_caches)`` —
    or ``((tokens, probs), new_caches)`` with the warped (S, V)
    distributions when ``return_probs`` (the speculative draft phase keeps
    them as ``q`` for the accept test)."""
    logits, new_caches = tfm.paged_mixed_step(params, cfg, caches, tokens,
                                              ranks=ranks,
                                              use_pallas=use_pallas)
    out = sample_rows(logits[0], sampling, use_pallas=use_pallas,
                      return_probs=return_probs)
    return out, new_caches


def _warp_rows(rows: jax.Array, temperature: jax.Array,
               top_k: Optional[jax.Array]) -> jax.Array:
    """Warped distributions for a (N, V) row batch with per-row knobs —
    numerically the same float32 warp the fused sampler applies, so a
    token the accept test draws from ``p`` is bitwise what the target-only
    device sampler would have drawn at the same key."""
    if top_k is None:
        thr = jnp.full(rows.shape[:1], -jnp.inf, jnp.float32)
    else:
        z = (rows.astype(jnp.float32)
             / jnp.maximum(jnp.asarray(temperature, jnp.float32),
                           1e-30)[:, None])
        thr = ops.topk_threshold(z, top_k)
    return ref.warp_probs_ref(rows, jnp.asarray(temperature, jnp.float32),
                              thr)


def device_accept(rows: jax.Array, accept: Dict):
    """Vectorized Leviathan accept/resample over one round's verify runs —
    the device port of ``spec.decoder.stochastic_accept`` (and of the
    greedy longest-accepted-prefix rule for greedy sequences).

    ``rows``: (P, K+1, V) target logits — each plan's ``k+1`` scored
    positions, padded to the round's static draft cap ``K`` (rows past a
    plan's own ``k`` are ignored). ``accept``:

      {'k' (P,), 'drafts' (P, K), 'committed' (P,),
       'temperature'/'seed'/'req_id' (P,),
       'top_k' (P,) or absent, 'q' (P, K, V) or absent}

    ``q`` are the draft row's warped proposal distributions (from the
    draft phase's ``return_probs`` output); greedy-only rounds omit it and
    skip the stochastic math entirely. Returns ``(commit (P, K+1) int32,
    accepted (P,) int32)``: every plan commits ``accepted + 1`` tokens —
    accepted drafts, then the first rejection's residual resample or the
    all-accepted bonus draw (``k = 0`` degenerates to one ``DRAW_TARGET``
    draw, the verify-only commit — token-identical to the non-speculative
    device engine)."""
    p_count, kk, v = rows.shape
    k_cap = kk - 1
    temps = jnp.asarray(accept["temperature"], jnp.float32)
    ks = jnp.asarray(accept["k"], jnp.int32)
    drafts = jnp.asarray(accept["drafts"], jnp.int32)
    committed = jnp.asarray(accept["committed"], jnp.int32)
    top_k = accept.get("top_k")

    greedy_tok = jnp.argmax(rows, axis=-1).astype(jnp.int32)   # (P, K+1)

    j = jnp.arange(k_cap, dtype=jnp.int32)[None, :]            # (1, K)
    in_run = j < ks[:, None]
    # greedy: longest prefix of drafts matching the target argmax
    g_ok = (drafts == greedy_tok[:, :k_cap]) & in_run
    g_m = jnp.sum(jnp.cumprod(g_ok.astype(jnp.int32), axis=1), axis=1)

    if accept.get("q") is None:
        m = g_m
        commit = jnp.where(jnp.arange(kk)[None, :] <= m[:, None],
                           greedy_tok, 0)
        return commit, m

    flat = rows.reshape(p_count * kk, v)
    p_warp = _warp_rows(
        flat, jnp.repeat(temps, kk),
        None if top_k is None else jnp.repeat(top_k, kk)
    ).reshape(p_count, kk, v)
    q = jnp.asarray(accept["q"], jnp.float32)                  # (P, K, V)
    seeds = jnp.asarray(accept["seed"], jnp.int32)
    reqs = jnp.asarray(accept["req_id"], jnp.int32)

    def per_plan(p_rows, q_rows, drafts_p, k_p, com, seed, req, g_tok, g_mp,
                 temp):
        jj = jnp.arange(k_cap, dtype=jnp.int32)
        u_acc = keyed_uniform(jnp.full((k_cap,), seed, jnp.int32),
                              jnp.full((k_cap,), req, jnp.int32),
                              jnp.full((k_cap,), DRAW_ACCEPT, jnp.int32),
                              com + jj)
        px = jnp.take_along_axis(p_rows[:k_cap], drafts_p[:, None],
                                 axis=-1)[:, 0]
        qx = jnp.take_along_axis(q_rows, drafts_p[:, None], axis=-1)[:, 0]
        # accept with prob min(1, p/q): u*q <= p sidesteps the q == 0 case
        ok = (u_acc * qx <= px) & (jj < k_p)
        m = jnp.sum(jnp.cumprod(ok.astype(jnp.int32)))
        # first rejection (m < k): resample the normalized residual
        p_m = p_rows[m]
        q_m = q_rows[jnp.minimum(m, k_cap - 1)]
        residual = jnp.maximum(p_m - q_m, 0.0)
        tot = jnp.sum(residual)
        res_w = jnp.where(tot > 1e-12, residual, p_m)
        u_res = keyed_uniform(seed, req, DRAW_RESIDUAL, com + m)
        res_tok = ref.sample_cdf_ref(res_w[None], u_res[None])[0]
        # all accepted (m == k): bonus draw straight from the target row
        u_bon = keyed_uniform(seed, req, DRAW_TARGET, com + m)
        bon_tok = ref.sample_cdf_ref(p_m[None], u_bon[None])[0]
        final = jnp.where(m == k_p, bon_tok, res_tok).astype(jnp.int32)
        idx = jnp.arange(kk, dtype=jnp.int32)
        drafts_pad = jnp.concatenate([drafts_p, jnp.zeros(1, jnp.int32)])
        commit = jnp.where(idx < m, drafts_pad,
                           jnp.where(idx == m, final, 0))
        # greedy sequences in the same round take the prefix-match rule
        g_commit = jnp.where(idx <= g_mp, g_tok, 0)
        return (jnp.where(temp > 0, commit, g_commit),
                jnp.where(temp > 0, m, g_mp))

    commit, m = jax.vmap(per_plan)(p_warp, q, drafts, ks, committed, seeds,
                                   reqs, greedy_tok, g_m, temps)
    return commit, m


def paged_verify_accept_step(params, cfg, caches: Dict, tokens,
                             accept: Dict, chunk_sampling: Optional[Dict],
                             *, ranks=None, use_pallas=False):
    """One speculative round's fused target forward: verify runs + riding
    prefill chunks in one flat batch, acceptance and first-token sampling
    in-jit, int32-only outputs.

    ``caches['sample_ids']`` must lay the gathered rows out as ``P``
    verify runs of exactly ``K+1`` rows each (plans pad their run to the
    round's draft cap by repeating a row — the padding rows are never
    read), followed by the finishing chunks' final-token rows described by
    ``chunk_sampling`` (or nothing, when ``None``). Returns ``(commit
    (P, K+1) int32, accepted (P,) int32, chunk_tokens ((C,) int32 or
    None), new_caches)``."""
    logits, new_caches = tfm.paged_mixed_step(params, cfg, caches, tokens,
                                              ranks=ranks,
                                              use_pallas=use_pallas)
    rows = logits[0]
    p_count, kk = accept["drafts"].shape[0], accept["drafts"].shape[1] + 1
    run_rows = rows[: p_count * kk].reshape(p_count, kk, -1)
    commit, m = device_accept(run_rows, accept)
    chunk_tokens = None
    if chunk_sampling is not None:
        c = chunk_sampling["temperature"].shape[0]
        chunk_tokens = sample_rows(rows[p_count * kk: p_count * kk + c],
                                   chunk_sampling, use_pallas=use_pallas)
    return commit, m, chunk_tokens, new_caches
