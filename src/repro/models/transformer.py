"""Model assembly: segments of scanned blocks -> full architectures.

Every architecture in the assigned pool is a composition of *segments*; each
segment is one ``lax.scan`` over stacked per-layer params, so HLO size and
compile time are O(segments), not O(layers). Heterogeneity inside a segment is
expressed as data (per-layer window sizes as scan xs); structural
heterogeneity (zamba units with a *shared* attention block, vision units with
interleaved cross-attention, enc-dec) is expressed as composite unit bodies.

Public API:
  model_spec(cfg)                      -> ParamSpec pytree
  forward(params, cfg, batch, ranks)   -> (logits, aux)          train/prefill
  init_decode_state(cfg, batch, len)   -> cache pytree (real or shape-only)
  decode_step(params, cfg, state, ...) -> (logits, state)        decode
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, Segment
from repro.distributed.meshctx import constrain
from repro.models import attention as attn
from repro.models import common as cm
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.common import ParamSpec, linear, rget

Array = jax.Array
GLOBAL_WINDOW = 1 << 30

# When True (set via ``unrolled_scans()``), segment scans run as python loops
# so activation taps fire with per-layer "@l" scopes — used only for the
# FlexRank calibration pass (core/flexrank.collect_moments). jit paths always
# use lax.scan.
_UNROLL = {"on": False}
# Activation checkpointing for the train step: when on, every scanned block
# body is jax.checkpoint'ed so only layer-boundary activations persist.
_REMAT = {"on": False}


@__import__("contextlib").contextmanager
def remat_blocks():
    prev = _REMAT["on"]
    _REMAT["on"] = True
    try:
        yield
    finally:
        _REMAT["on"] = prev


import contextlib


@contextlib.contextmanager
def unrolled_scans():
    prev = _UNROLL["on"]
    _UNROLL["on"] = True
    try:
        yield
    finally:
        _UNROLL["on"] = prev


def _scan(body, carry, xs):
    """lax.scan, or a tap-scoped python loop in calibration mode."""
    if not _UNROLL["on"]:
        if _REMAT["on"]:
            body = jax.checkpoint(body)
        return jax.lax.scan(body, carry, xs)
    leaves = jax.tree.leaves(xs)
    length = leaves[0].shape[0]
    ys_acc = []
    for l in range(length):
        xs_l = jax.tree.map(lambda a: a[l], xs)
        with cm.tap_scope(f"@{l}"):
            carry, y = body(carry, xs_l)
        ys_acc.append(y)
    if ys_acc and any(x is not None for x in jax.tree.leaves(ys_acc[0])):
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys_acc)
    else:
        ys = ys_acc[0] if ys_acc else None
    return carry, ys


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def _attn_block_spec(cfg: ModelConfig, *, moe: bool) -> Dict:
    spec = {
        "ln_attn": ParamSpec((cfg.d_model,), (None,), "zeros"),
        "ln_mlp": ParamSpec((cfg.d_model,), (None,), "zeros"),
        "attn": mla_mod.mla_spec(cfg) if cfg.mla else attn.attn_spec(cfg),
        "mlp": moe_mod.moe_spec(cfg) if moe else attn.ffn_spec(cfg),
    }
    return spec


def _mamba_block_spec(cfg: ModelConfig) -> Dict:
    return {
        "ln": ParamSpec((cfg.d_model,), (None,), "zeros"),
        "mamba": ssm_mod.mamba_spec(cfg),
    }


def _cross_block_spec(cfg: ModelConfig) -> Dict:
    return {
        "ln_attn": ParamSpec((cfg.d_model,), (None,), "zeros"),
        "ln_mlp": ParamSpec((cfg.d_model,), (None,), "zeros"),
        "gate": ParamSpec((1,), (None,), "zeros"),       # tanh-gated residual
        "attn": attn.attn_spec(cfg, cross=True, kv_dim=cfg.d_model),
        "mlp": attn.ffn_spec(cfg),
    }


def segment_spec(cfg: ModelConfig, seg: Segment) -> Dict:
    if seg.kind == "attn":
        return cm.stack_spec(_attn_block_spec(cfg, moe=cfg.moe is not None), seg.count)
    if seg.kind == "attn_dense":  # dense-FFN block in an otherwise MoE model
        return cm.stack_spec({
            "ln_attn": ParamSpec((cfg.d_model,), (None,), "zeros"),
            "ln_mlp": ParamSpec((cfg.d_model,), (None,), "zeros"),
            "attn": attn.attn_spec(cfg),
            "mlp": attn.ffn_spec(cfg),
        }, seg.count)
    if seg.kind == "mamba":
        return cm.stack_spec(_mamba_block_spec(cfg), seg.count)
    if seg.kind == "rwkv":
        return cm.stack_spec(rwkv_mod.rwkv_spec(cfg), seg.count)
    if seg.kind == "zamba_unit":
        unit = {
            "mambas": cm.stack_spec(_mamba_block_spec(cfg), seg.mamba_per_unit),
            "ln_attn": ParamSpec((cfg.d_model,), (None,), "zeros"),
            "ln_mlp": ParamSpec((cfg.d_model,), (None,), "zeros"),
            "mlp": attn.ffn_spec(cfg),
        }
        return cm.stack_spec(unit, seg.count)
    if seg.kind == "vision_unit":
        unit = {
            "selfs": cm.stack_spec(_attn_block_spec(cfg, moe=False), seg.self_per_unit),
            "cross": _cross_block_spec(cfg),
        }
        return cm.stack_spec(unit, seg.count)
    if seg.kind == "encoder":
        return cm.stack_spec(_attn_block_spec(cfg, moe=False), seg.count)
    if seg.kind == "decoder":
        unit = _attn_block_spec(cfg, moe=False)
        unit["cross"] = _cross_block_spec(cfg)
        return cm.stack_spec(unit, seg.count)
    raise ValueError(f"unknown segment kind {seg.kind}")


def model_spec(cfg: ModelConfig) -> Dict:
    spec: Dict[str, Any] = {
        "embed": ParamSpec((cfg.vocab_size, cfg.d_model), (cm.VOCAB, cm.EMBED)),
        "final_norm": ParamSpec((cfg.d_model,), (None,), "zeros"),
        "segments": [segment_spec(cfg, s) for s in cfg.segments],
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = {"w": ParamSpec((cfg.d_model, cfg.vocab_size), (cm.EMBED, cm.VOCAB))}
    if any(s.kind == "zamba_unit" for s in cfg.segments):
        # zamba's single *shared* full-attention block (weights reused per unit)
        spec["shared_attn"] = {
            "ln_attn": ParamSpec((cfg.d_model,), (None,), "zeros"),
            "attn": attn.attn_spec(cfg),
        }
    if cfg.frontend_dim:
        spec["frontend_proj"] = {"w": ParamSpec((cfg.frontend_dim, cfg.d_model), (None, cm.EMBED))}
    return spec


def window_schedule(cfg: ModelConfig, count: int, offset: int = 0) -> jnp.ndarray:
    """Per-layer attention window array (scan xs). GLOBAL_WINDOW = full."""
    if not cfg.local_window or not cfg.global_every:
        return jnp.full((count,), GLOBAL_WINDOW, jnp.int32)
    idx = jnp.arange(offset, offset + count)
    is_global = (idx + 1) % cfg.global_every == 0
    return jnp.where(is_global, GLOBAL_WINDOW, cfg.local_window).astype(jnp.int32)


# ---------------------------------------------------------------------------
# block applies (single layer; scanned by segments)
# ---------------------------------------------------------------------------

def _apply_attn_block(p, x, cfg, *, positions, window, ranks, cache, moe):
    h = cm.rms_norm(x, p["ln_attn"], eps=cfg.norm_eps)
    with cm.tap_scope("attn"):
        if cfg.mla:
            y, new_cache = mla_mod.mla_apply(p["attn"], h, cfg, positions=positions,
                                             window=window, ranks=rget_tree(ranks, "attn"),
                                             cache=cache)
        else:
            y, new_cache = attn.attn_apply(p["attn"], h, cfg, positions=positions,
                                           window=window, ranks=rget_tree(ranks, "attn"),
                                           cache=cache)
    x = x + y
    h = cm.rms_norm(x, p["ln_mlp"], eps=cfg.norm_eps)
    with cm.tap_scope("mlp"):
        if moe:
            apply_fn = (moe_mod.moe_apply_ep if (cache is None and h.shape[1] > 1)
                        else moe_mod.moe_apply)
            y, aux = apply_fn(p["mlp"], h, cfg, ranks=rget_tree(ranks, "mlp"))
        else:
            y, aux = attn.ffn_apply(p["mlp"], h, ranks=rget_tree(ranks, "mlp")), 0.0
    x = constrain(x + y, "batch", "sp", None)
    return x, new_cache, aux


def _apply_cross_block(p, x, cfg, *, kv_source, ranks, cache=None,
                       static_kv=None):
    h = cm.rms_norm(x, p["ln_attn"], eps=cfg.norm_eps)
    positions = jnp.arange(x.shape[1])
    with cm.tap_scope("cross"), cm.tap_scope("attn"):
        y, _ = attn.attn_apply(p["attn"], h, cfg, positions=positions,
                               window=GLOBAL_WINDOW, ranks=rget_tree(ranks, "attn"),
                               kv_source=kv_source, static_kv=static_kv,
                               causal=False, use_rope=False)
    x = x + jnp.tanh(p["gate"].astype(x.dtype)) * y
    h = cm.rms_norm(x, p["ln_mlp"], eps=cfg.norm_eps)
    with cm.tap_scope("cross"), cm.tap_scope("mlp"):
        x = x + attn.ffn_apply(p["mlp"], h, ranks=rget_tree(ranks, "mlp"))
    return x


def rget_tree(ranks, key):
    if not isinstance(ranks, dict):
        return None
    return ranks.get(key)


def _seg_ranks(ranks, i):
    """ranks pytree mirrors params: {'segments': [seg0, seg1, ...], ...}."""
    if not isinstance(ranks, dict) or "segments" not in ranks:
        return None
    segs = ranks["segments"]
    return segs[i] if i < len(segs) else None


def _slice_ranks(ranks, i):
    """Index scanned (L,)-leading rank arrays for layer i (host-side loop use)."""
    if ranks is None:
        return None
    return jax.tree.map(lambda a: a[i], ranks)


# ---------------------------------------------------------------------------
# segment runners
# ---------------------------------------------------------------------------

def run_segment(
    seg: Segment,
    params: Dict,
    x: Array,
    cfg: ModelConfig,
    *,
    positions: Array,
    ranks: Optional[Dict],
    cache: Optional[Dict],
    shared_attn_params: Optional[Dict],
    kv_source: Optional[Array],
    layer_offset: int,
    shared_attn_ranks: Optional[Dict] = None,
) -> Tuple[Array, Optional[Dict], Array]:
    """Scan one segment. Returns (x, new_cache, aux_sum)."""
    windows = window_schedule(cfg, seg.count, layer_offset)
    moe = cfg.moe is not None and seg.kind == "attn"

    if seg.kind in ("attn", "attn_dense", "encoder", "decoder"):
        causal = seg.kind != "encoder"

        def body(carry, xs):
            xx, aux = carry
            p_l, win_l, cache_l, ranks_l = xs
            cross_p = p_l.get("cross") if seg.kind == "decoder" else None
            if not causal:
                h = cm.rms_norm(xx, p_l["ln_attn"], eps=cfg.norm_eps)
                with cm.tap_scope("attn"):
                    y, _ = attn.attn_apply(p_l["attn"], h, cfg, positions=positions,
                                           window=GLOBAL_WINDOW, ranks=rget_tree(ranks_l, "attn"),
                                           causal=False)
                xx = xx + y
                h = cm.rms_norm(xx, p_l["ln_mlp"], eps=cfg.norm_eps)
                with cm.tap_scope("mlp"):
                    xx = xx + attn.ffn_apply(p_l["mlp"], h, ranks=rget_tree(ranks_l, "mlp"))
                new_cache_l = cache_l
            else:
                cache_self = cache_l
                if isinstance(cache_l, dict) and "cross_k" in cache_l:
                    cache_self = {k: cache_l[k] for k in ("k", "v", "idx")}
                xx, new_cache_l, aux_l = _apply_attn_block(
                    p_l, xx, cfg, positions=positions, window=win_l,
                    ranks=ranks_l, cache=cache_self, moe=moe)
                if isinstance(cache_l, dict) and "cross_k" in cache_l:
                    new_cache_l = dict(new_cache_l, cross_k=cache_l["cross_k"],
                                       cross_v=cache_l["cross_v"])
                aux = aux + aux_l
                skv = None
                if isinstance(cache_l, dict) and "cross_k" in cache_l:
                    skv = (cache_l["cross_k"], cache_l["cross_v"])
                if cross_p is not None and (kv_source is not None or skv is not None):
                    xx = _apply_cross_block(cross_p, xx, cfg, kv_source=kv_source,
                                            ranks=rget_tree(ranks_l, "cross"),
                                            static_kv=skv)
            return (xx, aux), new_cache_l

        xs = (params, windows, cache, ranks)
        (x, aux), new_cache = _scan(body, (x, jnp.zeros((), jnp.float32)), xs)
        return x, new_cache, aux

    if seg.kind == "mamba":
        def body(carry, xs):
            xx = carry
            p_l, state_l, ranks_l = xs
            h = cm.rms_norm(xx, p_l["ln"], eps=cfg.norm_eps)
            with cm.tap_scope("mamba"):
                y, new_state = ssm_mod.mamba_apply(p_l["mamba"], h, cfg,
                                                   ranks=rget_tree(ranks_l, "mamba"),
                                                   state=state_l)
            return xx + y, new_state

        x, new_cache = _scan(body, x, (params, cache, ranks))
        return x, new_cache, jnp.zeros((), jnp.float32)

    if seg.kind == "rwkv":
        def body(carry, xs):
            xx = carry
            p_l, state_l, ranks_l = xs
            y, new_state = rwkv_mod.rwkv_apply(p_l, xx, cfg, ranks=ranks_l, state=state_l)
            return y, new_state

        x, new_cache = _scan(body, x, (params, cache, ranks))
        return x, new_cache, jnp.zeros((), jnp.float32)

    if seg.kind == "zamba_unit":
        def body(carry, xs):
            xx = carry
            p_u, cache_u, ranks_u = xs

            def mamba_body(c2, xs2):
                p_l, state_l, ranks_l = xs2
                h = cm.rms_norm(c2, p_l["ln"], eps=cfg.norm_eps)
                with cm.tap_scope("mamba"):
                    y, new_state = ssm_mod.mamba_apply(p_l["mamba"], h, cfg,
                                                       ranks=rget_tree(ranks_l, "mamba"),
                                                       state=state_l)
                return c2 + y, new_state

            mcache = None if cache_u is None else cache_u["mamba"]
            mranks = rget_tree(ranks_u, "mambas")
            with cm.tap_scope("mambas"):
                xx, new_mcache = _scan(mamba_body, xx, (p_u["mambas"], mcache, mranks))

            # shared attention block (closed-over weights — zamba's trick)
            h = cm.rms_norm(xx, shared_attn_params["ln_attn"], eps=cfg.norm_eps)
            acache = None if cache_u is None else cache_u["attn"]
            with cm.tap_scope("shared_attn/attn", absolute=True):
                y, new_acache = attn.attn_apply(shared_attn_params["attn"], h, cfg,
                                                positions=positions, window=GLOBAL_WINDOW,
                                                ranks=rget_tree(shared_attn_ranks, "attn"),
                                                cache=acache)
            xx = xx + y
            h = cm.rms_norm(xx, p_u["ln_mlp"], eps=cfg.norm_eps)
            with cm.tap_scope("mlp"):
                xx = xx + attn.ffn_apply(p_u["mlp"], h, ranks=rget_tree(ranks_u, "mlp"))
            new_cache_u = None
            if cache_u is not None:
                new_cache_u = {"mamba": new_mcache, "attn": new_acache}
            return xx, new_cache_u

        x, new_cache = _scan(body, x, (params, cache, ranks))
        return x, new_cache, jnp.zeros((), jnp.float32)

    if seg.kind == "vision_unit":
        def body(carry, xs):
            xx, aux = carry
            p_u, cache_u, ranks_u = xs

            def self_body(c2, xs2):
                p_l, win_l, cache_l, ranks_l = xs2
                out, new_c, aux_l = _apply_attn_block(
                    p_l, c2[0], cfg, positions=positions, window=win_l,
                    ranks=ranks_l, cache=cache_l, moe=False)
                return (out, c2[1] + aux_l), new_c

            wins = jnp.full((seg.self_per_unit,), GLOBAL_WINDOW, jnp.int32)
            scache = None if cache_u is None else cache_u["selfs"]
            sranks = rget_tree(ranks_u, "selfs")
            with cm.tap_scope("selfs"):
                (xx, aux), new_scache = _scan(
                    self_body, (xx, aux), (p_u["selfs"], wins, scache, sranks))
            skv = None
            if isinstance(cache_u, dict) and "cross_k" in cache_u:
                skv = (cache_u["cross_k"], cache_u["cross_v"])
            if kv_source is not None or skv is not None:
                xx = _apply_cross_block(p_u["cross"], xx, cfg, kv_source=kv_source,
                                        ranks=rget_tree(ranks_u, "cross"),
                                        static_kv=skv)
            new_cache_u = None if cache_u is None else dict(cache_u, selfs=new_scache)
            return (xx, aux), new_cache_u

        (x, aux), new_cache = _scan(
            body, (x, jnp.zeros((), jnp.float32)), (params, cache, ranks))
        return x, new_cache, aux

    raise ValueError(seg.kind)


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def embed_tokens(params: Dict, tokens: Array, cfg: ModelConfig) -> Array:
    x = jnp.take(params["embed"], tokens, axis=0)
    return constrain(x.astype(jnp.bfloat16) if params["embed"].dtype == jnp.bfloat16 else x,
                     "batch", None, None)


def lm_logits(params: Dict, x: Array, cfg: ModelConfig) -> Array:
    x = cm.rms_norm(x, params["final_norm"], eps=cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype))
    else:
        logits = linear(params["lm_head"], x)
    return constrain(logits, "batch", None, "vocab")


def _decoder_segments(cfg: ModelConfig) -> List[Tuple[int, Segment]]:
    return [(i, s) for i, s in enumerate(cfg.segments) if s.kind != "encoder"]


def run_encoder(params: Dict, cfg: ModelConfig, enc_input: Array,
                ranks: Optional[Dict] = None) -> Array:
    """Encoder side for enc-dec models. enc_input: frontend embeds (B, T, F)."""
    x = enc_input
    if cfg.frontend_dim and x.shape[-1] == cfg.frontend_dim:
        x = linear(params["frontend_proj"], x)
    positions = jnp.arange(x.shape[1])
    for i, seg in enumerate(cfg.segments):
        if seg.kind != "encoder":
            continue
        seg_ranks = _seg_ranks(ranks, i)
        with cm.tap_scope(f"segments/{i}", absolute=True):
            x, _, _ = run_segment(seg, params["segments"][i], x, cfg,
                                  positions=positions, ranks=seg_ranks, cache=None,
                                  shared_attn_params=params.get("shared_attn"),
                                  kv_source=None, layer_offset=0)
    return cm.rms_norm(x, params["final_norm"], eps=cfg.norm_eps)


def forward(
    params: Dict,
    cfg: ModelConfig,
    tokens: Array,
    *,
    ranks: Optional[Dict] = None,
    frontend: Optional[Array] = None,
    positions: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """Train/prefill forward. tokens: (B, S). Returns (logits, aux_loss).

    ``frontend``: precomputed modality embeddings (B, T_f, frontend_dim) —
    encoder input for enc-dec (audio), cross-attn KV for vlm.
    """
    x = embed_tokens(params, tokens, cfg)
    if positions is None:
        positions = jnp.arange(tokens.shape[1])

    kv_source = None
    if cfg.family == "audio" and frontend is not None:
        kv_source = run_encoder(params, cfg, frontend, ranks)
    elif cfg.family == "vlm" and frontend is not None:
        kv_source = linear(params["frontend_proj"], frontend)

    aux_total = jnp.zeros((), jnp.float32)
    offset = 0
    for i, seg in enumerate(cfg.segments):
        if seg.kind == "encoder":
            continue
        seg_ranks = _seg_ranks(ranks, i)
        with cm.tap_scope(f"segments/{i}", absolute=True):
            x, _, aux = run_segment(seg, params["segments"][i], x, cfg,
                                    positions=positions, ranks=seg_ranks, cache=None,
                                    shared_attn_params=params.get("shared_attn"),
                                    kv_source=kv_source, layer_offset=offset,
                                    shared_attn_ranks=rget_tree(ranks, "shared_attn"))
        aux_total = aux_total + aux
        offset += seg.count
    return lm_logits(params, x, cfg), aux_total


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, batch: int, max_len: int, *,
                      dtype=jnp.bfloat16, cross_kv_len: int = 0) -> Dict:
    """Cache pytree matching segment structure (real arrays).

    ``cross_kv_len`` > 0 allocates precomputed cross-attention K/V buffers
    for vision/enc-dec decode (filled by ``attach_cross_kv``) — the decode
    step then skips the per-token K/V projection of the (static) source
    (EXPERIMENTS.md §Perf cell D)."""
    hd = cfg.resolved_head_dim

    def cross_bufs(count):
        shape = (count, batch, cross_kv_len, cfg.num_kv_heads, hd)
        return {"cross_k": jnp.zeros(shape, dtype),
                "cross_v": jnp.zeros(shape, dtype)}

    caches: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32), "segments": []}
    for seg in cfg.segments:
        if seg.kind == "encoder":
            caches["segments"].append(None)
        elif seg.kind in ("attn", "attn_dense", "decoder"):
            if cfg.mla:
                caches["segments"].append(
                    mla_mod.init_mla_cache(cfg, batch, max_len, dtype=dtype,
                                           num_instances=seg.count))
            else:
                c = attn.init_kv_cache(cfg, batch, max_len, dtype=dtype,
                                       num_instances=seg.count)
                if seg.kind == "decoder" and cross_kv_len:
                    c.update(cross_bufs(seg.count))
                caches["segments"].append(c)
        elif seg.kind == "mamba":
            caches["segments"].append(
                ssm_mod.init_mamba_state(cfg, batch, num_instances=seg.count))
        elif seg.kind == "rwkv":
            caches["segments"].append(
                rwkv_mod.init_rwkv_state(cfg, batch, num_instances=seg.count))
        elif seg.kind == "zamba_unit":
            caches["segments"].append({
                "mamba": jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (seg.count,) + a.shape),
                    ssm_mod.init_mamba_state(cfg, batch, num_instances=seg.mamba_per_unit)),
                "attn": jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (seg.count,) + a.shape),
                    attn.init_kv_cache(cfg, batch, max_len, dtype=dtype,
                                       num_instances=1)),
            })
            # squeeze inner instance dim of attn cache: one shared block per unit
            c = caches["segments"][-1]
            c["attn"] = jax.tree.map(lambda a: a[:, 0], c["attn"])
        elif seg.kind == "vision_unit":
            c = {
                "selfs": jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (seg.count,) + a.shape),
                    attn.init_kv_cache(cfg, batch, max_len, dtype=dtype,
                                       num_instances=seg.self_per_unit)),
            }
            if cross_kv_len:
                c.update(cross_bufs(seg.count))
            caches["segments"].append(c)
        else:
            raise ValueError(seg.kind)
    return caches


def attach_cross_kv(params: Dict, cfg: ModelConfig, state: Dict,
                    kv_source: Array) -> Dict:
    """Fill the cross-attention K/V buffers once per request.

    ``kv_source``: projected source — vlm: frontend_proj(patches); audio:
    encoder output. Returns the updated state."""
    state = dict(state, segments=list(state["segments"]))
    for i, seg in enumerate(cfg.segments):
        c = state["segments"][i]
        if not isinstance(c, dict) or "cross_k" not in c:
            continue
        cross_p = params["segments"][i]["cross"]["attn"]
        k, v = jax.vmap(lambda pl: attn.compute_cross_kv(pl, cfg, kv_source))(cross_p)
        state["segments"][i] = dict(c, cross_k=k.astype(c["cross_k"].dtype),
                                    cross_v=v.astype(c["cross_v"].dtype))
    return state


def has_cross_kv(state: Dict) -> bool:
    return any(isinstance(c, dict) and "cross_k" in c
               for c in state["segments"])


def decode_step(
    params: Dict,
    cfg: ModelConfig,
    state: Dict,
    tokens: Array,
    *,
    ranks: Optional[Dict] = None,
    kv_source: Optional[Array] = None,
) -> Tuple[Array, Dict]:
    """One decode step. tokens: (B, S). Returns (logits (B, S, V), new state).

    S = 1 is the classic decode step; S > 1 runs a *single-pass batched
    prefill* through the same cache (all projections + attention over the
    whole prompt in one forward) — see ``prefill``.
    """
    pos = state["pos"]
    positions = pos + jnp.arange(tokens.shape[1], dtype=jnp.int32)
    x = embed_tokens(params, tokens, cfg)

    cross_cached = has_cross_kv(state)
    if (cfg.family == "vlm" and kv_source is not None and not cross_cached
            and kv_source.shape[-1] == cfg.frontend_dim):
        kv_source = linear(params["frontend_proj"], kv_source)

    new_caches = {"pos": pos + tokens.shape[1], "segments": []}
    offset = 0
    for i, seg in enumerate(cfg.segments):
        if seg.kind == "encoder":
            new_caches["segments"].append(None)
            continue
        seg_ranks = _seg_ranks(ranks, i)
        x, new_c, _ = run_segment(seg, params["segments"][i], x, cfg,
                                  positions=positions, ranks=seg_ranks,
                                  cache=state["segments"][i],
                                  shared_attn_params=params.get("shared_attn"),
                                  kv_source=kv_source, layer_offset=offset,
                                  shared_attn_ranks=rget_tree(ranks, "shared_attn"))
        new_caches["segments"].append(new_c)
        offset += seg.count
    return lm_logits(params, x, cfg), new_caches


def prefill(
    params: Dict,
    cfg: ModelConfig,
    state: Dict,
    tokens: Array,
    *,
    ranks: Optional[Dict] = None,
    kv_source: Optional[Array] = None,
) -> Tuple[Array, Dict]:
    """Single-pass batched prefill: the whole prompt in ONE forward call that
    writes the decode cache (replaces the seed's per-token teacher-forced
    loop — O(1) dispatches instead of O(S)).

    tokens: (B, S). Returns (logits (B, S, V), state); ``logits[:, -1]``
    seeds the first generated token. For recurrent segments (mamba/rwkv) the
    carried-state path supports S up to the family's chunk size.
    """
    return decode_step(params, cfg, state, tokens, ranks=ranks,
                       kv_source=kv_source)


# ---------------------------------------------------------------------------
# paged decode (continuous-batching serving path)
# ---------------------------------------------------------------------------

def paged_compatible(cfg: ModelConfig) -> bool:
    """Paged decode covers pure self-attention stacks (incl. MoE FFNs)."""
    return (cfg.mla is None and cfg.frontend_dim == 0
            and all(s.kind in ("attn", "attn_dense") for s in cfg.segments))


def _run_paged_segments(params, cfg, x, caches, ranks, attn_fn):
    """Shared segment loop for the paged decode/mixed steps: rms_norm ->
    paged attention (``attn_fn``) -> residual -> rms_norm -> moe/ffn ->
    residual, scanned per segment — keeping the two paths structurally
    identical is what upholds the serving engine's token-identity guarantee.

    ``attn_fn(p_attn, h, window, k_pool, v_pool, ranks)`` -> (y, k_pool,
    v_pool); ``window`` is the per-layer traced window, or None for
    all-global configs (those hit the Pallas kernel; the attention layer
    serves windowed stacks on the oracle path, since the kernels have no
    window). Returns (x, new segment pools).
    """
    windowed = bool(cfg.local_window and cfg.global_every)
    new_segments = []
    offset = 0
    for i, seg in enumerate(cfg.segments):
        seg_ranks = _seg_ranks(ranks, i)
        pool = caches["segments"][i]
        moe = cfg.moe is not None and seg.kind == "attn"
        windows = window_schedule(cfg, seg.count, offset)

        def body(carry, xs):
            xx = carry
            p_l, win_l, kp_l, vp_l, ranks_l = xs
            h = cm.rms_norm(xx, p_l["ln_attn"], eps=cfg.norm_eps)
            y, kp_l, vp_l = attn_fn(p_l["attn"], h,
                                    win_l if windowed else None,
                                    kp_l, vp_l, rget_tree(ranks_l, "attn"))
            xx = xx + y
            h = cm.rms_norm(xx, p_l["ln_mlp"], eps=cfg.norm_eps)
            if moe:
                y, _ = moe_mod.moe_apply(p_l["mlp"], h, cfg,
                                         ranks=rget_tree(ranks_l, "mlp"))
            else:
                y = attn.ffn_apply(p_l["mlp"], h, ranks=rget_tree(ranks_l, "mlp"))
            return xx + y, {"k": kp_l, "v": vp_l}

        x, new_pool = _scan(body, x, (params["segments"][i], windows,
                                      pool["k"], pool["v"], seg_ranks))
        new_segments.append(new_pool)
        offset += seg.count
    return x, new_segments


def paged_decode_step(
    params: Dict,
    cfg: ModelConfig,
    caches: Dict,
    tokens: Array,
    *,
    ranks: Optional[Dict] = None,
    use_pallas=False,
) -> Tuple[Array, Dict]:
    """One continuous-batching decode step over a block-paged KV cache.

    tokens: (B, 1). ``caches``: {'positions': (B,) current 0-based token
    index per sequence, 'block_tables': (B, MB), 'segments': [{'k': (count,
    NB, BS, Hkv, D), 'v': ...} per segment]}. Unlike ``decode_step`` there is
    no shared scalar position — every sequence sits at its own length, which
    is what lets new requests join mid-decode. Returns (logits (B, 1, V),
    new caches with K/V scattered into each sequence's blocks).

    Model-level API: since the PR-1 full-prompt path retired, the serving
    engine runs every iteration through ``paged_mixed_step``'s flat-token
    layout instead; this one-token-per-slot entry (and the (B, MB)-grid
    decode kernel beneath it) is kept as the pure-decode fast path —
    it needs no per-token ``slot_ids`` indirection.
    """
    assert paged_compatible(cfg), cfg.name
    positions = caches["positions"]
    block_tables = caches["block_tables"]
    x = embed_tokens(params, tokens, cfg)

    def attn_fn(p, h, window, kp, vp, attn_ranks):
        return attn.paged_attn_apply(
            p, h, cfg, positions=positions, block_tables=block_tables,
            k_pool=kp, v_pool=vp, window=window, ranks=attn_ranks,
            use_pallas=use_pallas)

    x, segments = _run_paged_segments(params, cfg, x, caches, ranks, attn_fn)
    return lm_logits(params, x, cfg), {"positions": positions + 1,
                                       "block_tables": block_tables,
                                       "segments": segments}


def paged_mixed_step(
    params: Dict,
    cfg: ModelConfig,
    caches: Dict,
    tokens: Array,
    *,
    ranks: Optional[Dict] = None,
    use_pallas=False,
) -> Tuple[Array, Dict]:
    """One *mixed* chunked-prefill/decode iteration over the paged KV cache.

    tokens: (1, T) — a flat token batch: the running decode batch (one token
    per decoding slot) concatenated with FIFO prefill chunks, all under one
    per-iteration token budget (Sarathi/vLLM-style fused iterations). Unlike
    ``paged_decode_step`` there is no one-token-per-slot layout: ``caches``
    carries per-token routing instead —

      {'slot_ids':  (T,) block-table row per token (pads -> a null row),
       'positions': (T,) 0-based position of each token in its sequence,
       'block_tables': (B(+null rows), MB),
       'segments': [{'k': (count, NB, BS, Hkv, D), 'v': ...} per segment],
       'sample_ids': optional (S,) flat-token indices to score}

    Each token's K/V is scattered into its slot's blocks, then it attends
    over its own ``position + 1`` keys — so one dispatch advances every
    decoding sequence by a token AND pushes prefill chunks through, instead
    of stopping the world for a batch-1 prompt forward.

    **Sample-position gather**: when ``sample_ids`` is present, the LM head
    (and final norm) run only over the gathered hidden rows — the decode
    slots and chunk-final tokens whose next-token distributions are
    actually read — so the ``[T, vocab]`` logits tensor of the original
    mixed step shrinks to ``[S, vocab]``: mid-chunk prompt tokens never
    pay the vocab matmul. Returns (logits (1, S, V), new caches); without
    ``sample_ids`` the full (1, T, V) rows come back (kernel parity tests
    and the speculative decoder's host-oracle path use this form). Logits
    at a chunk's final prompt token seed the sequence's first generated
    token.
    """
    assert paged_compatible(cfg), cfg.name
    slot_ids = caches["slot_ids"]
    positions = caches["positions"]
    block_tables = caches["block_tables"]
    x = embed_tokens(params, tokens, cfg)

    def attn_fn(p, h, window, kp, vp, attn_ranks):
        return attn.paged_prefill_attn_apply(
            p, h, cfg, slot_ids=slot_ids, positions=positions,
            block_tables=block_tables, k_pool=kp, v_pool=vp, window=window,
            ranks=attn_ranks, use_pallas=use_pallas)

    x, segments = _run_paged_segments(params, cfg, x, caches, ranks, attn_fn)
    new_caches = {"slot_ids": slot_ids, "positions": positions,
                  "block_tables": block_tables, "segments": segments}
    if "sample_ids" in caches:
        x = jnp.take(x, caches["sample_ids"], axis=1)
        new_caches["sample_ids"] = caches["sample_ids"]
    return lm_logits(params, x, cfg), new_caches


def paged_verify_step(
    params: Dict,
    cfg: ModelConfig,
    caches: Dict,
    tokens: Array,
    *,
    ranks: Optional[Dict] = None,
    use_pallas=False,
) -> Tuple[Array, Dict]:
    """Full-row verification forward for nested self-speculative decoding:
    score ``k+1`` positions per sequence in ONE call over the paged cache.

    Layout is the flat-token layout of ``paged_mixed_step`` — each verifying
    sequence contributes a run of ``k+1`` consecutive tokens (its last
    committed token followed by ``k`` draft proposals) routed to its
    *target* cache slot via per-token ``slot_ids``/``positions``; target
    prefill chunks of other sequences may ride the same batch. Every run's
    K/V lands in the target slot's blocks before attention, so position
    ``i`` of a run attends over exactly the context target-only decoding
    would have seen — greedy acceptance over the returned logits is
    therefore token-identical to non-speculative decoding, and rejected
    suffixes are rolled back host-side with ``PagedKVCache.truncate_slot``.

    Return contract: the logits rows named by ``caches['sample_ids']``
    (all of them, ``(1, T, V)``, when the gather operand is absent). The
    old "full-logits-rows" contract — ship every scored row to the host so
    the accept test could compare whole distributions there — is retired:
    the device-resident pipeline gathers exactly the ``k+1`` verify rows
    per sequence (plus riding chunk-final rows) and runs the accept test
    ``min(1, p_tgt(x) / p_draft(x))`` and residual resample
    ``max(p_tgt - p_draft, 0)`` *inside* the jitted round
    (``serving.device_sampling.paged_verify_accept_step`` wraps this step
    with ``device_accept``), so a draft/verify round returns
    ``(accepted_len, tokens)`` as int32 instead of two full logits
    tensors. The host sampler path (``ElasticEngine(device_sampling=
    False)``) still consumes the gathered rows host-side as the test
    oracle.

    Sharing the ``paged_mixed_step`` body (same ``_run_paged_segments``
    loop, same ``paged_prefill_attention`` kernel) is deliberate: the PR-2
    parity suites that pin the mixed path to the sequential decode path are
    what carry the verify path's exactness.
    """
    return paged_mixed_step(params, cfg, caches, tokens, ranks=ranks,
                            use_pallas=use_pallas)
