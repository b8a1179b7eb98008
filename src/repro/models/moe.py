"""Mixture-of-Experts FFN with top-k routing, capacity-bounded gather
dispatch, shared experts, and load-balancing aux loss.

Dispatch is gather/scatter-based (sort-free): top-k routing picks expert ids
per token, a per-expert running cumsum assigns capacity slots, overflowing
tokens are dropped (standard capacity-factor semantics). Expert tensors carry
a leading ``experts`` axis which shards over the 'model' mesh axis (expert
parallelism); XLA lowers the gather/scatter across the EP axis into
all-to-all-style collectives visible in the dry-run HLO.

FlexRank: per-expert weights are factorized along their (d_in, d_out) dims —
each expert gets its own (u, v) pair stacked over the experts axis, truncated
by the same nested rank machinery as dense layers.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common as cm
from repro.models.common import ParamSpec, linear

Array = jax.Array


def moe_spec(cfg: ModelConfig) -> Dict:
    assert cfg.moe is not None
    d, m = cfg.d_model, cfg.moe
    spec: Dict = {
        "router": {"w": ParamSpec((d, m.num_experts), (cm.EMBED, None))},
        "experts": {
            "gate": {"w": ParamSpec((m.num_experts, d, m.d_ff_expert), (cm.EXPERTS, cm.EMBED, cm.MLP))},
            "up": {"w": ParamSpec((m.num_experts, d, m.d_ff_expert), (cm.EXPERTS, cm.EMBED, cm.MLP))},
            "down": {"w": ParamSpec((m.num_experts, m.d_ff_expert, d), (cm.EXPERTS, cm.MLP, cm.EMBED))},
        },
    }
    if m.num_shared:
        f_sh = m.d_ff_shared or m.d_ff_expert
        spec["shared"] = {
            "gate": {"w": ParamSpec((d, m.num_shared * f_sh), (cm.EMBED, cm.MLP))},
            "up": {"w": ParamSpec((d, m.num_shared * f_sh), (cm.EMBED, cm.MLP))},
            "down": {"w": ParamSpec((m.num_shared * f_sh, d), (cm.MLP, cm.EMBED))},
        }
    return spec


def _expert_linear(p: Dict, x: Array, *, rank: Optional[Array] = None,
                   tap: Optional[str] = None) -> Array:
    """Batched per-expert linear: x (B, E, C, d_in) @ W (E, d_in, d_out).

    Factorized form: w = v (E, d_in, r) ; u (E, d_out, r).
    """
    if cm.taps_active():
        cm.record_tap(tap, x)
    if "w" in p:
        return jnp.einsum("becd,edf->becf", x, p["w"].astype(x.dtype))
    if "u_hat" in p:  # GAR deploy form (see core/gar.py)
        z = jnp.einsum("becd,edr->becr", x, p["v_tilde"].astype(x.dtype))
        tail = jnp.einsum("becr,efr->becf", z, p["u_hat"].astype(x.dtype))
        y = jnp.concatenate([z, tail], axis=-1)
        return jnp.take_along_axis(y, p["perm_inv"][None, :, None, :], axis=-1)
    z = jnp.einsum("becd,edr->becr", x, p["v"].astype(x.dtype))
    if rank is not None:
        mask = (jnp.arange(z.shape[-1]) < rank).astype(z.dtype)
        z = z * mask
    return jnp.einsum("becr,efr->becf", z, p["u"].astype(x.dtype))


def moe_apply(
    p: Dict,
    x: Array,
    cfg: ModelConfig,
    *,
    ranks: Optional[Dict[str, Array]] = None,
) -> Tuple[Array, Array]:
    """Returns (output, aux_loss). x: (B, S, D).

    Dispatch is *row-local*: every batch row assigns its own capacity slots
    (C = ceil(S * top_k * cf / E)), so the scatter/gather pair stays sharded
    over the data axis and the only cross-device movement is the data<->expert
    all-to-all on the (B, E, C, d) tensor. (The first version flattened (B, S)
    into one global token list, whose capacity cumsum forced XLA to replicate
    and all-reduce the dispatch buffers — 370 GB/step on deepseek-moe-16b;
    see EXPERIMENTS.md §Perf cell B.) Per-row capacity is also what real EP
    serving systems enforce per device.
    """
    from repro.distributed.meshctx import constrain
    m = cfg.moe
    r = ranks or {}
    b, s, d = x.shape

    gate_logits = linear(p["router"], x.astype(jnp.float32))      # (B, S, E)
    probs = jax.nn.softmax(gate_logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, m.top_k)                  # (B, S, K)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

    capacity = int(math.ceil(s * m.top_k * m.capacity_factor / m.num_experts))
    capacity = max(capacity, 4)

    # slot assignment within each row: position in the expert queue
    flat_e = top_e.reshape(b, s * m.top_k)                        # (B, S*K)
    onehot = jax.nn.one_hot(flat_e, m.num_experts, dtype=jnp.int32)
    slot = jnp.cumsum(onehot, axis=1) * onehot
    flat_slot = jnp.sum(slot, axis=-1) - 1                        # (B, S*K)
    keep = flat_slot < capacity
    flat_gate = top_p.reshape(b, s * m.top_k) * keep.astype(top_p.dtype)

    # dispatch: ex_in[b, e, c] = x[b, token assigned to (e, c)]
    dest = flat_e * capacity + jnp.where(keep, flat_slot, capacity)
    token_idx = jnp.repeat(jnp.arange(s), m.top_k)[None, :]       # (1, S*K)
    rows = jnp.arange(b)[:, None]
    src = jnp.take_along_axis(x, jnp.broadcast_to(token_idx, (b, s * m.top_k))[..., None], axis=1)
    ex_in = jnp.zeros((b, m.num_experts * capacity + 1, d), x.dtype)
    ex_in = ex_in.at[rows, jnp.where(keep, dest, m.num_experts * capacity)].set(src)
    ex_in = ex_in[:, :-1].reshape(b, m.num_experts, capacity, d)
    # data<->expert all-to-all boundary (EP):
    ex_in = constrain(ex_in, "batch", "experts", None, None)

    h = cm.swiglu(
        _expert_linear(p["experts"]["gate"], ex_in, rank=cm.rget(r,"experts","gate"), tap="experts/gate"),
        _expert_linear(p["experts"]["up"], ex_in, rank=cm.rget(r,"experts","up"), tap="experts/up"),
    )
    ex_out = _expert_linear(p["experts"]["down"], h, rank=cm.rget(r,"experts","down"), tap="experts/down")
    ex_out = constrain(ex_out, "batch", "experts", None, None)
    ex_out = ex_out.reshape(b, m.num_experts * capacity, d)

    # combine: gather back per (token, k) slot and sum over k — no scatter
    gathered = jnp.take_along_axis(ex_out, jnp.where(keep, dest, 0)[..., None], axis=1)
    gathered = gathered * flat_gate[..., None].astype(ex_out.dtype)
    out = jnp.sum(gathered.reshape(b, s, m.top_k, d), axis=2)
    out = constrain(out, "batch", None, None).astype(x.dtype)

    if m.num_shared:
        sh = cm.swiglu(
            linear(p["shared"]["gate"], x, rank=cm.rget(r,"shared","gate"), tap="shared/gate"),
            linear(p["shared"]["up"], x, rank=cm.rget(r,"shared","up"), tap="shared/up"),
        )
        out = out + linear(p["shared"]["down"], sh, rank=cm.rget(r,"shared","down"), tap="shared/down")

    # load-balance aux (Switch-style): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=(0, 1))                             # (E,)
    ce = jnp.mean(jax.nn.one_hot(top_e[..., 0], m.num_experts, dtype=jnp.float32), axis=(0, 1))
    aux = m.num_experts * jnp.sum(me * ce) * m.router_aux_weight
    return out, aux


# ---------------------------------------------------------------------------
# shard_map expert-parallel path (§Perf cell B, iteration 3)
# ---------------------------------------------------------------------------
# The global-view dispatch above is correct everywhere but lets the SPMD
# partitioner replicate the (E, C, d) dispatch buffers and all-reduce them
# (hundreds of GB/step at deepseek-moe scale). This path is the textbook EP
# schedule instead: tokens are split across the 'model' axis, each device
# routes its own slice, a pair of all-to-alls moves (token, expert) shards,
# expert FFNs run on local experts, and an all-gather returns token outputs.
# Per-device collective volume drops to ~2 * T_slice * topk * cf * d bytes.

def _moe_inner(x_col, router_w, exp_params, rank_vals, *, cfg, axis="model"):
    """Per-device body. x_col: (Tc, d) — this device's token slice."""
    m = cfg.moe
    tc, d = x_col.shape
    # jax.lax.axis_size is too new for the floor jax version; psum(1) is the
    # portable spelling of the axis size
    n_dev = jax.lax.psum(1, axis)
    e_loc = m.num_experts // n_dev

    logits = x_col.astype(jnp.float32) @ router_w                # (Tc, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, m.top_k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

    capacity = int(math.ceil(tc * m.top_k * m.capacity_factor / m.num_experts))
    capacity = max(capacity, 4)
    # pad capacity so the all-to-all concat dim divides evenly
    flat_e = top_e.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, m.num_experts, dtype=jnp.int32)
    slot = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
    keep = slot < capacity
    gate = top_p.reshape(-1) * keep.astype(top_p.dtype)
    dest = flat_e * capacity + jnp.where(keep, slot, capacity)
    token_idx = jnp.repeat(jnp.arange(tc), m.top_k)

    ex_in = jnp.zeros((m.num_experts * capacity + 1, d), x_col.dtype)
    ex_in = ex_in.at[jnp.where(keep, dest, m.num_experts * capacity)].set(
        x_col[token_idx])
    ex_in = ex_in[:-1].reshape(m.num_experts, capacity, d)

    # EP exchange: (E, C, d) -> (E_loc, C * n_dev, d)
    ex_in = jax.lax.all_to_all(ex_in, axis, split_axis=0, concat_axis=1,
                               tiled=True)

    def elin(p, x, rank):
        if "w" in p:
            return jnp.einsum("ecd,edf->ecf", x, p["w"].astype(x.dtype))
        if "u_hat" in p:
            z = jnp.einsum("ecd,edr->ecr", x, p["v_tilde"].astype(x.dtype))
            tail = jnp.einsum("ecr,efr->ecf", z, p["u_hat"].astype(x.dtype))
            y = jnp.concatenate([z, tail], axis=-1)
            return jnp.take_along_axis(y, p["perm_inv"][:, None, :], axis=-1)
        z = jnp.einsum("ecd,edr->ecr", x, p["v"].astype(x.dtype))
        if rank is not None:
            z = z * (jnp.arange(z.shape[-1]) < rank).astype(z.dtype)
        return jnp.einsum("ecr,efr->ecf", z, p["u"].astype(x.dtype))

    h = cm.swiglu(elin(exp_params["gate"], ex_in, rank_vals.get("gate")),
                  elin(exp_params["up"], ex_in, rank_vals.get("up")))
    ex_out = elin(exp_params["down"], h, rank_vals.get("down"))

    # return exchange: (E_loc, C * n_dev, d) -> (E, C, d)
    ex_out = jax.lax.all_to_all(ex_out, axis, split_axis=1, concat_axis=0,
                                tiled=True)
    ex_out = ex_out.reshape(m.num_experts * capacity, d)
    gathered = ex_out[jnp.where(keep, dest, 0)] * gate[:, None].astype(ex_out.dtype)
    # each device combined exactly its own token slice — no gather needed;
    # the out_specs sequence-split layout hands resharding to XLA only where
    # the next op actually needs full sequence.
    out = jax.ops.segment_sum(gathered, token_idx, num_segments=tc)

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], m.num_experts, dtype=jnp.float32), axis=0)
    aux = m.num_experts * jnp.sum(me * ce) * m.router_aux_weight
    aux = jax.lax.pmean(aux, axis)
    return out.astype(x_col.dtype), aux


def moe_apply_ep(p: Dict, x: Array, cfg: ModelConfig, *,
                 ranks: Optional[Dict[str, Array]] = None) -> Tuple[Array, Array]:
    """shard_map EP MoE (train/prefill path on a mesh). Falls back to
    moe_apply when no mesh is active or token counts don't divide."""
    shard_map = functools.partial(jax.shard_map, check_vma=False)
    from jax.sharding import PartitionSpec as P
    from repro.distributed.meshctx import get_current_mesh, data_axes

    mesh = get_current_mesh()
    m = cfg.moe
    b, s, d = x.shape
    if mesh is None or "model" not in mesh.axis_names:
        return moe_apply(p, x, cfg, ranks=ranks)
    n_model = mesh.shape["model"]
    d_axes = data_axes(mesh)
    n_data = 1
    for a in d_axes:
        n_data *= mesh.shape[a]
    if (m.num_experts % n_model or (b * s) % (n_data * n_model)
            or b % n_data):
        return moe_apply(p, x, cfg, ranks=ranks)

    r = ranks or {}
    rank_vals = {k: cm.rget(r, "experts", k) for k in ("gate", "up", "down")}
    rank_vals = {k: (jnp.asarray(v) if v is not None else jnp.asarray(1 << 30))
                 for k, v in rank_vals.items()}

    batch_entry = d_axes if len(d_axes) > 1 else d_axes[0]
    exp_specs = jax.tree.map(lambda _: P("model", None, None), p["experts"])
    # perm_inv leaves are 2D (E, m); fix their spec rank
    exp_specs = jax.tree.map(
        lambda leaf, spec: P("model", None) if leaf.ndim == 2 else spec,
        p["experts"], exp_specs)

    def outer(x_in, router_w, exp_params, rvals):
        # x_in per device: (B_loc, S, d) token-split over 'model' via reshape
        bl, sl, dd = x_in.shape
        x_flat = x_in.reshape(bl * sl, dd)
        out, aux = _moe_inner(x_flat, router_w, exp_params,
                              {k: rvals[k] for k in rvals}, cfg=cfg)
        return out.reshape(bl, sl, dd), aux

    sm = shard_map(
        outer, mesh=mesh,
        in_specs=(P(batch_entry, "model", None), P(), exp_specs,
                  {k: P() for k in rank_vals}),
        out_specs=(P(batch_entry, "model", None), P()))
    out, aux = sm(x, p["router"]["w"].astype(jnp.float32), p["experts"], rank_vals)

    if m.num_shared:
        sh = cm.swiglu(
            linear(p["shared"]["gate"], x, rank=cm.rget(r, "shared", "gate"), tap="shared/gate"),
            linear(p["shared"]["up"], x, rank=cm.rget(r, "shared", "up"), tap="shared/up"),
        )
        out = out + linear(p["shared"]["down"], sh, rank=cm.rget(r, "shared", "down"), tap="shared/down")
    return out, aux
