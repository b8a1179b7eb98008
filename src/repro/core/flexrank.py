"""FlexRank orchestrator: paper Algorithm 1 end-to-end against the model zoo.

Pipeline:
  1. ``factorized_spec``     — rewrite eligible dense leaves to (u, v) pairs
  2. ``collect_moments``     — calibration pass with activation taps (App C.1)
  3. ``decompose``           — DataSVD init of every factor pair (Eq. 61)
  4. ``build_table``         — DP nested rank selection over probe curves
  5. ``consolidation step``  — stochastic nested-mask distillation (Eq. 5/6)
  6. ``gar_deploy``          — gauge-aligned deploy params at one budget

Rank granularity note (DESIGN.md §7): columns of the DP are factorized
*groups*. For scanned stacks a group covers all its layers with one rank —
this keeps shapes static under lax.scan and makes GAR deployable as stacked
tensors. Depth-heterogeneous rank profiles (paper Fig. 6) are recovered by
giving a model per-layer segments (the gpt2 paper config does exactly this),
where every layer is its own group.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import datasvd, dp_select, distill
from repro.core.profiles import ProfileTable, table_from_profiles
from repro.models import common as cm
from repro.models import transformer as tfm

Array = jax.Array
PyTree = Any

_SCAN_AXIS = cm.LAYERS


def _eligible(cfg: ModelConfig):
    excl = cfg.flexrank.exclude

    def predicate(path: str, spec) -> bool:
        return not any(tok in path for tok in excl)

    return predicate


def factorized_spec(cfg: ModelConfig) -> PyTree:
    spec = tfm.model_spec(cfg)
    fr = cfg.flexrank
    return cm.factorize_spec(spec, predicate=_eligible(cfg),
                             max_rank_fn=lambda p, s: fr.max_rank)


@dataclasses.dataclass
class GroupInfo:
    path: str
    scan_dims: Tuple[int, ...]   # leading LAYERS-axis dims (rank leaf shape)
    lead_dims: Tuple[int, ...]   # all leading dims of the dense leaf
    m: int                       # d_out
    n: int                       # d_in
    full_rank: int
    col: int                     # DP column index


def group_infos(cfg: ModelConfig) -> List[GroupInfo]:
    fact = factorized_spec(cfg)
    infos = []
    col = 0

    def walk(tree, prefix=""):
        nonlocal col
        if isinstance(tree, dict):
            if {"u", "v"} <= set(tree.keys()) and cm.is_spec(tree.get("u")):
                u, v = tree["u"], tree["v"]
                scan_dims = []
                for dim, ax in zip(u.shape, u.axes):
                    if ax == _SCAN_AXIS:
                        scan_dims.append(dim)
                    else:
                        break
                infos.append(GroupInfo(
                    path=prefix, scan_dims=tuple(scan_dims),
                    lead_dims=u.shape[:-2], m=u.shape[-2], n=v.shape[-2],
                    full_rank=u.shape[-1], col=col))
                col += 1
                return
            for k, v_ in tree.items():
                walk(v_, f"{prefix}/{k}" if prefix else k)
        elif isinstance(tree, (list, tuple)):
            for i, v_ in enumerate(tree):
                walk(v_, f"{prefix}/{i}" if prefix else str(i))

    walk(fact)
    return infos


# ---------------------------------------------------------------------------
# calibration + decomposition
# ---------------------------------------------------------------------------

def collect_moments(params: PyTree, cfg: ModelConfig, batches: Sequence[Dict],
                    *, frontend_fn=None) -> Dict[str, list]:
    """Unrolled eager calibration pass; returns {tap_key: [moment, count]}.

    Tap keys are param paths with scan indices marked "@l"
    ("segments/0/@3/attn/q"). ~10^2-10^3 sequences suffice (paper Fig. 7a).
    """
    store: Dict[str, list] = {}
    with cm.tap_recording(store), tfm.unrolled_scans():
        for batch in batches:
            tokens = jnp.asarray(batch["tokens"])[:, :-1]
            frontend = frontend_fn(batch) if frontend_fn else None
            tfm.forward(params, cfg, tokens, frontend=frontend)
    return store


_AT = re.compile(r"^@(\d+)$")


def _index_moments(store: Dict[str, list]) -> Dict[str, Dict[Tuple[int, ...], list]]:
    """tap key -> (group path, scan idx tuple) inverted index."""
    out: Dict[str, Dict[Tuple[int, ...], list]] = {}
    for key, ent in store.items():
        toks, idx = [], []
        for t in key.split("/"):
            m = _AT.match(t)
            if m:
                idx.append(int(m.group(1)))
            else:
                toks.append(t)
        out.setdefault("/".join(toks), {})[tuple(idx)] = ent
    return out


def decompose(
    dense_params: PyTree,
    cfg: ModelConfig,
    moments: Optional[Dict[str, list]] = None,
    *,
    damping: float = 1e-6,
) -> Tuple[PyTree, Dict[str, np.ndarray]]:
    """DataSVD-initialize factorized params from dense params.

    Returns (factorized params, error curves): ``curves[group_path]`` is the
    per-group whitened tail-energy curve summed over the group's layers —
    curve[r-1] = probe error of keeping rank r uniformly (DP input).

    Falls back to plain SVD per leaf when no moment was recorded for it.
    The factorizations run on the host (numpy LAPACK), whatever the backend.
    """
    import copy
    infos = group_infos(cfg)
    midx = _index_moments(moments or {})
    params = copy.deepcopy(jax.tree.map(lambda x: x, dense_params))
    curves: Dict[str, np.ndarray] = {}

    for info in infos:
        leaf = cm.tree_get(dense_params, info.path)
        w = np.asarray(leaf["w"], np.float32)           # (lead..., n, m) in x@w form
        lead = info.lead_dims
        r_full = info.full_rank
        u_out = np.zeros(lead + (info.m, r_full), np.float32)
        v_out = np.zeros(lead + (info.n, r_full), np.float32)
        curve = np.zeros(r_full, np.float64)
        group_moments = midx.get(info.path, {})

        for idx in np.ndindex(*lead) if lead else [()]:
            scan_idx = idx[: len(info.scan_dims)]
            ent = group_moments.get(tuple(scan_idx))
            w_slice = w[idx]                            # (n, m): y = x @ w
            w_paper = w_slice.T                         # (m, n): y = W x
            # numpy operands: the factorization runs on the host
            if ent is not None:
                f = datasvd.datasvd_factors(w_paper,
                                            np.asarray(ent[0], np.float32),
                                            ent[1], max_rank=r_full,
                                            damping=damping)
            else:
                f = datasvd.plain_svd_factors(w_paper, max_rank=r_full)
            u_np, v_np = np.asarray(f.u), np.asarray(f.v)
            rr = u_np.shape[1]
            u_out[idx][:, :rr] = u_np
            v_out[idx][:, :rr] = v_np
            # whitened singular values: |u_j|^2 = lambda_j exactly (P orthonormal,
            # sqrt(lambda) absorbed symmetrically); v columns are NOT Euclidean-
            # orthonormal (Sigma^{-1/2} factor), so don't use |v_j| here.
            lam2 = ((u_np * u_np).sum(0)) ** 2
            # whitened-metric tail energy: error of keeping rank r
            tail = lam2[::-1].cumsum()[::-1]
            c = np.zeros(r_full)
            c[:rr] = np.concatenate([tail[1:], [0.0]])
            curve += c

        cm.tree_set(params, info.path,
                    {"u": jnp.asarray(u_out), "v": jnp.asarray(v_out)})
        curves[info.path] = curve
    return params, curves


# ---------------------------------------------------------------------------
# DP selection -> profile table
# ---------------------------------------------------------------------------

def build_table(cfg: ModelConfig, curves: Dict[str, np.ndarray]) -> Tuple[ProfileTable, List[GroupInfo]]:
    infos = group_infos(cfg)
    cands = []
    names, max_ranks, costs = [], [], []
    for info in infos:
        n_lead = int(np.prod(info.lead_dims)) if info.lead_dims else 1
        cost_per_rank = float((info.m + info.n) * n_lead)
        curve = curves[info.path]
        cands.append(dp_select.make_layer_candidates(
            curve, cost_per_rank, num_levels=cfg.flexrank.rank_levels))
        names.append(info.path)
        max_ranks.append(info.full_rank)
        costs.append(cost_per_rank)
    chain = dp_select.dp_rank_selection(cands)
    total = float(np.dot([c for c in costs], max_ranks))
    picked = dp_select.select_profiles(chain, cfg.flexrank.budgets, total)
    # dedupe while preserving nestedness/order
    seen, rows = set(), []
    for p in picked:
        if p.ranks not in seen:
            rows.append(p)
            seen.add(p.ranks)
    table = table_from_profiles(names, rows, cfg.flexrank.budgets[: len(rows)], max_ranks)
    return table, infos


def table_device(table: ProfileTable) -> Array:
    return jnp.asarray(table.table, jnp.int32)


def ranks_tree(cfg: ModelConfig, infos: List[GroupInfo], table_dev: Array, k: Array) -> Dict:
    """Nested ranks pytree (mirrors params structure) for traced budget ``k``."""
    row = table_dev[k]                                  # (G,)
    tree: Dict = {}
    for info in infos:
        rank = row[info.col]
        leaf = (jnp.broadcast_to(rank, info.scan_dims) if info.scan_dims else rank)
        _nested_set(tree, info.path, leaf)
    return tree


def _nested_set(tree: Dict, path: str, value) -> None:
    toks = path.split("/")
    cur = tree
    for a, b in zip(toks[:-1], toks[1:]):
        if a.isdigit():
            a = int(a)
        if isinstance(cur, dict):
            cur = cur.setdefault(a, [] if str(b).isdigit() else {})
        else:  # list
            while len(cur) <= a:
                cur.append({} if not str(b).isdigit() else [])
            if not cur[a]:
                cur[a] = {} if not str(b).isdigit() else []
            cur = cur[a]
    last = toks[-1]
    if isinstance(cur, list):
        while len(cur) <= int(last):
            cur.append(None)
        cur[int(last)] = value
    else:
        cur[last] = value


# ---------------------------------------------------------------------------
# consolidation (Eq. 5/6)
# ---------------------------------------------------------------------------

def make_consolidation_loss(cfg: ModelConfig, infos: List[GroupInfo], table_dev: Array,
                            teacher_params: PyTree, *, weights=None):
    """Returns loss_fn(params, batch, rng) — sample budget k, distill."""
    num_k = table_dev.shape[0]

    def loss_fn(params, batch, rng):
        tokens = batch["tokens"][:, :-1]
        labels = batch["tokens"][:, 1:]
        k = jax.random.randint(rng, (), 0, num_k)
        ranks = ranks_tree(cfg, infos, table_dev, k)
        student_logits, aux = tfm.forward(params, cfg, tokens, ranks=ranks)
        teacher_logits, _ = tfm.forward(teacher_params, cfg, tokens)
        loss = distill.consolidation_loss(
            student_logits, teacher_logits, labels,
            kd_weight=cfg.flexrank.kd_weight,
            temperature=cfg.flexrank.kd_temperature)
        return loss + aux, {"loss": loss, "budget_k": k}

    return loss_fn


def eval_budget_loss(params, cfg, infos, table_dev, batch, k: int) -> float:
    tokens = batch["tokens"][:, :-1]
    labels = batch["tokens"][:, 1:]
    ranks = ranks_tree(cfg, infos, table_dev, jnp.asarray(k))
    logits, _ = tfm.forward(params, cfg, tokens, ranks=ranks)
    return float(distill.cross_entropy(logits, labels))


# ---------------------------------------------------------------------------
# GAR deployment (§3.5)
# ---------------------------------------------------------------------------

def gar_deploy(params_fact: PyTree, cfg: ModelConfig, infos: List[GroupInfo],
               table: ProfileTable, k: int) -> PyTree:
    """Deployable params at budget row ``k``: factorized leaves -> GAR leaves.

    Stacked groups become stacked GAR tensors (uniform rank per group), so the
    scanned model runs unchanged — common.linear dispatches on 'u_hat'.
    """
    from repro.core.gar import gar_transform
    import copy
    params = copy.deepcopy(jax.tree.map(lambda x: x, params_fact))
    row = table.table[k]
    for info in infos:
        leaf = cm.tree_get(params_fact, info.path)
        u = np.asarray(leaf["u"], np.float32)
        v = np.asarray(leaf["v"], np.float32)
        r = int(row[info.col])
        lead = info.lead_dims
        u_hats = np.zeros(lead + (info.m - r, r), np.float32)
        v_tildes = np.zeros(lead + (info.n, r), np.float32)
        perms = np.zeros(lead + (info.m,), np.int32)
        for idx in np.ndindex(*lead) if lead else [()]:
            g = gar_transform(u[idx], v[idx], r)
            u_hats[idx] = np.asarray(g.u_hat)
            v_tildes[idx] = np.asarray(g.v_tilde)
            perms[idx] = np.argsort(np.asarray(g.perm))
        cm.tree_set(params, info.path, {
            "u_hat": jnp.asarray(u_hats),
            "v_tilde": jnp.asarray(v_tildes),
            "perm_inv": jnp.asarray(perms),
        })
    return params


def is_nested_prefix(table: ProfileTable, draft_row: int,
                     target_row: int) -> bool:
    """True iff ``draft_row``'s ranks are a componentwise prefix of
    ``target_row``'s — i.e. the draft submodel's factors are literally the
    leading columns of the target's (the paper's importance-ordered
    nesting). This is what makes the draft row a *free* speculative-decoding
    draft model: no extra weights, no separate training."""
    t = table.table
    return bool(np.all(t[draft_row] <= t[target_row]))


def nested_prefix_row(table: ProfileTable, target_row: int, budget: float,
                      cost_table: Optional[np.ndarray] = None
                      ) -> Optional[int]:
    """Largest row strictly below ``target_row`` whose deployed cost stays
    within ``budget`` (fraction of the top row) and whose ranks are a
    nested prefix of the target row's.

    ``cost_table``: per-row deployed cost (the serving router's precomputed
    ``deployed_param_count`` table); defaults to rank sums, which order rows
    identically for nested tables. The profile table certifies global
    nestedness at construction, so every lower row qualifies structurally —
    this helper still validates the prefix property (defense against
    hand-built tables) and applies the budget cap. Returns ``None`` when no
    strictly-smaller prefix row fits (e.g. ``target_row == 0``): callers
    should then disable speculation for that row rather than draft with an
    equal-or-larger submodel.
    """
    if target_row <= 0:
        return None
    if cost_table is None:
        cost_table = table.table.sum(axis=1)
    cost_table = np.asarray(cost_table, np.float64)
    full = float(cost_table[-1])
    for row in range(target_row - 1, -1, -1):
        if not is_nested_prefix(table, row, target_row):
            continue
        if cost_table[row] <= budget * full + 1e-9:
            return row
    return None


def deployed_param_count(cfg: ModelConfig, infos: List[GroupInfo],
                         table: ProfileTable, k: int) -> int:
    """Parameters of the budget-k realization (GAR form, identity not stored)."""
    from repro.models.common import param_count
    dense_total = param_count(tfm.model_spec(cfg))
    fact_full = 0
    fact_at_k = 0
    for info in infos:
        n_lead = int(np.prod(info.lead_dims)) if info.lead_dims else 1
        r = int(table.table[k][info.col])
        fact_full += n_lead * info.m * info.n
        fact_at_k += n_lead * (info.m + info.n - r) * r
    return dense_total - fact_full + fact_at_k
