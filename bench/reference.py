"""The plain reference: what a served request's logits should be.

It imports nothing of the program. From the configuration file alone it
names every weight, rebuilds it from the seed (``weights.draw``), chooses
the budget row as the FlexRank ladder defines it (the nested DP over the
closed-form tail-energy curves, then the router's rule: the largest row
whose deployed parameters fit the budget), and runs the model's forward
pass in float32 at ``highest`` matmul precision, with no cache, no paging,
no batching and no kernels. Each rank-``r`` linear is the truncated
product ``x @ v[:, :r] @ u[:, :r].T`` of the full-rank factors, which the
program's GAR deployment represents in another gauge.

The model is the repo's dense block as the configuration states it:
RMSNorm ``x * rsqrt(mean(x**2) + eps) * (1 + scale)``; q/k RMS head norms;
RoPE over the whole head with ``base ** (-i / (D/2))`` on the two halves;
causal softmax attention scaled by ``1/sqrt(D)``; SwiGLU FFN
``down(silu(gate x) * up x)``; a tied or separate LM head.

``gaps`` compares served greedy tokens against this forward and
``topk_gaps`` served sampled tokens against its top-k; ``control_gaps``
compares the first choices of the same forward a precision step lower
(``CONTROLS``), for the check's upper reading; the benchmark's own runs
do not run it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

import weights

HIGHEST = jax.lax.Precision.HIGHEST


# ----------------------------------------------------------------- shapes

def groups(conf: dict):
    """(path, layers, d_out, d_in) of every factorised linear, in the
    order the ladder's DP visits them: layer by layer, by name within a
    layer (the order decides between groups of equal shape and curve)."""
    m = conf["model"]
    d, h, kv, f = m["d_model"], m["num_heads"], m["num_kv_heads"], m["d_ff"]
    hd = d // h
    per = m["layers_per_segment"]
    out = []
    for i in range(m["num_layers"] // per):
        p = f"segments/{i}"
        out += [(f"{p}/attn/k", per, kv * hd, d), (f"{p}/attn/o", per, d, h * hd),
                (f"{p}/attn/q", per, h * hd, d), (f"{p}/attn/v", per, kv * hd, d),
                (f"{p}/mlp/down", per, d, f), (f"{p}/mlp/gate", per, f, d),
                (f"{p}/mlp/up", per, f, d)]
    return out


def leaves(conf: dict):
    """Every weight as (path, shape, kind, arg) for ``weights.draw``."""
    m = conf["model"]
    d, v, hd = m["d_model"], m["vocab_size"], m["d_model"] // m["num_heads"]
    per = m["layers_per_segment"]
    power = conf["weights"]["singular_value_power"]
    std = conf["weights"]["embed_std_times_sqrt_d"] / math.sqrt(d)
    out = [("embed", (v, d), "normal", std), ("final_norm", (d,), "zeros", None)]
    if not m["tie_embeddings"]:
        out.append(("lm_head/w", (d, v), "normal", std))
    for i in range(m["num_layers"] // per):
        p = f"segments/{i}"
        out += [(f"{p}/ln_attn", (per, d), "zeros", None),
                (f"{p}/ln_mlp", (per, d), "zeros", None),
                (f"{p}/attn/q_norm", (per, hd), "zeros", None),
                (f"{p}/attn/k_norm", (per, hd), "zeros", None)]
    for path, layers, d_out, d_in in groups(conf):
        r = min(d_out, d_in)
        scale = weights.factor_scale(r, d_out, power)
        out += [(f"{path}/u", (layers, d_out, r), "factor", scale),
                (f"{path}/v", (layers, d_in, r), "factor", scale)]
    return out


# ----------------------------------------------------------- the ladder

def _candidates(curve, cost_per_rank, levels):
    full = len(curve)
    ranks = np.unique(np.linspace(1, full, levels).round().astype(int))
    return [(float((full - r) * cost_per_rank), float(curve[r - 1]), int(r))
            for r in ranks]


def _keep_min(states, quantize):
    best = {}
    for st in states:
        key = int(round(st[0] / quantize))
        if key not in best or st[1] < best[key][1]:
            best[key] = st
    return list(best.values())


def _pareto(profiles):
    """Profiles (saving, error, ranks) with strictly falling error as
    saving grows."""
    out, best = [], np.inf
    for p in reversed(sorted(profiles, key=lambda p: p[0])):
        if p[1] < best:
            out.append(p)
            best = p[1]
    return out[::-1]


def ladder(conf: dict, max_frontier: int = 4096):
    """The nested profile table (rows of per-group ranks, ascending) and
    the deployed parameter count of each row: the paper's DP over
    rank-level candidates, Pareto front, nested chain, one profile per
    budget."""
    power = conf["weights"]["singular_value_power"]
    levels = conf["flexrank"]["rank_levels"]
    gs = groups(conf)
    cands, costs, full = [], [], []
    for path, layers, d_out, d_in in gs:
        r = min(d_out, d_in)
        curve = weights.tail_curve(weights.singular_values(r, d_out, power),
                                   layers)
        cost = float((d_out + d_in) * layers)
        cands.append(_candidates(curve, cost, levels))
        costs.append(cost)
        full.append(r)
    frontier, backs = [(0.0, 0.0)], []
    for cs in cands:
        grown = [(s + c[0], e + c[1], i, c[2])
                 for i, (s, e) in enumerate(frontier) for c in cs]
        grown = _keep_min(grown, 1.0)
        if len(grown) > max_frontier:
            sv = np.array([g[0] for g in grown])
            width = max((sv.max() - sv.min()) / max_frontier, 1.0)
            grown = _keep_min(grown, width)
        grown = sorted(grown, key=lambda g: g[0])
        frontier, back, best = [], [], np.inf
        for s, e, i, r in reversed(grown):
            if e < best:
                frontier.append((s, e))
                back.append((i, r))
                best = e
        frontier.reverse()
        back.reverse()
        backs.append(back)
    profiles = []
    for idx, (s, e) in enumerate(frontier):
        ranks, h = [0] * len(gs), idx
        for layer in range(len(gs) - 1, -1, -1):
            h, r = backs[layer][h]
            ranks[layer] = r
        profiles.append((s, e, tuple(ranks)))
    profiles = _pareto(profiles)
    chain = []
    for p in sorted(profiles, key=lambda p: sum(p[2])):
        if not chain or all(a <= b for a, b in zip(chain[-1][2], p[2])):
            chain.append(p)
    total = float(np.dot(costs, full))
    rows, seen = [], set()
    for b in conf["flexrank"]["budgets"]:
        ok = [p for p in chain if total - p[0] <= b * total + 1e-9]
        if not ok:
            ok = [min(chain, key=lambda p: total - p[0])]
        pick = min(ok, key=lambda p: p[1])
        if pick[2] not in seen:
            rows.append(pick[2])
            seen.add(pick[2])
    table = np.asarray(sorted(rows, key=sum), np.int64)
    dense = sum(int(np.prod(s)) for _, s, _, _ in _dense_shapes(conf))
    fact_full = sum(layers * d_out * d_in for _, layers, d_out, d_in in gs)
    deployed = np.asarray([
        dense - fact_full + sum(layers * (d_out + d_in - r) * r
                                for (_, layers, d_out, d_in), r in zip(gs, row))
        for row in table], np.int64)
    return table, deployed


def _dense_shapes(conf: dict):
    """Every weight of the dense model (factor pairs as one matrix)."""
    out = [x for x in leaves(conf) if x[2] != "factor"]
    out += [(path, (layers, d_in, d_out), "dense", None)
            for path, layers, d_out, d_in in groups(conf)]
    return out


def route(deployed, budget: float) -> int:
    """The largest row whose deployed parameters fit ``budget`` of the top
    row's; the lowest row when none does."""
    limit = budget * float(deployed[-1]) * (1.0 + 1e-9)
    ok = np.flatnonzero(deployed <= limit)
    return int(ok[-1]) if ok.size else 0


# --------------------------------------------------------------- forward

def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def _rope(x, pos, base):
    half = x.shape[-1] // 2
    freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def forward(w: dict, ranks: dict, tokens, conf: dict, dtype=jnp.float32,
            precision=HIGHEST):
    """Logits (S, V) of one sequence, every position, in ``dtype`` with
    matmuls at ``precision``."""
    m = conf["model"]
    d, h, kv = m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd, eps, per = d // h, m["norm_eps"], m["layers_per_segment"]
    mm = lambda a, b: jnp.matmul(a, b, precision=precision)
    cast = lambda a: a.astype(dtype)
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[:, None] >= pos[None, :]

    def lin(x, path, layer):
        u, v = cast(w[f"{path}/u"][layer]), cast(w[f"{path}/v"][layer])
        keep = cast(jnp.arange(u.shape[-1]) < ranks[path])
        return mm(mm(x, v) * keep, u.T)

    x = cast(w["embed"])[tokens]
    for i in range(m["num_layers"] // per):
        p = f"segments/{i}"
        for l in range(per):
            y = _rms(x, w[f"{p}/ln_attn"][l], eps)
            q = _rms(lin(y, f"{p}/attn/q", l).reshape(s, h, hd),
                     w[f"{p}/attn/q_norm"][l], eps)
            k = _rms(lin(y, f"{p}/attn/k", l).reshape(s, kv, hd),
                     w[f"{p}/attn/k_norm"][l], eps)
            v = lin(y, f"{p}/attn/v", l).reshape(s, kv, hd)
            q, k = _rope(q, pos, m["rope_base"]), _rope(k, pos, m["rope_base"])
            k = jnp.repeat(k, h // kv, axis=1)
            v = jnp.repeat(v, h // kv, axis=1)
            logit = jnp.einsum("shd,thd->hst", q, k, precision=precision)
            logit = logit.astype(jnp.float32) / math.sqrt(hd)
            prob = jax.nn.softmax(jnp.where(causal, logit, -1e30), -1)
            att = jnp.einsum("hst,thd->shd", cast(prob), v, precision=precision)
            x = x + lin(att.reshape(s, h * hd), f"{p}/attn/o", l)
            y = _rms(x, w[f"{p}/ln_mlp"][l], eps)
            g = lin(y, f"{p}/mlp/gate", l)
            x = x + lin(jax.nn.silu(g) * lin(y, f"{p}/mlp/up", l),
                        f"{p}/mlp/down", l)
    x = _rms(x, w["final_norm"], eps)
    head = (cast(w["embed"]).T if m["tie_embeddings"]
            else cast(w["lm_head/w"]))
    return mm(x, head).astype(jnp.float32)


def _readings(w, ranks, tokens, targets, *, conf, top_k):
    """Per position: the reference's best logit, its ``top_k``-th largest
    and its logit at ``targets`` (the next served token)."""
    logits = forward(w, ranks, tokens, conf)
    picked = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return logits.max(-1), jax.lax.top_k(logits, top_k)[0][:, -1], picked


# the precision steps below float32 at ``highest``: three bfloat16 passes
# per product, one pass (a TPU's default for float32), bfloat16 throughout
CONTROLS = {"high": (jnp.float32, jax.lax.Precision.HIGH),
            "default": (jnp.float32, jax.lax.Precision.DEFAULT),
            "bfloat16": (jnp.bfloat16, jax.lax.Precision.DEFAULT)}


def _control(w, ranks, tokens, *, conf, kind):
    """Per position: the reference's best logit and its logit at the token
    that the lower-precision forward ``kind`` puts first."""
    ref = forward(w, ranks, tokens, conf)
    dtype, precision = CONTROLS[kind]
    low = forward(w, ranks, tokens, conf, dtype=dtype, precision=precision)
    top = jnp.argmax(low, -1)
    return ref.max(-1), jnp.take_along_axis(ref, top[:, None], -1)[:, 0]


class Reference:
    """Weights rebuilt from the seed, the served row's ranks, and the
    jitted readings at one padded length."""

    def __init__(self, conf: dict, seed: int, budget: float, length: int,
                 top_k: int = 1):
        self.conf = conf
        self.length = length
        table, deployed = ladder(conf)
        self.row = route(deployed, budget)
        self.deployed = int(deployed[self.row])
        self.ranks = {path: jnp.int32(r) for (path, *_), r
                      in zip(groups(conf), table[self.row])}
        self.w = weights.draw(seed, leaves(conf))
        fn = jax.jit(lambda w, r, t, g: _readings(w, r, t, g, conf=conf,
                                                  top_k=top_k))
        self._fn = fn
        self._cf = {k: jax.jit(lambda w, r, t, k=k: _control(w, r, t, conf=conf,
                                                             kind=k))
                    for k in CONTROLS}

    def _pad(self, seq):
        t = np.zeros(self.length, np.int32)
        t[: len(seq)] = seq
        return jnp.asarray(t)

    def _read(self, prompt, served):
        seq = np.concatenate([prompt, served]).astype(np.int32)
        targets = np.zeros(self.length, np.int32)
        targets[len(prompt) - 1: len(seq) - 1] = served
        out = self._fn(self.w, self.ranks, self._pad(seq), jnp.asarray(targets))
        sl = slice(len(prompt) - 1, len(seq) - 1)
        return [np.asarray(x)[sl] for x in out]

    def gaps(self, prompt, served):
        """How far each served token's reference logit lies below the
        reference's best at its position (0 where it is the best)."""
        best, _, got = self._read(prompt, served)
        return best - got

    def topk_gaps(self, prompt, served):
        """How far each served token's reference logit lies below the
        reference's ``top_k``-th largest at its position (0 inside the
        top k): a sampled token has to come from the top k."""
        _, kth, got = self._read(prompt, served)
        return np.maximum(kth - got, 0.0)

    def control_gaps(self, prompt, served, kind: str = "high"):
        """The same gap for the token the lower-precision forward ``kind``
        puts first, at each position of the same prompt and served
        tokens."""
        seq = np.concatenate([prompt, served]).astype(np.int32)
        best, got = self._cf[kind](self.w, self.ranks, self._pad(seq))
        sl = slice(len(prompt) - 1, len(seq) - 1)
        return np.asarray(best)[sl] - np.asarray(got)[sl]
