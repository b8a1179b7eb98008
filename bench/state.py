"""A FlexRank state made from the seed, in place of the offline build.

A deployment loads a state that was built once (calibration, DataSVD, the
DP); the benchmark makes one of the same shapes instead. The full-rank
factors of every group are drawn on the device from the seed
(``weights.draw``, shapes from ``FR.factorized_spec``), their tail-energy
curves follow from the stated singular-value profile in closed form, and
the repo's own DP (``FR.build_table``) turns the curves into the profile
table. The engine then deploys the rows it serves through its own
``gar_deploy``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.configs.base import (FlexRankConfig, MLAConfig, ModelConfig,
                                MoEConfig, Segment)
from repro.core import flexrank as FR
from repro.models import common as cm

import weights
from arch import dense


def model_config(conf: dict) -> ModelConfig:
    """The repo's ``ModelConfig`` as the configuration file states it:
    ``conf["model"]`` names its fields, ``segments`` is a list of
    ``{kind, count}`` (or ``layers_per_segment``, that many ``attn``
    layers a segment), ``moe`` and ``mla`` are dicts of ``MoEConfig`` and
    ``MLAConfig``. ``family`` is ``moe`` where ``moe`` is stated and
    ``dense`` otherwise, unless the file names it."""
    m = dict(conf["model"])
    m.pop("layers_per_segment", None)
    m["segments"] = tuple(Segment(**s)
                          for s in dense.segment_list(conf["model"]))
    known = {f.name for f in dataclasses.fields(ModelConfig)}
    unknown = sorted(set(m) - known)
    if unknown:
        raise ValueError(f"{conf['name']}: ModelConfig has no {unknown}")
    for key, cls in (("moe", MoEConfig), ("mla", MLAConfig)):
        if m.get(key) is not None:
            m[key] = cls(**m[key])
    m.setdefault("family", "moe" if m.get("moe") else "dense")
    fr = conf["flexrank"]
    return ModelConfig(
        name=conf["name"], source=conf["source"],
        flexrank=FlexRankConfig(enabled=True, budgets=tuple(fr["budgets"]),
                                rank_levels=fr["rank_levels"]),
        **m)


def _leaves(tree, prefix=""):
    if cm.is_spec(tree):
        return [(prefix, tree)]
    items = (tree.items() if isinstance(tree, dict) else enumerate(tree))
    out = []
    for k, v in items:
        out += _leaves(v, f"{prefix}/{k}" if prefix else str(k))
    return out


def _fill(tree, flat, prefix=""):
    if cm.is_spec(tree):
        return flat[prefix]
    if isinstance(tree, dict):
        return {k: _fill(v, flat, f"{prefix}/{k}" if prefix else k)
                for k, v in tree.items()}
    return [_fill(v, flat, f"{prefix}/{i}" if prefix else str(i))
            for i, v in enumerate(tree)]


def make_params(cfg: ModelConfig, conf: dict, seed: int):
    """Full-rank factorised parameters, float32, drawn on the device."""
    spec = FR.factorized_spec(cfg)
    groups = {info.path: info for info in FR.group_infos(cfg)}
    power = conf["weights"]["singular_value_power"]
    std = conf["weights"]["embed_std_times_sqrt_d"] / cfg.d_model ** 0.5
    leaves = []
    for path, s in _leaves(spec):
        group, _, which = path.rpartition("/")
        if group in groups and which in ("u", "v"):
            info = groups[group]
            leaves.append((path, s.shape, "factor",
                           weights.factor_scale(info.full_rank, info.m, power)))
        elif s.init == "zeros":
            leaves.append((path, s.shape, "zeros", None))
        else:
            leaves.append((path, s.shape, "normal", std))
    return _fill(spec, weights.draw(seed, leaves))


def curves(cfg: ModelConfig, conf: dict) -> dict:
    power = conf["weights"]["singular_value_power"]
    out = {}
    for info in FR.group_infos(cfg):
        layers = int(np.prod(info.lead_dims)) if info.lead_dims else 1
        out[info.path] = weights.tail_curve(
            weights.singular_values(info.full_rank, info.m, power), layers)
    return out


@dataclasses.dataclass
class State:
    cfg: ModelConfig
    params: dict
    table: object
    infos: list


def make_state(conf: dict, seed: int) -> State:
    """Factors from the seed, profile table from the repo's DP."""
    cfg = model_config(conf)
    params = make_params(cfg, conf, seed)
    table, infos = FR.build_table(cfg, curves(cfg, conf))
    return State(cfg, params, table, infos)
