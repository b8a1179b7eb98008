"""The architecture modules: each configuration's block against the
program's structure, the harness's numbers against its parent's, and a
block that is not in the benchmark (``data/moe.py``) run whole from its
own files."""
import ast
import copy
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import arch
import reference
import run
import smoke
import state
from items import Item

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FIXTURE = "moe-smoke"
SEED = 2 ** 33 + 11


def _fixture():
    with open(os.path.join(DATA, FIXTURE + ".json")) as f:
        return json.load(f)


@pytest.fixture
def fixture_dir(monkeypatch):
    """The module lookup pointed at the fixture's directory."""
    monkeypatch.setattr(arch, "DIR", DATA)


# every configuration at its full widths and at smoke size, and the fixture
CASES = ([(n, "full") for n in smoke.CONFIGS]
         + [(n, "smoke") for n in smoke.CONFIGS] + [(FIXTURE, "fixture")])


@pytest.fixture
def case_conf(request, monkeypatch):
    name, size = request.param
    if size == "fixture":
        monkeypatch.setattr(arch, "DIR", DATA)
        return _fixture()
    return smoke.full(name) if size == "full" else smoke.conf(name)


@pytest.mark.parametrize("case_conf", CASES, indirect=True,
                         ids=[f"{n}-{s}" for n, s in CASES])
def test_the_groups_are_the_programs(case_conf):
    from repro.core import flexrank as FR
    c = case_conf
    infos = FR.group_infos(state.model_config(c))
    mine = [(path, tuple(lead), d_out, d_in)
            for path, lead, d_out, d_in, _ in arch.module(c).groups(c)]
    assert mine == [(g.path, tuple(g.lead_dims), g.m, g.n) for g in infos]


@pytest.mark.parametrize("case_conf", CASES, indirect=True,
                         ids=[f"{n}-{s}" for n, s in CASES])
def test_the_leaves_are_the_programs(case_conf):
    from repro.core import flexrank as FR
    c = case_conf
    spec = FR.factorized_spec(state.model_config(c))
    program = sorted((p, tuple(s.shape)) for p, s in state._leaves(spec))
    mine = sorted((p, tuple(shape)) for p, shape, _, _ in
                  arch.module(c).leaves(c))
    assert mine == program


def test_the_fixtures_ladder_is_the_programs(fixture_dir):
    from repro.core import flexrank as FR
    c = _fixture()
    cfg = state.model_config(c)
    table, infos = FR.build_table(cfg, state.curves(cfg, c))
    mine, deployed = reference.ladder(c)
    np.testing.assert_array_equal(mine, table.table)
    assert [FR.deployed_param_count(cfg, infos, table, k)
            for k in range(len(mine))] == deployed.tolist()


def test_the_moe_fixture_runs_whole_and_is_correct(fixture_dir):
    res = run.run_cell(smoke.spec(config=_fixture()), SEED, 1.0, False,
                       require_chip=False, use_pallas="interpret",
                       log=lambda s: None)
    assert res["correct"] is True and res["failed"] == 0
    assert res["check"]["greedy_gap"]["tokens"] > 0
    assert res["check"]["row_params"]["value"] == \
        res["check"]["row_params"]["limit"]


def test_a_routed_experts_fault_in_the_fixture_is_not_correct(
        fixture_dir, monkeypatch):
    """The program's routed experts left out, only the shared ones added
    (a fault where the MoE FFN is produced): the fixture's reference, which
    computes them, fails it."""
    from repro.models import common as cm
    from repro.models import moe

    def shared_only(p, x, cfg, *, ranks=None):
        r = ranks or {}
        sh = cm.swiglu(
            cm.linear(p["shared"]["gate"], x, rank=cm.rget(r, "shared", "gate")),
            cm.linear(p["shared"]["up"], x, rank=cm.rget(r, "shared", "up")))
        return cm.linear(p["shared"]["down"], sh,
                         rank=cm.rget(r, "shared", "down")), 0.0

    monkeypatch.setattr(moe, "moe_apply", shared_only)
    res = run.run_cell(smoke.spec(config=_fixture()), SEED, 1.0, False,
                       require_chip=False, use_pallas=False,
                       log=lambda s: None)
    assert res["correct"] is False
    assert res["check"]["greedy_gap"]["value"] > \
        res["check"]["greedy_gap"]["limit"]


# ------------------------------------------------- the parent's numbers

PINNED = sorted(n for n in smoke.CONFIGS
                if os.path.exists(os.path.join(DATA, "parent", n + ".json")))


@pytest.mark.parametrize("name", PINNED)
def test_the_harness_numbers_are_the_parents(name):
    """``data/parent/<config>.json`` holds literals that the harness of
    the parent commit of the architecture modules computed at the
    configuration's full widths: the ladder, the served row, its flops per
    position, the work counts over fixed items, and ``ModelConfig``."""
    with open(os.path.join(DATA, "parent", name + ".json")) as f:
        pin = json.load(f)
    c = smoke.full(name)
    blk = arch.module(c)
    table, deployed = reference.ladder(c)
    assert table.tolist() == pin["table"]
    assert deployed.tolist() == pin["deployed"]
    row = reference.route(deployed, pin["budget"])
    assert (row, int(deployed[row])) == (pin["row"], pin["row_params"])
    ranks = {g[0]: int(r) for g, r in zip(blk.groups(c), table[row])}
    ppf = run.load_module("work", "model").linear_flops(blk.groups(c), ranks)
    assert ppf == pin["per_position_flops"]
    shape = dict(blk.attention(c), d_model=c["model"]["d_model"],
                 vocab_size=c["model"]["vocab_size"])
    its = [Item(*i) for i in pin["items"]]
    for kernel in ("paged_prefill_attention", "topk_mask_sample"):
        got = run.load_module("work", kernel).work(its, shape)
        assert list(got) == pin[kernel]
    assert run.load_module("work", "model").work(its, shape, ppf) == \
        pin["model"]
    assert repr(state.model_config(c)) == pin["model_config"]


def _parent_forward(w, ranks, tokens, conf, dtype=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST):
    """The dense forward of ``reference.py`` on the parent commit of the
    architecture modules, frozen here."""
    def _rms(x, scale, eps):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
        return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)

    def _rope(x, pos, base):
        half = x.shape[-1] // 2
        freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos[:, None].astype(jnp.float32) * freq
        sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
        x1 = x[..., :half].astype(jnp.float32)
        x2 = x[..., half:].astype(jnp.float32)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1).astype(x.dtype)

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


DENSE = [n for n in smoke.CONFIGS if smoke.full(n)["arch"] == "dense"]


@pytest.mark.parametrize("tie", [True, False])
@pytest.mark.parametrize("kind", ["highest", "bfloat16"])
@pytest.mark.parametrize("name", DENSE)
def test_the_dense_forward_is_the_parents_bit_for_bit(name, kind, tie):
    c = smoke.conf(name, tie=tie)
    ref = reference.Reference(c, SEED, 0.85, 32)
    dtype, precision = ((jnp.float32, jax.lax.Precision.HIGHEST)
                        if kind == "highest" else reference.CONTROLS[kind])
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, c["model"]["vocab_size"], 32), jnp.int32)
    new = jax.jit(lambda w, r, t: arch.module(c).forward(
        w, r, t, c, dtype, precision))(ref.w, ref.ranks, tokens)
    old = jax.jit(lambda w, r, t: _parent_forward(
        w, r, t, c, dtype, precision))(ref.w, ref.ranks, tokens)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


# ------------------------------------------------ configuration and lookup

def test_a_configuration_without_an_arch_is_refused(tmp_path):
    cell = smoke.BENCHMARK["workloads"][0]
    entry = {c["name"]: c for c in smoke.BENCHMARK["configs"]}[cell["config"]]
    conf = smoke.full(cell["config"])
    del conf["arch"]
    os.makedirs(os.path.dirname(tmp_path / entry["file"]))
    (tmp_path / entry["file"]).write_text(json.dumps(conf))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(smoke.BENCHMARK))
    with pytest.raises(ValueError, match='"arch"'):
        run.load_spec(str(tmp_path), cell["name"])


def test_a_module_is_one_object_however_it_is_reached(monkeypatch):
    """The lookup loads ``arch.<name>``, so a module that imports another
    shares the object the lookup gives, also from the fixture's
    directory."""
    from arch import dense
    assert arch.module({"arch": "dense"}) is dense
    monkeypatch.setattr(arch, "DIR", DATA)
    moe = arch.module({"arch": "moe"})
    assert moe.__name__ == "arch.moe" and moe.dense is dense
    assert arch.module({"arch": "moe"}) is moe


def test_model_config_reads_segments_moe_and_refuses_unknown_keys():
    from repro.configs.base import MoEConfig, Segment
    c = _fixture()
    cfg = state.model_config(c)
    assert cfg.family == "moe" and cfg.head_dim == 16
    assert cfg.segments == (Segment("attn_dense", 1), Segment("attn", 2))
    assert cfg.moe == MoEConfig(num_experts=8, top_k=2, d_ff_expert=32,
                                num_shared=2, d_ff_shared=32,
                                capacity_factor=4.0)
    dense = smoke.conf()
    per = dense["model"]["layers_per_segment"]
    listed = copy.deepcopy(dense)
    del listed["model"]["layers_per_segment"]
    listed["model"]["segments"] = [{"kind": "attn", "count": per}] * (
        dense["model"]["num_layers"] // per)
    assert state.model_config(listed) == state.model_config(dense)
    c["model"]["experts_per_token"] = 2
    with pytest.raises(ValueError, match="experts_per_token"):
        state.model_config(c)


def _imports(path):
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module


def test_the_reference_imports_nothing_of_the_program():
    """Neither ``reference.py`` nor any architecture module names the
    program, and loading them all, and running their shapes, brings none
    of it in."""
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = [os.path.join(bench, "reference.py"), os.path.join(DATA, "moe.py")]
    files += [os.path.join(arch.DIR, f) for f in os.listdir(arch.DIR)
              if f.endswith(".py")]
    for path in files:
        assert not [m for m in _imports(path)
                    if m.split(".")[0] == "repro"], path
    names = [f[:-3] for f in os.listdir(arch.DIR)
             if f.endswith(".py") and f not in ("__init__.py", "common.py")]
    code = (
        "import json, sys\n"
        f"sys.path[:0] = [{bench!r}]\n"
        "import arch, reference, smoke\n"
        "for n in smoke.CONFIGS:\n"
        "    c = smoke.conf(n)\n"
        "    reference.ladder(c); arch.module(c).leaves(c)\n"
        f"for n in {names!r}:\n"
        "    arch.module({'arch': n})\n"
        f"arch.DIR = {DATA!r}\n"
        "arch.module({'arch': 'moe'})\n"
        "assert not [m for m in sys.modules if m.split('.')[0] == 'repro']\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       cwd=os.path.join(bench, "tests"), capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
