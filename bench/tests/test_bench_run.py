"""Whole runs of the benchmark at smoke size on the CPU: the contract's
last line, the check that decides ``correct``, and the faults it catches."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import reference
import run
import smoke
import state
import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 33 + 7


def _spec():
    return smoke.spec()


def test_a_whole_run_prints_the_contracts_last_line():
    res = run.run_cell(_spec(), SEED, 1.0, False, require_chip=False,
                       use_pallas="interpret", log=lambda s: None)
    line = json.loads(json.dumps(res))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "check"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == round(4.0 * 1.0)
    assert set(line["metrics"]) == {"ttft_p90_ms", "itl_p50_ms", "itl_p95_ms",
                                    "tokens_per_s", "setup_s"}
    assert all(m["value"] >= 0 for m in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    assert line["check"]["greedy_gap"]["value"] <= \
        line["check"]["greedy_gap"]["limit"]
    assert line["check"]["topk_gap"]["value"] <= \
        line["check"]["topk_gap"]["limit"]
    assert line["check"]["row_params"]["value"] == \
        line["check"]["row_params"]["limit"]


def _alter_tokens(engine):
    """A token altered where it is produced: every sampled row's token of
    every step is moved to the next id."""
    step = engine._sample_jit
    vocab = engine.cfg.vocab_size

    def altered(p, caches, tok, sampling):
        tokens, new = step(p, caches, tok, sampling)
        return (tokens + 1) % vocab, new

    engine._sample_jit = altered


def test_a_token_altered_where_it_is_produced_is_not_correct():
    res = run.run_cell(_spec(), SEED, 1.0, False, require_chip=False,
                       use_pallas=False, fault=_alter_tokens,
                       log=lambda s: None)
    assert res["correct"] is False
    assert res["check"]["greedy_gap"]["value"] > \
        res["check"]["greedy_gap"]["limit"]


def _wrong_row(engine):
    """The budget routed to a row other than the one it names."""
    route = engine.router.route
    engine.router.route = lambda b: max(0, route(b) - 1)


def _no_topk(engine):
    """The sampling kernel's top-k mask left out: sampled rows draw from
    the whole vocabulary."""
    pack = engine._pack_sampling

    def loose(metas, width):
        out = pack(metas, width)
        out["top_k"] = None
        return out

    engine._pack_sampling = loose


def test_a_sampled_token_outside_the_top_k_is_not_correct():
    res = run.run_cell(_spec(), SEED, 1.0, False, require_chip=False,
                       use_pallas=False, fault=_no_topk, log=lambda s: None)
    assert res["correct"] is False
    assert res["check"]["topk_gap"]["value"] > \
        res["check"]["topk_gap"]["limit"]
    assert res["check"]["greedy_gap"]["value"] <= \
        res["check"]["greedy_gap"]["limit"]


def test_a_request_routed_to_the_wrong_row_is_not_correct():
    res = run.run_cell(_spec(), SEED, 1.0, False, require_chip=False,
                       use_pallas=False, fault=_wrong_row, log=lambda s: None)
    assert res["correct"] is False


def _fake_trace(run_obj, marks):
    """A device trace of the traced span: one attention kernel, a copy and
    a sampling kernel per iteration, with gaps between iterations."""
    lo, hi = 0, int((marks[1] - marks[0]) * 1e9)
    dev, host = [], [("python", xplane.WINDOW, lo, hi)]
    steps = [t for t, *_ in run_obj.metrics.steps if marks[0] <= t < marks[1]]
    for t in steps:
        s = int((t - marks[0]) * 1e9)
        dev += [("paged_prefill_attention.3", s, s + 400_000),
                ("copy.1", s + 400_000, s + 500_000),
                ("topk_mask_sample", s + 500_000, s + 600_000)]
        host.append(("python", "paged_sample_step", s, s + 100_000))
    return xplane.clip({"device": {"/device:TPU:0": dev}, "host": host})


def test_a_traced_run_reports_every_per_layer_metric(monkeypatch):
    sp = _spec()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        sp["per_layer"] = json.load(f)["per_layer"]
    seen = {}
    import client
    orig = client.serve

    def serve(*a, **kw):
        r = orig(*a, **kw)
        seen["run"] = r
        return r

    monkeypatch.setattr(client, "serve", serve)
    monkeypatch.setattr(xplane, "load", lambda d: _fake_trace(
        seen["run"], (seen["lo"], seen["hi"])))
    monkeypatch.setattr(xplane, "start", lambda d: None)
    monkeypatch.setattr(xplane, "stop", lambda: None)
    import jax

    class Ann:
        def __init__(self, name):
            pass

        def __enter__(self):
            import time
            seen["lo"] = time.perf_counter()

        def __exit__(self, *exc):
            import time
            seen["hi"] = time.perf_counter()

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    monkeypatch.setattr(run, "memory_peak", lambda chips: 1)
    # the readers' peaks are the chip's: the rehearsal borrows them
    monkeypatch.setattr(run, "device_info", lambda chips, need: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    res = run.run_cell(sp, SEED, 4.0, True, require_chip=False,
                       use_pallas=False, log=lambda s: None)
    names = {m["name"] for m in sp["per_layer"]}
    assert set(res["metrics"]) == names
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert res["breakdown"]["device_ops"][0][0] == "paged_prefill_attention"
    for name in ("paged_prefill_attention_roofline",
                 "topk_mask_sample_roofline", "step.mfu"):
        assert 0 < res["metrics"][name]["value"] < 100


def test_without_a_chip_the_command_exits_3_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "gpt2-small.chat", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 3
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_the_engine_serves_the_seeded_state_as_the_reference_computes():
    """Greedy requests through ``generate()`` with the interpret kernels:
    every served token is the reference's first choice at float32."""
    from repro.serving import Request
    sp = _spec()
    engine, row, params = run.build(sp, SEED, use_pallas="interpret",
                                    log=lambda s: None)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, 512, n).astype(np.int32),
                    max_new_tokens=k, budget=sp["mix"]["budget"])
            for n, k in ((5, 9), (17, 6), (30, 12), (9, 3))]
    results = engine.generate(reqs, mode="continuous")
    ref = reference.Reference(sp["conf"], SEED, sp["mix"]["budget"], 64)
    assert ref.deployed == params
    for rq, rs in zip(reqs, results):
        served = rs.tokens[len(rq.prompt):]
        assert len(served) == rq.max_new_tokens
        assert ref.gaps(rq.prompt, served).max() == 0.0


def test_the_ladder_is_the_programs_own():
    """The reference's ladder (its own copy of the DP and the router's
    rule) gives the program's table and deployed counts, at smoke size and
    at both configurations' full widths."""
    from repro.core import flexrank as FR
    import copy
    confs = [smoke.conf(), smoke.conf(tie=False)]
    for name in ("gpt2-small",):
        with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
            confs.append(json.load(f))
    for c in confs:
        cfg = state.model_config(copy.deepcopy(c))
        table, infos = FR.build_table(cfg, state.curves(cfg, c))
        mine, deployed = reference.ladder(c)
        np.testing.assert_array_equal(mine, table.table)
        assert [FR.deployed_param_count(cfg, infos, table, k)
                for k in range(len(mine))] == deployed.tolist()
