"""Kernel work counts against brute-force counts at smoke size, and the
peaks table."""
import importlib.util
import os

import numpy as np
import pytest

import peaks
import reference
from items import Item, items
from smoke import conf

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _work(name):
    spec = importlib.util.spec_from_file_location(
        f"w_{name}", os.path.join(BENCH, "work", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ITEMS = [Item(0.0, 0, 8, False), Item(0.1, 8, 5, True),
         Item(0.2, 13, 1, True), Item(0.3, 40, 1, True)]


def test_attention_work_matches_a_brute_force_count():
    m = conf()["model"]
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["d_model"] // m["num_heads"]
    flops = nbytes = 0
    for it in ITEMS:
        kv_rows = set()
        for p in range(it.start, it.start + it.n):
            for key in range(p + 1):          # every (query, key) pair
                flops += h * hd * 2 * 2        # QK^T and PV, mul + add
                kv_rows.add(key)
            nbytes += 2 * h * hd * 4           # the query in, its output out
        nbytes += len(kv_rows) * 2 * kv * hd * 4
    f, b = _work("paged_prefill_attention").work(ITEMS, m)
    assert f == flops * m["num_layers"]
    assert b == nbytes * m["num_layers"]


def test_sampling_work_matches_a_brute_force_count():
    m = conf()["model"]
    rows = [it for it in ITEMS if it.sampled]
    f, b = _work("topk_mask_sample").work(ITEMS, m)
    assert b == sum(m["vocab_size"] * 4 + 4 for _ in rows)
    assert f == sum(4 * m["vocab_size"] for _ in rows)


def test_model_flops_match_a_brute_force_count():
    c = conf()
    m = c["model"]
    table, deployed = reference.ladder(c)
    row = table[reference.route(deployed, 0.85)]
    groups = reference.groups(c)
    ranks = {g[0]: int(r) for g, r in zip(groups, row)}
    wm = _work("model")
    per = 0
    for path, layers, d_out, d_in in groups:
        r = ranks[path]
        # GAR: z = x @ v_tilde (d_in x r), tail = z @ u_hat.T ((d_out - r) x r)
        per += layers * 2 * (d_in * r + (d_out - r) * r)
    assert wm.linear_flops(groups, ranks) == per
    hd = m["d_model"] // m["num_heads"]
    brute = 0
    for it in ITEMS:
        for p in range(it.start, it.start + it.n):
            brute += per + m["num_layers"] * 4 * m["num_heads"] * hd * (p + 1)
        if it.sampled:
            brute += 2 * m["d_model"] * m["vocab_size"]
    assert wm.work(ITEMS, m, per) == brute


def test_items_follow_the_clients_tokens():
    class A:
        prompt = np.zeros(20, np.int32)

    class R:
        arrival = A()
        times = [1.0, 1.1, 1.2, 1.3]

    its = items([R()], chunk=8)
    prompt = [it for it in its if not (it.n == 1 and it.start >= 20)]
    assert [(it.start, it.n) for it in prompt] == [(0, 8), (8, 8), (16, 4)]
    assert [it.sampled for it in prompt] == [False, False, True]
    assert prompt[-1].time == 1.0
    assert prompt[0].time == pytest.approx(1.0 - 2 * 0.1)
    decode = [it for it in its if it.start >= 20]
    assert [(it.start, it.time) for it in decode] == [(20, 1.1), (21, 1.2),
                                                      (22, 1.3)]


def test_an_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99 imaginary")
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_roofline_share_names_its_bound():
    p = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert peaks.roofline_share(50.0, 1.0, 1.0, p) == (50.0, "flops")
    assert peaks.roofline_share(1.0, 5.0, 1.0, p) == (50.0, "bytes")
