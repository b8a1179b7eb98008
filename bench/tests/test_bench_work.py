"""Kernel work counts against brute-force counts at smoke size, and the
peaks table."""
import importlib.util
import os

import numpy as np
import pytest

import arch
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

# the smoke configuration's shape, and one with windowed layers and keys
# and values of different widths read from one latent row of the keys'
# width (as absorbed latent attention has)
SHAPES = {
    "smoke": None,
    "windowed": {"heads": 4, "kv_heads": 1, "qk_dim": 24, "v_dim": 16,
                 "kv_width": 24,
                 "windows": [6, None, 6, 3], "d_model": 64,
                 "vocab_size": 512},
}


def _shape(name):
    if SHAPES[name] is not None:
        return SHAPES[name]
    c = conf()
    return dict(arch.module(c).attention(c), d_model=c["model"]["d_model"],
                vocab_size=c["model"]["vocab_size"])


def _attended(it, window):
    """Every (position, key) pair of the run, one by one."""
    for p in range(it.start, it.start + it.n):
        for key in range(p + 1):
            if window is None or p - key < window:
                yield p, key


@pytest.mark.parametrize("name", SHAPES)
def test_attention_work_matches_a_brute_force_count(name):
    sh = _shape(name)
    h, kv, dqk, dv = sh["heads"], sh["kv_heads"], sh["qk_dim"], sh["v_dim"]
    flops = nbytes = 0
    for window in sh["windows"]:
        for it in ITEMS:
            kv_rows = set()
            for p, key in _attended(it, window):
                flops += h * (dqk + dv) * 2    # QK^T and PV, mul + add
                kv_rows.add(key)
            nbytes += it.n * h * (dqk + dv) * 4      # queries in, outputs out
            nbytes += len(kv_rows) * kv * sh["kv_width"] * 4
    f, b = _work("paged_prefill_attention").work(ITEMS, sh)
    assert f == flops
    assert b == nbytes


def test_sampling_work_matches_a_brute_force_count():
    sh = _shape("smoke")
    rows = [it for it in ITEMS if it.sampled]
    f, b = _work("topk_mask_sample").work(ITEMS, sh)
    assert b == sum(sh["vocab_size"] * 4 + 4 for _ in rows)
    assert f == sum(4 * sh["vocab_size"] for _ in rows)


@pytest.mark.parametrize("name", SHAPES)
def test_model_flops_match_a_brute_force_count(name):
    c = conf()
    table, deployed = reference.ladder(c)
    row = table[reference.route(deployed, 0.85)]
    groups = arch.module(c).groups(c)
    ranks = {g[0]: int(r) for g, r in zip(groups, row)}
    wm = _work("model")
    per = 0
    for path, lead, d_out, d_in, per_position in groups:
        r = ranks[path]
        # GAR: z = x @ v_tilde (d_in x r), tail = z @ u_hat.T ((d_out - r) x r)
        per += per_position * 2 * (d_in * r + (d_out - r) * r)
    assert wm.linear_flops(groups, ranks) == per
    sh = _shape(name)
    brute = 0
    for it in ITEMS:
        for window in sh["windows"]:
            for _ in _attended(it, window):
                brute += 2 * sh["heads"] * (sh["qk_dim"] + sh["v_dim"])
        brute += per * it.n
        if it.sampled:
            brute += 2 * sh["d_model"] * sh["vocab_size"]
    assert wm.work(ITEMS, sh, per) == brute


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
