"""Trace reduction: busy time, idle gaps, op and copy time per iteration,
on a hand-made trace and on a small trace recorded on a TPU v5e."""
import json
import os

import pytest

import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


def _trace():
    host = [("python", xplane.WINDOW, 10 * MS, 110 * MS),
            ("python", "serve", 0, 200 * MS),                  # outer
            ("python", "plan", 60 * MS, 80 * MS),              # inner
            ("python", "paged_sample_step", 12 * MS, 13 * MS),
            ("python", "paged_sample_step", 52 * MS, 53 * MS)]
    dev = [("paged_prefill_attention.7", 5 * MS, 30 * MS),     # cut at 10
           ("copy.3", 30 * MS, 40 * MS),
           ("copy-done", 35 * MS, 45 * MS),                    # overlaps
           ("paged_prefill_attention", 50 * MS, 60 * MS),
           ("topk_mask_sample.1", 90 * MS, 100 * MS),
           ("fusion.12", 105 * MS, 120 * MS)]                  # cut at 110
    return xplane.clip({"device": {"/device:TPU:0": dev}, "host": host})


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    red = xplane.reduce(_trace())
    assert red["window_s"] == pytest.approx(0.100)
    # 10-45, 50-60, 90-100, 105-110 ms
    assert red["busy_s"] == pytest.approx(0.035 + 0.010 + 0.010 + 0.005)


def test_op_time_by_base_name_and_copy_prefix():
    red = xplane.reduce(_trace())
    assert red["ops"]["paged_prefill_attention"] == pytest.approx(0.030)
    assert xplane.op_seconds(red, "copy") == pytest.approx(0.020)
    assert red["ops"]["topk_mask_sample"] == pytest.approx(0.010)
    assert red["device_ops"][0] == ["paged_prefill_attention",
                                    pytest.approx(0.030)]


def test_idle_gaps_are_named_by_the_innermost_host_event():
    red = xplane.reduce(_trace())
    # gaps: 60-90 ('plan' covers its middle, 75), 45-50 and 100-105
    assert red["idle_gaps"][0] == ["plan", pytest.approx(0.030)]
    assert [g[0] for g in red["idle_gaps"][1:]] == ["serve", "serve"]
    assert [round(g[1], 6) for g in red["idle_gaps"]] == [0.03, 0.005, 0.005]
    assert red["host_counts"]["paged_sample_step"] == 2


def test_the_innermost_host_event_wins():
    tr = _trace()
    assert xplane._host_at(tr["host"], 75 * MS) == "plan"
    assert xplane._host_at(tr["host"], 46 * MS) == "serve"


def test_a_trace_without_the_window_annotation_is_refused():
    with pytest.raises(ValueError):
        xplane.clip({"device": {}, "host": [("python", "x", 0, 1)]})


def test_a_trace_with_no_device_op_is_refused():
    tr = xplane.clip({"device": {"/device:TPU:0": []},
                      "host": [("python", xplane.WINDOW, 0, 10)]})
    with pytest.raises(ValueError):
        xplane.reduce(tr)


RECORDED = os.path.join(DATA, "trace_v5e.json")


def test_a_recorded_v5e_trace():
    with open(RECORDED) as f:
        rec = json.load(f)
    tr = rec["trace"]
    tr["device"] = {c: [tuple(e) for e in evs]
                    for c, evs in tr["device"].items()}
    tr["host"] = [tuple(e) for e in tr["host"]]
    red = xplane.reduce(tr)
    for name, value in rec["expect"].items():
        assert red[name] == pytest.approx(value, rel=1e-9), name
    assert 0 < red["busy_s"] <= red["window_s"]
    # on this chip XLA's copies outweigh the attention kernel
    assert xplane.op_seconds(red, "copy") > \
        xplane.op_seconds(red, "paged_prefill_attention") > 0
    assert red["device_ops"][0][0] == "copy"
