"""The traffic generator: the same shapes for every seed, in another order,
and lateness measured from the due time."""
import numpy as np
import pytest

import traffic
from smoke import mix

SEEDS = (1, 2 ** 33 + 17)


def _sched(seed, rate=20.0, seconds=4.0):
    return traffic.schedule(mix(), rate, seconds, seed, vocab=512)


@pytest.mark.parametrize("phase", traffic.PHASES)
def test_two_seeds_share_the_multiset_of_shapes(phase):
    a, b = (_sched(s) for s in SEEDS)
    assert traffic.shapes(a, phase) == traffic.shapes(b, phase)
    order_a = [(len(x.prompt), x.max_new) for x in a if x.phase == phase]
    order_b = [(len(x.prompt), x.max_new) for x in b if x.phase == phase]
    if phase == "lead_in":                      # every run ramps up alike
        assert order_a == order_b
        assert [x.due for x in a if x.phase == phase] == \
            [x.due for x in b if x.phase == phase]
    else:
        assert order_a != order_b               # the seed permutes them


def test_two_seeds_share_the_gaps_and_the_window_count():
    a, b = (_sched(s) for s in SEEDS)
    gaps = [sorted(np.round(np.diff([0.0] + [x.due for x in s]), 9))
            for s in (a, b)]
    assert gaps[0] == gaps[1]
    m = mix()
    for s in (a, b):
        win = [x for x in s if x.phase == "window"]
        assert len(win) == round(20.0 * 4.0)
        assert all(m["lead_in_s"] < x.due <= m["lead_in_s"] + 4.0 for x in win)
        assert max(x.due for x in win) == pytest.approx(m["lead_in_s"] + 4.0)


def test_same_seed_same_schedule_and_tokens_differ_by_seed():
    a, b, c = _sched(SEEDS[0]), _sched(SEEDS[0]), _sched(SEEDS[1])
    assert [x.due for x in a] == [x.due for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c)
                   if len(x.prompt) == len(y.prompt))


def test_lengths_are_stratified_quantiles_within_the_clip():
    spec = mix()["prompt_tokens"]
    q = traffic.length_quantiles(spec, 41)
    assert q.min() >= spec["min"] and q.max() <= spec["max"]
    assert np.all(np.diff(q) >= 0)
    assert q[20] == spec["median"]               # the middle quantile


def test_gaps_add_up_to_the_phase():
    g = traffic.gap_quantiles(30, 7.5)
    assert g.sum() == pytest.approx(7.5)
    assert np.all(g > 0)


def test_greedy_share_is_the_same_for_every_seed():
    for s in SEEDS:
        win = [x for x in _sched(s) if x.phase == "window"]
        assert sum(x.greedy for x in win) == round(mix()["greedy_share"] * 80)


def test_lateness_counts_from_the_due_time():
    assert traffic.lateness(due=10.0, sent=10.25) == pytest.approx(0.25)
    assert traffic.lateness(due=10.0, sent=9.9) == 0.0
