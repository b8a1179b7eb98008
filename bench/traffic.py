"""Open-loop traffic from a mix file: every seed gets the same shapes.

A mix states the distributions of prompt and output lengths, the share of
greedy requests and the lead-in; the cell states the rate. For a phase of
``n`` requests the lengths are the distributions' quantiles at
``(i + 0.5) / n`` and the inter-arrival gaps the exponential's quantiles
at the same points, scaled so that they add up to the phase's length. The
seed only permutes the order of each of these and draws token ids and
per-request sampling seeds; which output length goes with which prompt
length is fixed by the mix. So two seeds give the same multiset of
request shapes (prompt length, output length, greedy or sampled) and of
arrival gaps, and the same number of requests due in the window.

Phases: ``lead_in`` (due before the window opens: a burst of ``burst``
requests fills the batch, Poisson arrivals at the cell's rate bring it to
steady occupancy; its order is the mix's own, the same for every seed, so
every run ramps up through the same program shapes), ``window`` (due inside it: every one of them counts in
the tails), ``tail`` (due after it: keeps the load steady while the
window's last requests finish; they count in nothing but tokens/s).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

PHASES = ("lead_in", "window", "tail")


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One request of the schedule. ``due`` is seconds from the start of
    the lead-in; ``greedy`` requests are the ones the check compares."""
    index: int
    phase: str
    due: float
    prompt: np.ndarray
    max_new: int
    greedy: bool
    seed: int


def length_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a clipped lognormal, as integers."""
    z = np.asarray([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def gap_quantiles(n: int, seconds: float) -> np.ndarray:
    """``n`` stratified exponential gaps that add up to ``seconds``."""
    g = np.asarray([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return g * (seconds / g.sum())


def shapes_of(mix: dict, n: int):
    """The ``n`` request shapes of a phase, the same for every seed:
    (prompt lengths, output lengths, greedy flags). Which output length
    and flag go with which prompt length is fixed by a permutation of
    the mix's own (``pairing_seed``), not by the run's seed."""
    fixed = np.random.default_rng(mix["pairing_seed"])
    prompts = length_quantiles(mix["prompt_tokens"], n)
    outputs = fixed.permutation(length_quantiles(mix["output_tokens"], n))
    greedy = np.zeros(n, bool)
    greedy[: round(mix["greedy_share"] * n)] = True
    return prompts, outputs, fixed.permutation(greedy)


def phase_counts(mix: dict, rate: float, window_s: float) -> dict:
    return {"lead_in": mix["burst"] + max(1, round(rate * mix["lead_in_s"])),
            "window": max(1, round(rate * window_s)),
            "tail": max(1, round(rate * mix["tail_s"]))}


def phase_seconds(mix: dict, window_s: float) -> dict:
    return {"lead_in": mix["lead_in_s"], "window": window_s,
            "tail": mix["tail_s"]}


def schedule(mix: dict, rate: float, window_s: float, seed: int,
             vocab: int):
    """The whole arrival schedule, ordered by due time. The window opens at
    ``mix['lead_in_s']`` seconds. Returns a list of ``Arrival``."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x7EA]))
    counts = phase_counts(mix, rate, window_s)
    secs = phase_seconds(mix, window_s)
    out = []
    start = 0.0
    for phase in PHASES:
        n = counts[phase]
        # the lead-in is the same for every seed but for its token ids, so
        # every run ramps up through the same program shapes
        shuffle = (np.random.default_rng(mix["pairing_seed"] + 1)
                   if phase == "lead_in" else rng)
        order = shuffle.permutation(n)
        prompts, outputs, greedy = (x[order] for x in shapes_of(mix, n))
        burst = mix["burst"] if phase == "lead_in" else 0
        gaps = shuffle.permutation(gap_quantiles(n - burst, secs[phase]))
        # due times: a burst at the lead-in's start fills the batch at once
        # (a server that comes up to a queue), then each request is due one
        # gap after the one before, the phase's last exactly at its end
        due = start + np.concatenate([np.arange(burst) * 1e-3,
                                      np.cumsum(gaps)])
        due[-1] = start + secs[phase]
        for i in range(n):
            out.append(Arrival(
                index=len(out), phase=phase,
                due=float(min(due[i], start + secs[phase])),
                prompt=rng.integers(0, vocab, int(prompts[i])).astype(np.int32),
                max_new=int(outputs[i]), greedy=bool(greedy[i]),
                seed=int(rng.integers(0, 2 ** 31 - 1))))
        start += secs[phase]
    return out


def shapes(arrivals, phase: str):
    """The multiset of (prompt length, output length, greedy) of a phase,
    sorted: what every seed shares."""
    return sorted((len(a.prompt), a.max_new, a.greedy) for a in arrivals
                  if a.phase == phase)


def lateness(due: float, sent: float) -> float:
    """How late a submission ran behind its due time (never negative)."""
    return max(0.0, sent - due)
