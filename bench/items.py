"""The work the served requests made the device do, token by token.

Built from what the clients saw and the configuration alone, so it does
not change when the implementation or its padding does. Each ``Item`` is
a run of consecutive positions of one sequence that went through the
model together:

* a decode input (``n = 1``): the k-th served token, fed back at position
  ``P + k - 1``; it finished when token ``k + 1`` arrived, and it was
  sampled (one LM-head row);
* a prompt chunk: the engine splits a prompt into chunks of at most
  ``prefill_chunk`` tokens, one per iteration, the last one finishing
  when the first token arrived (and sampled). Earlier chunks are placed
  one iteration apart, at the request's median gap between tokens.

Times are the host clock at which the work was seen done; a window's
work is the items that finished in it.
"""
from __future__ import annotations

import dataclasses
from statistics import median


@dataclasses.dataclass(frozen=True)
class Item:
    time: float
    start: int          # first position (0-based) of the run
    n: int              # positions in the run
    sampled: bool       # the run's last position had its logits sampled


def items(records, chunk: int):
    out = []
    for r in records:
        if not r.times:
            continue
        p = len(r.arrival.prompt)
        gaps = [b - a for a, b in zip(r.times, r.times[1:])]
        step = median(gaps) if gaps else 0.0
        starts = list(range(0, p, chunk))
        for j, s in enumerate(starts):
            out.append(Item(r.times[0] - (len(starts) - 1 - j) * step, s,
                            min(chunk, p - s), j == len(starts) - 1))
        for k in range(1, len(r.times)):
            out.append(Item(r.times[k], p + k - 1, 1, True))
    return out


def between(its, lo: float, hi: float):
    return [it for it in its if lo <= it.time < hi]


def keys(it: Item, window=None) -> int:
    """Query-key pairs of the run: position ``p`` attends over ``p + 1``
    keys, or the last ``window`` of them."""
    if window is None:
        return it.n * it.start + it.n * (it.n + 1) // 2
    return sum(min(p + 1, window) for p in range(it.start, it.start + it.n))


def context(it: Item, window=None) -> int:
    """Rows of keys and values the run reads, each once: its whole context,
    or from the first position's window on."""
    first = 0 if window is None else max(0, it.start - window + 1)
    return it.start + it.n - first
