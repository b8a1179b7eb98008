"""Useful work of the sampling kernel (``topk_mask_sample``): one float32
logits row over the vocabulary per sampled position, read once; per
element a temperature scale, a cutoff compare, an exponential and a
running sum (4 flops), and one token written back."""
from __future__ import annotations


def work(its, shape: dict):
    rows = sum(1 for it in its if it.sampled)
    v = shape["vocab_size"]
    return 4.0 * v * rows, rows * (4.0 * v + 4.0)
