"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU
v5e at gpt2-small widths (d768: 12 heads of 64, vocab 50257, block 16).

Nothing runs: the TPU compiler, which ships with ``libtpu``, compiles for a
described chip and refuses what the chip would refuse — blocks off the
(8, 128) tile, primitives Mosaic cannot lower, VMEM overruns. Interpret-mode
parity (``test_kernels.py``) cannot see any of that. Each test asserts the
kernel survived as a ``tpu_custom_call`` in the compiled HLO. The compiled
mixed serving step is also read for copies of the KV pool: the step must
scatter into and attend over the donated pools where they lie.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and a module that touched it
while being collected would leave the other test workers unable to.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import Segment
from repro.configs.gpt2_small import CONFIG as GPT2
from repro.kernels.gar_matmul import gar_matmul
from repro.kernels.paged_attention import (paged_attention,
                                           paged_prefill_attention)
from repro.kernels.sampling import topk_mask_sample
from repro.models import common as cm
from repro.models import transformer as tfm
from repro.serving.kv_cache import PagedKVCache

# gpt2-small serving widths: 8 decode slots, a 64-token prefill chunk
# (flat batch 8 + 64), 12 heads of 64, 16-token blocks, 256-token contexts
B, T, HQ, HKV, D, BS, MB = 8, 72, 12, 12, 64, 16, 16
NB = B * MB + 1
VOCAB = 50257

# (hq, hkv, d): gpt2-small, then grouped-query heads whose pool row
# (Hkv * D lanes) is not a multiple of 128: 5 x 64 and the smoke model's 4 x 16
HEADS = [(HQ, HKV, D), (10, 5, 64), (4, 4, 16)]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


@pytest.mark.parametrize("hq,hkv,d", HEADS)
def test_paged_attention_compiles_for_v5e(one_chip, hq, hkv, d):
    _compile(paged_attention, one_chip,
             ((B, hq, d), jnp.float32), ((NB, BS, hkv * d), jnp.float32),
             ((NB, BS, hkv * d), jnp.float32), ((B, MB), jnp.int32),
             ((B,), jnp.int32))


@pytest.mark.parametrize("hq,hkv,d", HEADS)
def test_paged_prefill_attention_compiles_for_v5e(one_chip, hq, hkv, d):
    # block tables carry one appended null row for pad tokens
    _compile(paged_prefill_attention, one_chip,
             ((T, hq, d), jnp.float32), ((NB, BS, hkv * d), jnp.float32),
             ((NB, BS, hkv * d), jnp.float32), ((B + 1, MB), jnp.int32),
             ((T,), jnp.int32), ((T,), jnp.int32))


def _copied_shapes(hlo: str):
    """Result shapes of every ``copy`` and ``copy-start`` in compiled HLO
    text (a ``copy-start``'s first tuple element is its destination)."""
    for line in hlo.splitlines():
        if re.search(r" copy(-start)?\(", line):
            dims = re.search(r"= \(?\w+\[([\d,]*)\]", line).group(1)
            yield tuple(int(x) for x in dims.split(",") if x)


def test_paged_mixed_step_keeps_pools_in_place(one_chip):
    """The served mixed step at the benchmark's shapes (gpt2-small widths, 2
    of its layers, 2048 + 1 blocks of 16, a 96-token flat batch, 32-block
    tables, caches donated as the engine does) copies no KV pool: the
    scatter and the Pallas kernel both work on the pool in the layout the
    compiler gives it, instead of relayouting 2 x 100 MB a layer."""
    cfg = dataclasses.replace(GPT2, num_layers=2,
                              segments=(Segment("attn", 1),) * 2)
    t, mb, batch = 96, 32, 32
    pools = jax.eval_shape(lambda: PagedKVCache(
        cfg, max_batch=batch, max_len=1024, num_blocks=2048).pools)
    params = jax.eval_shape(lambda: cm.instantiate(
        tfm.model_spec(cfg), jax.random.PRNGKey(0)))

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    caches = {"slot_ids": on_chip(jnp.zeros((t,), jnp.int32)),
              "positions": on_chip(jnp.zeros((t,), jnp.int32)),
              "block_tables": on_chip(jnp.zeros((batch + 1, mb), jnp.int32)),
              "segments": jax.tree.map(on_chip, pools)}
    step = jax.jit(lambda p, c, tok: tfm.paged_mixed_step(
        p, cfg, c, tok, use_pallas=True), donate_argnums=(1,))
    hlo = step.lower(jax.tree.map(on_chip, params), caches,
                     on_chip(jnp.zeros((1, t), jnp.int32))).compile().as_text()
    assert "tpu_custom_call" in hlo
    pool_size = math.prod(pools[0]["k"].shape)
    big = [s for s in _copied_shapes(hlo) if math.prod(s) >= pool_size]
    assert not big, f"pool-sized copies in the compiled step: {big}"


@pytest.mark.parametrize("return_probs", [False, True])
def test_topk_mask_sample_compiles_for_v5e(one_chip, return_probs):
    # 5 sample rows: the wrapper must pad them to the 8-row tile
    s = 5
    _compile(lambda lg, t, th, u: topk_mask_sample(
                 lg, t, th, u, return_probs=return_probs),
             one_chip, ((s, VOCAB), jnp.float32), ((s,), jnp.float32),
             ((s,), jnp.float32), ((s,), jnp.float32))


def test_gar_matmul_compiles_for_v5e(one_chip):
    # an MLP up-projection (768 -> 3072) kept at rank 512: the tail is
    # the remaining 2560 outputs
    _compile(gar_matmul, one_chip, ((256, 768), jnp.float32),
             ((768, 512), jnp.float32), ((2560, 512), jnp.float32))
