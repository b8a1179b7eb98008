"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU
v5e at gpt2-small widths (d768: 12 heads of 64, vocab 50257, block 16).

Nothing runs: the TPU compiler, which ships with ``libtpu``, compiles for a
described chip and refuses what the chip would refuse — blocks off the
(8, 128) tile, primitives Mosaic cannot lower, VMEM overruns. Interpret-mode
parity (``test_kernels.py``) cannot see any of that. Each test asserts the
kernel survived as a ``tpu_custom_call`` in the compiled HLO.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and a module that touched it
while being collected would leave the other test workers unable to.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gar_matmul import gar_matmul
from repro.kernels.paged_attention import (paged_attention,
                                           paged_prefill_attention)
from repro.kernels.sampling import topk_mask_sample

# gpt2-small serving widths: 8 decode slots, a 64-token prefill chunk
# (flat batch 8 + 64), 12 heads of 64, 16-token blocks, 256-token contexts
B, T, HQ, HKV, D, BS, MB = 8, 72, 12, 12, 64, 16, 16
NB = B * MB + 1
VOCAB = 50257


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


def test_paged_attention_compiles_for_v5e(one_chip):
    _compile(paged_attention, one_chip,
             ((B, HQ, D), jnp.float32), ((NB, BS, HKV, D), jnp.float32),
             ((NB, BS, HKV, D), jnp.float32), ((B, MB), jnp.int32),
             ((B,), jnp.int32))


def test_paged_prefill_attention_compiles_for_v5e(one_chip):
    # block tables carry one appended null row for pad tokens
    _compile(paged_prefill_attention, one_chip,
             ((T, HQ, D), jnp.float32), ((NB, BS, HKV, D), jnp.float32),
             ((NB, BS, HKV, D), jnp.float32), ((B + 1, MB), jnp.int32),
             ((T,), jnp.int32), ((T,), jnp.int32))


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
