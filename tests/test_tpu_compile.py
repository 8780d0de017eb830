"""Compile every main-path Pallas kernel for a described TPU v5e.

Interpret mode runs a kernel body in Python and never asks the TPU compiler
anything, so tiling, layout and lowering refusals only show when Mosaic
compiles the kernel.  These tests compile each kernel ahead of time for one
chip of a described ``v5e:2x2`` topology, at yi-9b's published widths
(d_model 4096, 32 q / 4 kv heads of 128, d_ff 11008, vocab 64,000; 8 slots,
a 4,096-token cache stripe, 16-token paged blocks, 256-token prefill chunks)
and, for the RG-LRU scan, at recurrentgemma-9b's 4,096 recurrence width;
the paged decode kernel also at qwen2.5-14b's 8 kv heads.
Nothing runs: a compile that passes says nothing about results or times.

The topology is described inside a module-scoped fixture (never at import),
so that only the test worker given this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (decode_attention, fused_residual, lru_scan,
                           prefill_attention, topk_shard, wquant_matmul)

SLOTS, HQ, HKV, HD = 8, 32, 4, 128
D, FF, VOCAB = 4096, 11008, 64000
STRIPE, BLOCK, CHUNK = 4096, 16, 256
NBPS = STRIPE // BLOCK
POOL = 1 + SLOTS * NBPS
LRU_WIDTH = 4096
SCALE = HD ** -0.5


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # the TPU compiler is not installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU compile written to the persistent cache cannot be read back
    # without a chip, so the cache stays off while these tests compile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


def _custom_call_named(hlo, name):
    """The kernel's custom call carries ``name``: a profiler trace names the
    kernel's operation after it, and the paged kernels are found by it."""
    return re.search(rf"^\s*(ROOT )?%{name}(\.\d+)? = .* custom-call\(",
                     hlo, re.M) is not None


def test_decode_attention(one_chip):
    _compile(lambda q, k, v, valid: decode_attention.decode_attention_partial(
        q, k, v, valid, SCALE, interpret=False), one_chip,
        ((SLOTS, HQ, 1, HD), jnp.bfloat16),
        ((SLOTS, HKV, STRIPE, HD), jnp.bfloat16),
        ((SLOTS, HKV, STRIPE, HD), jnp.bfloat16),
        ((STRIPE,), jnp.bool_))


def test_paged_decode_attention(one_chip):
    hlo = _compile(lambda q, kp, vp, bt, valid:
             decode_attention.paged_decode_attention_partial(
                 q, kp, vp, bt, valid, SCALE, interpret=False), one_chip,
             ((SLOTS, HQ, 1, HD), jnp.bfloat16),
             ((POOL, HKV, BLOCK, HD), jnp.bfloat16),
             ((POOL, HKV, BLOCK, HD), jnp.bfloat16),
             ((SLOTS, NBPS), jnp.int32),
             ((SLOTS, STRIPE), jnp.bool_))
    assert _custom_call_named(hlo, "paged_decode_attention_partial")


def test_paged_decode_attention_qwen_widths(one_chip):
    """qwen2.5-14b widths: 40 q / 8 kv heads of 128 (group 5), 24 slots of
    2,048 tokens.  Each grid step's K and V buffers hold all 8 heads, so
    this is the widest page the kernel's VMEM budget has to take."""
    slots, hq, hkv, stripe = 24, 40, 8, 2048
    nbps = stripe // BLOCK
    pool = 1 + slots * nbps
    hlo = _compile(lambda q, kp, vp, bt, valid:
                   decode_attention.paged_decode_attention_partial(
                       q, kp, vp, bt, valid, SCALE, interpret=False), one_chip,
                   ((slots, hq, 1, HD), jnp.bfloat16),
                   ((pool, hkv, BLOCK, HD), jnp.bfloat16),
                   ((pool, hkv, BLOCK, HD), jnp.bfloat16),
                   ((slots, nbps), jnp.int32),
                   ((slots, stripe), jnp.bool_))
    assert _custom_call_named(hlo, "paged_decode_attention_partial")


def test_flash_prefill(one_chip):
    _compile(lambda q, k, v, qp: prefill_attention.flash_prefill(
        q, k, v, qp, SCALE, interpret=False), one_chip,
        ((SLOTS, HQ, CHUNK, HD), jnp.bfloat16),
        ((SLOTS, HKV, STRIPE, HD), jnp.bfloat16),
        ((SLOTS, HKV, STRIPE, HD), jnp.bfloat16),
        ((SLOTS, CHUNK), jnp.int32))


def test_flash_verify(one_chip):
    _compile(lambda q, k, v, qp: prefill_attention.flash_verify(
        q, k, v, qp, SCALE, interpret=False), one_chip,
        ((SLOTS, HQ, 5, HD), jnp.bfloat16),
        ((SLOTS, HKV, STRIPE, HD), jnp.bfloat16),
        ((SLOTS, HKV, STRIPE, HD), jnp.bfloat16),
        ((SLOTS, 5), jnp.int32))


def test_paged_flash_prefill(one_chip):
    hlo = _compile(lambda q, kp, vp, bt, qp: prefill_attention.paged_flash_prefill(
        q, kp, vp, bt, qp, SCALE, interpret=False), one_chip,
        ((SLOTS, HQ, CHUNK, HD), jnp.bfloat16),
        ((POOL, HKV, BLOCK, HD), jnp.bfloat16),
        ((POOL, HKV, BLOCK, HD), jnp.bfloat16),
        ((SLOTS, NBPS), jnp.int32),
        ((SLOTS, CHUNK), jnp.int32))
    assert _custom_call_named(hlo, "paged_flash_prefill")


@pytest.mark.parametrize("t", [SLOTS, CHUNK])
def test_dequant_matmul_int8(one_chip, t):
    _compile(lambda x, q, s: wquant_matmul.dequant_matmul(
        x, q, s, mode="int8", group=0, interpret=False), one_chip,
        ((t, D), jnp.bfloat16), ((D, FF), jnp.int8), ((FF,), jnp.bfloat16))


@pytest.mark.parametrize("t", [SLOTS, CHUNK])
def test_dequant_matmul_int4(one_chip, t):
    g = 128
    _compile(lambda x, q, s: wquant_matmul.dequant_matmul(
        x, q, s, mode="int4", group=g, interpret=False), one_chip,
        ((t, D), jnp.bfloat16), ((D // 2, FF), jnp.uint8),
        ((D // g, FF), jnp.bfloat16))


def test_fused_dual_matmul(one_chip):
    _compile(lambda a, wa, b, wb: fused_residual.fused_dual_matmul(
        a, wa, b, wb, interpret=False), one_chip,
        ((CHUNK, D), jnp.bfloat16), ((D, D), jnp.bfloat16),
        ((CHUNK, FF), jnp.bfloat16), ((FF, D), jnp.bfloat16))


def test_topk(one_chip):
    _compile(lambda x: topk_shard.topk(x, 40, interpret=False), one_chip,
             ((SLOTS, VOCAB), jnp.float32))


def test_lru_scan(one_chip):
    _compile(lambda a, b, h0: lru_scan.lru_scan(a, b, h0, interpret=False),
             one_chip,
             ((SLOTS, CHUNK, LRU_WIDTH), jnp.float32),
             ((SLOTS, CHUNK, LRU_WIDTH), jnp.float32),
             ((SLOTS, LRU_WIDTH), jnp.float32))
