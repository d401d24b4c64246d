"""Compile the serving path's Pallas kernels for a described TPU v5e.

Interpret mode on the CPU checks what a kernel computes, not whether
Mosaic accepts it: block shapes whose last two dims are neither
(8, 128)-divisible nor the array's own pass every interpret-mode test
and are refused by the chip's compiler.  These tests compile each
kernel wrapper with ``interpret=False`` at qwen2-0.5b widths (14 query
heads, 2 KV heads, head dim 64, bf16 pages of 16 positions) for one
chip of a described ``v5e:2x2`` topology, and assert that the compiled
program holds the kernel (``tpu_custom_call``).  Nothing runs; no chip
is needed.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and the test
workers all import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

H, KV, D, PAGE = 14, 2, 64, 16          # qwen2-0.5b attention widths
PAGES_PER_SEQ = 64                      # max_seq_len 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    assert "tpu_custom_call" in lowered.compile().as_text()


def _pages(one_chip, batch):
    n = batch * PAGES_PER_SEQ + 1                        # + trash page
    return (_spec(one_chip, (n, PAGE, KV, D), jnp.bfloat16),
            _spec(one_chip, (n, PAGE, KV, D), jnp.bfloat16),
            _spec(one_chip, (batch, PAGES_PER_SEQ), jnp.int32))


@pytest.mark.parametrize("window,softcap", [(None, None), (64, 50.0)])
def test_paged_decode_attention_compiles(one_chip, no_compile_cache,
                                         window, softcap):
    B = 8
    k, v, tables = _pages(one_chip, B)
    _assert_kernel(ops.paged_decode_attention.lower(
        _spec(one_chip, (B, 1, H, D), jnp.bfloat16), k, v, tables,
        _spec(one_chip, (B,), jnp.int32),
        window=window, softcap=softcap, interpret=False))


@pytest.mark.parametrize("window,softcap", [(None, None), (64, 50.0)])
def test_paged_prefill_attention_compiles(one_chip, no_compile_cache,
                                          window, softcap):
    B, C = 4, 16
    k, v, tables = _pages(one_chip, B)
    rows = _spec(one_chip, (B,), jnp.int32)
    _assert_kernel(ops.paged_prefill_attention.lower(
        _spec(one_chip, (B, C, H, D), jnp.bfloat16), k, v, tables, rows,
        rows, window=window, softcap=softcap, interpret=False))


def test_mha_flash_attention_compiles(one_chip, no_compile_cache):
    S = 1024
    _assert_kernel(ops.mha_flash_attention.lower(
        _spec(one_chip, (1, S, H, D), jnp.bfloat16),
        _spec(one_chip, (1, S, KV, D), jnp.bfloat16),
        _spec(one_chip, (1, S, KV, D), jnp.bfloat16), interpret=False))


def test_rmsnorm_compiles(one_chip, no_compile_cache):
    d_model = 896
    _assert_kernel(ops.rmsnorm.lower(
        _spec(one_chip, (256, d_model), jnp.bfloat16),
        _spec(one_chip, (d_model,), jnp.float32), interpret=False))


def test_ssd_compiles(one_chip, no_compile_cache):
    # mamba2-1.3b head geometry: head dim 64, state 128, one group
    b, S, heads, P, N = 1, 512, 4, 64, 128
    f32 = jnp.float32
    _assert_kernel(ops.ssd.lower(
        _spec(one_chip, (b, S, heads, P), f32),
        _spec(one_chip, (b, S, heads), f32),
        _spec(one_chip, (heads,), f32),
        _spec(one_chip, (b, S, 1, N), f32),
        _spec(one_chip, (b, S, 1, N), f32), chunk=256, interpret=False))
