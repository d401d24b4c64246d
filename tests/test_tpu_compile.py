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

The grouped expert matmul of the MoE layer compiles at qwen3-moe-30b-a3b
widths, and the engine's MoE programs hand it the stacked expert weights
with no per-layer copy.  The engine's two paged programs
(``jit_serve_step``, ``jit_prefill_paged``)
are compiled whole, at qwen2-0.5b and deepseek-coder-33b widths, to
check that they update the donated KV pool in place: no pool-sized
copy, slice or update-slice, and the pool aliased from input to output;
and, built from the tree the engine holds, that they convert no float32
weight to the compute dtype.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and the test
workers all import this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import transformer as T
from repro.serving import ServingEngine

H, KV, D, PAGE = 14, 2, 64, 16          # qwen2-0.5b attention widths
PAGES_PER_SEQ = 64                      # max_seq_len 1024
LAYERS = 2                              # layers of the stacked pool


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
    """The stacked pool (LAYERS, pages + trash, PAGE, KV * D), a layer
    index and the block tables."""
    n = batch * PAGES_PER_SEQ + 1                        # + trash page
    pool = (LAYERS, n, PAGE, KV * D)
    return (_spec(one_chip, pool, jnp.bfloat16),
            _spec(one_chip, pool, jnp.bfloat16),
            _spec(one_chip, (), jnp.int32),
            _spec(one_chip, (batch, PAGES_PER_SEQ), jnp.int32))


@pytest.mark.parametrize("window,softcap", [(None, None), (64, 50.0)])
def test_paged_decode_attention_compiles(one_chip, no_compile_cache,
                                         window, softcap):
    B = 8
    k, v, layer, tables = _pages(one_chip, B)
    _assert_kernel(ops.paged_decode_attention.lower(
        _spec(one_chip, (B, 1, H, D), jnp.bfloat16), k, v, layer, tables,
        _spec(one_chip, (B,), jnp.int32),
        window=window, softcap=softcap, interpret=False))


@pytest.mark.parametrize("window,softcap", [(None, None), (64, 50.0)])
def test_paged_prefill_attention_compiles(one_chip, no_compile_cache,
                                          window, softcap):
    B, C = 4, 16
    k, v, layer, tables = _pages(one_chip, B)
    rows = _spec(one_chip, (B,), jnp.int32)
    _assert_kernel(ops.paged_prefill_attention.lower(
        _spec(one_chip, (B, C, H, D), jnp.bfloat16), k, v, layer, tables,
        rows, rows, window=window, softcap=softcap, interpret=False))


# ---------------------------------------------------------------------------
# the engine's paged programs keep the pool in place
# ---------------------------------------------------------------------------

# (heads, kv heads, head dim, d_model, d_ff, vocab, tied, qkv bias, slots)
WIDTHS = {
    "qwen2-0.5b": (14, 2, 64, 896, 4864, 151_936, True, True, 32),
    "deepseek-coder-33b": (56, 8, 128, 7168, 19_200, 32_256, False, False,
                           16),
}
# pool blocks of the compiled programs (the trash block makes it odd, so
# no other dimension matches it).  A pool that fits the chip's VMEM is
# staged there by the compiler, which a deployment's pool never is; at
# 4,097 blocks of two layers every pool leaf is too large for that.
BLOCKS = 4097
_HLO_OP = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                     re.M)
# ops that move data (fusions are named after the ops they fuse)
_MOVES = re.compile(r"copy|dynamic-(update-)?slice")


def _pool_moves(hlo: str, layer_elems: int):
    """The ops that copy, slice or update-slice a buffer of the pool's
    block count holding at least one layer's pages."""
    found = []
    for name, dims, op in _HLO_OP.findall(hlo):
        shape = [int(d) for d in dims.split(",") if d]
        moves = _MOVES.fullmatch(op.replace("-start", "").replace(
            "-done", "")) or (op == "fusion" and _MOVES.search(name))
        if moves and BLOCKS in shape and np.prod(shape) >= layer_elems:
            found.append(f"{op} {name} {shape}")
    return found


@pytest.fixture(scope="module")
def paged_compiles():
    """(arch, program) -> (engine, f32 tree, pool, compiled): the pool and
    weight tests read the same compile."""
    return {}


def _paged_program(compiles, one_chip, monkeypatch, arch, program):
    """The engine's paged ``program`` at ``arch`` widths, compiled for the
    described chip from the tree the engine holds."""
    if (arch, program) in compiles:
        return compiles[arch, program]
    h, kv, d, d_model, d_ff, vocab, tied, bias, slots = WIDTHS[arch]
    cfg = ModelConfig(name=arch, family="dense", num_layers=LAYERS,
                      d_model=d_model, num_heads=h, num_kv_heads=kv,
                      head_dim=d, d_ff=d_ff, vocab_size=vocab,
                      qkv_bias=bias, tie_embeddings=tied, dtype="bfloat16",
                      param_dtype="float32", kv_cache_dtype="bfloat16")
    # the kernels compile for the described chip, not the interpreter
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    jax.clear_caches()
    params = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: T.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    # the engine's own jitted programs, lowered at the chosen pool size
    eng = ServingEngine(cfg, params, max_seq_len=4096, max_slots=slots,
                        paged=True, num_blocks=8, prefill_batch=4)
    pool = {name: _spec(one_chip, leaf.shape[:1] + (BLOCKS,)
                        + leaf.shape[2:], leaf.dtype)
            for name, leaf in eng.kv.cache.items()}
    bps, i32 = eng.kv.blocks_per_slot, jnp.int32
    if program == "decode":
        compiled = eng._step.lower(eng.params, {
            "tokens": _spec(one_chip, (slots, 1), i32),
            "positions": _spec(one_chip, (slots,), i32),
            "block_tables": _spec(one_chip, (slots, bps), i32)},
            pool).compile()
    else:
        rows = _spec(one_chip, (4,), i32)
        compiled = eng._prefill_paged.lower(
            eng.params, _spec(one_chip, (4, eng.prefill_chunk), i32), rows,
            rows, pool, _spec(one_chip, (4, bps), i32)).compile()
    compiles[arch, program] = eng, params, pool, compiled
    return compiles[arch, program]


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_programs_update_the_pool_in_place(one_chip, no_compile_cache,
                                                 paged_compiles, monkeypatch,
                                                 arch, program):
    _, _, pool, compiled = _paged_program(paged_compiles, one_chip,
                                          monkeypatch, arch, program)
    kv, d = WIDTHS[arch][1:3]
    kernel = {"decode": "paged_decode_attention",
              "prefill": "paged_prefill_attention"}[program]
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and kernel in hlo
    layer_elems = BLOCKS * PAGE * kv * d
    assert _pool_moves(hlo, layer_elems) == []
    pool_bytes = sum(np.prod(a.shape) * a.dtype.itemsize
                     for a in pool.values())
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes


_HLO_TYPE = re.compile(r"%(\S+) = (\w+)\[([\d,]*)\]")
_HLO_CONVERT = re.compile(r"= \w+\[[\d,]*\]\S* convert\(%([^,)\s]+)\)")


def _f32_converts(hlo: str):
    """The shapes of the float32 operands that the program converts (the
    compiled text names an operand; its type is on its own line)."""
    types = {name: (dt, tuple(int(x) for x in dims.split(",") if x))
             for name, dt, dims in _HLO_TYPE.findall(hlo)}
    return {types[a][1] for a in _HLO_CONVERT.findall(hlo)
            if types.get(a, ("",))[0] == "f32"}


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_programs_cast_no_weights(one_chip, no_compile_cache,
                                        paged_compiles, monkeypatch, arch,
                                        program):
    """Compiled from the tree the engine holds, the paged programs convert
    no float32 weight to the compute dtype: not a whole stack, not one
    layer's slice."""
    eng, params, _, compiled = _paged_program(paged_compiles, one_chip,
                                              monkeypatch, arch, program)
    shapes = set()
    for path, a in jax.tree_util.tree_leaves_with_path(params):
        shapes.add(a.shape)
        if getattr(path[0], "key", None) == "layers":
            shapes.add(a.shape[2:])          # one layer of (group, member)
    held = jax.tree.leaves(eng.params)
    assert any(a.dtype == jnp.bfloat16 for a in held)
    assert _f32_converts(compiled.as_text()) & shapes == set()


def test_expert_gmm_compiles(one_chip, no_compile_cache):
    """The grouped expert matmul at qwen3-moe-30b-a3b widths (d 2,048,
    experts of 768, 32 held) over a decode step's 1,024 assignment rows
    (128 slots, 8 experts a token), reading one layer's experts from the
    float32 masters of all 8 layers, stacked."""
    m, d, F, G, W = 1024, 2048, 768, 32, 8 * 32
    f32 = jnp.float32
    compiled = ops.expert_gmm.lower(
        _spec(one_chip, (m, d), jnp.bfloat16),
        _spec(one_chip, (W, d, F), f32), _spec(one_chip, (W, d, F), f32),
        _spec(one_chip, (W, F, d), f32), _spec(one_chip, (G,), jnp.int32),
        _spec(one_chip, (), jnp.int32), interpret=False).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "expert_gmm" in hlo


def _moves_of(hlo: str, elems: int):
    """The ops that copy, slice or update-slice a buffer of at least
    ``elems`` elements."""
    found = []
    for name, dims, op in _HLO_OP.findall(hlo):
        shape = [int(d) for d in dims.split(",") if d]
        moves = _MOVES.fullmatch(op.replace("-start", "").replace(
            "-done", "")) or (op == "fusion" and _MOVES.search(name))
        if moves and np.prod(shape) >= elems:
            found.append(f"{op} {name} {shape}")
    return found


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_moe_programs_read_the_experts_in_place(one_chip, no_compile_cache,
                                                monkeypatch, program):
    """The engine's paged MoE programs at qwen3-moe-30b-a3b widths (two
    layers of 32 held experts of a 128-wide router) hand the grouped
    matmul every layer's stacked expert weights: no copy or slice of a
    layer's experts (32 x 2,048 x 768 float32) is made per call."""
    E, d, F = 32, 2048, 768
    cfg = ModelConfig(name="qwen3-moe-stage", family="moe", num_layers=2,
                      d_model=d, num_heads=32, num_kv_heads=4, head_dim=128,
                      d_ff=F, num_experts=E, num_experts_per_tok=8,
                      moe_router_width=128, qk_norm=True,
                      vocab_size=151_936, tie_embeddings=False,
                      dtype="bfloat16", param_dtype="float32",
                      kv_cache_dtype="bfloat16")
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    jax.clear_caches()
    params = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: T.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    slots, i32 = 128, jnp.int32
    eng = ServingEngine(cfg, params, max_seq_len=1152, max_slots=slots,
                        paged=True, num_blocks=8, prefill_batch=4)
    pool = {name: _spec(one_chip, leaf.shape, leaf.dtype)
            for name, leaf in eng.kv.cache.items()}
    bps = eng.kv.blocks_per_slot
    if program == "decode":
        compiled = eng._step.lower(params, {
            "tokens": _spec(one_chip, (slots, 1), i32),
            "positions": _spec(one_chip, (slots,), i32),
            "valid": _spec(one_chip, (slots,), jnp.bool_),
            "block_tables": _spec(one_chip, (slots, bps), i32)},
            pool).compile()
    else:
        rows = _spec(one_chip, (4,), i32)
        compiled = eng._prefill_paged.lower(
            params, _spec(one_chip, (4, eng.prefill_chunk), i32), rows,
            rows, pool, _spec(one_chip, (4, bps), i32)).compile()
    hlo = compiled.as_text()
    assert "expert_gmm" in hlo
    assert _moves_of(hlo, E * d * F) == []


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
