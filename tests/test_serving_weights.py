"""The serving engine holds the weights that every program casts to the
compute dtype already in it (``transformer.serving_params``).

The programs then compute the same numbers from the held tree as from
the float32 masters: the paged decode and paged prefill programs give
bitwise-equal logits and write a bitwise-equal KV pool, on the smoke
configurations of a dense model with QKV bias and tied embeddings, gemma2
(sliding window, softcaps, post-block norms, scaled embeddings), a dense
model with an untied head, and qwen3-moe (q/k norm, routed experts).
The engine's weight counters report the bytes it holds in each dtype."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import transformer as T
from repro.serving import ReplicaGateway, ServingEngine

ATTN = {f"layers/attn/{m}/w" for m in ("wq", "wk", "wv", "wo")}
BIAS = {f"layers/attn/{m}/b" for m in ("wq", "wk", "wv")}
MLP = {f"layers/mlp/{m}/w" for m in ("wi", "wg", "wo")}

# arch -> the leaves held in the compute dtype; every other leaf is f32
HELD = {
    "qwen2-0.5b": ATTN | BIAS | MLP | {"embed/table"},
    "gemma2-2b": ATTN | MLP | {"embed/table"},
    "deepseek-coder-33b": ATTN | MLP | {"embed/table", "lm_head/w"},
    "qwen3-moe-30b-a3b": ATTN | {"embed/table", "lm_head/w"},
}
SLOTS, MAX_SEQ, PAGE, ROWS, CHUNK = 4, 64, 8, 4, 8


def _leaves(tree):
    return {"/".join(str(getattr(k, "key", k)) for k in path): a
            for path, a in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.fixture(scope="module", params=sorted(HELD))
def engine(request):
    cfg = get_smoke_config(request.param)
    params = T.init_params(cfg, jax.random.PRNGKey(7))
    # every leaf moved off its init (zero biases, zero norm gains), so a
    # leaf cast that changed a value would change the logits
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(8), len(leaves))
    params = tree.unflatten([a + 0.05 * jax.random.normal(k, a.shape, a.dtype)
                             for a, k in zip(leaves, keys)])
    eng = ServingEngine(cfg, params, max_seq_len=MAX_SEQ, max_slots=SLOTS,
                        kv_block_size=PAGE, prefill_chunk=CHUNK,
                        prefill_batch=ROWS, paged=True)
    return request.param, cfg, params, eng


def test_held_tree_dtypes(engine):
    arch, cfg, params, eng = engine
    held = _leaves(eng.params)
    assert set(held) == set(_leaves(params))
    in_compute = {p for p, a in held.items() if a.dtype == jnp.dtype(cfg.dtype)}
    assert in_compute == HELD[arch]
    for p, a in held.items():
        if p not in HELD[arch]:
            assert a.dtype == jnp.float32, p
    # norm gains, the router and the expert stacks stay float32
    f32 = [p for p in held if p.endswith("scale") or "/moe/" in p]
    assert f32 and all(held[p].dtype == jnp.float32 for p in f32)
    if cfg.family == "moe":
        assert {"layers/moe/router/w", "layers/moe/wi", "layers/moe/wg",
                "layers/moe/wo", "layers/attn/q_norm/scale"} <= set(f32)


def _pool(eng, seed):
    """A pool of nonzero bf16 history, fresh (both programs donate it)."""
    rng = np.random.default_rng(seed)
    return {n: jnp.asarray(rng.normal(size=a.shape).astype(np.float32),
                           jnp.bfloat16)
            for n, a in eng.kv.cache.items()}


def _tables(eng, rows):
    bps = eng.kv.blocks_per_slot
    return jnp.asarray(np.arange(rows * bps, dtype=np.int32).reshape(rows, bps))


def _assert_same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_paged_prefill_is_bitwise_identical(engine):
    arch, cfg, params, eng = engine
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (ROWS, CHUNK)),
                         jnp.int32)
    starts = jnp.asarray([0, 5, 16, 3], jnp.int32)
    q_lens = jnp.asarray([CHUNK, 7, CHUNK, 0], jnp.int32)
    out = [eng._prefill_paged(p, tokens, starts, q_lens, _pool(eng, 2),
                              _tables(eng, ROWS))
           for p in (params, eng.params)]
    _assert_same(out[0], out[1])


def test_paged_decode_is_bitwise_identical(engine):
    arch, cfg, params, eng = engine
    rng = np.random.default_rng(3)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                                (SLOTS, 1)), jnp.int32),
             "positions": jnp.asarray([0, 9, 30, 63], jnp.int32),
             "block_tables": _tables(eng, SLOTS)}
    if cfg.family == "moe":
        batch["valid"] = jnp.asarray([True, True, False, True])
    out = [eng._step(p, batch, _pool(eng, 4)) for p in (params, eng.params)]
    _assert_same(out[0], out[1])


def _bytes_by_dtype(tree):
    out = {}
    for a in jax.tree.leaves(tree):
        out[a.dtype.name] = out.get(a.dtype.name, 0) + a.nbytes
    return out


def test_weight_counters_report_the_held_bytes():
    cfg = get_smoke_config("qwen2-0.5b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    engines = [ServingEngine(cfg, params, max_seq_len=MAX_SEQ, max_slots=2,
                             kv_block_size=PAGE, paged=True)
               for _ in range(2)]
    held = _bytes_by_dtype(engines[0].params)
    assert set(held) == {"bfloat16", "float32"}
    f32 = _bytes_by_dtype(params)["float32"]
    # the compute-dtype leaves take half their float32 bytes
    assert held["float32"] + 2 * held["bfloat16"] == f32
    gw = ReplicaGateway.from_engines(engines)
    stats = gw.stats()
    for rep in gw.replicas:
        assert rep.scheduler.metrics.summary()["weights"] == held
        assert stats["replicas"][rep.name]["weights"] == held
    assert stats["totals"]["weights"] == {k: 2 * v for k, v in held.items()}
