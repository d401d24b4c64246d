"""Paged attention: Pallas decode kernel over block tables + the paged
serving path (block storage, undersized pools, preemption) — validated
in interpret mode on CPU with the dense engine as the oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ref import attention_ref, paged_attention_ref
from repro.serving import (OutOfBlocks, PagedKVCache, Request,
                           SamplingParams, Scheduler, ServingEngine)


@pytest.fixture(scope="module")
def qwen():
    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    cfg = get_smoke_config("qwen2-0.5b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


LAYERS, LAYER = 3, 1      # a stacked pool; the kernels read its middle layer


def _pages(key, B, KV, G, D, NP, page, pps, dtype=jnp.float32):
    """q and a stacked (LAYERS, NP, page, KV * D) K/V pool of noise."""
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, KV, G, D), dtype)
    kp = jax.random.normal(ks[1], (LAYERS, NP, page, KV * D), dtype)
    vp = jax.random.normal(ks[2], (LAYERS, NP, page, KV * D), dtype)
    tbl = jax.random.randint(ks[3], (B, pps), 0, NP, jnp.int32)
    return q, kp, vp, tbl


# ---------------------------------------------------------------------------
# kernel vs oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,KV,G,D,NP,page,pps,window,softcap", [
    (3, 2, 2, 32, 9, 8, 4, None, None),
    (2, 1, 4, 16, 5, 4, 4, 6, None),
    (4, 2, 1, 64, 17, 16, 3, None, 30.0),
    (1, 1, 1, 8, 2, 4, 2, 3, 10.0),
])
def test_paged_kernel_matches_ref(B, KV, G, D, NP, page, pps, window,
                                  softcap, rng_key):
    q, kp, vp, tbl = _pages(rng_key, B, KV, G, D, NP, page, pps)
    lens = jnp.array([1 + (7 * i) % (pps * page) for i in range(B)],
                     jnp.int32)
    out = paged_attention(q, kp, vp, LAYER, tbl, lens, window=window,
                          softcap=softcap, interpret=True)
    ref = paged_attention_ref(q, kp, vp, LAYER, tbl, lens, window=window,
                              softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
    # the other layers' pages are never read
    other = jnp.zeros_like(kp).at[LAYER].set(kp[LAYER])
    np.testing.assert_array_equal(
        np.asarray(paged_attention(q, other, vp, LAYER, tbl, lens,
                                   window=window, softcap=softcap,
                                   interpret=True)), np.asarray(out))


def test_paged_ref_matches_dense_attention(rng_key):
    """Gathering pages laid out by a permutation table reproduces dense
    contiguous attention exactly: paging changes layout, not math."""
    B, KV, G, D, page, pps = 2, 2, 2, 16, 4, 4
    T = page * pps
    ks = jax.random.split(rng_key, 4)
    q = jax.random.normal(ks[0], (B, KV, G, D))
    k = jax.random.normal(ks[1], (B, T, KV, D))
    v = jax.random.normal(ks[2], (B, T, KV, D))
    # scatter the dense sequences into one layer's pages via a permuted
    # table (head h of position p in lanes [h*D, (h+1)*D) of its row);
    # the other layers hold noise
    perm = np.random.default_rng(0).permutation(B * pps)
    tbl = jnp.asarray(perm.reshape(B, pps), jnp.int32)
    noise = jax.random.normal(ks[3], (LAYERS, B * pps, page, KV * D))
    kp, vp = noise, -noise
    for b in range(B):
        for j in range(pps):
            rows = slice(j * page, (j + 1) * page)
            kp = kp.at[LAYER, perm[b * pps + j]].set(
                k[b, rows].reshape(page, KV * D))
            vp = vp.at[LAYER, perm[b * pps + j]].set(
                v[b, rows].reshape(page, KV * D))
    lens = jnp.array([T, T - 3], jnp.int32)
    out = paged_attention_ref(q, kp, vp, LAYER, tbl, lens)
    # dense oracle: fold (B, KV, G) and attend with the last-row slice
    for b in range(B):
        L = int(lens[b])
        qf = q[b].reshape(KV * G, 1, D)
        kf = jnp.repeat(k[b, :L].transpose(1, 0, 2), G, axis=0)
        vf = jnp.repeat(v[b, :L].transpose(1, 0, 2), G, axis=0)
        # causal with a single query at the LAST position == no mask
        ref = attention_ref(qf, kf, vf, causal=False)
        np.testing.assert_allclose(
            np.asarray(out[b].reshape(KV * G, 1, D)), np.asarray(ref),
            atol=2e-5, rtol=2e-5)


def test_ops_wrapper_gqa_layout(rng_key):
    """Model layout (B, 1, H, D) folds to grouped heads consistently."""
    B, KV, G, D, NP, page, pps = 2, 2, 3, 16, 7, 4, 3
    q, kp, vp, tbl = _pages(rng_key, B, KV, G, D, NP, page, pps)
    lens = jnp.array([5, 11], jnp.int32)
    ref = paged_attention_ref(q, kp, vp, LAYER, tbl, lens)
    qm = q.reshape(B, 1, KV * G, D)
    out = ops.paged_decode_attention(qm, kp, vp, LAYER, tbl, lens,
                                     interpret=True)
    np.testing.assert_allclose(
        np.asarray(out[:, 0].reshape(B, KV, G, D)), np.asarray(ref),
        atol=2e-6, rtol=2e-6)


def test_kernel_ignores_garbage_table_entries(rng_key):
    """Entries past a sequence's length (trash/stale ids, even
    out-of-range) must not change the result."""
    B, KV, G, D, NP, page, pps = 1, 1, 2, 16, 6, 4, 4
    q, kp, vp, tbl = _pages(rng_key, B, KV, G, D, NP, page, pps)
    lens = jnp.array([6], jnp.int32)                   # pages 2, 3 unused
    base = paged_attention(q, kp, vp, LAYER, tbl, lens, interpret=True)
    junk = tbl.at[0, 2].set(99999).at[0, 3].set(-7)
    out = paged_attention(q, kp, vp, LAYER, junk, lens, interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out))


# ---------------------------------------------------------------------------
# paged cache storage
# ---------------------------------------------------------------------------

def test_paged_cache_storage_and_tables(qwen):
    cfg, _ = qwen
    kv = PagedKVCache(cfg, max_slots=2, max_seq_len=32, block_size=8,
                      paged=True, num_blocks=5)
    # storage: every layer's blocks (+1 trash) stacked, one block of
    # positions per block, each position's KV heads side by side
    for leaf in jax.tree.leaves(kv.cache):
        assert leaf.shape == (cfg.num_layers, 6, 8,
                              cfg.num_kv_heads * cfg.resolved_head_dim)
        assert leaf.dtype == jnp.bfloat16
    s = kv.alloc_slot(prompt_len=10)                   # 2 blocks
    tbl = np.asarray(kv.device_block_tables())
    assert list(tbl[s, :2]) == kv.block_table[s]
    assert all(tbl[s, 2:] == kv.trash_block)
    kv.ensure_capacity(s, 17)                          # third block
    tbl = np.asarray(kv.device_block_tables())
    assert list(tbl[s, :3]) == kv.block_table[s]
    kv.free_slot(s)
    assert (np.asarray(kv.device_block_tables()) == kv.trash_block).all()
    assert kv.pool.in_use == 0


def test_paged_pool_smaller_than_worst_case_is_real(qwen):
    cfg, _ = qwen
    kv = PagedKVCache(cfg, max_slots=4, max_seq_len=32, block_size=8,
                      paged=True, num_blocks=3)
    s0 = kv.alloc_slot(prompt_len=16)                  # 2 blocks
    with pytest.raises(OutOfBlocks):
        kv.alloc_slot(prompt_len=16)                   # needs 2, 1 left
    # failed alloc is all-or-nothing: nothing leaked
    assert kv.pool.in_use == 2 and kv.free_slot_count == 3
    s1 = kv.alloc_slot(prompt_len=5)                   # 1 block fits
    with pytest.raises(OutOfBlocks):
        kv.ensure_capacity(s1, 9)                      # pool dry
    kv.free_slot(s0)
    kv.ensure_capacity(s1, 9)                          # recycled
    assert kv.pool.in_use == 2


def test_dense_mode_rejects_num_blocks_knob(qwen):
    cfg, _ = qwen
    with pytest.raises(ValueError):
        PagedKVCache(cfg, max_slots=2, max_seq_len=32, block_size=8,
                     num_blocks=3)


def test_paged_rejects_nonpositional_families():
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("mamba2-1.3b")
    with pytest.raises(ValueError):
        PagedKVCache(cfg, max_slots=2, max_seq_len=32, block_size=8,
                     paged=True)


# ---------------------------------------------------------------------------
# end-to-end: paged engine == dense engine
# ---------------------------------------------------------------------------

def _outputs(qwen, prompts, sps, **kw):
    cfg, params = qwen
    eng = ServingEngine(cfg, params, max_seq_len=64, max_slots=3,
                        kv_block_size=16, **kw)
    sched = Scheduler(eng)
    rids = [sched.submit(Request(p, sp)) for p, sp in zip(prompts, sps)]
    sched.run()
    return [sched.output(r) for r in rids], eng, sched


def test_paged_engine_bit_identical_to_dense(qwen):
    cfg, _ = qwen
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in (5, 12, 3, 20, 7)]
    sps = [SamplingParams(max_new_tokens=m, greedy=True)
           for m in (6, 4, 8, 5, 7)]
    dense, _, _ = _outputs(qwen, prompts, sps, paged=False)
    paged, eng, _ = _outputs(qwen, prompts, sps, paged=True)
    for a, b in zip(dense, paged):
        np.testing.assert_array_equal(a, b)
    assert eng.kv.paged and eng.kv.pool.in_use == 0


def test_undersized_pool_stress_no_drops_no_leaks(qwen):
    """num_blocks far below worst case + mixed prompt lengths + prefix
    cache on: every request completes (none dropped), greedy outputs
    match the dense path bit-for-bit, and at drain every prefix pin has
    been released (the whole tree is evictable)."""
    cfg, params = qwen
    rng = np.random.default_rng(1)
    shared = rng.integers(0, cfg.vocab_size, 9, dtype=np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(0, cfg.vocab_size, n,
                                            dtype=np.int32)])
               for n in (7, 15, 4, 11, 9, 6, 2, 13)]
    sps = [SamplingParams(max_new_tokens=10, greedy=True) for _ in prompts]

    def serve(**kw):
        eng = ServingEngine(cfg, params, max_seq_len=48, max_slots=4,
                            kv_block_size=8, **kw)
        sched = Scheduler(eng)
        rids = [sched.submit(Request(p, sp))
                for p, sp in zip(prompts, sps)]
        sched.run()
        return [sched.output(r) for r in rids], eng, sched

    dense, _, _ = serve(paged=False)
    # worst case would be 4 slots * 6 blocks = 24; give it 7
    paged, eng, sched = serve(paged=True, num_blocks=7,
                              prefix_cache_blocks=8)
    assert len(paged) == len(prompts)                  # nobody dropped
    for a, b in zip(dense, paged):
        np.testing.assert_array_equal(a, b)
    # the pool actually ran dry and the scheduler coped
    assert sched.preemptions + sched.admission_stalls > 0
    assert eng.kv.pool.high_water == 7
    # drain state: no KV blocks held, no leaked prefix pins — with every
    # request retired the full radix tree must be evictable
    assert eng.kv.pool.in_use == 0
    eng.prefix_cache.evict(10 ** 9)
    assert eng.kv.prefix_pool.in_use == 0


def test_paged_pool_holds_more_sequences_than_dense_at_equal_kv_bytes(qwen):
    """The claim paging exists for: at the same physical KV bytes (the
    dense stripes equal the paged blocks, trash block included), block
    tables serve strictly more concurrent sequences than the dense
    worst-case layout, with bit-identical greedy outputs."""
    cfg, params = qwen
    block, max_seq_len, dense_slots, paged_slots = 16, 96, 3, 9
    num_blocks = dense_slots * (max_seq_len // block) - 1
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, 3, dtype=np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(0, cfg.vocab_size,
                                            4 + (i * 3) % 13,
                                            dtype=np.int32)])
               for i in range(12)]

    def serve(**kw):
        eng = ServingEngine(cfg, params, max_seq_len=max_seq_len,
                            kv_block_size=block, **kw)
        sched = Scheduler(eng)
        rids = [sched.submit(Request(p, SamplingParams(max_new_tokens=6,
                                                       greedy=True)))
                for p in prompts]
        peak = 0
        while sched.has_work:
            sched.step()
            peak = max(peak, len(sched.active))
        return [sched.output(r) for r in rids], peak, eng

    dense, dense_peak, dense_eng = serve(max_slots=dense_slots)
    paged, paged_peak, paged_eng = serve(max_slots=paged_slots, paged=True,
                                         num_blocks=num_blocks)
    assert paged_eng.kv.kv_bytes() == dense_eng.kv.kv_bytes()
    assert paged_peak > dense_peak, (paged_peak, dense_peak)
    for a, b in zip(dense, paged):
        np.testing.assert_array_equal(a, b)


def test_preempted_request_resumes_correctly(qwen):
    """Force a decode-time preemption and check the deferred request's
    final output still matches its solo greedy run."""
    cfg, params = qwen
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in (14, 14)]
    sps = [SamplingParams(max_new_tokens=12, greedy=True)] * 2
    solo = [_outputs(qwen, [p], [sp], paged=False)[0][0]
            for p, sp in zip(prompts, sps)]

    cfgp = dict(paged=True, num_blocks=4, kv_block_size=8)
    eng = ServingEngine(cfg, params, max_seq_len=32, max_slots=2, **cfgp)
    sched = Scheduler(eng)
    rids = [sched.submit(Request(p, sp)) for p, sp in zip(prompts, sps)]
    sched.run()
    assert sched.preemptions > 0                       # really preempted
    for rid, ref in zip(rids, solo):
        np.testing.assert_array_equal(sched.output(rid), ref)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "gemma2-2b"])
def test_paged_in_place_bit_identical_under_hits_preemption_interleaving(
        arch):
    """The pool updated in place through prefix hits, decode-time
    preemption and budgeted prefill interleaving serves the dense
    layout's greedy tokens bit for bit — for a qwen2-like stack and a
    gemma2-like one (local/global pattern of 2 over two scan groups, so
    layer l = 2 * group + i; sliding window; logit softcaps)."""
    import dataclasses

    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    cfg = get_smoke_config(arch)
    if cfg.local_global_pattern:
        cfg = dataclasses.replace(cfg, num_layers=4)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    shared = rng.integers(0, cfg.vocab_size, 16, dtype=np.int32)
    prompts = [np.concatenate([shared, rng.integers(
        0, cfg.vocab_size, n, dtype=np.int32)]) for n in (5, 11, 2, 14, 7, 9)]
    sps = [SamplingParams(max_new_tokens=m, greedy=True)
           for m in (12, 9, 14, 6, 10, 8)]

    def serve(budget=None, **kw):
        # raw argmax: bit-identity is the property pinned here
        eng = ServingEngine(cfg, params, max_seq_len=48, max_slots=3,
                            kv_block_size=8, prefill_chunk=8,
                            prefill_batch=2, greedy_tie_eps=0.0, **kw)
        sched = Scheduler(eng, prefill_token_budget=budget)
        rids = [sched.submit(Request(p, sp)) for p, sp in zip(prompts, sps)]
        interleaved = 0
        while sched.has_work:
            sched.step()
            interleaved += bool(sched.prefilling and sched.active)
        return [sched.output(r) for r in rids], eng, sched, interleaved

    dense = serve(paged=False)[0]
    # 8 blocks for 3 slots of up to 6: decode growth runs the pool dry
    paged, eng, sched, interleaved = serve(
        budget=8, paged=True, num_blocks=8, prefix_cache_blocks=8)
    for a, b in zip(dense, paged):
        np.testing.assert_array_equal(a, b)
    assert eng.cached_prefix_tokens > 0                # prefix hits
    assert sched.preemptions > 0                       # really preempted
    assert interleaved > 0                             # decode mid-prefill
    assert eng.kv.pool.in_use == 0


def test_paged_programs_donate_the_pool_and_keep_the_tables(qwen):
    """decode_once and a prefill round take the pool donated: the buffer
    they were given is deleted, not kept alive beside the new one, while
    the engine's cached device block tables (plain and masked) are not
    donated and stay usable across both programs."""
    cfg, params = qwen
    eng = ServingEngine(cfg, params, max_seq_len=64, max_slots=3,
                        kv_block_size=16, paged=True)
    rng = np.random.default_rng(4)
    long, short = (rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
                   for n in (20, 9))
    pos = np.zeros(eng.max_slots, np.int32)
    toks = np.zeros(eng.max_slots, np.int32)

    def donates(run):
        before = jax.tree.leaves(eng.kv.cache)
        out = run()
        assert all(leaf.is_deleted() for leaf in before)
        assert not any(leaf.is_deleted()
                       for leaf in jax.tree.leaves(eng.kv.cache))
        return out

    cursors = eng.begin_prefill([long, short])
    done = donates(lambda: eng.advance_prefill(cursors, token_budget=1))
    assert [c.slot for c in done] == [cursors[1].slot]       # long in flight

    # decode with the in-flight slot masked: the masked upload is cached
    masked = eng.kv.device_block_tables(mask_slots=eng._inflight)
    pos[cursors[1].slot] = len(short)
    donates(lambda: eng.decode_once(toks, pos))
    assert not masked.is_deleted()
    assert eng.kv.device_block_tables(mask_slots=eng._inflight) is masked

    # finish the long prompt, then decode with the plain tables
    donates(eng.advance_prefill)
    tables = eng.kv.device_block_tables()
    pos[cursors[0].slot] = len(long)
    logits = donates(lambda: eng.decode_once(toks, pos))
    assert not tables.is_deleted() and not masked.is_deleted()
    assert eng.kv.device_block_tables() is tables
    np.testing.assert_array_equal(np.asarray(tables), eng.kv._tables)
    assert np.isfinite(np.asarray(logits)).all()
