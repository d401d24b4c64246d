"""Paged chunked prefill: the Pallas prefill kernel over block tables +
batched multi-slot co-admission — validated in interpret mode on CPU
with the dense engine / whole-prompt scan as oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.paged_prefill import paged_prefill
from repro.kernels.ref import attention_ref, paged_prefill_ref
from repro.serving import (Request, SamplingParams, Scheduler, ServingEngine)


@pytest.fixture(scope="module")
def qwen():
    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    cfg = get_smoke_config("qwen2-0.5b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


LAYERS, LAYER = 3, 1      # a stacked pool; the kernels read its middle layer


def _pages(key, B, C, KV, G, D, NP, page, pps, dtype=jnp.float32):
    """q and a stacked (LAYERS, NP, page, KV * D) K/V pool of noise."""
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, C, KV, G, D), dtype)
    kp = jax.random.normal(ks[1], (LAYERS, NP, page, KV * D), dtype)
    vp = jax.random.normal(ks[2], (LAYERS, NP, page, KV * D), dtype)
    tbl = jax.random.randint(ks[3], (B, pps), 0, NP, jnp.int32)
    return q, kp, vp, tbl


# ---------------------------------------------------------------------------
# kernel vs oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,C,KV,G,D,NP,page,pps,window,softcap", [
    (3, 5, 2, 2, 32, 9, 8, 4, None, None),      # GQA, odd chunk
    (2, 4, 1, 4, 16, 5, 4, 4, 6, None),         # sliding window
    (4, 7, 2, 1, 64, 17, 16, 3, None, 30.0),    # softcap, partial tail
    (1, 3, 1, 1, 8, 2, 4, 2, 3, 10.0),          # window + softcap
])
def test_prefill_kernel_matches_ref(B, C, KV, G, D, NP, page, pps, window,
                                    softcap, rng_key):
    q, kp, vp, tbl = _pages(rng_key, B, C, KV, G, D, NP, page, pps)
    T_ = pps * page
    # starts land mid-page; q_lens include partial (and empty) rows
    start = jnp.array([(5 * b + 3) % (T_ - C) for b in range(B)], jnp.int32)
    qlens = jnp.array([max(0, C - b) for b in range(B)], jnp.int32)
    qf = q.transpose(0, 2, 1, 3, 4).reshape(B, KV, C * G, D)
    out = paged_prefill(qf, kp, vp, LAYER, tbl, start, qlens, group=G,
                        window=window, softcap=softcap, interpret=True)
    ref = paged_prefill_ref(q, kp, vp, LAYER, tbl, start, qlens,
                            window=window, softcap=softcap)
    np.testing.assert_allclose(
        np.asarray(out.reshape(B, KV, C, G, D).transpose(0, 2, 1, 3, 4)),
        np.asarray(ref), atol=2e-6, rtol=2e-6)
    # the other layers' pages are never read
    other = jnp.zeros_like(vp).at[LAYER].set(vp[LAYER])
    np.testing.assert_array_equal(
        np.asarray(paged_prefill(qf, kp, other, LAYER, tbl, start, qlens,
                                 group=G, window=window, softcap=softcap,
                                 interpret=True)), np.asarray(out))


def test_prefill_ref_matches_dense_attention(rng_key):
    """Pages laid out by a permutation table reproduce dense contiguous
    causal attention for a mid-sequence query chunk: paging changes
    layout, not math."""
    B, C, KV, G, D, page, pps = 2, 4, 2, 2, 16, 4, 4
    T_ = page * pps
    ks = jax.random.split(rng_key, 4)
    q = jax.random.normal(ks[0], (B, C, KV, G, D))
    k = jax.random.normal(ks[1], (B, T_, KV, D))
    v = jax.random.normal(ks[2], (B, T_, KV, D))
    # one layer's pages (head h of position p in lanes [h*D, (h+1)*D)
    # of its row); the other layers hold noise
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
    start = jnp.array([6, 9], jnp.int32)
    qlens = jnp.array([C, C], jnp.int32)
    out = paged_prefill_ref(q, kp, vp, LAYER, tbl, start, qlens)
    for b in range(B):
        s0 = int(start[b])
        L = s0 + C                           # newest attended position + 1
        # fold heads; causal over absolute positions == causal mask on a
        # q chunk placed at the END of the first L keys
        qf = q[b].transpose(1, 2, 0, 3).reshape(KV * G, C, D)
        kf = jnp.repeat(k[b, :L].transpose(1, 0, 2), G, axis=0)
        vf = jnp.repeat(v[b, :L].transpose(1, 0, 2), G, axis=0)
        # attention_ref's causal mask is qpos >= kpos with qpos = row
        # index; shift by padding the q chunk's positions via window-less
        # manual mask instead: compute dense scores directly
        s = jnp.einsum("hqd,hkd->hqk", qf.astype(jnp.float32),
                       kf.astype(jnp.float32)) / np.sqrt(D)
        mask = (jnp.arange(L)[None, :] <= (s0 + jnp.arange(C))[:, None])
        s = jnp.where(mask[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        refb = jnp.einsum("hqk,hkd->hqd", p, vf.astype(jnp.float32))
        np.testing.assert_allclose(
            np.asarray(out[b].transpose(1, 2, 0, 3).reshape(KV * G, C, D)),
            np.asarray(refb), atol=2e-5, rtol=2e-5)


def test_ops_wrapper_gqa_layout(rng_key):
    """Model layout (B, C, H, D) folds to grouped chunk rows
    consistently."""
    B, C, KV, G, D, NP, page, pps = 2, 3, 2, 3, 16, 7, 4, 3
    q, kp, vp, tbl = _pages(rng_key, B, C, KV, G, D, NP, page, pps)
    start = jnp.array([2, 7], jnp.int32)
    qlens = jnp.array([3, 2], jnp.int32)
    ref = paged_prefill_ref(q, kp, vp, LAYER, tbl, start, qlens)
    qm = q.reshape(B, C, KV * G, D)
    out = ops.paged_prefill_attention(qm, kp, vp, LAYER, tbl, start, qlens,
                                      interpret=True)
    np.testing.assert_allclose(
        np.asarray(out.reshape(B, C, KV, G, D)), np.asarray(ref),
        atol=2e-6, rtol=2e-6)


def test_kernel_skips_padding_rows_and_garbage_tables(rng_key):
    """q_len = 0 rows return zeros whatever their table holds, and table
    entries past a row's extent (even out-of-range ids) don't change the
    result."""
    B, C, KV, G, D, NP, page, pps = 2, 4, 1, 2, 16, 6, 4, 4
    q, kp, vp, tbl = _pages(rng_key, B, C, KV, G, D, NP, page, pps)
    start = jnp.array([2, 0], jnp.int32)
    qlens = jnp.array([4, 0], jnp.int32)
    qf = q.transpose(0, 2, 1, 3, 4).reshape(B, KV, C * G, D)
    base = paged_prefill(qf, kp, vp, LAYER, tbl, start, qlens, group=G,
                         interpret=True)
    assert not np.asarray(base[1]).any()               # padding row: zeros
    junk = tbl.at[0, 3].set(99999).at[1, 0].set(-5)    # past row 0's extent
    out = paged_prefill(qf, kp, vp, LAYER, junk, start, qlens, group=G,
                        interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out))


# ---------------------------------------------------------------------------
# chunked prefill vs one-shot oracle (dense AND paged, with resume)
# ---------------------------------------------------------------------------

def _oneshot_last_logits(eng, prompt):
    """The whole-prompt scan — the pre-chunking reference prefill."""
    from repro.models import transformer as T
    cache = T.init_cache(eng.cfg, 1, eng.max_seq_len)
    _, _, ref = eng._prefill(eng.params, jnp.asarray(prompt)[None],
                             cache, None)
    return np.asarray(ref[0])


@pytest.mark.parametrize("chunk", [1, 5, 16])
@pytest.mark.parametrize("paged", [False, True])
def test_chunked_prefill_matches_oneshot(qwen, chunk, paged):
    """Odd prompt lengths x chunk sizes x start_pos resume offsets: the
    chunked path (dense scan or paged kernel) reproduces the one-shot
    prefill — greedy-identical first tokens, near-identical logits."""
    cfg, params = qwen
    eng = ServingEngine(cfg, params, max_seq_len=64, max_slots=2,
                        kv_block_size=8, prefill_chunk=chunk, paged=paged,
                        prefix_cache_blocks=16)
    pc = eng.prefix_cache
    for plen in (3, 7, 17, 29):
        prompt = ((np.arange(plen) * 5 + 2) % cfg.vocab_size).astype(np.int32)
        slot, last = eng.prefill_into_slot(prompt)
        ref = _oneshot_last_logits(eng, prompt)
        assert int(np.argmax(last)) == int(np.argmax(ref))
        np.testing.assert_allclose(last, ref, atol=3e-2, rtol=3e-2)
        # start_pos resume: insert this prompt, then prefill a sibling
        # sharing all but the final token (resume offset = cached match)
        pc.insert(prompt, slot)
        sib = np.concatenate(
            [prompt[:plen - 1],
             [(int(prompt[-1]) + 1) % cfg.vocab_size, 3, 9]]
        ).astype(np.int32)
        cached, blocks = pc.lookup(sib)
        assert cached > 0
        slot2, last2 = eng.prefill_into_slot(sib, start_pos=cached,
                                             prefix_blocks=blocks)
        ref2 = _oneshot_last_logits(eng, sib)
        assert int(np.argmax(last2)) == int(np.argmax(ref2))
        np.testing.assert_allclose(last2, ref2, atol=3e-2, rtol=3e-2)
        pc.release(blocks)
        eng.free_slot(slot)
        eng.free_slot(slot2)


@pytest.mark.parametrize("chunk", [5, 16])
def test_generate_greedy_bit_identical_dense_vs_paged_vs_serial(qwen, chunk):
    """End-to-end greedy outputs are bit-identical across the dense
    layout, batched paged co-admission, and one-row paged prefill
    (``prefill_batch=1``)."""
    cfg, params = qwen
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in (5, 13, 3, 21, 7, 9)]
    sps = [SamplingParams(max_new_tokens=m, greedy=True)
           for m in (6, 4, 7, 3, 5, 6)]

    def serve(paged, prefill_batch):
        eng = ServingEngine(cfg, params, max_seq_len=32, max_slots=4,
                            kv_block_size=8, prefill_chunk=chunk,
                            paged=paged, prefill_batch=prefill_batch)
        sched = Scheduler(eng)
        rids = [sched.submit(Request(p, sp))
                for p, sp in zip(prompts, sps)]
        sched.run()
        return [sched.output(r) for r in rids]

    dense = serve(False, 4)
    batched = serve(True, 4)
    serial = serve(True, 1)
    for a, b, c in zip(dense, batched, serial):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


@pytest.mark.parametrize("arch", ["gemma2-2b", "qwen3-moe-30b-a3b"])
def test_paged_prefill_window_softcap_families(arch):
    """gemma2 (sliding window + logit softcaps + local/global pattern)
    and MoE route through the paged-prefill kernel bit-identically."""
    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    cfg = get_smoke_config(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in (5, 11, 17)]
    sps = [SamplingParams(max_new_tokens=4, greedy=True)] * 3

    def serve(paged):
        # raw argmax (eps=0): softcaps compress the logit spectrum, so a
        # 1e-2 tie set puts tokens at its boundary where dense/paged
        # summation noise flips membership — the bit-identity this test
        # pins is the stronger property for these workloads
        eng = ServingEngine(cfg, params, max_seq_len=32, max_slots=3,
                            kv_block_size=8, paged=paged,
                            greedy_tie_eps=0.0)
        sched = Scheduler(eng)
        rids = [sched.submit(Request(p, sp))
                for p, sp in zip(prompts, sps)]
        sched.run()
        return [sched.output(r) for r in rids]

    for a, b in zip(serve(False), serve(True)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# no dense stripe / telemetry
# ---------------------------------------------------------------------------

def test_paged_prefill_allocates_no_dense_stripe(qwen, monkeypatch):
    """Acceptance: a paged prefill of a max_seq_len-length prompt never
    materializes the dense batch-1 stripe — T.init_cache is not called,
    the transient-bytes telemetry stays zero, and the resident KV bytes
    are exactly the preallocated pool blocks."""
    import repro.models.transformer as T
    cfg, params = qwen
    eng = ServingEngine(cfg, params, max_seq_len=64, max_slots=2,
                        kv_block_size=16, paged=True)
    pool_bytes = eng.kv.kv_bytes()

    def boom(*a, **k):
        raise AssertionError("dense stripe allocated during paged prefill")

    monkeypatch.setattr(T, "init_cache", boom)
    prompt = (np.arange(64, dtype=np.int32) * 3 + 1) % cfg.vocab_size
    slot, last = eng.prefill_into_slot(prompt.astype(np.int32))
    assert last is not None and last.shape == (cfg.vocab_size,)
    assert eng.transient_prefill_bytes == 0
    assert eng.kv.kv_bytes() == pool_bytes   # pool blocks only, no stripe
    eng.free_slot(slot)


def test_dense_prefill_allocates_a_transient_stripe(qwen):
    """The contrast to the paged case above: the dense layout prefills
    the same prompt through a batch-1 staging cache, and the
    transient-bytes telemetry records it."""
    cfg, params = qwen
    eng = ServingEngine(cfg, params, max_seq_len=64, max_slots=2,
                        kv_block_size=16)
    prompt = (np.arange(64, dtype=np.int32) * 3 + 1) % cfg.vocab_size
    slot, last = eng.prefill_into_slot(prompt.astype(np.int32))
    assert last is not None and last.shape == (cfg.vocab_size,)
    assert eng.transient_prefill_bytes > 0
    eng.free_slot(slot)


def test_prefill_padding_accounting(qwen):
    """real vs executed vs padding: one wave of the compiled (Bp, C)
    program runs rounds * C * Bp token positions; the split shows up in
    the engine counters and the metrics summary."""
    cfg, params = qwen
    eng = ServingEngine(cfg, params, max_seq_len=32, max_slots=4,
                        kv_block_size=8, prefill_chunk=16, paged=True,
                        prefill_batch=4)
    sched = Scheduler(eng)
    for n in (7, 9, 20):
        sched.submit(Request(
            ((np.arange(n) * 7 + 1) % cfg.vocab_size).astype(np.int32),
            SamplingParams(max_new_tokens=1, greedy=True)))
    sched.run()
    # one wave, rounds = ceil(20/16) = 2 -> 2 * 16 * 4 = 128 executed
    assert eng.prefill_tokens == 36
    assert eng.prefill_tokens_executed == 128
    assert eng.prefill_tokens_padding == 92
    s = sched.metrics.summary()["prefill_tokens"]
    assert s == {"real": 36, "executed": 128, "padding": 92,
                 "padding_fraction": 92 / 128}


def test_decode_once_keeps_logits_on_device(qwen):
    """The decode-step logits stay device-resident; the host transfer is
    deferred to sample_tokens (one sync per step, not two)."""
    cfg, params = qwen
    eng = ServingEngine(cfg, params, max_seq_len=32, max_slots=2,
                        kv_block_size=8)
    slot, _ = eng.prefill_into_slot(np.array([1, 2, 3], np.int32))
    logits = eng.decode_once(np.zeros(2, np.int32),
                             np.array([3, 0], np.int32))
    assert isinstance(logits, jax.Array)
    toks = eng.sample_tokens(logits, np.zeros(2, np.float32),
                             np.ones(2, bool))
    assert toks.shape == (2,) and toks.dtype.kind == "i"
    eng.free_slot(slot)


def test_first_token_retire_with_queue_waiting_is_not_deadlock(qwen):
    """Regression: with 2 slots and 3 requests that retire at their
    first sampled token, a step can leave no active sequence while the
    queue is non-empty — a round that made progress, not an admission
    deadlock."""
    cfg, params = qwen
    eng = ServingEngine(cfg, params, max_seq_len=32, max_slots=2,
                        kv_block_size=8)
    sched = Scheduler(eng)
    rids = [sched.submit(Request(np.array([1 + i, 2, 3], np.int32),
                                 SamplingParams(max_new_tokens=1,
                                                greedy=True)))
            for i in range(3)]
    sched.run()                              # used to raise RuntimeError
    for r in rids:
        assert len(sched.output(r)) == 1


def test_prefill_into_slots_all_or_nothing(qwen):
    """A co-admission batch that cannot fully allocate releases every
    slot it claimed before OutOfBlocks propagates."""
    from repro.serving import OutOfBlocks
    cfg, params = qwen
    eng = ServingEngine(cfg, params, max_seq_len=32, max_slots=4,
                        kv_block_size=8, paged=True, num_blocks=3)
    prompts = [np.arange(1, 9, dtype=np.int32),      # 1 block
               np.arange(1, 17, dtype=np.int32),     # 2 blocks
               np.arange(1, 10, dtype=np.int32)]     # 2 blocks -> dry
    with pytest.raises(OutOfBlocks):
        eng.prefill_into_slots(prompts)
    assert eng.kv.pool.in_use == 0
    assert eng.kv.free_slot_count == 4
