"""Batched serving engine: slot-granular prefill/decode primitives.

The serving counterpart of the deployment story: the same capsule image
serves a model with continuously batched requests.  The engine owns the
pooled decode cache (a :class:`~repro.serving.kvcache.PagedKVCache` over
``max_slots`` sequences) and exposes the primitives the scheduler drives:

* ``prefill_into_slots`` — co-prefill a *batch* of prompts, one
  fixed-shape chunked program per round.  In paged mode every chunk's
  K/V is written **straight into pool blocks** through the slots' block
  tables (the Pallas paged-prefill kernel gathers the history back out),
  so paged prefill never allocates the transient dense ``max_seq_len``
  batch-1 stripe the old path scattered from.  Prompts are length-sorted
  into waves of ``prefill_batch`` rows so similar suffix lengths share
  rounds; rows whose prompt ran out ride along as ``q_len = 0`` padding
  the kernel skips at page granularity.  Per-row ``start_pos`` resumes
  from a cached prefix (block-to-block loads from the prefix store) and
  each row's last *real* token's logits are extracted for the first
  sample.  Dense mode serves the same interface through the original
  batch-1 ``lax.scan`` chunk replay (the correctness oracle).
* ``begin_prefill`` / ``advance_prefill`` / ``cancel_prefill`` — the
  *resumable* form of the same work, the substrate of SplitFuse-style
  prefill/decode interleaving.  ``begin_prefill`` claims slots (and
  prefix blocks) and registers one :class:`PrefillCursor` per prompt on
  the engine; ``advance_prefill`` runs chunk rounds against the
  in-flight cursors under a *token budget* (executed token positions,
  the FLOPs proxy) and returns the cursors that completed, each with
  its last real token's logits; cursors that did not finish stay parked
  on the engine — their prefill state (position cursor, and in dense
  mode the staging cache) persists **between scheduler steps**, so a
  decode step for every running sequence can run in between.
  ``cancel_prefill`` abandons a partially-prefilled slot (preemption).
* ``prefill_into_slot`` — single-prompt compatibility wrapper.
* ``decode_once`` — one token for every slot against the pooled cache;
  while cursors are in flight their slots' block-table rows are masked
  to the trash block, so the dummy decode rows of mid-prefill slots can
  never corrupt the KV the prefill already wrote;
  the dense ``serve_step`` is the exact program the decode dry-run
  shapes lower; the paged one takes the pool as its own, donated
  argument and updates it in place.  Logits stay **on device**; the
  host transfer is deferred to ``sample_tokens`` so each decode step
  costs one sync, not two.

Sampling is vectorized per slot (``sample_tokens``): each row gets its own
temperature / greedy flag, fixing the seed bug where ``requests[0].params``
was applied to the whole batch.  ``generate()`` survives as a thin
compatibility wrapper that routes through the continuous-batching
scheduler.

Telemetry: ``prefill_tokens`` counts real prompt tokens,
``prefill_tokens_executed`` counts every token position the compiled
prefill programs actually ran (chunk padding and dummy batch rows
included — the FLOPs proxy), and ``prefill_tokens_padding`` is their
difference.  ``transient_prefill_bytes`` records the peak size of any
batch-1 staging cache a prefill allocated: nonzero for the dense path,
**always zero in paged mode** — the assertion behind the no-dense-stripe
guarantee.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.serving.kvcache import PagedKVCache
from repro.serving.tracing import Span, span


@dataclass
class SamplingParams:
    temperature: float = 1.0
    greedy: bool = False
    max_new_tokens: int = 32
    eos_token: Optional[int] = None      # early-exit on this token id


@dataclass
class Request:
    prompt: np.ndarray                       # (prompt_len,) int32
    params: SamplingParams = field(default_factory=SamplingParams)
    # enc-dec (whisper): precomputed frame embeddings (enc_seq, d_model);
    # the engine runs the encoder once at prefill
    encoder_input: Optional[np.ndarray] = None
    # SLO tenant label: threaded submit -> scheduler -> metrics so
    # mixed-SLA traffic gets per-tenant percentiles (serving/slo.py)
    tenant: str = "default"


@dataclass
class PrefillCursor:
    """Progress of one in-flight (resumable) prefill.

    ``tokens`` is the full target sequence, ``start_pos`` the
    prefix-cache resume offset, and ``pos`` the next position to
    execute: ``start_pos <= pos <= len(tokens)``.  ``seq`` is the
    begin-order stamp advance rounds are scheduled by (FIFO — no
    admission can be starved by a stream of later, shorter ones).
    ``last_logits`` is set (device-resident) once the row's last real
    token has run.  In dense mode ``dense_cache`` carries the batch-1
    staging cache across ``advance_prefill`` calls — the state that
    makes mid-prompt suspension possible; it materializes lazily at the
    cursor's first chunk (so co-admitted prompts waiting their turn
    hold no stripe) and ``prefix_blocks`` keeps the pinned block ids
    until then.  Paged mode needs neither: chunks land straight in pool
    blocks, which persist by construction."""
    slot: int
    tokens: np.ndarray
    start_pos: int
    pos: int
    seq: int = 0
    encoder_input: Optional[np.ndarray] = None
    prefix_blocks: Tuple[int, ...] = ()
    dense_cache: object = None
    enc1: object = None
    last_logits: object = None

    @property
    def remaining(self) -> int:
        return len(self.tokens) - self.pos

    @property
    def done(self) -> bool:
        return self.pos >= len(self.tokens)


def make_serve_step(cfg, *, long_context: bool = False):
    """serve_step(params, batch) -> (logits, new_cache); batch carries
    tokens (B,1), positions (B,), cache (and encoder_output / mrope)."""
    def serve_step(params, batch):
        return T.decode_step(params, cfg, batch, long_context=long_context)
    return serve_step


def make_paged_serve_step(cfg):
    """serve_step(params, batch, pool) -> (logits, new_pool, expert
    counts): the paged decode program, a one-token ``T.prefill_step``
    chunk per row.  batch carries tokens (B,1), positions (B,) and
    block_tables (and ``valid`` (B,) for an MoE model); the pool rides
    alone so that it, and only it, can be donated — the engine's cached
    device block tables stay alive."""
    def serve_step(params, batch, pool):
        return T.prefill_step(params, cfg, {**batch, "cache": pool})
    return serve_step


class ServingEngine:
    """Fixed-slot batched engine (continuous batching over ``max_slots``).

    ``prefix_cache_blocks > 0`` turns on the prefix-cache subsystem (see
    :mod:`repro.serving.prefix_cache`): the paged cache grows a prefix
    store of that many KV blocks and ``self.prefix_cache`` holds the
    radix index the scheduler probes at admission.  Families whose decode
    cache is not positional (SSM/hybrid state) or whose KV depends on
    more than the token ids (enc-dec) silently leave it disabled.

    ``paged=True`` switches the decode cache to physical block storage
    gathered through per-slot block tables by the Pallas paged-attention
    kernel; ``num_blocks`` then sizes the KV pool (default: worst case),
    and sizing it *below* ``max_slots * ceil(max_seq_len/block_size)``
    makes ``OutOfBlocks`` a real event the scheduler handles by deferring
    admissions and preempting decode — the memory-oversubscription mode
    that lets one replica serve more concurrent sequences than the dense
    layout at the same KV budget.  Requires a positional, non-int8
    attention cache (dense / MoE / VLM families).

    ``device`` places the engine's parameters and KV pool on one device
    (several one-chip replicas in one process); every program the engine
    runs then follows them there.  ``None`` keeps JAX's default device.

    ``self.params`` holds the weights that every program casts to
    ``cfg.dtype`` already in it (:func:`T.serving_params`), so no program
    call casts them; ``weight_bytes`` gives the bytes held in each dtype,
    which the scheduler's metrics report under ``"weights"``.
    """

    def __init__(self, cfg, params, max_seq_len: int, max_slots: int = 8,
                 rng_seed: int = 0, kv_block_size: int = 16,
                 prefix_cache_blocks: int = 0, prefill_chunk: int = 16,
                 paged: bool = False, num_blocks: Optional[int] = None,
                 prefill_batch: int = 4, greedy_tie_eps: float = 1e-2,
                 device: Optional[jax.Device] = None):
        self.cfg = cfg
        if device is not None:
            params = jax.device_put(params, device)
        # cast once here, not in every program call
        self.params = T.serving_params(cfg, params)
        self.weight_bytes: Dict[str, int] = {}   # dtype name -> bytes held
        for a in jax.tree.leaves(self.params):
            k = a.dtype.name
            self.weight_bytes[k] = (self.weight_bytes.get(k, 0)
                                    + a.size * a.dtype.itemsize)
        self.max_seq_len = max_seq_len
        self.max_slots = max_slots
        self.key = jax.random.PRNGKey(rng_seed)
        self.prefill_chunk = prefill_chunk
        # > 0 makes greedy argmax layout-deterministic: any token whose
        # logit is within eps of the max is eligible and the LOWEST id
        # wins, so the ~1e-3 page-order summation noise between the
        # paged and dense layouts can no longer flip a near-tie argmax.
        # On by default (1e-2) since the chaos/failover suites held
        # bit-identity with it armed across every fault schedule; pass
        # 0.0 to restore the historical raw-argmax outputs
        self.greedy_tie_eps = float(greedy_tie_eps)
        # rows per compiled paged-prefill program (co-admission width);
        # dense mode prefills serially whatever the batch size
        self.prefill_batch = max(1, min(prefill_batch, max_slots))
        self.paged = paged
        want_prefix = prefix_cache_blocks > 0
        with jax.default_device(device):
            self.kv = PagedKVCache(
                cfg, max_slots, max_seq_len, block_size=kv_block_size,
                prefix_blocks=(prefix_cache_blocks if want_prefix and
                               self._family_supports_prefix(cfg) else 0),
                num_blocks=num_blocks, paged=paged)
        if device is not None:
            # committed: host inputs (tokens, tables) follow these arrays
            self.kv.cache = jax.device_put(self.kv.cache, device)
            if self.kv.prefix_store is not None:
                self.kv.prefix_store = jax.device_put(self.kv.prefix_store,
                                                      device)
        self.prefix_cache = None
        if self.kv.prefix_pool is not None:
            from repro.serving.prefix_cache import PrefixCache
            self.prefix_cache = PrefixCache(self.kv)
        self.decode_steps = 0                # accounting (tested)
        self.prefill_tokens = 0              # real tokens run through prefill
        self.prefill_tokens_executed = 0     # incl. padding (FLOPs proxy)
        self.prefill_tokens_padding = 0      # executed - real
        self.cached_prefix_tokens = 0        # tokens served from the store
        self.transient_prefill_bytes = 0     # peak batch-1 staging cache
        # MoE (paged): rows the held experts got from real tokens, and
        # the rows their grouped matmul ran, as read back so far.  Each
        # program's counts stay on the device until the step's sampled
        # tokens are read back, and come back with them
        self.moe = cfg.family == "moe"
        self.expert_rows = 0
        self.expert_rows_executed = 0
        self._expert_counts: List[tuple] = []   # (device counts, tokens)
        # bound by the scheduler that drives this engine (one tracer per
        # replica); None until then — engine-side trace emission is
        # guarded so direct primitive use stays untraced
        self.tracer = None
        # deterministic fault injection (serving/faults.py): bound by
        # the scheduler alongside the tracer; None = no hooks fire
        self.fault_injector = None
        # jit recompilation telemetry: each compiled program's argument
        # shape signature is reported per call; post-warm novelty is the
        # variable-batch shape-churn bug (serving/profiling.py)
        from repro.serving.profiling import RecompilationTracker
        self.recompiles = RecompilationTracker()
        self._inflight: Dict[int, PrefillCursor] = {}   # slot -> cursor
        self._begin_seq = 0                  # FIFO stamp for cursors
        if paged:
            # both paged programs take the pool donated and update it in
            # place: the buffer passed in is deleted, so callers keep
            # only the returned pool
            self._step = jax.jit(make_paged_serve_step(cfg),
                                 donate_argnums=2)

            def prefill_paged(params, tokens, starts, q_lens, cache, tables):
                """One co-prefill round: (Bp, C) chunk straight into the
                rows' pool blocks.  ONE compiled program for every wave
                and every prompt length (shapes are all fixed)."""
                batch = {"tokens": tokens, "positions": starts,
                         "q_lens": q_lens, "cache": cache,
                         "block_tables": tables}
                return T.prefill_step(params, cfg, batch)

            self._prefill_paged = jax.jit(prefill_paged, donate_argnums=4)
        else:
            self._step = jax.jit(make_serve_step(cfg))

        def prefill(params, tokens, cache, encoder_output):
            """Replay (B, P) prompt tokens through decode_step via scan."""
            B, P = tokens.shape

            def body(carry, t):
                cache, pos = carry
                batch = {"tokens": tokens[:, t][:, None], "positions": pos,
                         "cache": cache}
                if encoder_output is not None:
                    batch["encoder_output"] = encoder_output
                logits, cache = T.decode_step(params, cfg, batch)
                return (cache, pos + 1), logits[:, 0]

            (cache, pos), logits = jax.lax.scan(
                body, (cache, jnp.zeros((B,), jnp.int32)), jnp.arange(P))
            return cache, pos, logits[-1]

        self._prefill = jax.jit(prefill)     # whole-prompt reference path

        def prefill_chunk_fn(params, tokens, cache, pos0, encoder_output):
            """One fixed-width chunk from dynamic start position ``pos0``:
            tokens (1, C) -> (cache, per-step logits (C, V)).  Compiled
            once; every prompt length reuses the same program."""
            C = tokens.shape[1]

            def body(carry, t):
                cache, pos = carry
                batch = {"tokens": tokens[:, t][:, None], "positions": pos,
                         "cache": cache}
                if encoder_output is not None:
                    batch["encoder_output"] = encoder_output
                logits, cache = T.decode_step(params, cfg, batch)
                return (cache, pos + 1), logits[:, 0]

            (cache, _), logits = jax.lax.scan(
                body, (cache, pos0), jnp.arange(C))
            return cache, logits[:, 0]       # (C, V): batch row 0

        self._prefill_chunk = jax.jit(prefill_chunk_fn, donate_argnums=2)

        tie_eps = self.greedy_tie_eps        # jit closure constant

        def sample(key, logits, temps, greedy):
            # temperatures below epsilon ARE greedy: dividing by a tiny
            # clamp overflows f32 and feeds categorical NaN-producing
            # logits, so route those rows through argmax instead
            greedy = jnp.logical_or(greedy, temps < 1e-4)
            safe_t = jnp.where(greedy, jnp.float32(1.0), temps)
            cat = jax.random.categorical(key, logits / safe_t[:, None])
            if tie_eps > 0.0:
                # deterministic tie break: lowest token id within eps of
                # the max, immune to summation-order noise across the
                # paged/dense layouts (ROADMAP near-tie caveat)
                amax = jnp.max(logits, axis=-1, keepdims=True)
                g_tok = jnp.argmax(logits >= amax - tie_eps, axis=-1)
            else:
                g_tok = jnp.argmax(logits, axis=-1)
            return jnp.where(greedy, g_tok, cat)

        self._sample_vec = jax.jit(sample)

        self._enc_pool = None
        if cfg.family == "encdec":
            self._encode = jax.jit(
                lambda params, frames: T._encode(params["encoder"], cfg,
                                                 frames))
            self._enc_pool = jnp.zeros(
                (max_slots, cfg.encoder_seq, cfg.d_model),
                jnp.dtype(cfg.dtype))

    @staticmethod
    def _family_supports_prefix(cfg) -> bool:
        if cfg.family == "encdec":       # KV depends on the audio frames too
            return False
        return all(ax is not None
                   for ax in PagedKVCache._seq_axis_per_leaf(cfg, 1))

    # -- scheduler-facing primitives ----------------------------------------

    def prefill_into_slot(self, prompt: np.ndarray,
                          encoder_input: Optional[np.ndarray] = None,
                          *, start_pos: int = 0,
                          prefix_blocks: Sequence[int] = (),
                          ) -> Tuple[int, np.ndarray]:
        """Prefill one prompt into a free slot of the pooled cache.

        ``start_pos > 0`` resumes from a cached prefix: ``prefix_blocks``
        (from :meth:`PrefixCache.lookup`) back positions
        ``[0, start_pos)`` and only ``prompt[start_pos:]`` runs through
        the model, in ``prefill_chunk``-sized pieces.

        Returns ``(slot, last_logits (V,))`` — the scheduler samples the
        first new token from these logits, so admission costs one
        (suffix) prefill and the request joins the very next decode round.
        """
        [(slot, last)] = self.prefill_into_slots(
            [prompt], [encoder_input], start_pos=[start_pos],
            prefix_blocks=[list(prefix_blocks)])
        return slot, last

    def prefill_into_slots(self, prompts: Sequence[np.ndarray],
                           encoder_inputs: Optional[Sequence] = None,
                           *, start_pos: Optional[Sequence[int]] = None,
                           prefix_blocks: Optional[Sequence] = None,
                           ) -> List[Tuple[int, np.ndarray]]:
        """Co-prefill a batch of prompts into free slots, to completion.

        One ``begin_prefill`` + one unbudgeted ``advance_prefill``: the
        wave-at-once shape.  Paged mode packs every round as ONE
        compiled ``(Bp, C)`` chunk program whose K/V lands straight in
        the slots' pool blocks; dense mode (and enc-dec) replays
        batch-1 chunks — identical math, so greedy outputs are
        bit-identical across the two layouts.  All-or-nothing: an error
        anywhere (allocation, prefix load, a prefill round) releases
        every slot the call claimed before it propagates.

        Returns ``[(slot, last_logits (V,))]`` in **input order**.
        """
        cursors = self.begin_prefill(prompts, encoder_inputs,
                                     start_pos=start_pos,
                                     prefix_blocks=prefix_blocks)
        self.advance_prefill(cursors)        # cleans up all slots on error
        # one host-transfer pass AFTER every round dispatched
        return [(c.slot, np.asarray(c.last_logits)) for c in cursors]

    def begin_prefill(self, prompts: Sequence[np.ndarray],
                      encoder_inputs: Optional[Sequence] = None,
                      *, start_pos: Optional[Sequence[int]] = None,
                      prefix_blocks: Optional[Sequence] = None,
                      ) -> List[PrefillCursor]:
        """Claim slots for a batch of prompts and register one in-flight
        :class:`PrefillCursor` per row — no model compute yet beyond the
        enc-dec encoder and prefix-block loads.  Slot allocation is
        all-or-nothing: on ``OutOfBlocks`` every slot claimed so far is
        released before the error propagates.  Cursors persist on the
        engine until ``advance_prefill`` completes them or
        ``cancel_prefill`` abandons them."""
        n = len(prompts)
        prompts = [np.asarray(p, np.int32) for p in prompts]
        encoder_inputs = encoder_inputs or [None] * n
        start_pos = list(start_pos) if start_pos is not None else [0] * n
        prefix_blocks = (list(prefix_blocks) if prefix_blocks is not None
                         else [()] * n)
        for p, sp in zip(prompts, start_pos):
            assert 0 <= sp < len(p), (sp, len(p))
        cursors: List[PrefillCursor] = []
        try:
            for p, e, sp, pb in zip(prompts, encoder_inputs, start_pos,
                                    prefix_blocks):
                slot = self.kv.alloc_slot(len(p))
                cur = PrefillCursor(slot=slot, tokens=p, start_pos=sp,
                                    pos=sp, seq=self._begin_seq,
                                    encoder_input=e,
                                    prefix_blocks=tuple(pb))
                self._begin_seq += 1
                cursors.append(cur)
                if self.paged:
                    if sp:
                        self.kv.load_prefix_blocks_paged(slot, pb)
                elif self.cfg.family == "encdec":
                    cur.enc1 = self._encode(self.params,
                                            jnp.asarray(e)[None])
                    self._enc_pool = self._enc_pool.at[slot].set(cur.enc1[0])
                # the dense batch-1 staging cache materializes lazily at
                # the cursor's first advance chunk: N co-admitted dense
                # prompts waiting their FIFO turn hold N cursors but at
                # most ONE transient stripe, like the old serial path
        except Exception:
            for cur in cursors:              # all-or-nothing
                self.kv.free_slot(cur.slot)
            raise
        for cur in cursors:
            self._inflight[cur.slot] = cur
        self.cached_prefix_tokens += sum(start_pos)
        return cursors

    def _materialize_dense(self, cur: PrefillCursor) -> None:
        """Build the cursor's batch-1 staging cache (dense mode only):
        a fresh ``init_cache`` stripe with the cached prefix loaded."""
        cache1 = T.init_cache(self.cfg, 1, self.max_seq_len)
        self.transient_prefill_bytes = max(
            self.transient_prefill_bytes,
            sum(leaf.nbytes for leaf in jax.tree.leaves(cache1)))
        if cur.start_pos:
            cache1 = self.kv.load_prefix_blocks(cache1, cur.prefix_blocks)
        cur.dense_cache = cache1

    def advance_prefill(self, cursors: Optional[Sequence[PrefillCursor]]
                        = None, token_budget: Optional[int] = None,
                        ) -> List[PrefillCursor]:
        """Run chunk rounds against in-flight prefills (``cursors``
        defaults to every cursor on the engine) until all complete or
        ``token_budget`` *executed* token positions have run — the
        FLOPs/latency proxy: a paged round costs ``prefill_batch *
        prefill_chunk`` whatever the real row contents, a dense chunk
        costs ``prefill_chunk``.  The first round always runs, so a
        budget below one round still makes progress (the budget is a
        cap checked *between* rounds).  Rounds are scheduled FIFO by
        begin order, so a long prompt keeps advancing even under a
        sustained stream of later short admissions — no starvation,
        bounded TTFT for every row.

        Returns the cursors that **completed during this call**, each
        with device-resident ``last_logits``; unfinished cursors stay
        parked on the engine for the next call.  On any error every
        cursor this call touched — finished earlier in the call or
        still in flight — has its slot released before the error
        propagates, so one failed round can never leak slots or blocks.
        """
        working = [c for c in (cursors if cursors is not None
                               else list(self._inflight.values()))
                   if not c.done]
        for c in working:
            assert self._inflight.get(c.slot) is c, \
                f"cursor for slot {c.slot} is not in flight"
        involved = list(working)
        finished: List[PrefillCursor] = []
        spent = 0
        C = self.prefill_chunk
        tr = self.tracer

        def budget_left():
            return (token_budget is None or spent < token_budget
                    or spent == 0)

        try:
            # fault hook INSIDE the all-or-nothing block: an injected
            # prefill fault takes the same slot-release path a real
            # engine error does, so the scheduler's requeue stays exact
            if self.fault_injector is not None:
                self.fault_injector.on_engine_op("prefill")
            working.sort(key=lambda c: c.seq)    # FIFO by begin order
            if self.paged:
                Bp = self.prefill_batch
                while working and budget_left():
                    sel = working[:Bp]
                    with span(tr, "engine.prefill_round", rows=len(sel),
                              executed_tokens=Bp * C) as sp:
                        tables = np.full((Bp, self.kv.blocks_per_slot),
                                         self.kv.trash_block, np.int32)
                        toks = np.zeros((Bp, C), np.int32)
                        starts = np.zeros(Bp, np.int32)
                        qlens = np.zeros(Bp, np.int32)
                        for r, cur in enumerate(sel):
                            tables[r] = self.kv.table_row(cur.slot)
                            ql = min(cur.remaining, C)
                            toks[r, :ql] = cur.tokens[cur.pos:cur.pos + ql]
                            starts[r] = cur.pos
                            qlens[r] = ql
                        self.recompiles.observe(
                            "prefill_paged", (toks.shape, tables.shape),
                            tracer=tr)
                        logits, self.kv.cache, experts = self._prefill_paged(
                            self.params, jnp.asarray(toks),
                            jnp.asarray(starts), jnp.asarray(qlens),
                            self.kv.cache, jnp.asarray(tables))
                        real = int(qlens.sum())
                        sp.set(real_tokens=real)
                        if experts:
                            self._expert_counts.append((experts, real))
                    # FLOPs proxy: every row of the compiled (Bp, C)
                    # program executes every round, dummy rows included
                    spent += Bp * C
                    self.prefill_tokens += real
                    self.prefill_tokens_executed += Bp * C
                    self.prefill_tokens_padding += Bp * C - real
                    for r, cur in enumerate(sel):
                        cur.pos += int(qlens[r])
                        if tr is not None and tr.enabled and qlens[r]:
                            tr.prefill_advance(cur.slot, int(qlens[r]),
                                               cur.pos, len(cur.tokens))
                        if cur.done:
                            # device-resident slice: no host sync inside
                            # the round loop, so rounds keep dispatching
                            cur.last_logits = logits[r, int(qlens[r]) - 1]
                            finished.append(cur)
                    working = [c for c in working if not c.done]
            else:
                for cur in working:
                    while not cur.done and budget_left():
                        ql = min(cur.remaining, C)
                        with span(tr, "engine.prefill_round", rows=1,
                                  real_tokens=ql, executed_tokens=C):
                            if cur.dense_cache is None:
                                self._materialize_dense(cur)
                            chunk = np.zeros(C, np.int32)
                            chunk[:ql] = cur.tokens[cur.pos:cur.pos + ql]
                            self.recompiles.observe(
                                "prefill_chunk", (1, C), tracer=tr)
                            cur.dense_cache, logits = self._prefill_chunk(
                                self.params, jnp.asarray(chunk)[None],
                                cur.dense_cache,
                                jnp.full((1,), cur.pos, jnp.int32),
                                cur.enc1)
                        li = (len(cur.tokens) - 1) - cur.pos
                        if 0 <= li < C:      # row's last real token here
                            cur.last_logits = logits[li]
                        cur.pos += ql
                        spent += C
                        self.prefill_tokens += ql
                        self.prefill_tokens_executed += C
                        self.prefill_tokens_padding += C - ql
                        if tr is not None and tr.enabled:
                            tr.prefill_advance(cur.slot, ql, cur.pos,
                                               len(cur.tokens))
                    if cur.done:
                        self.kv.write_prefill(cur.slot, cur.dense_cache)
                        cur.dense_cache = None
                        finished.append(cur)
                    if not budget_left():
                        break
        except Exception:
            # all-or-nothing per call: an error anywhere releases every
            # slot this call touched (the caller never learned of the
            # rows that finished just before the failure), so nothing
            # leaks past the caller's error handling
            for cur in involved:
                self._inflight.pop(cur.slot, None)
                self.kv.free_slot(cur.slot)
            raise
        for cur in finished:
            del self._inflight[cur.slot]
        return finished

    def cancel_prefill(self, slot: int) -> None:
        """Abandon an in-flight prefill (mid-prefill preemption): the
        cursor is dropped, the slot and its KV blocks return to the
        pool, and any dense staging cache is discarded.  The caller
        re-queues the request; it resumes later from whatever the prefix
        cache still holds."""
        self._inflight.pop(slot)
        self.kv.free_slot(slot)

    @property
    def inflight_prefill_tokens(self) -> int:
        """Real token positions still to execute across in-flight
        cursors (telemetry; the scheduler's budget debt)."""
        return sum(c.remaining for c in self._inflight.values())

    def decode_once(self, tokens: np.ndarray,
                    positions: np.ndarray) -> jnp.ndarray:
        """One decode step over all slots.  ``tokens``/``positions`` are
        (max_slots,); rows for free slots carry dummies (their cache
        writes land in region the next prefill overwrites).  Returns
        logits (max_slots, V) **on device** — pass them straight to
        ``sample_tokens`` so the step costs one host sync, not two."""
        if self.fault_injector is not None:
            # before any state mutation: a decode-site fault leaves the
            # cache untouched, so the scheduler can retry the same step
            self.fault_injector.on_engine_op("decode")
        # live rows: slots holding a sequence that is not mid-prefill
        live = len(self.kv.block_table) - len(self._inflight)
        with span(self.tracer, "engine.decode", live_rows=live) as sp:
            if Span.recording():
                sp.set(ctx_tokens=sum(
                    int(positions[s]) + 1 for s in self.kv.block_table
                    if s not in self._inflight))
            batch = {"tokens": jnp.asarray(tokens, jnp.int32)[:, None],
                     "positions": jnp.asarray(positions, jnp.int32)}
            if self.moe:
                # rows of free and mid-prefill slots get no expert rows
                rows = np.zeros(np.shape(tokens), bool)
                rows[[s for s in self.kv.block_table
                      if s not in self._inflight]] = True
                batch["valid"] = jnp.asarray(rows)
            self.recompiles.observe(
                "decode_step", (np.shape(tokens), np.shape(positions)),
                tracer=self.tracer)
            if self.paged:
                # free slots' rows point at the trash block; their dummy
                # writes and speculative gathers never touch live KV.  A
                # mid-prefill slot's table maps real blocks already, so
                # its row is masked to the trash block too — otherwise
                # its dummy decode write at position 0 would corrupt KV
                # the prefill just produced
                batch["block_tables"] = self.kv.device_block_tables(
                    mask_slots=self._inflight)
                logits, self.kv.cache, experts = self._step(
                    self.params, batch, self.kv.cache)
                if experts:
                    self._expert_counts.append((experts, live))
            else:
                batch["cache"] = self.kv.cache
                if self._enc_pool is not None:
                    batch["encoder_output"] = self._enc_pool
                logits, self.kv.cache = self._step(self.params, batch)
        self.decode_steps += 1
        return logits[:, 0]                  # device-resident; no sync here

    def sample_tokens(self, logits: np.ndarray, temps: np.ndarray,
                      greedy: np.ndarray) -> np.ndarray:
        """Per-row sampling: row i uses temps[i] / greedy[i].  Rows whose
        temperature is below 1e-4 (including exactly 0.0) sample greedily."""
        shape = np.shape(logits)
        with span(self.tracer, "engine.sample", rows=shape[0]):
            self.key, sub = jax.random.split(self.key)
            self.recompiles.observe("sample", shape, tracer=self.tracer)
            toks = self._sample_vec(sub, jnp.asarray(logits),
                                    jnp.asarray(temps, jnp.float32),
                                    jnp.asarray(greedy))
            # deliberate: THE one host sync per step — the scheduler
            # needs concrete token ids for EOS/retirement bookkeeping
            if not self._expert_counts:
                return np.asarray(toks)  # repro-lint: disable=RL001
            # the expert counts of the programs run since the last
            # read-back ride on the same one
            toks, counts = jax.device_get(  # repro-lint: disable=RL001
                (toks, self._expert_counts))
            self._expert_counts = []
            self._record_expert_rows(counts)
            return toks

    def _record_expert_rows(self, counts: List[tuple]) -> None:
        """Add read-back expert counts to the engine's totals and to the
        scheduler's metrics: real tokens routed, rows routed to held
        experts, rows the grouped matmul ran, (layer, expert) pairs that
        got a row (each reads that expert's weights) and grouped-matmul
        calls (one a layer a program)."""
        for c, tokens in counts:
            rows, executed = c["expert_rows"], c["expert_rows_executed"]
            self.expert_rows += int(rows.sum())
            self.expert_rows_executed += int(executed.sum())
            if self.tracer is not None:
                self.tracer.expert_rows(
                    tokens=tokens, rows=int(rows.sum()),
                    executed=int(executed.sum()),
                    experts_hit=int((rows > 0).sum()), calls=len(executed))

    def free_slot(self, slot: int) -> None:
        self.kv.free_slot(slot)

    # -- compatibility wrapper ----------------------------------------------

    def generate(self, requests: List[Request]) -> List[np.ndarray]:
        """Serve a batch of requests through the scheduler path and return
        generated tokens in submission order."""
        from repro.serving.scheduler import Scheduler
        sched = Scheduler(self)
        rids = [sched.submit(r) for r in requests]
        sched.run()
        return [sched.output(rid) for rid in rids]
