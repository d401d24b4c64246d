"""The Qwen3 sparse-expert family on the CPU: a tiny configuration that
holds 4 of a router's 8 experts, served through ``ServingEngine`` and
compared on logits with the plain reference; the harness's MoE readers;
and a pin of the benchmark's stage configuration (weight-tree shapes,
sizes and work counts)."""
import copy

import jax
import numpy as np
import pytest

from perfbench import check, experts, harness, model, work
from perfbench.references import qwen3_moe as reference
from perfbench.tests import tiny

TINY = {
    "name": "tiny-qwen3-moe", "family": "qwen3_moe", "reference": "qwen3_moe",
    "hidden_size": 64, "moe_intermediate_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 4, "first_expert": 2, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "vocab_size": 307, "rms_norm_eps": 1e-6, "rope_theta": 1000000.0,
    "rope_scaling": None, "attention_bias": False,
    "tie_word_embeddings": False, "published": {"num_experts": 8},
    "served": {"compute_dtype": "bfloat16", "param_dtype": "float32",
               "kv_cache_dtype": "bfloat16"},
}


def _f32(c):
    c = copy.deepcopy(c)
    c["served"] = dict(c["served"], compute_dtype="float32")
    return c


def _serve_logits(c, seed):
    """Two prompts co-prefilled through the paged pool across chunk
    boundaries, then greedy paged decode steps; every logits row the
    engine handed out, with the sequence it was computed on."""
    from repro.serving import ServingEngine
    cfg = model.program_config(c)
    w = model.make_weights(c, seed)
    eng = ServingEngine(cfg, w, max_seq_len=128, max_slots=4, paged=True,
                        prefill_batch=2)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, c["vocab_size"], n, dtype=np.int32)
               for n in (37, 21)]
    seqs, rows = [list(p) for p in prompts], [[] for _ in prompts]
    firsts = eng.prefill_into_slots(prompts)
    slots = [s for s, _ in firsts]
    for i, (_, lg) in enumerate(firsts):
        rows[i].append(lg)
    toks = np.zeros(eng.max_slots, np.int32)
    pos = np.zeros(eng.max_slots, np.int32)
    for _ in range(6):
        for i, s in enumerate(slots):
            toks[s] = int(np.argmax(rows[i][-1]))
            seqs[i].append(toks[s])
            pos[s] = len(seqs[i]) - 1
        out = np.asarray(eng.decode_once(toks, pos))
        for i, s in enumerate(slots):
            rows[i].append(out[s])
    return w, eng, [(np.asarray(q, np.int32), np.stack(r))
                    for q, r in zip(seqs, rows)]


def test_served_logits_match_the_reference():
    """Paged prefill then paged decode, computed in float32, agree with
    the reference's full forward pass to 1e-2 of the largest logit: the
    pool keeps K and V in bfloat16 pages (2**-9 relative rounding of
    every attended key and value, over two layers; 3.5e-3 read at this
    seed), and the rest differs only in float32 summation order.  The
    float8 control misses by 0.35 here, so a float8 program fails."""
    c = _f32(TINY)
    w, eng, got = _serve_logits(c, 7)
    assert eng.expert_rows == 0          # counts wait for a sampled read
    worst = ctl = 0.0
    for seq, served in got:
        n = len(seq) - len(served) + 1
        rows = np.arange(n - 1, len(seq))
        want = reference.logits(w, c, seq, rows)
        low = reference.logits(w, c, seq, rows, mode="fp8")
        scale = np.abs(want).max()
        worst = max(worst, np.abs(served - want).max() / scale)
        ctl = max(ctl, np.abs(low - want).max() / scale)
    assert worst <= 1e-2
    assert ctl > 1e-2


def test_served_tokens_against_reference_and_counts():
    """As served (bfloat16), through the gateway with a prefix-cache
    resume: every served token lies at the reference's top, the float8
    control's first choices do not, and the expert counters read back
    with the sampled tokens."""
    from repro.serving import (ReplicaGateway, Request, SamplingParams,
                               ServingEngine)
    from perfbench.traffic import Req
    cfg = model.program_config(TINY)
    w = model.make_weights(TINY, 5)
    eng = ServingEngine(cfg, w, max_seq_len=256, max_slots=4, paged=True,
                        prefill_batch=2, prefix_cache_blocks=16)
    gw = ReplicaGateway.from_engines([eng])
    rng = np.random.default_rng(1)
    shared = rng.integers(0, 307, 40, dtype=np.int32)
    reqs = []
    for i in range(3):
        p = np.concatenate([shared, rng.integers(0, 307, 5 + 7 * i,
                                                 dtype=np.int32)])
        h = gw.submit(Request(p, SamplingParams(max_new_tokens=12,
                                                greedy=True)))
        gw.run()
        r = Req(i, p, 12, 0.0)
        r.tokens = list(gw.result(h))
        reqs.append(r)
    totals = gw.stats()["totals"]
    assert totals["prefix_cache"]["hits"] >= 2
    moe = totals["moe"]
    real = (totals["prefill_tokens"]["real"] + 3 * 11)   # + decode tokens
    assert moe["tokens"] == real
    assert moe["calls"] > 0 and moe["experts_hit"] > 0
    assert moe["expert_rows_executed"] >= moe["expert_rows"] > 0
    assert moe["expert_rows"] == eng.expert_rows
    got = check.readings(w, TINY, reqs, modes=("f32", "fp8"))
    assert got["tokens"] == 36
    assert got["logit_gap"] <= tiny.LIMIT
    assert got["logit_gap_fp8"] > tiny.LIMIT


# The harness path's limit at these widths: which requests finish in
# the window depends on the host's speed, and in bfloat16 a near-tie of
# two router outputs can flip one expert of 4 held, so sound runs read up
# to 0.034 over their 11-18 compared tokens (0.0 in most).  It checks the
# path, not the precision: the float32 comparison above does that.
HARNESS_LIMIT = 0.1


def test_harness_runs_the_moe_family_correctly():
    """The harness's own path over the tiny MoE configuration: correct
    under its limit, with tokens in the window."""
    cell = tiny.cell(tiny.BATCH, config=TINY,
                     workload="tiny-qwen3-moe.tiny-batch")
    res = harness.run(cell, 2**33 + 3, 2.0, False, chip_peaks=tiny.V5E,
                      limits={"logit_gap": HARNESS_LIMIT},
                      log=lambda s: None)
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["output_tok_s"]["value"] > 0


class _Ctx:
    """A run's context as the MoE readers see it."""

    def __init__(self, trace=None):
        self.dims = model.dims(STAGE)
        self.peaks = tiny.V5E
        self.stats0 = {"totals": {"moe": dict(
            tokens=1000, expert_rows=4000, expert_rows_executed=9000,
            experts_hit=900, calls=40)}}
        self.stats1 = {"totals": {"moe": dict(
            tokens=3000, expert_rows=8000, expert_rows_executed=29000,
            experts_hit=1700, calls=80)}}
        self.trace = trace
        self.work = harness.WorkLog(decode_ctx=[10] * 100,
                                    prefill_rows=[(0, 50), (5, 0)])
        self.trace_window = (0.0, 1.0)

    def steps_in(self, a, b):
        return [(0.0, 0.5)]


def test_moe_readers():
    """Pad share from the window's counters; the kernel's roofline from
    its trace events and the window's rates; a program without the
    counters reads None."""
    from perfbench import trace as T
    ctx = _Ctx()
    assert experts.pad_share(ctx) == pytest.approx(100.0 * 16000 / 20000)
    assert experts.gmm_roofline(ctx) is None          # no trace
    ctx.trace = T.Trace(ops={"/device:TPU:0": [
        ("%expert_gmm.1 = bf16[8]{0} custom-call()", 1.0, 1.002),
        ("%expert_gmm.1 = bf16[8]{0} custom-call()", 2.0, 2.002)]})
    n = ctx.dims
    rows = 150 * 2.0                          # trace tokens x rows/token
    fl = 6.0 * n["d"] * n["F"] * rows
    by = (2 * 20.0 * 3 * n["d"] * n["F"] * 4 + rows * 2 * n["d"] * 2)
    want, _ = work.roofline_share(fl, by, 0.004, tiny.V5E)
    assert experts.gmm_roofline(ctx) == pytest.approx(want)
    missing = _Ctx(T.Trace(ops={"/device:TPU:0": [
        ("%fusion.1 = bf16[8]{0} fusion()", 1.0, 1.5)]}))
    with pytest.raises(T.MissingKernel):        # work counted, no kernel
        experts.gmm_roofline(missing)
    without = _Ctx(ctx.trace)
    without.stats1 = {"totals": {}}
    without.stats0 = {"totals": {}}
    assert experts.pad_share(without) is None
    assert experts.gmm_roofline(without) is None
    assert experts.mfu(without) is None


STAGE = harness.load_json(tiny.BENCH / "configs"
                          / "qwen3-moe-30b-a3b-stage.json")


def test_stage_configuration_pin():
    """The stage as the benchmark builds it: the program's ModelConfig,
    the weight tree in the program's layout, 1.983 B parameters, 16 KiB
    of K/V a token, and the work a token costs."""
    from repro.models import transformer as T
    n = model.dims(STAGE)
    cfg = model.program_config(STAGE)
    assert (cfg.family, cfg.num_layers, cfg.num_experts, cfg.router_width,
            cfg.moe_first_expert, cfg.num_experts_per_tok, cfg.d_ff,
            cfg.qk_norm, cfg.tie_embeddings) == (
        "moe", 8, 32, 128, 0, 8, 768, True, False)
    want = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: model.family(STAGE)._tree(
        n, jax.random.PRNGKey(0)))
    leaves = (lambda t: [(jax.tree_util.keystr(p), x.shape, str(x.dtype))
                         for p, x in
                         jax.tree_util.tree_flatten_with_path(t)[0]])
    assert leaves(got) == leaves(want)
    assert sum(np.prod(s) for _, s, _ in leaves(got)) == 1_983_418_368
    assert cfg.param_count() + 2048 == 1_983_418_368     # + final norm
    kv = n["L"] * 2 * n["KV"] * n["D"] * 2
    assert kv == 16 * 1024
    attn = 2048 * 4096 * 2 + 2 * 2048 * 512
    assert work.matmul_params(n) == attn + 2048 * 128 + 2 * 3 * 2048 * 768
    assert work.model_flops(n, decode_ctx=[100], prefill_rows=[(0, 64)]) == \
        pytest.approx(2 * 8 * work.matmul_params(n) * 65
                      + 2 * 2048 * 151936
                      + work.decode_attention(n, [100])[0]
                      + work.prefill_attention(n, [(0, 64)])[0])
