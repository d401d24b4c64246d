"""Roofline and MFU arithmetic from live lengths."""
import numpy as np
import pytest

from perfbench import harness, model, work
from perfbench.tests import tiny

N = model.dims(tiny.CONFIG)


def test_decode_counts_live_context_only():
    fl, by = work.decode_attention(N, [10, 30])
    H, KV, D, L = N["H"], N["KV"], N["D"], N["L"]
    assert fl == pytest.approx(L * 4 * 40 * H * D)
    assert by == pytest.approx(L * (2 * 40 * KV * D * 2 + 2 * 2 * H * D * 2))


def test_prefill_padding_rows_add_nothing():
    with_pad = work.prefill_attention(N, [(16, 5), (0, 0), (0, 0)])
    alone = work.prefill_attention(N, [(16, 5)])
    assert with_pad == alone
    # causal: query t of a row starting at 16 attends 17 + t positions
    H, D, L = N["H"], N["D"], N["L"]
    assert alone[0] == pytest.approx(L * 4 * H * D * sum(17 + t
                                                         for t in range(5)))


def test_model_flops_and_shares():
    per_tok = 2 * work.matmul_params(N) * N["L"]
    fl = work.model_flops(N, decode_ctx=[8], prefill_rows=[(0, 4), (4, 0)],
                          logits_rows=1)
    head = 2 * N["d"] * N["V"] * 2
    att = (work.decode_attention(N, [8])[0]
           + work.prefill_attention(N, [(0, 4)])[0])
    assert fl == pytest.approx(per_tok * 5 + head + att)
    peaks = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_share(50.0, 1.0, 1.0, peaks) == (50.0, "compute")
    assert work.roofline_share(1.0, 5.0, 2.0, peaks) == (25.0, "memory")


def test_recorded_lengths_are_live_not_padded():
    """The engine wrapper records each decode row's live context and each
    prefill row's real query length: never the max_seq_len stripe, never
    the chunk or batch padding."""
    from repro.serving import (ReplicaGateway, Request, SamplingParams,
                               ServingEngine)
    cfg = model.program_config(tiny.CONFIG)
    w = model.make_weights(tiny.CONFIG, 3)
    eng = ServingEngine(cfg, w, max_seq_len=128, max_slots=4, paged=True,
                        prefill_batch=2)
    log = harness.WorkLog(on=True)
    harness.record_work(eng, log)
    gw = ReplicaGateway.from_engines([eng])
    prompts = [np.arange(n, dtype=np.int32) % 300 + 1 for n in (21, 37)]
    for p in prompts:
        gw.submit(Request(p, SamplingParams(max_new_tokens=3, greedy=True)))
    gw.run()
    assert sum(q for _, q in log.prefill_rows) == 21 + 37
    assert all(q <= eng.prefill_chunk for _, q in log.prefill_rows)
    # two decode steps per request: contexts len+1 and len+2
    assert sorted(log.decode_ctx) == [22, 23, 38, 39]
    assert max(log.decode_ctx) < eng.max_seq_len
