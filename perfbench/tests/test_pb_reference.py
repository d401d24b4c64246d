"""The plain reference against the serving program at smoke widths, for
both configurations' layer equations: qwen2's (QKV bias, tied head) and
deepseek-coder's (no bias, separate head)."""
import copy

import jax
import numpy as np
import pytest

from perfbench import check, model
from perfbench.references import dense
from perfbench.tests import tiny

DEEPSEEK_LIKE = dict(tiny.CONFIG, name="tiny-llama", qkv_bias=False,
                     tie_word_embeddings=False, rope_theta=100000)
CONFIGS = [tiny.CONFIG, DEEPSEEK_LIKE]


@pytest.mark.parametrize("config", CONFIGS, ids=["qwen2-like", "llama-like"])
def test_reference_equals_program_forward_in_float32(config):
    """The program's own forward pass computed in float32 agrees with the
    reference to float32 rounding: the two implement the same equations
    on the same weights."""
    from repro.models import transformer as T
    c = copy.deepcopy(config)
    c["served"] = dict(c["served"], compute_dtype="float32")
    cfg = model.program_config(c)
    w = model.make_weights(c, 11)
    toks = np.random.default_rng(0).integers(0, c["vocab_size"], 70,
                                             dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(T.forward(w, cfg, {"tokens": toks[None]})[0][0])
    want = dense.logits(w, c, toks, np.arange(70))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale


@pytest.mark.parametrize("config", CONFIGS, ids=["qwen2-like", "llama-like"])
def test_served_tokens_against_reference(config):
    """Tokens served through the paged path (prefill across chunk
    boundaries, a prefix-cache resume, decode) lie at the reference's top,
    while the float8 control's first choices do not."""
    from repro.serving import (ReplicaGateway, Request, SamplingParams,
                               ServingEngine)
    from perfbench.traffic import Req
    cfg = model.program_config(config)
    w = model.make_weights(config, 5)
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
        gw.run()                   # one at a time: later ones resume
        r = Req(i, p, 12, 0.0)
        r.tokens = list(gw.result(h))
        reqs.append(r)
    assert gw.stats()["totals"]["prefix_cache"]["hits"] >= 2
    got = check.readings(w, config, reqs, modes=("f32", "fp8"))
    assert got["tokens"] == 36
    assert got["logit_gap"] <= tiny.LIMIT
    assert got["logit_gap_fp8"] > tiny.LIMIT
