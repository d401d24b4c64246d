"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and
its serving checks hold at the smoke widths and fail loudly on a
replica fault.  The full-width run needs the chip."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def small(cs, monkeypatch):
    """Smoke widths and a short run: 4 requests over a 32-token shared
    prefix, 4 new tokens each."""
    from repro.configs import get_smoke_config
    for name, value in (("MAX_SEQ_LEN", 128), ("SHARED_PREFIX", 32),
                        ("N_REQUESTS", 4), ("MAX_NEW", 4)):
        monkeypatch.setattr(cs, name, value)
    cfg = get_smoke_config("qwen2-0.5b")
    return cfg, cs.init_params(cfg, 0)


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env,
                       cwd=ROOT, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_chip_smoke_serving_checks_pass_at_smoke_widths(cs, small):
    cfg, params = small
    prompts = cs.make_prompts(cfg.vocab_size, cs.N_REQUESTS, 0)
    assert all(len(p) > cs.SHARED_PREFIX for p in prompts)
    outputs, logits, engines, stats = cs.serve(cfg, params, [None], prompts)
    assert [len(o) for o in outputs] == [cs.MAX_NEW] * len(prompts)
    assert stats["totals"]["prefix_cache"]["hits"] > 0
    assert set(logits) == {tuple(p.tolist()) for p in prompts}
    for i in (0, len(prompts) - 1):
        assert cs.check_logits(cfg, params, logits, prompts[i],
                               f"request {i}") == 0
    # on the CPU the kernels are interpreted: no tpu_custom_call
    texts = cs.kernel_programs(engines[0])
    assert set(texts) == {"prefill_paged", "decode_step"}
    assert all("tpu_custom_call" not in t for t in texts.values())


def test_chip_smoke_fails_on_a_replica_exception(cs, small):
    from repro.serving import FaultPlan, FaultSpec
    cfg, params = small
    prompts = cs.make_prompts(cfg.vocab_size, cs.N_REQUESTS, 0)
    plan = FaultPlan([FaultSpec(kind="raise", site="prefill", at_step=0)])
    with pytest.raises(cs.SmokeFailure,
                       match="injected transient fault") as err:
        cs.serve(cfg, params, [None], prompts, fault_plan=plan)
    assert "failures=1" in str(err.value)


def test_chip_smoke_logit_check_rejects_a_wrong_logit(cs, small):
    cfg, params = small
    prompt = cs.make_prompts(cfg.vocab_size, 1, 0)[0]
    from repro.models import transformer as T
    import jax
    ref = np.asarray(jax.jit(lambda p, t: T.forward(
        p, cfg, {"tokens": t}, last_only=True)[0][0, -1])(
            params, prompt[None]))
    key = tuple(prompt.tolist())
    assert cs.check_logits(cfg, params, {key: (0, ref)}, prompt, "ok") == 0
    off = ref.copy()
    off[0] += 2 * cs.LOGIT_TOL * np.max(np.abs(ref))
    with pytest.raises(cs.SmokeFailure, match="differ from T.forward"):
        cs.check_logits(cfg, params, {key: (0, off)}, prompt, "off")
