"""The harness end to end at smoke widths on the CPU (the look for a chip
skipped), and its refusal to run without one."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import harness
from perfbench.tests import tiny

LIMIT = {"logit_gap": tiny.LIMIT}


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(tiny.BENCH / "run.py"), "--workload",
         "qwen2-0.5b.chat-sessions", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tiny.BENCH.parent, env=env,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("mix", [tiny.CHAT, tiny.BATCH], ids=["chat", "batch"])
def test_sound_run_is_correct(mix):
    cell = tiny.cell(mix, workload=f"tiny-dense.{mix['name']}")
    res = harness.run(cell, 2**31 + 77, 2.0, False, chip_peaks=tiny.V5E,
                      limits=LIMIT, log=lambda s: None)
    res.pop("_check")
    waits = res.pop("_samples")["first_token"]
    line = json.loads(json.dumps(res))
    assert len(waits) == line["attempted"]
    assert line["correct"] is True, line["checks"]
    assert list(line)[-1] == "checks"
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        assert k in line["device"]
    assert line["device"]["count"] == 1
    for m in cell.metrics("end_to_end"):
        assert m["name"] in line["metrics"], m["name"]
    assert line["attempted"] > 0 and line["failed"] == 0


def test_altered_token_is_not_correct(monkeypatch):
    """A token altered where it is produced: the sampler returns the next
    id for one row of every batch.  The comparison must see it."""
    from repro.serving.engine import ServingEngine
    orig = ServingEngine.sample_tokens

    def altered(self, logits, temps, greedy):
        toks = np.array(orig(self, logits, temps, greedy))
        toks[0] = (toks[0] + 1) % self.cfg.vocab_size
        return toks

    monkeypatch.setattr(ServingEngine, "sample_tokens", altered)
    cell = tiny.cell()
    res = harness.run(cell, 5, 2.0, False, chip_peaks=tiny.V5E,
                      limits=LIMIT, log=lambda s: None)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > tiny.LIMIT
