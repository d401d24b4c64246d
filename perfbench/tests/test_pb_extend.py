"""A later change adds a configuration, a traffic mix and a per-layer
metric as new files only: the harness finds them by the names in
BENCHMARK.json, and no file the benchmark already has is edited."""
import hashlib
import json
import shutil
import subprocess
import sys

from perfbench.tests import tiny

PROBE = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
from perfbench import harness, traffic
cell = harness.load_cell("tiny-dense.tiny-chat")
names = [m["name"] for m in cell.metrics("per_layer")]
read = harness.reader("prompt_tokens_per_request")
class C:  # the reader sees a run's context
    pass
ctx = C(); ctx.stats0 = {"totals": {"prefix_cache": {"prompt_tokens": 10}, "requests_completed": 1}}
ctx.stats1 = {"totals": {"prefix_cache": {"prompt_tokens": 70}, "requests_completed": 4}}
gen = traffic.make(cell.mix, cell.config["vocab_size"], 1, 30)
print(json.dumps({"config": cell.config["name"], "mix": cell.mix["name"],
                  "metrics": names, "value": read(ctx),
                  "due": len(gen.due_until(10.0))}))
"""

METRIC = '''"""Prompt tokens admitted per completed request in the window."""
from perfbench import readers


def read(ctx):
    n = readers.counter_delta(ctx, "requests_completed")
    return readers.counter_delta(ctx, "prefix_cache", "prompt_tokens") / n
'''


def _digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()
                                                    ).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_add_config_mix_and_metric_as_new_files(tmp_path):
    bench = tmp_path / "perfbench"
    shutil.copytree(tiny.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    before = _digest(bench)
    (bench / "configs" / "tiny-dense.json").write_text(
        json.dumps(tiny.CONFIG))
    (bench / "mixes" / "tiny-chat.json").write_text(json.dumps(tiny.CHAT))
    (bench / "metrics" / "prompt_tokens_per_request.py").write_text(METRIC)
    spec = json.loads((tiny.BENCH.parent / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-dense", "source": "test",
                            "file": "perfbench/configs/tiny-dense.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-dense.tiny-chat",
                              "config": "tiny-dense", "traffic": "tiny-chat",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "prompt_tokens_per_request",
                              "unit": "tokens", "better": "lower",
                              "source": "program_counter",
                              "layer": "scheduler", "moves": "itl_p95_ms",
                              "workloads": ["tiny-dense.tiny-chat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before
    p = subprocess.run([sys.executable, "-c", PROBE, str(tmp_path),
                        str(tiny.BENCH.parent / "src")],
                       capture_output=True, text=True, timeout=120,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode == 0, p.stderr
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["config"] == "tiny-dense" and out["mix"] == "tiny-chat"
    assert out["metrics"] == ["prompt_tokens_per_request"]
    assert out["value"] == 20.0
    assert out["due"] > 0
