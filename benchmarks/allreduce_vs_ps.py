"""Paper §II-H: Horovod allreduce vs TensorFlow parameter servers.

Compiles the SAME training step under both collective strategies on an
8-rank host mesh and compares per-rank collective bytes from the HLO:
ring allreduce moves O(2·P) per rank; the PS pattern's all-gather +
broadcast moves O(N·P) — the measured contrast that motivated Horovod.
"""
from __future__ import annotations

import os
import subprocess
import sys
from typing import List, Tuple

_PROG = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ranks}"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp
from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.core import hvd, paramserver
from repro.launch.mesh import make_mesh
from repro import optim
from repro.launch.dryrun import collective_bytes
cfg = ModelConfig(name="t", family="dense", num_layers=4, d_model=256,
                  num_heads=8, num_kv_heads=4, d_ff=1024, vocab_size=32000)
key = jax.random.PRNGKey(0)
mesh = make_mesh(({ranks},), ("data",))
opt = optim.rmsprop(1e-3)
loss_fn = lambda p, b: T.lm_loss(p, cfg, b)
p_s = jax.eval_shape(lambda k: T.init_params(cfg, k), key)
s_s = jax.eval_shape(opt.init, p_s)
B = {ranks} * 2
b_s = {{"tokens": jax.ShapeDtypeStruct((B, 128), jnp.int32),
       "labels": jax.ShapeDtypeStruct((B, 128), jnp.int32)}}
n_params = sum(int(jnp.prod(jnp.asarray(l.shape))) for l in jax.tree.leaves(p_s))
for name, maker in [("hvd", hvd.make_train_step),
                    ("ps", paramserver.make_train_step)]:
    step = maker(loss_fn, opt, mesh, donate=False)
    c = step.lower(p_s, s_s, b_s).compile()
    cb = collective_bytes(c.as_text())
    print(f"RES {{name}} {{sum(cb.values())}} {{n_params}}")
"""


def run(ranks: int = 8) -> List[Tuple[str, float, str]]:
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _PROG.format(ranks=ranks)],
                       capture_output=True, text=True, env=env, timeout=560)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])
    res = {}
    n_params = 0
    for line in r.stdout.splitlines():
        if line.startswith("RES"):
            _, name, nbytes, npar = line.split()
            res[name] = int(nbytes)
            n_params = int(npar)
    grad_bytes = n_params * 4
    rows = [
        (f"hvd_allreduce/{ranks}ranks", 0.0,
         f"{res['hvd']:,} B/rank ({res['hvd']/grad_bytes:.2f}x grad bytes)"),
        (f"paramserver/{ranks}ranks", 0.0,
         f"{res['ps']:,} B/rank ({res['ps']/grad_bytes:.2f}x grad bytes)"),
        ("ps_vs_hvd_ratio", 0.0,
         f"{res['ps']/max(res['hvd'],1):.2f}x more collective traffic "
         f"(paper: why Horovod replaced parameter servers)"),
    ]
    assert res["ps"] > res["hvd"], "PS must move more bytes than allreduce"
    return rows


if __name__ == "__main__":
    for row in run():
        print(",".join(str(x) for x in row))
