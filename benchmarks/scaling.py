"""Paper Table 1 / Fig 2: multi-node scaling of the 3DGAN training.

The paper reports near-linear strong scaling of one 3DGAN epoch on 4-32
SuperMUC-NG nodes (3806s -> 504s, 94% efficiency).  This container has ONE
physical core, so wall-clock multi-device timing is meaningless; we
reproduce the claim two ways:

1. **Cost model** (validated against the paper's own numbers): per-epoch
   time = compute/N + ring-allreduce time with the paper's hardware
   (Skylake 48c, OmniPath 100 Gbit/s, 1M-param f32 gradients, steps/epoch
   from the dataset size).  The model must reproduce Table 1 within a few
   percent and predict >=90% efficiency at 32 nodes — the paper's claim.

2. **Collective-bytes measurement**: the hvd-DP train step is compiled for
   1..32 ranks and the per-rank allreduce bytes parsed from the HLO —
   demonstrating the O(2·P) per-rank property that makes (1) hold.
"""
from __future__ import annotations

import re
import subprocess
import sys
from typing import Dict, List, Tuple

import numpy as np

# paper Table 1
PAPER_TABLE1 = {4: 3806.0, 8: 1910.0, 16: 1001.0, 32: 504.0}

# SuperMUC-NG constants
OMNIPATH_BW = 100e9 / 8            # bytes/s
GAN_PARAMS = 1.0e6                 # paper: "slightly less than 1 million"
GRAD_BYTES = GAN_PARAMS * 4


def epoch_time_model(nodes: int, t_compute_4: float,
                     steps_per_epoch: int = 6000,
                     inter_island_penalty: float = 4.0) -> float:
    """t(N) = serial_compute/N + steps * ring_allreduce(N).

    ring allreduce moves 2*(N-1)/N * grad_bytes per rank per step; beyond
    one island (>= 24 nodes here) the pruned 4:1 fat-tree divides effective
    bandwidth (paper §III-A).
    """
    compute = t_compute_4 * 4 / nodes
    bw = OMNIPATH_BW / (inter_island_penalty if nodes > 24 else 1.0)
    allreduce = steps_per_epoch * 2 * (nodes - 1) / nodes * GRAD_BYTES / bw
    # per-step framework overhead (launch, host sync) ~ constant
    overhead = steps_per_epoch * 2e-3
    return compute + allreduce + overhead


def model_vs_paper() -> List[Tuple[str, float, str]]:
    # calibrate single free parameter (compute at 4 nodes) on the first row
    t4 = PAPER_TABLE1[4]
    steps = 6000
    t_compute_4 = t4 - epoch_time_model(4, 0.0, steps)     # residual=comm
    rows = []
    for n, t_paper in PAPER_TABLE1.items():
        t_model = epoch_time_model(n, t_compute_4, steps)
        err = 100 * (t_model - t_paper) / t_paper
        rows.append((f"table1_model/{n}nodes", t_model * 1e6,
                     f"paper={t_paper:.0f}s model={t_model:.0f}s "
                     f"err={err:+.1f}%"))
    t4m = epoch_time_model(4, t_compute_4, steps)
    t32m = epoch_time_model(32, t_compute_4, steps)
    eff = t4m * 4 / (t32m * 32) * 100
    rows.append(("table1_model/scaling_efficiency_32n", 0.0,
                 f"{eff:.1f}% (paper claims ~94%)"))
    return rows


_COLL_RE = re.compile(r"all-reduce")


def measured_allreduce_bytes(ranks: int) -> int:
    """Compile the hvd 3DGAN D-step for ``ranks`` host devices (subprocess)
    and return per-rank all-reduce bytes from the HLO."""
    prog = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ranks}"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.models import gan3d as G
from repro.core import hvd
from repro.launch.mesh import make_mesh
from repro import optim
from repro.launch.dryrun import collective_bytes
cfg = G.GAN3DConfig(g_fc_ch=6, g_base=16, d_base=8)
key = jax.random.PRNGKey(0)
mesh = make_mesh(({ranks},), ("data",))
d_opt = optim.rmsprop(1e-3)
def local(dp, ds, gp, batch, z):
    grads, m = jax.grad(G.d_loss, has_aux=True)(dp, gp, cfg, batch, z)
    upd, ds = hvd.DistributedOptimizer(d_opt, ("data",)).update(grads, ds, dp)
    return optim.apply_updates(dp, upd), ds
import functools
B = {ranks} * 2
gp_s = jax.eval_shape(lambda k: G.init_generator(k, cfg), key)
dp_s = jax.eval_shape(lambda k: G.init_discriminator(k, cfg), key)
ds_s = jax.eval_shape(d_opt.init, dp_s)
batch_s = {{"images": jax.ShapeDtypeStruct((B,25,25,25,1), jnp.float32),
           "energies": jax.ShapeDtypeStruct((B,), jnp.float32)}}
z_s = jax.ShapeDtypeStruct((B, cfg.latent_dim), jnp.float32)
f = jax.jit(jax.shard_map(local, mesh=mesh,
    in_specs=(P(), P(), P(), {{"images": P("data"), "energies": P("data")}}, P("data")),
    out_specs=(P(), P()), check_vma=False))
c = f.lower(dp_s, ds_s, gp_s, batch_s, z_s).compile()
cb = collective_bytes(c.as_text())
print("BYTES", sum(cb.values()))
"""
    import os
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, timeout=560)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-1500:])
    return int([l for l in r.stdout.splitlines()
                if l.startswith("BYTES")][0].split()[1])


def run(quick: bool = True) -> List[Tuple[str, float, str]]:
    rows = model_vs_paper()
    sizes = [2, 8] if quick else [2, 4, 8, 16, 32]
    per_rank = {}
    for n in sizes:
        per_rank[n] = measured_allreduce_bytes(n)
        rows.append((f"allreduce_bytes/{n}ranks", 0.0,
                     f"{per_rank[n]:,} B/rank/step"))
    # O(2P) property: per-rank bytes ~ constant in N (ring allreduce)
    vals = list(per_rank.values())
    ratio = max(vals) / max(min(vals), 1)
    rows.append(("allreduce_bytes/flatness", 0.0,
                 f"max/min={ratio:.2f} (ring allreduce: ~2x grad bytes, "
                 f"constant per rank)"))
    return rows


if __name__ == "__main__":
    for r in run(quick=False):
        print(",".join(str(x) for x in r))
