"""Train the CERN 3DGAN (the paper's §IV/§V workload) with Horovod-DP.

Full paper pipeline: deploy an environment capsule, then inside it train
the ~1M-parameter 3D convolutional ACGAN on synthetic CLIC calorimeter
showers with RMSProp, gradients exchanged by allreduce over the data axis
(one rank per device — the paper's one-rank-per-node layout).

Run:  PYTHONPATH=src python examples/train_3dgan.py --steps 100
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
          PYTHONPATH=src python examples/train_3dgan.py --steps 50
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import optim
from repro.core import hvd
from repro.data import CalorimeterSpec, generate_batch
from repro.launch.mesh import make_host_mesh
from repro.models import gan3d as G


def make_gan_steps(cfg, mesh, d_opt, g_opt):
    """Paper-faithful DP: replicated params, psum-mean gradients."""
    def d_step(dp, ds, gp, batch, z):
        grads, m = jax.grad(G.d_loss, has_aux=True)(dp, gp, cfg, batch, z)
        upd, ds = hvd.DistributedOptimizer(d_opt, ("data",)).update(grads, ds, dp)
        return optim.apply_updates(dp, upd), ds, hvd.allreduce(m, ("data",))

    def g_step(gp, gs, dp, batch, z):
        grads, m = jax.grad(G.g_loss, has_aux=True)(gp, dp, cfg, batch, z)
        upd, gs = hvd.DistributedOptimizer(g_opt, ("data",)).update(grads, gs, gp)
        return optim.apply_updates(gp, upd), gs, hvd.allreduce(m, ("data",))

    def shard(fn, n_out=3):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(), P(), P(), {"images": P("data"), "energies": P("data")},
                      P("data")),
            out_specs=(P(), P(), P()), check_vma=False))

    return shard(d_step), shard(g_step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    cfg = G.GAN3DConfig()
    mesh = make_host_mesh()
    n_dev = len(jax.devices())
    print(f"devices={n_dev}  global_batch={args.batch}  (paper: RMSProp, "
          f"allreduce DP)")

    key = jax.random.PRNGKey(0)
    gp = G.init_generator(key, cfg)
    dp = G.init_discriminator(jax.random.fold_in(key, 1), cfg)
    print(f"G params: {G.param_count(gp):,}  D params: {G.param_count(dp):,}")

    # D at half the G rate: keeps the adversary from overpowering the
    # generator in short CPU runs (paper trains far longer at scale)
    d_opt = optim.rmsprop(args.lr * 0.5, clip_norm=1.0)
    g_opt = optim.rmsprop(args.lr, clip_norm=1.0)
    ds, gs = d_opt.init(dp), g_opt.init(gp)
    d_step, g_step = make_gan_steps(cfg, mesh, d_opt, g_opt)

    spec = CalorimeterSpec()
    t0 = time.time()
    for i in range(args.steps):
        batch = {k: jnp.asarray(v)
                 for k, v in generate_batch(spec, args.batch, step=i).items()}
        key, kz1, kz2 = jax.random.split(key, 3)
        z1 = jax.random.normal(kz1, (args.batch, cfg.latent_dim))
        dp, ds, dm = d_step(dp, ds, gp, batch, z1)
        z2 = jax.random.normal(kz2, (args.batch, cfg.latent_dim))
        gp, gs, gm = g_step(gp, gs, dp, batch, z2)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  d_loss {float(dm['d_loss']):.4f}  "
                  f"g_loss {float(gm['g_loss']):.4f}  "
                  f"D(real acc) {float(dm['acc_real']):.2f}  "
                  f"D(fake acc) {float(dm['acc_fake']):.2f}")
    dt = time.time() - t0
    print(f"\n{args.steps} steps in {dt:.1f}s "
          f"({args.steps * args.batch / dt:.1f} img/s) — compare Table 2")

    # physics sanity: generated total deposition should track requested energy
    e_test = jnp.linspace(50, 400, 8)
    z = jax.random.normal(key, (8, cfg.latent_dim))
    fake = G.generator(gp, cfg, z, e_test)
    totals = jnp.sum(fake, axis=(1, 2, 3, 4))
    corr = np.corrcoef(np.asarray(e_test), np.asarray(totals))[0, 1]
    print(f"corr(requested E, generated deposition) = {corr:.3f}")


if __name__ == "__main__":
    main()
