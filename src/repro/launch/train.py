"""Production training launcher.

In-capsule entrypoint: the Slurm script generated by ``repro.core.slurm``
runs ``ch-run <image> -- python -m repro.launch.train --arch <id> ...`` on
every rank.  On real hardware the mesh spans the Slurm allocation (one
process per chip host); here it spans whatever devices exist.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \\
      --smoke --steps 20 --strategy dp
"""
from __future__ import annotations

import argparse
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-sized)")
    ap.add_argument("--strategy", default=None,
                    choices=["dp", "dp_tp", "fsdp_tp"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adam", "rmsprop", "sgd"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import checkpoint as ck
    from repro import optim
    from repro.configs import (default_strategy, get_config, get_smoke_config)
    from repro.configs.base import InputShape
    from repro.data import SyntheticTokenSource, TokenDatasetSpec
    from repro.distributed import stepfn
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as T

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    strategy = args.strategy or ("dp" if args.smoke else default_strategy(cfg))
    mesh = make_host_mesh()
    shape = InputShape("cli-train", args.seq_len, args.global_batch, "train")
    opt = optim.get(args.optimizer, args.lr, clip_norm=1.0)

    print(f"arch={cfg.name} family={cfg.family} strategy={strategy} "
          f"devices={len(jax.devices())} "
          f"params~{cfg.param_count()/1e6:.1f}M")

    params = T.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    step_fn, _, _ = stepfn.make_train_step(cfg, opt, mesh, strategy, shape)
    source = SyntheticTokenSource(TokenDatasetSpec(
        cfg.vocab_size, args.seq_len, args.global_batch))

    t0 = time.time()
    loss0 = None
    for i in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in source.batch(i).items()}
        if cfg.family in ("encdec", "vlm"):
            from repro.configs.base import synthesize_inputs
            extra = synthesize_inputs(cfg, shape, jax.random.PRNGKey(i))
            extra.update({k: batch[k] for k in batch if k in extra})
            batch = extra
        params, opt_state, m = step_fn(params, opt_state, batch)
        loss = float(m["loss"])
        loss0 = loss0 if loss0 is not None else loss
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {loss:.4f}")
        if args.ckpt_dir and args.ckpt_every and (i + 1) % args.ckpt_every == 0:
            ck.save(args.ckpt_dir, i + 1, {"params": params, "opt": opt_state})
    print(f"{args.steps} steps in {time.time()-t0:.1f}s; "
          f"loss {loss0:.4f} -> {loss:.4f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
