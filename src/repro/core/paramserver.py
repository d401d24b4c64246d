"""Parameter-server baseline — the architecture the paper's Horovod replaces.

TensorFlow's classic distributed mode: workers push gradients to central
parameter servers, which apply the update and serve fresh parameters back.
On a flat collective fabric this costs O(N · |params|) on the busiest link
(gather at the server + re-broadcast) versus ring allreduce's O(2 · |params|)
per link — the reason the paper (and Horovod) moved to allreduce.

We express the PS communication pattern with ``lax`` collectives so the
dry-run HLO exposes the contrast measurably: ``all_gather`` of the full
gradient pytree (server ingest) followed by a masked-psum broadcast of the
updated params (server egress).  ``benchmarks/allreduce_vs_ps.py`` parses
both programs' collective bytes out of the compiled HLO.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import hvd


def make_train_step(loss_fn: Callable, optimizer, mesh: Mesh,
                    axes: Sequence[str] = ("data",),
                    donate: bool = True) -> Callable:
    """Parameter-server-patterned ``step(params, opt_state, batch)``."""
    axes = tuple(axes)

    def local_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)

        # --- workers -> server: gather EVERY worker's full gradient -------
        gathered = jax.tree.map(
            lambda g: lax.all_gather(g, axes, axis=0), grads)

        # --- server applies the update (replica 0 is "the server"; all
        # replicas execute the same arithmetic on the gathered copy, which
        # is how a PS round looks from the collective-traffic viewpoint) ---
        mean_grads = jax.tree.map(lambda g: jnp.mean(g, axis=0), gathered)
        updates, opt_state = optimizer.update(mean_grads, opt_state, params)
        new_params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                  params, updates)

        # --- server -> workers: broadcast refreshed parameters ------------
        new_params = hvd.broadcast(new_params, axes, root=0)

        metrics = hvd.allreduce(dict(metrics, loss=loss), axes)
        return new_params, opt_state, metrics

    def step(params, opt_state, batch):
        return jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(P(), P(), jax.tree.map(lambda _: P(tuple(axes)), batch)),
            out_specs=(P(), P(), P()),
            check_vma=False)(params, opt_state, batch)

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())
