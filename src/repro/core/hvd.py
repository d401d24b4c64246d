"""Horovod-style data parallelism over jax.shard_map — the paper's §II-H.

The paper's recipe: take a single-process TensorFlow script, add four calls
(`hvd.init()`, pin one rank per node, wrap the optimizer in
``DistributedOptimizer``, broadcast initial variables) and run it under
``mpiexec``.  Gradient exchange is MPI *allreduce* — explicitly contrasted
with TensorFlow's parameter-server architecture (see
``repro.core.paramserver`` for that baseline).

The JAX mapping: one Horovod rank = one mesh slice along the data axes.
``allreduce`` = ``lax.pmean`` inside ``shard_map`` (XLA lowers it to the
ICI ring reduce — the same ring allreduce Horovod uses over OmniPath).
``make_train_step`` returns the paper-faithful replicated-weights DP step:
params/opt-state replicated (in_specs P()), batch sharded on dim 0, grads
pmean'd, every rank applies the identical update — bitwise-identical
replicas, exactly Horovod's contract.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


# ---------------------------------------------------------------------------
# Inside-shard_map collective API (Horovod vocabulary)
# ---------------------------------------------------------------------------

def rank(axes: Sequence[str]) -> jnp.ndarray:
    """Linearized rank across ``axes`` (row-major, like MPI_Comm_rank)."""
    r = jnp.zeros((), jnp.int32)
    for ax in axes:
        r = r * lax.axis_size(ax) + lax.axis_index(ax)
    return r


def size(axes: Sequence[str]) -> int:
    s = 1
    for ax in axes:
        s *= lax.axis_size(ax)
    return s


def allreduce(x, axes: Sequence[str], average: bool = True):
    op = lax.pmean if average else lax.psum
    return jax.tree.map(lambda a: op(a, tuple(axes)), x)


def allgather(x, axes: Sequence[str]):
    def g(a):
        for ax in reversed(tuple(axes)):
            a = lax.all_gather(a, ax, axis=0)
            a = a.reshape((-1,) + a.shape[2:]) if a.ndim > 1 else a
        return a
    return jax.tree.map(g, x)


def hierarchical_allreduce(x, inner: Sequence[str], outer: Sequence[str],
                           average: bool = True):
    """Pod-aware allreduce: reduce-scatter over the ``inner`` (intra-pod)
    axes, allreduce the shard over the ``outer`` (inter-pod) axes, then
    all-gather back over ``inner``.

    Beyond-paper optimization (DESIGN.md §3): the inter-pod link carries
    1/|inner| of the gradient bytes instead of all of them — the same
    bandwidth shape as the paper's pruned 4:1 inter-island fat-tree, where
    hierarchical reduction is what kept their 32-node scaling near-linear.
    """
    inner, outer = tuple(inner), tuple(outer)
    n_inner = 1
    for ax in inner:
        n_inner *= lax.axis_size(ax)
    denom = float(n_inner)
    for ax in outer:
        denom *= lax.axis_size(ax)

    def per_leaf(a):
        flat = a.reshape(-1)
        pad = (-flat.shape[0]) % n_inner
        if pad:
            flat = jnp.pad(flat, (0, pad))
        shard = lax.psum_scatter(flat, inner, scatter_dimension=0, tiled=True)
        shard = lax.psum(shard, outer)
        full = lax.all_gather(shard, inner, axis=0, tiled=True)
        if pad:
            full = full[:-pad]
        out = full.reshape(a.shape)
        return out / denom if average else out

    return jax.tree.map(per_leaf, x)


def broadcast(x, axes: Sequence[str], root: int = 0):
    """Broadcast from linearized rank ``root`` (Horovod's initial-variable
    broadcast).  Implemented as a masked psum — one allreduce, no tree."""
    r = rank(axes)

    def b(a):
        mask = (r == root).astype(a.dtype)
        return lax.psum(a * mask, tuple(axes))
    return jax.tree.map(b, x)


# ---------------------------------------------------------------------------
# DistributedOptimizer
# ---------------------------------------------------------------------------

class DistributedOptimizer:
    """Wraps a ``repro.optim`` optimizer: allreduce grads before update.

    Only meaningful inside shard_map (the paper's rank context).
    """

    def __init__(self, optimizer, axes: Sequence[str]):
        self.inner = optimizer
        self.axes = tuple(axes)

    def init(self, params):
        return self.inner.init(params)

    def update(self, grads, state, params):
        grads = allreduce(grads, self.axes, average=True)
        return self.inner.update(grads, state, params)


# ---------------------------------------------------------------------------
# The paper-faithful replicated-DP train step
# ---------------------------------------------------------------------------

def _batch_specs(batch, axes):
    spec = P(tuple(axes))
    return jax.tree.map(lambda _: spec, batch)


def make_train_step(loss_fn: Callable, optimizer, mesh: Mesh,
                    axes: Sequence[str] = ("data",),
                    donate: bool = True,
                    hierarchical: bool = False) -> Callable:
    """Returns jitted ``step(params, opt_state, batch) ->
    (params, opt_state, metrics)`` with Horovod-DP semantics:

    * params & optimizer state replicated on every chip,
    * batch sharded along its leading dim over ``axes``,
    * grads pmean'd (ring allreduce), update applied identically everywhere.

    hierarchical=True (multi-pod meshes): gradients take the pod-aware
    reduce-scatter/allreduce/all-gather path instead of one flat allreduce.
    """
    axes = tuple(axes)
    dist_opt = DistributedOptimizer(optimizer, axes)
    inner = tuple(a for a in axes if a != "pod")
    outer = tuple(a for a in axes if a == "pod")

    def local_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        if hierarchical and outer:
            grads = hierarchical_allreduce(grads, inner, outer)
            updates, opt_state = optimizer.update(grads, opt_state, params)
        else:
            updates, opt_state = dist_opt.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                              params, updates)
        metrics = dict(metrics, loss=loss)
        metrics = allreduce(metrics, axes, average=True)
        return params, opt_state, metrics

    def step(params, opt_state, batch):
        sharded = jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(P(), P(), _batch_specs(batch, axes)),
            out_specs=(P(), P(), P()),
            check_vma=False)
        return sharded(params, opt_state, batch)

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def make_eval_step(loss_fn: Callable, mesh: Mesh,
                   axes: Sequence[str] = ("data",)) -> Callable:
    axes = tuple(axes)

    def local_eval(params, batch):
        loss, metrics = loss_fn(params, batch)
        return allreduce(dict(metrics, loss=loss), axes, average=True)

    def step(params, batch):
        return jax.shard_map(
            local_eval, mesh=mesh,
            in_specs=(P(), _batch_specs(batch, axes)),
            out_specs=P(), check_vma=False)(params, batch)

    return jax.jit(step)
