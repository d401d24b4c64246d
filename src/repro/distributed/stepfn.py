"""pjit step builders: train_step / prefill_step / serve_step with explicit
in/out shardings derived from ``repro.distributed.sharding`` strategies.

These are the programs the multi-pod dry-run lowers and the roofline
analysis reads; the same builders drive real training in
``repro.launch.train`` (on whatever mesh exists).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import optim
from repro.configs import base as cfgbase
from repro.distributed import sharding as sh
from repro.models import transformer as T


def params_struct(cfg) -> Any:
    """ShapeDtypeStruct pytree of the model params (no allocation)."""
    return jax.eval_shape(
        functools.partial(T.init_params, cfg), jax.random.PRNGKey(0))


def opt_state_struct(cfg, optimizer) -> Any:
    return jax.eval_shape(optimizer.init, params_struct(cfg))


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------

def make_train_step(cfg, optimizer, mesh: Mesh, strategy: str,
                    shape: cfgbase.InputShape, *, long_context: bool = False,
                    loss_variant: str = "plain", seq_chunk: int = 512,
                    microbatches: int = 1):
    """Returns (jitted_step, in_shardings, out_shardings).

    step(params, opt_state, batch) -> (params, opt_state, metrics).
    loss_variant: "plain" | "chunked_ce" (fused CE without the (B,S,V)
    logits tensor — beyond-paper memory optimization, see §Perf).
    microbatches > 1: gradient accumulation — the global batch is split
    along its leading dim into M microbatches scanned sequentially with
    grad accumulation (activation memory / M, identical update for
    token-mean losses).
    """
    pstruct = params_struct(cfg)
    ostruct = jax.eval_shape(optimizer.init, pstruct)
    bstruct = cfgbase.input_specs(cfg, shape)

    pspec = sh.params_pspec(pstruct, cfg, strategy, mesh)
    ospec = sh.opt_state_pspec(ostruct, pspec)
    bspec = sh.batch_pspec(bstruct, mesh, cfg, shape, strategy)

    in_shardings = (sh.named(mesh, pspec), sh.named(mesh, ospec),
                    sh.named(mesh, bspec))
    out_shardings = (in_shardings[0], in_shardings[1],
                     NamedSharding(mesh, P()))

    def loss_fn(p, b):
        if loss_variant == "chunked_ce":
            return T.lm_loss_chunked(p, cfg, b, long_context=long_context,
                                     seq_chunk=seq_chunk)
        return T.lm_loss(p, cfg, b, long_context=long_context)

    def step(params, opt_state, batch):
        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            B = batch["tokens"].shape[0]
            assert B % microbatches == 0, (B, microbatches)

            def split(a):
                return a.reshape(microbatches, B // microbatches,
                                 *a.shape[1:])

            micro = jax.tree.map(split, batch)
            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def body(carry, mb):
                acc, loss_acc = carry
                (l, m), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                acc = jax.tree.map(
                    lambda a, gi: a + gi.astype(jnp.float32) / microbatches,
                    acc, g)
                return (acc, loss_acc + l / microbatches), m

            (grads, loss), ms = jax.lax.scan(
                body, (zero_grads, jnp.zeros((), jnp.float32)), micro)
            metrics = jax.tree.map(lambda a: jnp.mean(a, axis=0), ms)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optim.apply_updates(params, updates)
        return params, opt_state, dict(metrics, loss=loss)

    jitted = jax.jit(step, in_shardings=in_shardings,
                     out_shardings=out_shardings, donate_argnums=(0, 1))
    return jitted, (pstruct, ostruct, bstruct), (in_shardings, out_shardings)


# ---------------------------------------------------------------------------
# Prefill (inference): full-sequence forward, emit ONLY last-token logits
# ---------------------------------------------------------------------------

def make_prefill_step(cfg, mesh: Mesh, strategy: str,
                      shape: cfgbase.InputShape, *,
                      long_context: bool = False):
    pstruct = params_struct(cfg)
    bstruct = cfgbase.input_specs(cfg, shape)
    pspec = sh.params_pspec(pstruct, cfg, strategy, mesh)
    bspec = sh.batch_pspec(bstruct, mesh, cfg, shape, strategy)
    in_shardings = (sh.named(mesh, pspec), sh.named(mesh, bspec))

    def prefill(params, batch):
        logits, _ = T.forward(params, cfg, batch, long_context=long_context,
                              last_only=True)
        return logits                                      # (B, 1, V)

    jitted = jax.jit(prefill, in_shardings=in_shardings)
    return jitted, (pstruct, bstruct), in_shardings


# ---------------------------------------------------------------------------
# Decode (serve_step): ONE token against a seq_len cache
# ---------------------------------------------------------------------------

def make_serve_step(cfg, mesh: Mesh, strategy: str,
                    shape: cfgbase.InputShape, *, long_context: bool = False):
    pstruct = params_struct(cfg)
    bstruct = cfgbase.input_specs(cfg, shape)
    pspec = sh.params_pspec(pstruct, cfg, strategy, mesh)
    bspec = sh.batch_pspec(bstruct, mesh, cfg, shape, strategy)
    in_shardings = (sh.named(mesh, pspec), sh.named(mesh, bspec))
    # new cache keeps the input cache's sharding; logits replicated
    cache_sharding = sh.named(mesh, bspec)["cache"]
    out_shardings = (NamedSharding(mesh, P()), cache_sharding)

    def serve(params, batch):
        logits, new_cache = T.decode_step(params, cfg, batch,
                                          long_context=long_context,
                                          capacity_moe=True)
        return logits, new_cache

    # donate the batch so the updated cache aliases the input cache buffers
    jitted = jax.jit(serve, in_shardings=in_shardings,
                     out_shardings=out_shardings,
                     donate_argnums=(1,))
    return jitted, (pstruct, bstruct), in_shardings


def make_step_for_shape(cfg, mesh, strategy, shape, optimizer=None):
    """Dispatch on the shape kind; returns (jitted, arg_structs)."""
    long_context = shape.name == "long_500k"
    if shape.kind == "train":
        optimizer = optimizer or optim.adamw(1e-4)
        jitted, structs, _ = make_train_step(cfg, optimizer, mesh, strategy,
                                             shape, long_context=long_context)
        return jitted, structs
    if shape.kind == "prefill":
        jitted, structs, _ = make_prefill_step(cfg, mesh, strategy, shape,
                                               long_context=long_context)
        return jitted, structs
    jitted, structs, _ = make_serve_step(cfg, mesh, strategy, shape,
                                         long_context=long_context)
    return jitted, structs
