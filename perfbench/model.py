"""A configuration file as the serving program runs it, and its weights.

A configuration names its model family (``"family"``); the family's
module, ``families/<family>.py``, states its sizes, the program's
``ModelConfig``, its weight tree, one layer's weights as the reference
reads them and the weights one token multiplies through in a layer.
This module finds that module by the name and hands each call to it, so
a configuration of another family arrives as new files only.

The benchmark makes the weights itself, from ``--seed``, in one jitted
call on the device, so that the plain reference never reads anything the
program made.  They come as the program's parameter tree (stacked layers,
float32 master copies, norm gains stored as ``1 + scale``): that tree is
the interface the program's ``ServingEngine`` takes.
"""
from __future__ import annotations

import importlib

import jax
import numpy as np

# serving settings ``launch/serve.py`` exposes; a mix's ``engine`` block
# sets them and leaves every other engine argument at its default
ENGINE_KEYS = ("max_seq_len", "max_slots", "paged", "num_blocks",
               "prefill_batch", "prefix_cache_blocks")


def family(c: dict):
    """The family module of a configuration, or of its :func:`dims`."""
    return importlib.import_module(f"perfbench.families.{c['family']}")


def dims(c: dict) -> dict:
    """Sizes every other module of the benchmark works from, with the
    family's name under ``"family"``."""
    return dict(family(c).dims(c), family=c["family"])


def program_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    return family(c).program_config(c)


def prng_key(seed: int, tag: int = 0):
    """A PRNG key for any whole ``seed``, 64-bit ones included."""
    k = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(jax.random.fold_in(k, np.uint32(seed >> 32)),
                              tag)


def make_weights(c: dict, seed: int):
    """The configuration's weights from ``seed``: one jitted call, made on
    the device in float32 (the type the program serves them in)."""
    n = dims(c)
    tree = family(c)._tree
    return jax.jit(lambda k: tree(n, k))(prng_key(seed, 1))


def layer(c: dict, weights, i: int) -> dict:
    """Layer ``i``'s weights as a flat dict (reference side); ``c`` is the
    configuration or its :func:`dims`."""
    return family(c).layer(weights, i)
