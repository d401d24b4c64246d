"""Pallas TPU grouped expert matmul: the SwiGLU FFN of the experts a chip
holds, over rows sorted by expert.

The expert-share layer (``models/moe.py``) lays a step's assignments to
held experts out as ``m`` rows sorted by expert: ``group_sizes[g]`` rows
of expert ``g`` follow those of expert ``g - 1``, and rows past
``sum(group_sizes)`` are padding.  One call computes, for every row ``r``
of group ``g``,

    out[r] = (silu(x[r] @ wg[g]) * (x[r] @ wi[g])) @ wo[g]

with no capacity and no padding to a per-expert size: a group spans as
many rows as the router gave it.

TPU adaptation (after the megablox grouped matmul): rows are cut into
tiles of ``tm``.  A *visit* is one (group, row tile) pair in which the
group has rows; the visits are listed in row-tile order, so a tile that
two groups share is visited twice in a row and its output block stays
in VMEM between the two.  The visit table (group id and row tile of each
visit), the groups' row offsets and the index of group 0's weights ride
in as scalar-prefetch operands, so each visit's DMAs (the row tile, and
its group's weight tiles) are known before its body runs.  The weights
may be every layer's, stacked: a layer's groups are then a block of
them, picked by that index, and no layer's weights are copied out of the
stack.  The grid is ``(visits, F tiles)`` with room
for the most visits any routing needs; the visits past the step's own
count repeat its last one, so they fetch nothing new and compute
nothing (a step whose tokens route to no held expert runs no visit at
all, and the grid is never empty).  Inside a visit the F axis is walked
in tiles of ``tf``: gate and up tiles ``(d, tf)``, the activation, and
the down tile ``(tf, d)`` accumulate the row tile's ``(tm, d)`` output in
float32 scratch; the last F tile writes the rows of the visit's group
and keeps the rows of the tile's other groups.  Weights are read at the
dtype they are stored in and cast to the rows' dtype tile by tile in
VMEM, so float32 masters are read once, with no bfloat16 copy.

Validated against ``repro.kernels.ref.expert_gmm_ref`` in interpret mode
on the CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT = 64 * 1024 * 1024


def f_tile(F: int) -> int:
    """The F tile: the largest of 512, 256 and 128 that divides ``F``,
    else ``F`` whole."""
    return next((t for t in (512, 256, 128) if F % t == 0), F)


def visits(group_sizes, m: int, tm: int):
    """The visit table of rows sorted by group: ``(group_ids, row_tiles,
    offsets, n)``.  Visit ``v < n`` computes group ``group_ids[v]``'s rows
    in row tile ``row_tiles[v]``; ``offsets[g]`` is group ``g``'s first
    row (``offsets[G]`` the end of the last).  Entries past ``n`` repeat
    the last visit.  The table has ``m // tm + G - 1`` entries, the most
    visits any routing needs."""
    G = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    n = jnp.sum(tiles)
    vend = jnp.cumsum(tiles)                      # visits up to group g
    v = jnp.minimum(jnp.arange(m // tm + G - 1, dtype=jnp.int32),
                    jnp.maximum(n - 1, 0))
    gid = jnp.minimum(jnp.searchsorted(vend, v, side="right",
                                       method="compare_all"),
                      G - 1).astype(jnp.int32)
    row_tile = first[gid] + v - (vend[gid] - tiles[gid])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return gid, row_tile.astype(jnp.int32), offsets, n


def _kernel(n_ref, gid_ref, tile_ref, off_ref, x_ref, wi_ref, wg_ref,
            wo_ref, o_ref, acc_ref, *, tm: int):
    # n_ref: (visits this call, index of group 0's weights)
    v = pl.program_id(0)
    j = pl.program_id(1)
    cdt = x_ref.dtype
    live = v < n_ref[0]

    @pl.when(live & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _accumulate():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[...].astype(cdt),
                    preferred_element_type=jnp.float32)
        u = jnp.dot(x, wi_ref[...].astype(cdt),
                    preferred_element_type=jnp.float32)
        h = (g / (1.0 + jnp.exp(-g)) * u).astype(cdt)
        acc_ref[...] += jnp.dot(h, wo_ref[...].astype(cdt),
                                preferred_element_type=jnp.float32)

    @pl.when(live & (j == pl.num_programs(1) - 1))
    def _store():
        tile = tile_ref[v]
        grp = gid_ref[v]

        @pl.when((v == 0) | (tile != tile_ref[jnp.maximum(v - 1, 0)]))
        def _fresh():               # first visit of this row tile
            o_ref[...] = jnp.zeros_like(o_ref)

        row = tile * tm + jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
        mine = (row >= off_ref[grp]) & (row < off_ref[grp + 1])
        o_ref[...] = jnp.where(mine, acc_ref[...],
                               o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def expert_gmm(x, wi, wg, wo, group_sizes, first=0, *, tm: int = 128,
               interpret: bool = False):
    """x: (m, d) rows sorted by group, ``m`` a multiple of ``tm``;
    group_sizes: (G,) int32 with ``sum <= m``; wi, wg: (W, d, F) and wo:
    (W, F, d), ``W >= G``, group ``g``'s weights at index ``first + g``
    (``first`` an int32 scalar, traced or not).  Returns ``(out (m, d) in
    x.dtype, executed)``: ``executed`` (int32) is the rows the kernel ran,
    ``tm`` per visit, tile padding included.  Rows past
    ``sum(group_sizes)`` are not computed and hold no defined value."""
    m, d = x.shape
    F = wi.shape[2]
    assert m % tm == 0, (m, tm)
    tf = f_tile(F)
    gid, row_tile, offsets, n = visits(group_sizes, m, tm)
    nf = F // tf
    # past the step's visits, hold the F tile too: no new weight fetch
    last = lambda v, j, n: jnp.where(v < n[0], j, nf - 1)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(gid.shape[0], nf),
        in_specs=[
            pl.BlockSpec((tm, d), lambda v, j, n, g, t, o: (t[v], 0)),
            pl.BlockSpec((pl.squeezed, d, tf), lambda v, j, n, g, t, o: (
                n[1] + g[v], 0, last(v, j, n))),
            pl.BlockSpec((pl.squeezed, d, tf), lambda v, j, n, g, t, o: (
                n[1] + g[v], 0, last(v, j, n))),
            pl.BlockSpec((pl.squeezed, tf, d), lambda v, j, n, g, t, o: (
                n[1] + g[v], last(v, j, n), 0)),
        ],
        out_specs=pl.BlockSpec((tm, d),
                               lambda v, j, n, g, t, o: (t[v], 0)),
        scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="expert_gmm",
    )(jnp.stack([n, jnp.asarray(first, jnp.int32)]), gid, row_tile,
      offsets, x, wi, wg, wo)
    return out, n * tm
