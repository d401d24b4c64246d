"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

TPU adaptation of the SSD algorithm (arXiv:2405.21060): the GPU reference
parallelizes the inter-chunk recurrence with a warp-level scan; on TPU the
grid's trailing axis executes *sequentially*, so the (N, P) inter-chunk
state lives in a VMEM scratch accumulator carried across chunk steps, and
each chunk step is three MXU matmuls (C·Bᵀ score tile, M·x intra-chunk
output, state-weighted Bᵀ·x update) over an (L=chunk)-aligned tile —
exactly the structure of ``repro.models.ssm.ssd_chunked``, which is the
oracle this kernel is validated against.

Grid: (batch*heads, num_chunks); per-(bh) state resets at chunk 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dtr_ref, dtc_ref, b_ref, c_ref, y_ref,
                state_ref, *, chunk: int):
    bh = pl.program_id(0)
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    a = a_ref[bh]                                          # per-head decay rate
    x = x_ref[0, 0].astype(jnp.float32)                    # (L, P)
    dt_row = dtr_ref[0, 0].astype(jnp.float32)             # (1, L)
    dt_col = dtc_ref[0, 0].astype(jnp.float32)             # (L, 1)
    B = b_ref[0, 0].astype(jnp.float32)                    # (L, N)
    C = c_ref[0, 0].astype(jnp.float32)                    # (L, N)

    # inclusive cumulative log-decays, as a column and as a row: a
    # lower-triangular ones matmul (the MXU's cumsum), exact in f32
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = li >= lj
    tri = causal.astype(jnp.float32)                       # tri[i, j] = j <= i
    hi = jax.lax.Precision.HIGHEST
    cum_col = jax.lax.dot_general(tri, dt_col * a, (((1,), (0,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)  # (L, 1)
    cum_row = jax.lax.dot_general(dt_row * a, tri, (((1,), (1,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)  # (1, L)
    seg = jnp.sum(dt_row * a, axis=1, keepdims=True)       # (1, 1)

    # ---- intra-chunk: masked attention-like matmul (MXU) -------------------
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (L, L)
    decay = jnp.exp(cum_col - cum_row)
    gates = jnp.where(causal, decay, 0.0)
    M = scores * gates * dt_row
    y_intra = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # ---- inter-chunk: contribution of the carried state ----------------------
    state_in = state_ref[...]                              # (N, P)
    Cg = C * jnp.exp(cum_col)
    y_inter = jax.lax.dot_general(Cg, state_in, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # ---- state update ----------------------------------------------------------
    w = jnp.exp(seg - cum_col) * dt_col                    # (L, 1)
    Bw = B * w                                             # (L, N)
    new_contrib = jax.lax.dot_general(Bw, x, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
    state_ref[...] = jnp.exp(seg) * state_in + new_contrib

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)


def ssd_scan(x, dt, A, B, C, chunk: int, interpret: bool = False):
    """x: (BH, S, P); dt: (BH, S); A: (BH,); B, C: (BH, S, N).

    Heads are pre-folded into the leading dim (GQA-style groups repeated by
    the caller — see ops.py).  Returns y: (BH, S, P) in x.dtype.

    ``A`` rides in as a scalar-prefetch operand (SMEM), and ``dt`` as a
    row and a column block per chunk, so every VMEM block's last two
    dims are the array's own, as Mosaic requires.
    """
    BH, S, P = x.shape
    N = B.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    nc = S // L

    xr = x.reshape(BH, nc, L, P)
    dt_rows = dt.reshape(BH, nc, 1, L)
    dt_cols = dt.reshape(BH, nc, L, 1)
    Br = B.reshape(BH, nc, L, N)
    Cr = C.reshape(BH, nc, L, N)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, c, a: (b, c, 0, 0)),  # x
            pl.BlockSpec((1, 1, 1, L), lambda b, c, a: (b, c, 0, 0)),  # dt
            pl.BlockSpec((1, 1, L, 1), lambda b, c, a: (b, c, 0, 0)),  # dt
            pl.BlockSpec((1, 1, L, N), lambda b, c, a: (b, c, 0, 0)),  # B
            pl.BlockSpec((1, 1, L, N), lambda b, c, a: (b, c, 0, 0)),  # C
        ],
        out_specs=pl.BlockSpec((1, 1, L, P), lambda b, c, a: (b, c, 0, 0)),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
    )
    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=L),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, nc, L, P), x.dtype),
        interpret=interpret,
    )(A.astype(jnp.float32), xr, dt_rows, dt_cols, Br, Cr)
    return y.reshape(BH, S, P)
