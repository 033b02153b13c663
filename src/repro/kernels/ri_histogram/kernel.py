"""Reuse-interval binning Pallas TPU kernel (LERN feature extraction).

Maps each reuse interval to its F_RI bin ([1,10], (10,100], (100,500],
(500,inf); -1 = no-reuse -> bin -1) and emits per-block partial bin counts
(summed by the ops wrapper).  Pure VPU work: vectorized compares + block
reductions; one pass over HBM.

The flat interval array is viewed as ``[N / 128, 128]`` so every block is
a whole number of (8, 128) int32 tiles.  Each grid step writes its counts
as one (8, 128) tile: row ``j < NUM_BINS`` holds the per-lane count of bin
``j`` (a sublane reduction), the other rows are zero — no scalar ever
lands in VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIN_EDGES = (10, 100, 500)
NUM_BINS = 4
LANES = 128
SUBLANES = 8
TILE = SUBLANES * LANES   # elements in one int32 vreg tile


def _kernel(ri_ref, bin_ref, cnt_ref):
    ri = ri_ref[...]                                   # [rows, 128]
    e0, e1, e2 = BIN_EDGES
    b = jnp.where(ri <= e0, 0,
                  jnp.where(ri <= e1, 1, jnp.where(ri <= e2, 2, 3)))
    b = jnp.where(ri < 0, -1, b).astype(jnp.int32)
    bin_ref[...] = b
    row = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
    cnt = jnp.zeros((SUBLANES, LANES), jnp.int32)
    for j in range(NUM_BINS):
        lane_cnt = jnp.sum((b == j).astype(jnp.int32), axis=0, keepdims=True)
        cnt = jnp.where(row == j, lane_cnt, cnt)
    cnt_ref[...] = cnt


def ri_histogram(ri: jnp.ndarray, *, block_n: int = 4096,
                 interpret: bool = True):
    """ri [N] int32 (N % block_n == 0, block_n % TILE == 0) ->
    (bin_idx [N] int32, partial_counts [grid * 8, 128] int32)."""
    n = ri.shape[0]
    block_n = min(block_n, n)
    assert n % block_n == 0 and block_n % TILE == 0, (n, block_n)
    rows = block_n // LANES
    grid = (n // block_n,)
    bins, partial = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                   pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n // LANES, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((grid[0] * SUBLANES, LANES),
                                        jnp.int32)],
        interpret=interpret,
    )(ri.reshape(n // LANES, LANES))
    return bins.reshape(n), partial
