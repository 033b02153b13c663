"""K-means assignment Pallas TPU kernel (LERN's offline hot loop).

Distance via the MXU-friendly decomposition ||x-c||^2 = ||x||^2 - 2 x.c
+ ||c||^2 (the c.x term is a [K, D] x [D, block_n] matmul); the ||x||^2
term is constant per row and dropped from the argmin.  Feature dims are
padded to the 128-lane register width by the ops wrapper; centers stay
VMEM-resident across the grid.

Scores are laid out [K, block_n] — points along the lanes — so the
assignment comes out as one lane-dense ``[1, block_n]`` row per block:
the output array is ``[1, N]``, whose block obeys the TPU tiling rule for
any ``block_n`` that is a multiple of 128 (or all of N), also under vmap.
The argmin over the K centre rows is an unrolled first-minimum select,
the same tie rule as ``jnp.argmin``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _nearest(x: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """x [block_n, D], c [K, D] -> [1, block_n] int32 nearest-centre index."""
    cx = jax.lax.dot_general(c, x, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    c2 = jnp.sum(c * c, axis=1, keepdims=True)  # [K, 1]
    d2 = c2 - 2.0 * cx                          # [K, block_n]
    best = d2[0:1]
    idx = jnp.zeros(best.shape, jnp.int32)
    for k in range(1, c.shape[0]):
        better = d2[k:k + 1] < best
        idx = jnp.where(better, k, idx)
        best = jnp.where(better, d2[k:k + 1], best)
    return idx


def _kernel(x_ref, c_ref, o_ref):
    o_ref[...] = _nearest(x_ref[...].astype(jnp.float32),
                          c_ref[...].astype(jnp.float32))


def _seg_kernel(bseg_ref, x_ref, c_ref, o_ref):
    del bseg_ref  # consumed by the index maps (scalar prefetch)
    # c_ref[0]: this block's segment's [K, D] centroid slab
    o_ref[...] = _nearest(x_ref[...].astype(jnp.float32),
                          c_ref[0].astype(jnp.float32))


def kmeans_assign_segmented(x: jnp.ndarray, centers: jnp.ndarray,
                            block_seg: jnp.ndarray, *, block_n: int = 128,
                            interpret: bool = True) -> jnp.ndarray:
    """Segment-blocked assignment: x [P, D] (P % block_n == 0), centers
    [S, K, D], block_seg [P // block_n] int32 mapping each row block to its
    segment -> assignment [P] int32.

    The flat-segmented k-means layout pads every segment's point run to a
    multiple of ``block_n`` (``kernels.common.SEG_BLOCK``), so a block never
    straddles segments; ``block_seg`` is scalar-prefetched and drives the
    centroid BlockSpec index map — each program instance only ever sees its
    own segment's [K, D] centroid slab, not the full [S, K, D] table.
    """
    from jax.experimental.pallas import tpu as pltpu

    p, d = x.shape
    s, k, _ = centers.shape
    assert p % block_n == 0 and block_seg.shape[0] == p // block_n, \
        (p, block_n, block_seg.shape)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(p // block_n,),
        in_specs=[pl.BlockSpec((block_n, d), lambda b, bs: (b, 0)),
                  pl.BlockSpec((1, k, d), lambda b, bs: (bs[b], 0, 0))],
        out_specs=pl.BlockSpec((1, block_n), lambda b, bs: (0, b)))
    out = pl.pallas_call(
        _seg_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, p), jnp.int32),
        interpret=interpret,
    )(block_seg, x, centers)
    return out.reshape(p)


def kmeans_assign(x: jnp.ndarray, centers: jnp.ndarray, *,
                  block_n: int = 1024, interpret: bool = True) -> jnp.ndarray:
    """x [N, D] (N % block_n == 0, D % 128 == 0 — ops pads), centers [K, D]
    -> assignment [N] int32."""
    n, d = x.shape
    block_n = min(block_n, n)
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, d), lambda i: (i, 0)),
                  pl.BlockSpec(centers.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
    )(x, centers)
    return out.reshape(n)
