"""Shared helpers for the kernel ops wrappers.

``INTERPRET`` is resolved once at import: Pallas kernels compile to Mosaic
on TPU and fall back to interpret mode everywhere else.  Resolving it at
module level (instead of inside each jitted wrapper) keeps the backend
check out of traced code, so it can never show up as a retrace trigger.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# True -> run Pallas kernels in interpret mode (non-TPU backends).
INTERPRET: bool = jax.default_backend() != "tpu"

# Row granularity of the flat-segmented k-means layout: every segment's
# point run is padded to a multiple of this, so each SEG_BLOCK-row block
# belongs to exactly one segment and the segmented assignment kernel can
# map block -> centroid slab with one prefetched id per block.  The
# kernel's rank-1 output block must be a multiple of the 128-lane width.
SEG_BLOCK = 128


def round_up(n: int, multiple: int) -> int:
    """Smallest multiple of ``multiple`` that is >= ``n``."""
    return ((n + multiple - 1) // multiple) * multiple


def block_and_pad(n: int, block: int, floor: int = 8) -> tuple:
    """Pick a block size for an ``n``-row input and the padded row count.

    Inputs at least ``block`` rows long keep the full block; shorter ones
    shrink to ``max(floor, n)`` so tiny traces don't pay for a full block
    of padding.  Returns ``(block_n, n_padded)`` with
    ``n_padded % block_n == 0``.
    """
    block_n = block if n >= block else max(floor, n)
    return block_n, round_up(n, block_n)


def pad_rows(x: jnp.ndarray, n_padded: int, fill) -> jnp.ndarray:
    """Pad ``x`` along axis 0 to ``n_padded`` rows with ``fill``."""
    shape = (n_padded,) + x.shape[1:]
    return jnp.full(shape, fill, x.dtype).at[: x.shape[0]].set(x)
