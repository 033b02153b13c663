"""jit'd wrapper: pads to the block size, folds partial counts."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import INTERPRET, pad_rows, round_up
from .kernel import LANES, NUM_BINS, SUBLANES, TILE, ri_histogram

BLOCK = 4096


@jax.jit
def histogram(ri: jnp.ndarray):
    """ri [N] int32 -> (bin_idx [N] int32, counts [4] int32)."""
    n = ri.shape[0]
    block = min(BLOCK, round_up(max(n, 1), TILE))
    rp = pad_rows(ri, round_up(max(n, 1), block), -1)
    bins, partial = ri_histogram(rp, block_n=block, interpret=INTERPRET)
    counts = partial.reshape(-1, SUBLANES, LANES)[:, :NUM_BINS].sum((0, 2))
    return bins[:n], counts
