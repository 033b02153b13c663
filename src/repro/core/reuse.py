"""Reuse Interval / Reuse Count signature extraction (paper §IV-A, Table I).

Definitions (cache-line granularity):
* occurrence positions of line c_i in the trace: r_i = (m_1 < m_2 < ... < m_Ti)
* Reuse Interval at occurrence j:  RI_{i,j} = r_{i,j+1} - r_{i,j}; the last
  occurrence has RI = -1.
* Reuse Count T_i = number of occurrences of c_i (the running count at
  position m_j is j).

Two implementations: a numpy one for the offline LERN pipeline, and a JAX
(sort-based, fixed-shape) one used by tests/property checks and by the
vectorized feature path.  Both are oracle-tested against Table I.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

RI_BIN_EDGES = (10, 100, 500)  # bins: [1,10], (10,100], (100,500], (500,inf)
NUM_RI_BINS = 4


def reuse_signature_np(lines: np.ndarray) -> Dict[str, np.ndarray]:
    """Per-access RI (forward) and running RC, plus per-unique-line data.

    Returns dict with:
      ri        int64 [M]   forward reuse interval per access (-1 if last)
      rc_run    int64 [M]   running occurrence count per access (1-based)
      uniq      int64 [N]   unique line addresses (sorted)
      inv       int64 [M]   index into uniq per access
      count     int64 [N]   total reuse count T_i per unique line
    """
    lines = np.asarray(lines, dtype=np.int64)
    m = lines.shape[0]
    uniq, inv, count = np.unique(lines, return_inverse=True,
                                 return_counts=True)
    # stable sort by (line, position): positions ascending within each line
    order = np.argsort(inv, kind="stable")
    sorted_inv = inv[order]
    sorted_pos = order.astype(np.int64)
    same_next = np.empty(m, dtype=bool)
    same_next[:-1] = sorted_inv[1:] == sorted_inv[:-1]
    same_next[-1] = False
    ri_sorted = np.where(same_next,
                         np.concatenate([sorted_pos[1:], [0]]) - sorted_pos,
                         -1)
    ri = np.empty(m, dtype=np.int64)
    ri[order] = ri_sorted
    # running count: index within the line's segment (1-based)
    seg_start = np.empty(m, dtype=bool)
    seg_start[0] = True
    seg_start[1:] = sorted_inv[1:] != sorted_inv[:-1]
    seg_id = np.cumsum(seg_start) - 1
    first_of_seg = np.flatnonzero(seg_start)
    rc_sorted = np.arange(m, dtype=np.int64) - first_of_seg[seg_id] + 1
    rc_run = np.empty(m, dtype=np.int64)
    rc_run[order] = rc_sorted
    return {"ri": ri, "rc_run": rc_run, "uniq": uniq, "inv": inv,
            "count": count}


def ri_histogram_np(lines: np.ndarray, sig: Dict[str, np.ndarray] = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-unique-line features: (F_RI [N,4] histogram, F_RC [N] counts).

    The final -1 interval of each line is excluded from the histogram, per
    Table I (c_1 RV={1,1,3,1,-1} -> F_RI={4,0,0,0})."""
    if sig is None:
        sig = reuse_signature_np(lines)
    ri, inv, n = sig["ri"], sig["inv"], sig["uniq"].shape[0]
    valid = ri >= 0
    e0, e1, e2 = RI_BIN_EDGES
    bin_idx = np.where(ri <= e0, 0, np.where(ri <= e1, 1,
                       np.where(ri <= e2, 2, 3)))
    f_ri = np.zeros((n, NUM_RI_BINS), dtype=np.int64)
    np.add.at(f_ri, (inv[valid], bin_idx[valid]), 1)
    return f_ri, sig["count"]


# ----------------------------------------------------------------------------
# JAX implementation (fixed shapes, jit-able) — the device-resident LERN
# training path (lern.train_model_batched) and the property tests.
# ----------------------------------------------------------------------------
import functools

import jax
import jax.numpy as jnp

# Padding sentinel for fixed-shape line arrays.  The device path carries
# lines as int32 (x64 is disabled); host traces are int64 but their values
# are small element offsets (and L-RPT-hashed training addresses are masked
# to <= 18 bits), so the mapping is exact.  ``lines_to_device`` checks the
# range.  PAD_LINE sorts after every real line.
PAD_LINE = np.int32(np.iinfo(np.int32).max)


def lines_to_device(lines: np.ndarray) -> np.ndarray:
    """Exact int64 -> int32 narrowing for device-side feature extraction."""
    lines = np.asarray(lines, dtype=np.int64)
    if lines.size and (lines.min() < 0 or lines.max() >= int(PAD_LINE)):
        raise ValueError("line addresses out of int32 device range")
    return lines.astype(np.int32)


@jax.jit
def reuse_signature_jax(lines: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """JAX version of per-access RI / running-RC (no unique tables)."""
    m = lines.shape[0]
    order = jnp.argsort(lines, stable=True)
    sorted_lines = lines[order]
    sorted_pos = order.astype(jnp.int32)
    nxt = jnp.concatenate([sorted_pos[1:], jnp.array([-1], jnp.int32)])
    same_next = jnp.concatenate(
        [sorted_lines[1:] == sorted_lines[:-1], jnp.array([False])])
    ri_sorted = jnp.where(same_next, nxt - sorted_pos, -1)
    ri = jnp.zeros(m, jnp.int32).at[order].set(ri_sorted)

    seg_start = jnp.concatenate(
        [jnp.array([True]), sorted_lines[1:] != sorted_lines[:-1]])
    idx = jnp.arange(m, dtype=jnp.int32)
    first_of_run = jax.lax.cummax(jnp.where(seg_start, idx, -1), axis=0)
    rc_sorted = idx - first_of_run + 1
    rc_run = jnp.zeros(m, jnp.int32).at[order].set(rc_sorted)
    return {"ri": ri, "rc_run": rc_run}


def ri_bin(ri: jnp.ndarray) -> jnp.ndarray:
    """Map a (non-negative) reuse interval to its bin index 0..3."""
    e0, e1, e2 = RI_BIN_EDGES
    return jnp.where(ri <= e0, 0,
                     jnp.where(ri <= e1, 1, jnp.where(ri <= e2, 2, 3)))


def _ri_bins_kernel(ri: jnp.ndarray) -> jnp.ndarray:
    """Per-access RI bin (-1 for no-reuse) through the Pallas kernel."""
    from repro.kernels.ri_histogram.ops import histogram

    return histogram(ri)[0]


def reuse_features_jax(lines: jnp.ndarray, n_valid: jnp.ndarray,
                       use_kernel: bool = True) -> Dict[str, jnp.ndarray]:
    """Fixed-shape per-unique-line LERN features (Table I, device-resident).

    ``lines`` is an int32 [M] array (``lines_to_device`` narrows int64
    traces exactly) whose first ``n_valid`` entries are real accesses (the
    rest is padding — any value, it is replaced by PAD_LINE).  All outputs
    are integer and therefore bitwise-identical to the numpy oracle
    (``reuse_signature_np`` + ``ri_histogram_np``) on the valid prefix,
    for any amount of padding:

      uniq    int32 [M]  sorted unique line addresses, PAD_LINE-padded
      f_ri    int32 [M,4] per-unique-line RI-bin histogram (final -1
                          interval excluded, per Table I)
      f_rc    int32 [M]  per-unique-line reuse count T_i (0 for padding)
      n_uniq  int32 []   number of real unique lines

    The RI-binning runs through the ``ri_histogram`` Pallas kernel
    (``use_kernel=False`` selects the jnp reference binning — same math,
    used to cross-check the kernel in tests).  Shapes are static, so the
    whole function vmaps/jits into the batched training program.
    """
    m = lines.shape[0]
    idx = jnp.arange(m, dtype=jnp.int32)
    lx = jnp.where(idx < n_valid, lines, PAD_LINE)
    order = jnp.argsort(lx, stable=True)          # padding sorts to the end
    sorted_lines = lx[order]
    sorted_pos = order.astype(jnp.int32)
    real = sorted_lines != PAD_LINE

    # forward reuse interval per (sorted) access: next occurrence of the
    # same line minus this position; -1 at each line's final occurrence
    nxt = jnp.concatenate([sorted_pos[1:], jnp.array([0], jnp.int32)])
    same_next = jnp.concatenate(
        [sorted_lines[1:] == sorted_lines[:-1], jnp.array([False])])
    ri_sorted = jnp.where(same_next, nxt - sorted_pos, -1)
    bins = (_ri_bins_kernel(ri_sorted) if use_kernel
            else jnp.where(ri_sorted < 0, -1, ri_bin(ri_sorted)))

    # segment id per sorted access == index into the unique-line table
    seg_start = jnp.concatenate(
        [jnp.array([True]), sorted_lines[1:] != sorted_lines[:-1]])
    sid = jnp.cumsum(seg_start.astype(jnp.int32)) - 1

    counted = real & (ri_sorted >= 0)
    f_ri = jnp.zeros((m, NUM_RI_BINS), jnp.int32).at[
        sid, jnp.maximum(bins, 0)].add(counted.astype(jnp.int32))
    f_rc = jnp.zeros(m, jnp.int32).at[sid].add(real.astype(jnp.int32))
    uniq = jnp.full(m, PAD_LINE, jnp.int32).at[sid].set(
        jnp.where(real, sorted_lines, PAD_LINE))
    n_uniq = jnp.sum((seg_start & real).astype(jnp.int32))
    return {"uniq": uniq, "f_ri": f_ri, "f_rc": f_rc, "n_uniq": n_uniq}


@functools.partial(jax.jit, static_argnames=("n_layers", "use_kernel"))
def reuse_features_flat(lines: jnp.ndarray, layer: jnp.ndarray,
                        n_valid: jnp.ndarray, n_layers: int,
                        use_kernel: bool = True) -> Dict[str, jnp.ndarray]:
    """Whole-model reuse features in one flat pass (no per-layer padding).

    The batched LERN trainer's extraction program: instead of padding every
    layer to the longest one, the full concatenated trace is sorted once by
    the composite (layer, line) key — two stable argsorts — so the padded
    volume is the trace length, not layers x max-layer.

    Requires ``layer`` to be non-decreasing over the valid prefix (each
    layer's accesses contiguous — the trainer stable-sorts the trace by
    layer first if needed): per-layer reuse intervals are then exactly the
    global position differences, bitwise-matching the per-layer numpy
    oracle.

    Returns flat per-unique tables grouped by layer (each layer's segment
    contiguous, lines ascending within it):

      uniq    int32 [M]   PAD_LINE-padded, layer-grouped unique lines
      f_ri    int32 [M,4] per-unique-line RI-bin histogram
      f_rc    int32 [M]   per-unique-line reuse count
      n_uniq  int32 [n_layers] unique-line count per layer
    """
    m = lines.shape[0]
    idx = jnp.arange(m, dtype=jnp.int32)
    valid = idx < n_valid
    lx = jnp.where(valid, lines, PAD_LINE)
    ly = jnp.where(valid, layer, n_layers)
    ord1 = jnp.argsort(lx, stable=True)
    order = ord1[jnp.argsort(ly[ord1], stable=True)]
    s_lines = lx[order]
    s_layer = ly[order]
    s_pos = order.astype(jnp.int32)
    real = s_lines != PAD_LINE

    nxt = jnp.concatenate([s_pos[1:], jnp.array([0], jnp.int32)])
    same_next = jnp.concatenate(
        [(s_lines[1:] == s_lines[:-1]) & (s_layer[1:] == s_layer[:-1]),
         jnp.array([False])])
    ri_sorted = jnp.where(same_next, nxt - s_pos, -1)
    bins = (_ri_bins_kernel(ri_sorted) if use_kernel
            else jnp.where(ri_sorted < 0, -1, ri_bin(ri_sorted)))

    seg_start = jnp.concatenate(
        [jnp.array([True]),
         (s_lines[1:] != s_lines[:-1]) | (s_layer[1:] != s_layer[:-1])])
    sid = jnp.cumsum(seg_start.astype(jnp.int32)) - 1

    counted = real & (ri_sorted >= 0)
    f_ri = jnp.zeros((m, NUM_RI_BINS), jnp.int32).at[
        sid, jnp.maximum(bins, 0)].add(counted.astype(jnp.int32))
    f_rc = jnp.zeros(m, jnp.int32).at[sid].add(real.astype(jnp.int32))
    uniq = jnp.full(m, PAD_LINE, jnp.int32).at[sid].set(
        jnp.where(real, s_lines, PAD_LINE))
    n_uniq = jnp.zeros(n_layers + 1, jnp.int32).at[s_layer].add(
        (seg_start & real).astype(jnp.int32))[:n_layers]
    return {"uniq": uniq, "f_ri": f_ri, "f_rc": f_rc, "n_uniq": n_uniq}
