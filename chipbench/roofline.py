"""Operations and bytes each kernel's algorithm needs, and the roofline
share they give against the chip's peaks (``peaks.json``, keyed by the
``device_kind`` JAX reports; an unknown device is an error).

The counts are the algorithm's, from the sizes of the work, never from
the padded shapes an implementation chooses:

- ri_histogram bins N reuse intervals into the four bins of the paper's
  Table I and counts them: per interval 4 comparisons and 1 count update
  (5 operations); it reads the int32 interval and writes the int32 bin
  (8 bytes).
- kmeans_assign_segmented assigns P points of D features to the nearest
  of K centres of their own segment: per point K*D*3 operations (subtract,
  square, accumulate) and K-1 comparisons; it reads the point (4*D bytes)
  and writes its int32 label (4 bytes); each call also reads every
  segment's K*D float32 centres.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
KMEANS_K = 4
KMEANS_D = 4


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "chipbench/peaks.json")
    return table["devices"][device_kind]


def ri_histogram_work(n_intervals: int):
    return 5 * n_intervals, 8 * n_intervals


def kmeans_assign_work(n_points: int, n_segment_calls: int,
                       k: int = KMEANS_K, d: int = KMEANS_D):
    """``n_points`` point assignments over all calls; ``n_segment_calls``
    the segments summed over the calls (each reads its centres once)."""
    ops = n_points * (k * d * 3 + k - 1)
    byts = n_points * (4 * d + 4) + n_segment_calls * k * d * 4
    return ops, byts


def share(ops: float, byts: float, seconds: float, device_kind: str):
    """(roofline share in %, the bound that binds) for work that took
    ``seconds`` of kernel time."""
    pk = peaks(device_kind)
    t_flops = ops / pk["flops_per_s"]
    t_bytes = byts / pk["hbm_bytes_per_s"]
    bound = "memory" if t_bytes >= t_flops else "compute"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
