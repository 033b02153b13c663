"""The comparison that decides ``correct`` for a LERN model: the integer
feature tables must be equal, and the label tables must be a Lloyd fixed
point of those features in float64 with the paper's label order (§IV-B).
Imports nothing of the simulator.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def _features(lines: np.ndarray):
    """Per unique line (sorted): the reuse-interval histogram over the
    bins [1,10], (10,100], (100,500], (500,inf) and the reuse count
    (paper Table I; a line's last occurrence has no interval)."""
    uniq, inv, count = np.unique(np.asarray(lines, np.int64),
                                 return_inverse=True, return_counts=True)
    pos = np.arange(lines.shape[0])
    order = np.lexsort((pos, inv))
    nxt_same = np.zeros(order.size, bool)
    nxt_same[:-1] = inv[order][1:] == inv[order][:-1]
    ri = np.where(nxt_same, np.append(order[1:], 0) - order, -1)
    b = np.searchsorted(np.array([10, 100, 500]), ri, side="left")
    f_ri = np.zeros((uniq.size, 4), np.int64)
    keep = ri >= 0
    np.add.at(f_ri, (inv[order][keep], b[keep]), 1)
    return uniq, f_ri, count


class LernReference:
    """Reference features of one trace, computed once per run."""
    MIN_MULTI = 8           # fewer reused lines than this: no clusters

    def __init__(self, lines: np.ndarray, layer: np.ndarray, n_layers: int):
        self.layers = [_features(lines[layer == li]) for li in
                       range(n_layers)]

    def compare(self, model) -> Dict[str, float]:
        """feature_mismatch (count), label_gap (widest float64 distance
        excess of a line's own cluster centre over its nearest one, in
        squared normalised units), order_inversions (count) and
        center_rel_gap (widest relative gap of the model's de-normalised
        cluster centres to the float64 means of their members)."""
        mism = 0
        gap = 0.0
        inv = 0
        cgap = 0.0
        n_uniq = np.asarray(model.n_uniq)
        for li, (uniq, f_ri, count) in enumerate(self.layers):
            n = int(n_uniq[li]) if li < n_uniq.size else -1
            if n != uniq.size:
                mism += abs(n - uniq.size) + 1
                continue
            mism += int(np.sum(np.asarray(model.uniq[li, :n]) != uniq))
            multi = count > 1
            rc = np.asarray(model.rc_cluster[li, :n], np.int64)
            ri = np.asarray(model.ri_cluster[li, :n], np.int64)
            feats = np.asarray(model.features_ri[li], np.int64)
            if feats.shape != f_ri[multi].shape:
                mism += int(multi.sum()) + 1
            else:
                mism += int(np.sum(feats != f_ri[multi]))
            eligible = int(multi.sum()) >= self.MIN_MULTI
            want = multi if eligible else np.zeros_like(multi)
            mism += int(np.sum((rc >= 0) != want))
            mism += int(np.sum((ri >= 0) != want))
            if not eligible or np.any((rc >= 0) != multi) \
                    or np.any((ri >= 0) != multi):
                continue
            xrc = np.log1p(count[multi].astype(np.float64))
            lo, hi = xrc.min(), xrc.max()
            xrc = ((xrc - lo) / max(hi - lo, 1e-9))[:, None]
            raw = f_ri[multi].astype(np.float64)
            xri = raw / np.maximum(raw.sum(1, keepdims=True), 1e-9)
            g, c = _fixed_point_gap(xrc, rc[multi])
            gap = max(gap, g)
            inv += _inversions(c[:, 0])
            cgap = max(cgap, _center_gap(
                model.rc_centers[li], np.expm1(c[:, 0] * (hi - lo) + lo)))
            g, _ = _fixed_point_gap(xri, ri[multi])
            gap = max(gap, g)
            inv += _inversions(_expected_bin(raw, ri[multi]))
            cgap = max(cgap, _center_gap(model.ri_centers[li],
                                         _mean_by_label(raw, ri[multi])))
        return {"feature_mismatch": mism, "label_gap": gap,
                "order_inversions": inv, "center_rel_gap": cgap}


def _fixed_point_gap(x: np.ndarray, label: np.ndarray):
    """Centres are the means of their members; every point must lie
    nearest its own centre.  Returns (widest excess, centres by label)."""
    k = 4
    cent = np.full((k, x.shape[1]), np.nan)
    for j in range(k):
        m = label == j
        if m.any():
            cent[j] = x[m].mean(0)
    d = ((x[:, None, :] - cent[None]) ** 2).sum(-1)
    d = np.where(np.isnan(d), np.inf, d)
    own = d[np.arange(x.shape[0]), label]
    return float(np.max(own - d.min(1))), cent


def _mean_by_label(x: np.ndarray, label: np.ndarray) -> np.ndarray:
    out = np.full((4, x.shape[1]), np.nan)
    for j in range(4):
        m = label == j
        if m.any():
            out[j] = x[m].mean(0)
    return out


def _center_gap(got, want) -> float:
    """Widest relative gap over the labels that have members (a centre
    of 0 is compared absolutely)."""
    got = np.asarray(got, np.float64).reshape(want.shape)
    ok = ~np.isnan(want)
    d = np.abs(got[ok] - want[ok])
    return float(np.max(d / np.maximum(np.abs(want[ok]), 1.0),
                        initial=0.0))


def _expected_bin(raw: np.ndarray, label: np.ndarray) -> np.ndarray:
    """Mean expected RI bin of each label's member histograms."""
    out = np.full(4, np.nan)
    for j in range(4):
        m = label == j
        if m.any():
            c = raw[m].mean(0)
            out[j] = (c / max(c.sum(), 1e-9)) @ np.arange(4)
    return out


def _inversions(v: np.ndarray, tol: float = 1e-6) -> int:
    """Labels whose value is below an earlier (lower) label's by more than
    float32 rounding: the labels must run Cold..Hot / Immediate..Remote."""
    v = v[~np.isnan(v)]
    return int(sum(np.sum(v[i + 1:] < v[i] - tol) for i in range(v.size)))
