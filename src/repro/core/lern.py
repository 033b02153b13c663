"""LERN — clustering-based learning & prediction of accelerator reuse
(paper §IV).  Pipeline:

    per-layer trace -> cache-line collapse (optionally through the L-RPT
    hash, §VI-J) -> reuse signature -> (F_RI, F_RC) features -> two
    K-means(k=4) -> semantic annotation -> per-line (RC_cluster, RI_cluster)
    lookup tables, loaded layer-by-layer into the L-RPT at runtime.

Three training entry points:

* ``train_model_batched`` — the production path.  All layers of a
  (model x accel-config) train as one device program pair: flat
  whole-trace feature extraction (``reuse.reuse_features_flat``: one
  composite (layer, line) sort + ``ri_histogram`` Pallas binning) and one
  jitted k-means call over every layer (``_fit_groups``: layers vmapped
  in power-of-two capacity buckets).  No per-layer Python loop touches
  the hot path; only the O(k) semantic annotation runs on the host.
* ``train`` — the host-reference path: per-layer numpy feature oracle +
  the same shared jitted fit at the same bucket shapes.  Because every
  floating-point step lives in ``_fit_layer`` (shared) and the feature
  tables are integers, the two paths produce the same cluster tables
  (centres equal up to float reassociation under vmap;
  tests/test_lern_batched).
* ``train_host_numpy`` — the seed-era per-layer pipeline, kept only as
  the bench_lern.json perf baseline.

Lines with a single occurrence are assigned the No-Reuse cluster (-1, -1).
The model stores stacked per-layer lookup arrays (``uniq`` / ``rc_cluster``
/ ``ri_cluster`` — [L, N] device-friendly tables consumed directly by
``lrpt.pack_tables`` and ``sim.trace_clusters``); ``model.layers`` offers
per-layer views for analysis code.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import kmeans as km
from .reuse import (NUM_RI_BINS, PAD_LINE, RI_BIN_EDGES, lines_to_device,
                    reuse_features_flat, reuse_signature_np, ri_histogram_np)
from .tracegen import Trace

# correct-bin sets per RI cluster label for the §IV-D accuracy metric:
# Immediate<->{bin0}, Near<->{bin0,bin1}, Far<->{bin1,bin2}, Remote<->{bin2,bin3}
_CORRECT_BINS = {0: (0,), 1: (0, 1), 2: (1, 2), 3: (2, 3)}

MIN_MULTI = 8  # need enough multi-occurrence lines for 4 clusters

# How the batched trainers run their k-means fits:
#   "bucketed"  — layers padded into power-of-two capacity buckets, each
#                 bucket vmapped over `_fit_layer` (the oracle path: same
#                 cluster tables as the per-layer host reference `train`).
#   "segmented" — all layers' points concatenated into ONE flat array with a
#                 segment-id column; seeding and the Lloyd loop run as
#                 segment-wise reductions (`kmeans.kmeans_fit_segmented`) —
#                 no capacity padding, one dispatch for the whole family.
#                 Cluster-assignment-equal to the bucketed oracle (same
#                 labels; centroids agree to FP reassociation).
#   "auto"      — segmented (it wins in both regimes; the bucketed oracle
#                 stays reachable via REPRO_LERN_FIT=bucketed).
FIT_ENGINE = os.environ.get("REPRO_LERN_FIT", "auto")


def resolve_engine(engine: Optional[str] = None) -> str:
    """Resolve a fit-engine override (or the module default) to the
    concrete engine name."""
    e = engine or FIT_ENGINE
    if e == "auto":
        e = "segmented"
    if e not in ("bucketed", "segmented"):
        raise ValueError(f"unknown LERN fit engine {e!r} "
                         "(expected bucketed|segmented|auto)")
    return e


@contextlib.contextmanager
def fit_engine_override(engine: Optional[str]):
    """Temporarily pin the module-default fit engine (``FIT_ENGINE``) —
    how ``exp.ExecPlan.fit_engine`` reaches call sites that consult the
    default at fit time.  ``None`` is a no-op (keep the ambient default);
    spawn pool workers get the same pin via ``sweep._worker_init``."""
    global FIT_ENGINE
    if engine is None:
        yield
        return
    resolve_engine(engine)  # validate eagerly, before any fit runs
    prev = FIT_ENGINE
    FIT_ENGINE = engine
    try:
        yield
    finally:
        FIT_ENGINE = prev


def _bucket(n: int) -> int:
    """Next power of two (>= 8): the fixed-shape padding capacity."""
    return max(8, 1 << (int(n) - 1).bit_length())


@dataclasses.dataclass
class LayerClusters:
    """Per-layer view over the trained model (analysis/tests interface)."""
    uniq: np.ndarray         # [N] unique (possibly hashed) line addresses
    rc_cluster: np.ndarray   # [N] 0..3 or -1 (No Reuse)
    ri_cluster: np.ndarray   # [N] 0..3 or -1
    rc_centers: np.ndarray   # [4] de-normalized, label-ordered (Cold..Hot)
    ri_centers: np.ndarray   # [4, 4] de-normalized, label-ordered
    features_ri: np.ndarray  # [n_multi, 4] raw histograms (Fig. 5 PCA plots)
    _sil: Optional[float] = None

    def silhouette(self) -> float:
        """RI-cluster silhouette (Fig. 5), computed lazily from the stored
        features — keeps the O(n^2) score out of the training hot path."""
        if self._sil is None:
            labels = self.ri_cluster[self.rc_cluster >= 0]
            if labels.shape[0] != self.features_ri.shape[0] or \
                    labels.shape[0] < MIN_MULTI:
                self._sil = 0.0
            else:
                raw = self.features_ri.astype(np.float64)
                xri = raw / np.maximum(raw.sum(1, keepdims=True), 1e-9)
                self._sil = km.silhouette_score(xri, labels)
        return self._sil


@dataclasses.dataclass
class LernModel:
    """Trained LERN predictor for one (ML model x accel config).

    The lookup tables are stacked fixed-shape arrays (padded with
    PAD_LINE / -1) so the L-RPT loader and the sweep engine's artifact
    loader consume them as flat device-friendly gathers instead of
    per-layer Python dicts."""
    uniq: np.ndarray        # [L, N] int64, per-layer sorted, PAD_LINE-padded
    rc_cluster: np.ndarray  # [L, N] int8, -1 = No Reuse / padding
    ri_cluster: np.ndarray  # [L, N] int8
    n_uniq: np.ndarray      # [L] int32
    rc_centers: np.ndarray  # [L, 4] float32, label-ordered (Cold..Hot)
    ri_centers: np.ndarray  # [L, 4, 4] float32, label-ordered
    features_ri: List[np.ndarray]  # ragged [n_multi_i, 4] (Fig. 5)
    hash_fn: Optional[Callable[[np.ndarray], np.ndarray]] = None

    @property
    def n_layers(self) -> int:
        return self.uniq.shape[0]

    @property
    def layers(self) -> List[LayerClusters]:
        """Per-layer views (sliced to the real unique count)."""
        views = getattr(self, "_views", None)
        if views is None:
            views = [LayerClusters(
                uniq=self.uniq[li, :n], rc_cluster=self.rc_cluster[li, :n],
                ri_cluster=self.ri_cluster[li, :n],
                rc_centers=self.rc_centers[li], ri_centers=self.ri_centers[li],
                features_ri=self.features_ri[li])
                for li, n in enumerate(self.n_uniq)]
            object.__setattr__(self, "_views", views)
        return views

    @classmethod
    def from_layers(cls, layers: List[LayerClusters],
                    hash_fn: Optional[Callable] = None) -> "LernModel":
        """Stack per-layer results into the fixed-shape model tables."""
        n_tab = _bucket(max((lc.uniq.shape[0] for lc in layers), default=1))
        n_l = len(layers)
        uniq = np.full((n_l, n_tab), int(PAD_LINE), np.int64)
        rc = np.full((n_l, n_tab), -1, np.int8)
        ri = np.full((n_l, n_tab), -1, np.int8)
        n_uniq = np.zeros(n_l, np.int32)
        rc_c = np.zeros((n_l, 4), np.float32)
        ri_c = np.zeros((n_l, 4, NUM_RI_BINS), np.float32)
        for li, lc in enumerate(layers):
            n = lc.uniq.shape[0]
            uniq[li, :n] = lc.uniq
            rc[li, :n] = lc.rc_cluster
            ri[li, :n] = lc.ri_cluster
            n_uniq[li] = n
            rc_c[li] = lc.rc_centers
            ri_c[li] = lc.ri_centers
        return cls(uniq=uniq, rc_cluster=rc, ri_cluster=ri, n_uniq=n_uniq,
                   rc_centers=rc_c, ri_centers=ri_c,
                   features_ri=[lc.features_ri for lc in layers],
                   hash_fn=hash_fn)

    def replace_layers(self, layer_idxs, other: "LernModel") -> "LernModel":
        """New model with ``layer_idxs`` rows swapped in from ``other``
        (the online-LERN retrain hook updates tables in place this way)."""
        n_tab = max(self.uniq.shape[1], other.uniq.shape[1])

        def expand(a: np.ndarray, pad) -> np.ndarray:
            out = np.full((a.shape[0], n_tab), pad, a.dtype)
            out[:, :a.shape[1]] = a
            return out

        uniq = expand(self.uniq, int(PAD_LINE))
        rc = expand(self.rc_cluster, -1)
        ri = expand(self.ri_cluster, -1)
        n_uniq = self.n_uniq.copy()
        rc_c = self.rc_centers.copy()
        ri_c = self.ri_centers.copy()
        feats = list(self.features_ri)
        for li in layer_idxs:
            n = int(other.n_uniq[li])
            uniq[li], rc[li], ri[li] = int(PAD_LINE), -1, -1
            uniq[li, :n] = other.uniq[li, :n]
            rc[li, :n] = other.rc_cluster[li, :n]
            ri[li, :n] = other.ri_cluster[li, :n]
            n_uniq[li] = n
            rc_c[li] = other.rc_centers[li]
            ri_c[li] = other.ri_centers[li]
            feats[li] = other.features_ri[li]
        return LernModel(uniq=uniq, rc_cluster=rc, ri_cluster=ri,
                         n_uniq=n_uniq, rc_centers=rc_c, ri_centers=ri_c,
                         features_ri=feats, hash_fn=self.hash_fn)


# ---------------------------------------------------------------------------
# shared jitted per-layer fit (the single source of floating-point truth)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _fit_layer(f_ri: jnp.ndarray, f_rc: jnp.ndarray, n_multi: jnp.ndarray,
               key: jnp.ndarray, use_kernel: Optional[bool] = None) -> Dict:
    """Fit RC + RI clusters for one layer's compacted feature tables.

    ``f_ri`` [N, 4] / ``f_rc`` [N] hold the multi-occurrence lines in the
    first ``n_multi`` rows (uniq order), zero-padded to the fixed capacity
    N.  Fixed-shape and mask-driven, so ``train_model_batched`` vmaps it
    over layers while ``train_layer`` calls it per layer at the same
    padded shape — both give the same cluster tables.
    """
    n = f_rc.shape[0]
    cmask = jnp.arange(n, dtype=jnp.int32) < n_multi
    # --- RC clustering (1-D, log1p + min-max normalized) -------------------
    xrc = jnp.log1p(f_rc.astype(jnp.float32))[:, None]
    lo = jnp.min(jnp.where(cmask[:, None], xrc, jnp.inf), 0)
    hi = jnp.max(jnp.where(cmask[:, None], xrc, -jnp.inf), 0)
    xn = jnp.where(cmask[:, None],
                   (xrc - lo) / jnp.maximum(hi - lo, 1e-9), 0.0)
    rc_res = km.kmeans_fit_masked(xn, cmask, jax.random.fold_in(key, 0),
                                  k=4, use_kernel=use_kernel)
    rc_centers = jnp.expm1(rc_res.centers * (hi - lo) + lo).reshape(-1)
    # --- RI clustering (4-D histogram rows, L1-normalized) -----------------
    raw = f_ri.astype(jnp.float32)
    xri = jnp.where(cmask[:, None],
                    raw / jnp.maximum(raw.sum(1, keepdims=True), 1e-9), 0.0)
    ri_res = km.kmeans_fit_masked(xri, cmask, jax.random.fold_in(key, 1),
                                  k=4, use_kernel=use_kernel)
    # de-normalized centers: mean raw histogram of each cluster's members
    oh = jax.nn.one_hot(ri_res.assign, 4, dtype=jnp.float32) \
        * cmask[:, None].astype(jnp.float32)
    cnt = jnp.sum(oh, 0)
    ri_centers = (jnp.dot(oh.T, raw, precision=jax.lax.Precision.HIGHEST)
                  / jnp.maximum(cnt, 1.0)[:, None])
    return {"rc_assign": rc_res.assign, "rc_centers": rc_centers,
            "rc_centers_norm": rc_res.centers.reshape(-1),
            "ri_assign": ri_res.assign, "ri_centers": ri_centers}


def _seed_keys(seeds: jnp.ndarray) -> jnp.ndarray:
    """[n] int32 seeds -> [n, 2] uint32 k-means keys, built inside the
    calling jitted program (no eager dispatch or read-back per key).  A
    32-bit seed's key words are ``[0, seed]``, so each row is bitwise
    ``jax.random.PRNGKey(seed)`` for 0 <= seed < 2**31."""
    return jax.vmap(jax.random.PRNGKey)(seeds)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _fit_groups(groups, use_kernel: Optional[bool] = None):
    """All layers' k-means fits as one jitted device program.

    ``groups`` is a tuple of capacity buckets — each a
    ``(f_ri [G, cap, 4], f_rc [G, cap], n_multi [G], seeds [G])`` tuple
    of layers padded to the same power-of-two point count, ``seeds`` the
    int32 k-means seed of each layer.  Each bucket is vmapped; the whole
    tuple compiles (and dispatches) as a single XLA program, so there is
    no per-layer Python k-means loop and small layers don't pay the
    largest layer's padding."""
    fit = functools.partial(_fit_layer, use_kernel=use_kernel)
    return tuple(jax.vmap(fit)(f_ri, f_rc, nm, _seed_keys(seeds))
                 for f_ri, f_rc, nm, seeds in groups)


@functools.partial(jax.jit, static_argnames=("n_seg",))
def _seg_prep(f_ri: jnp.ndarray, f_rc: jnp.ndarray, seg: jnp.ndarray,
              seeds: jnp.ndarray, n_seg: int) -> Dict:
    """Normalize the flat feature rows into the combined 2*n_seg-segment
    point array (RC half zero-padded to the RI feature width — distances
    are unchanged).  Elementwise-identical to ``_fit_layer``'s
    normalization (log1p + per-segment min-max for RC, row L1 for RI).
    ``seeds`` [n_seg] int32 become the segments' keys here, in-program."""
    p = f_rc.shape[0]
    valid = seg < n_seg
    segc = jnp.minimum(seg, n_seg - 1)
    xrc = jnp.log1p(f_rc.astype(jnp.float32))
    lo = jax.ops.segment_min(jnp.where(valid, xrc, jnp.inf), segc,
                             num_segments=n_seg)
    hi = jax.ops.segment_max(jnp.where(valid, xrc, -jnp.inf), segc,
                             num_segments=n_seg)
    rng = jnp.maximum(hi - lo, 1e-9)
    xn = jnp.where(valid, (xrc - lo[segc]) / rng[segc], 0.0)
    x_rc = jnp.zeros((p, NUM_RI_BINS), jnp.float32).at[:, 0].set(xn)
    raw = f_ri.astype(jnp.float32)
    x_ri = jnp.where(valid[:, None],
                     raw / jnp.maximum(raw.sum(1, keepdims=True), 1e-9), 0.0)
    xx = jnp.concatenate([x_rc, x_ri], axis=0)
    seg2 = jnp.concatenate([jnp.where(valid, seg, 2 * n_seg),
                            jnp.where(valid, seg + n_seg, 2 * n_seg)])
    keys = _seed_keys(seeds)
    keys2 = jnp.concatenate([
        jax.vmap(lambda kk: jax.random.fold_in(kk, 0))(keys),
        jax.vmap(lambda kk: jax.random.fold_in(kk, 1))(keys)])
    return {"xx": xx, "seg2": seg2, "keys2": keys2, "lo": lo, "hi": hi}


@functools.partial(jax.jit, static_argnames=("n_seg",))
def _seg_post(assign2: jnp.ndarray, centers2: jnp.ndarray,
              f_ri: jnp.ndarray, seg: jnp.ndarray, lo: jnp.ndarray,
              hi: jnp.ndarray, n_seg: int) -> Dict:
    """Host-facing fit tables from the combined segmented fit result:
    de-normalized RC centers (expm1, exactly as ``_fit_layer``) and the
    mean-raw-histogram RI centers per (segment, cluster)."""
    p = f_ri.shape[0]
    valid = seg < n_seg
    rc_centers_norm = centers2[:n_seg, :, 0]              # [S, 4]
    rc_centers = jnp.expm1(rc_centers_norm * (hi - lo)[:, None]
                           + lo[:, None])
    ri_assign = assign2[p:]
    raw = f_ri.astype(jnp.float32)
    sid = jnp.where(valid, seg * 4 + ri_assign, n_seg * 4)
    fvalid = valid.astype(jnp.float32)
    cnt = jax.ops.segment_sum(fvalid, sid,
                              num_segments=n_seg * 4 + 1)[
        :n_seg * 4].reshape(n_seg, 4)
    sums = jax.ops.segment_sum(raw * fvalid[:, None], sid,
                               num_segments=n_seg * 4 + 1)[
        :n_seg * 4].reshape(n_seg, 4, NUM_RI_BINS)
    ri_centers = sums / jnp.maximum(cnt, 1.0)[:, :, None]
    return {"rc_assign": assign2[:p], "rc_centers": rc_centers,
            "rc_centers_norm": rc_centers_norm,
            "ri_assign": ri_assign, "ri_centers": ri_centers}


def _fit_segmented(f_ri: jnp.ndarray, f_rc: jnp.ndarray, seg: jnp.ndarray,
                   seg_off: np.ndarray, seg_cnt: np.ndarray,
                   seeds: jnp.ndarray, n_seg: int,
                   use_kernel: Optional[bool] = None) -> Dict:
    """All eligible layers' RC + RI fits as one flat segmented dispatch.

    ``f_ri`` [P, 4] / ``f_rc`` [P] hold every layer's multi-occurrence
    feature rows in the flat-segmented layout (layer s's rows contiguous at
    ``seg_off[s]``, ``seg_cnt[s]`` real rows, runs padded to SEG_BLOCK
    multiples with ``seg == n_seg``; both host arrays, so the layout is
    never read back from the device).  ``seeds`` [n_seg] int32 is each
    layer's k-means seed.  The two per-layer fits of
    ``_fit_layer`` become 2*n_seg segments of one
    ``kmeans.kmeans_fit_segmented`` call: the RC points under
    ``fold_in(key, 0)``, the RI points under ``fold_in(key, 1)``, matching
    the bucketed key sequence segment for segment — so the segmented fit
    is cluster-assignment-equal to the bucketed oracle without any
    power-of-two capacity padding.  (Host function: the segmented fit
    itself compacts unconverged segments between dispatches.)
    """
    p = int(f_rc.shape[0])
    prep = _seg_prep(f_ri, f_rc, seg, seeds, n_seg)
    off2 = np.concatenate([np.asarray(seg_off, np.int32),
                           np.asarray(seg_off, np.int32) + p])
    cnt2 = np.concatenate([np.asarray(seg_cnt, np.int32)] * 2)
    res = km.kmeans_fit_segmented(prep["xx"], prep["seg2"], off2, cnt2,
                                  prep["keys2"], n_seg=2 * n_seg, k=4,
                                  use_kernel=use_kernel)
    out = _seg_post(res.assign, res.centers, f_ri, seg, prep["lo"],
                    prep["hi"], n_seg)
    return dict(out, n_iter=res.n_iter)


def _annotate(fit: Dict, n_multi: int) -> Dict:
    """Host-side O(k) semantic annotation of one layer's fit result."""
    label_rc = km.annotate_rc(np.asarray(fit["rc_centers_norm"]))
    centers_d = np.asarray(fit["ri_centers"])
    label_ri = km.annotate_ri(centers_d)
    return {
        "rc_label": label_rc[np.asarray(fit["rc_assign"][:n_multi])],
        "ri_label": label_ri[np.asarray(fit["ri_assign"][:n_multi])],
        "rc_centers": np.asarray(fit["rc_centers"])[np.argsort(label_rc)],
        "ri_centers": centers_d[np.argsort(label_ri)],
    }


def _fit_host_features(uniq: np.ndarray, f_ri: np.ndarray, f_rc: np.ndarray,
                       seed: int, cap: Optional[int]) -> LayerClusters:
    """Cluster one layer from host-extracted integer features through the
    shared jitted ``_fit_layer`` program at ``cap``-padded shape."""
    n = uniq.shape[0]
    rc_cluster = np.full(n, -1, dtype=np.int64)
    ri_cluster = np.full(n, -1, dtype=np.int64)
    multi = f_rc > 1  # single-occurrence lines -> No Reuse
    n_multi = int(multi.sum())

    rc_centers = np.zeros(4, np.float32)
    ri_centers = np.zeros((4, NUM_RI_BINS), np.float32)
    if n_multi >= MIN_MULTI:
        cap = cap or _bucket(n_multi)
        f_ri_c = np.zeros((cap, NUM_RI_BINS), np.int32)
        f_rc_c = np.zeros(cap, np.int32)
        f_ri_c[:n_multi] = f_ri[multi]
        f_rc_c[:n_multi] = f_rc[multi]
        fit = _fit_layer(jnp.asarray(f_ri_c), jnp.asarray(f_rc_c),
                         jnp.int32(n_multi), jax.random.PRNGKey(seed))
        ann = _annotate(fit, n_multi)
        rc_cluster[multi] = ann["rc_label"]
        ri_cluster[multi] = ann["ri_label"]
        rc_centers, ri_centers = ann["rc_centers"], ann["ri_centers"]

    return LayerClusters(uniq=uniq, rc_cluster=rc_cluster,
                         ri_cluster=ri_cluster, rc_centers=rc_centers,
                         ri_centers=ri_centers,
                         features_ri=f_ri[multi] if multi.any()
                         else np.zeros((0, NUM_RI_BINS), np.int64))


def train_layer(lines: np.ndarray, seed: int = 0,
                cap: Optional[int] = None) -> LayerClusters:
    """Host-reference LERN pipeline on one layer's line trace.

    Features come from the numpy oracle; the clustering runs through the
    same jitted ``_fit_layer`` program as the batched trainer, padded to
    ``cap`` points.  The default — this layer's own power-of-two bucket —
    is exactly the capacity its row gets in ``train_model_batched``'s
    bucket groups, which is what makes the two paths label-equal."""
    sig = reuse_signature_np(lines)
    f_ri, f_rc = ri_histogram_np(lines, sig)
    return _fit_host_features(sig["uniq"], f_ri, f_rc, seed, cap)


def _layer_lines(trace: Trace, hash_fn: Optional[Callable]) -> List[np.ndarray]:
    out = []
    for li in range(len(trace.layer_names)):
        lines = trace.line[trace.layer == li]
        out.append(hash_fn(lines) if hash_fn is not None else lines)
    return out


def train(trace: Trace, hash_fn: Optional[Callable] = None,
          seed: int = 0) -> LernModel:
    """Host-reference trainer: per-layer numpy features + shared jitted fit.

    ``hash_fn`` (paper §VI-J): when the L-RPT is smaller than the address
    space, training runs on *hashed* addresses so the predictor internalizes
    aliasing (LOptv1..v4).  Each layer fits at its own power-of-two
    capacity — the same shape its bucket row has in the batched trainer —
    so this produces the same model as ``train_model_batched`` (bitwise on
    the cluster tables)."""
    layers = [train_layer(lines, seed=seed + li)
              for li, lines in enumerate(_layer_lines(trace, hash_fn))]
    return LernModel.from_layers(layers, hash_fn=hash_fn)


def _extract_flat(lines_all: np.ndarray, layer_all: np.ndarray, n_l: int):
    """Device program 1 + host eligibility scan, shared by the trainers
    and the bench_lern fit-stage benchmark: one ``reuse_features_flat``
    extraction over the concatenated trace, then the per-layer
    multi-occurrence masks and MIN_MULTI eligibility (integer work,
    O(N)).  Returns (uniq_f, f_ri_f, f_rc_f, n_uniq, offs, per_layer,
    elig)."""
    m = lines_all.shape[0]
    m_pad = max(8, ((m + 4095) // 4096) * 4096)
    lines32 = np.full(m_pad, int(PAD_LINE), np.int32)
    lines32[:m] = lines_to_device(lines_all)
    layer32 = np.full(m_pad, n_l, np.int32)
    layer32[:m] = layer_all
    feats = reuse_features_flat(jnp.asarray(lines32), jnp.asarray(layer32),
                                jnp.int32(m), n_l)
    uniq_f = np.asarray(feats["uniq"], np.int64)
    f_ri_f = np.asarray(feats["f_ri"])
    f_rc_f = np.asarray(feats["f_rc"])
    n_uniq = np.asarray(feats["n_uniq"], np.int32)
    offs = np.concatenate([[0], np.cumsum(n_uniq)])
    per_layer = []  # (multi_mask, n_multi)
    elig = []
    for li in range(n_l):
        multi = f_rc_f[offs[li]:offs[li + 1]] > 1
        nm = int(multi.sum())
        per_layer.append((multi, nm))
        if nm >= MIN_MULTI:
            elig.append(li)
    return uniq_f, f_ri_f, f_rc_f, n_uniq, offs, per_layer, elig


def _fit_flat(extracted, key_seeds: List[int], use_kernel: Optional[bool],
              fit_engine: Optional[str] = None):
    """Shared fit core of the batched trainers, under the ``lern.fit``
    span.

    From ``_extract_flat``'s result (the flat trace's per-layer feature
    tables and eligibility), every eligible layer's k-means fits
    in one device dispatch — either the padded capacity-bucket path
    (``_fit_groups``, the oracle) or the flat-segmented path
    (``_fit_segmented``) per ``fit_engine``; ``key_seeds[li]`` seeds layer
    li's k-means draws either way.  Returns everything the assembly step
    needs: (uniq_f, f_ri_f, f_rc_f, n_uniq, offs, per_layer, layer_fits)
    where ``layer_fits[li]`` is the host-side fit dict ``_annotate``
    consumes (absent for ineligible layers)."""
    engine = resolve_engine(fit_engine)
    uniq_f, f_ri_f, f_rc_f, n_uniq, offs, per_layer, elig = extracted
    with jax.profiler.TraceAnnotation("lern.fit"):
        # --- device program 2: all fits in one jitted call -----------------
        if engine == "segmented":
            layer_fits = _fit_flat_segmented(f_ri_f, f_rc_f, offs,
                                             per_layer, elig, key_seeds,
                                             use_kernel)
        else:
            layer_fits = _fit_flat_bucketed(f_ri_f, f_rc_f, offs, per_layer,
                                            elig, key_seeds, use_kernel)
    return uniq_f, f_ri_f, f_rc_f, n_uniq, offs, per_layer, layer_fits


def _fit_flat_bucketed(f_ri_f, f_rc_f, offs, per_layer, elig, key_seeds,
                       use_kernel: Optional[bool]) -> Dict[int, Dict]:
    """Oracle fit path: layers vmapped in power-of-two capacity buckets."""
    buckets: Dict[int, List[int]] = {}
    for li in elig:
        buckets.setdefault(_bucket(per_layer[li][1]), []).append(li)
    groups = []
    group_of: Dict[int, tuple] = {}
    for cap in sorted(buckets):
        members = buckets[cap]
        g_ri = np.zeros((len(members), cap, NUM_RI_BINS), np.int32)
        g_rc = np.zeros((len(members), cap), np.int32)
        g_nm = np.zeros(len(members), np.int32)
        g_seed = np.zeros(len(members), np.int32)
        for gi, li in enumerate(members):
            multi, nm = per_layer[li]
            sl = slice(offs[li], offs[li + 1])
            g_ri[gi, :nm] = f_ri_f[sl][multi]
            g_rc[gi, :nm] = f_rc_f[sl][multi]
            g_nm[gi] = nm
            g_seed[gi] = key_seeds[li]
            group_of[li] = (len(groups), gi)
        groups.append((jnp.asarray(g_ri), jnp.asarray(g_rc),
                       jnp.asarray(g_nm), jnp.asarray(g_seed)))
    fits = _fit_groups(tuple(groups), use_kernel=use_kernel)
    fits_np = jax.device_get(fits)
    return {li: {k: v[gi] for k, v in fits_np[g].items()}
            for li, (g, gi) in group_of.items()}


def _fit_flat_segmented(f_ri_f, f_rc_f, offs, per_layer, elig, key_seeds,
                        use_kernel: Optional[bool]) -> Dict[int, Dict]:
    """Flat-segmented fit path: every eligible layer's multi-occurrence
    feature rows concatenated into ONE [P, F] array with a segment-id
    column — no capacity padding (runs padded only to SEG_BLOCK multiples,
    the total to a 2048 multiple to bound compile shapes)."""
    if not elig:
        return {}
    counts = [per_layer[li][1] for li in elig]
    seg_off, total = km.segment_layout(counts)
    n_seg = len(elig)
    p = max(((total + 2047) // 2048) * 2048, km.SEG_BLOCK)
    f_ri_m = np.zeros((p, NUM_RI_BINS), np.int32)
    f_rc_m = np.zeros(p, np.int32)
    seg = np.full(p, n_seg, np.int32)
    seeds = np.zeros(n_seg, np.int32)
    for si, li in enumerate(elig):
        multi, nm = per_layer[li]
        sl = slice(offs[li], offs[li + 1])
        o = seg_off[si]
        f_ri_m[o:o + nm] = f_ri_f[sl][multi]
        f_rc_m[o:o + nm] = f_rc_f[sl][multi]
        seg[o:o + nm] = si
        seeds[si] = key_seeds[li]
    fit = _fit_segmented(jnp.asarray(f_ri_m), jnp.asarray(f_rc_m),
                         jnp.asarray(seg), seg_off,
                         np.asarray(counts, np.int32),
                         jnp.asarray(seeds), n_seg=n_seg,
                         use_kernel=use_kernel)
    fit_np = jax.device_get(fit)
    out: Dict[int, Dict] = {}
    for si, li in enumerate(elig):
        nm = per_layer[li][1]
        o = seg_off[si]
        out[li] = {"rc_assign": fit_np["rc_assign"][o:o + nm],
                   "ri_assign": fit_np["ri_assign"][o:o + nm],
                   "rc_centers": fit_np["rc_centers"][si],
                   "rc_centers_norm": fit_np["rc_centers_norm"][si],
                   "ri_centers": fit_np["ri_centers"][si]}
    return out


def _assemble(flat, lo: int, hi: int,
              hash_fn: Optional[Callable]) -> LernModel:
    """Build the LernModel for layer range [lo, hi) of a flat fit (host
    annotation and tables, under the ``lern.assemble`` span)."""
    with jax.profiler.TraceAnnotation("lern.assemble"):
        uniq_f, f_ri_f, f_rc_f, n_uniq_all, offs, per_layer, layer_fits = flat
        n_l = hi - lo
        n_uniq = n_uniq_all[lo:hi]
        n_tab = _bucket(int(n_uniq.max(initial=1)))
        uniq = np.full((n_l, n_tab), int(PAD_LINE), np.int64)
        rc = np.full((n_l, n_tab), -1, np.int8)
        ri = np.full((n_l, n_tab), -1, np.int8)
        rc_c = np.zeros((n_l, 4), np.float32)
        ri_c = np.zeros((n_l, 4, NUM_RI_BINS), np.float32)
        features: List[np.ndarray] = []
        for li in range(lo, hi):
            k = li - lo
            nu = int(n_uniq_all[li])
            multi, nm = per_layer[li]
            sl = slice(offs[li], offs[li + 1])
            uniq[k, :nu] = uniq_f[sl]
            features.append(f_ri_f[sl][multi].astype(np.int64))
            if li not in layer_fits:
                continue
            ann = _annotate(layer_fits[li], nm)
            rc[k, :nu][multi] = ann["rc_label"].astype(np.int8)
            ri[k, :nu][multi] = ann["ri_label"].astype(np.int8)
            rc_c[k], ri_c[k] = ann["rc_centers"], ann["ri_centers"]
        return LernModel(uniq=uniq, rc_cluster=rc, ri_cluster=ri,
                         n_uniq=n_uniq, rc_centers=rc_c, ri_centers=ri_c,
                         features_ri=features, hash_fn=hash_fn)


def _layer_sorted(trace: Trace):
    """(lines, layer) int64 arrays with each layer contiguous; a stable
    sort by layer preserves within-layer order (exact reuse intervals)."""
    lines = np.asarray(trace.line, np.int64)
    layer = np.asarray(trace.layer, np.int64)
    if np.any(np.diff(layer) < 0):
        order = np.argsort(layer, kind="stable")
        lines, layer = lines[order], layer[order]
    return lines, layer


def train_model_batched(trace: Trace, hash_fn: Optional[Callable] = None,
                        seed: int = 0,
                        use_kernel: Optional[bool] = None,
                        fit_engine: Optional[str] = None) -> LernModel:
    """Device-resident trainer: the whole model as two device programs.

    Program 1 (``reuse.reuse_features_flat``) extracts every layer's
    integer feature tables from the *flat* concatenated trace — one
    composite (layer, line) sort, RI-binning through the ``ri_histogram``
    Pallas kernel (an elementwise pass, so the kernel runs even on
    interpret backends) — padded to the trace length, not layers x
    max-layer.  Program 2 (``_fit_groups``) runs every layer's two masked
    k-means fits as one jitted call, layers grouped into power-of-two
    capacity buckets (``use_kernel``: None = Pallas assignment where it
    compiles).  No per-layer Python k-means loop; only the O(k)-sized
    semantic annotation runs on the host.  With ``fit_engine="bucketed"``
    it is label-equal to ``train`` (the float pipeline is the shared
    ``_fit_layer`` at identical padded shapes); the default segmented
    engine is cluster-assignment-equal to that oracle (same label tables,
    centers to FP reassociation) with no capacity padding.

    Profiler spans: ``lern.train`` around the whole training, tiled by
    ``lern.extract`` (host sort and padding, program 1 and its read-back),
    ``lern.fit`` (``_fit_flat``) and ``lern.assemble`` (``_assemble``)."""
    with jax.profiler.TraceAnnotation("lern.train"):
        n_l = max(len(trace.layer_names), 1)
        with jax.profiler.TraceAnnotation("lern.extract"):
            lines_all, layer_all = _layer_sorted(trace)
            if hash_fn is not None:
                lines_all = hash_fn(lines_all)
            extracted = _extract_flat(lines_all, layer_all, n_l)
        flat = _fit_flat(extracted, [seed + li for li in range(n_l)],
                         use_kernel, fit_engine)
        return _assemble(flat, 0, n_l, hash_fn)


def train_family_batched(traces: List[Trace],
                         hash_fn: Optional[Callable] = None,
                         seed: int = 0,
                         use_kernel: Optional[bool] = None,
                         fit_engine: Optional[str] = None
                         ) -> List[LernModel]:
    """Train several configs' LERN models in ONE device dispatch pair.

    The config1-class tiny workloads are host-bound when trained one at
    a time (bench_lern.json speedup < 1: the two dispatches cost more
    than the work) — so concatenate every trace with offset layer ids
    into one flat extraction, and let the capacity buckets mix all
    configs' layers in one ``_fit_groups`` call.  Each returned model is
    **bitwise-identical** to ``train_model_batched(traces[i], ...)``:
    per-layer integer features are position-exact under concatenation,
    bucket rows are independent under vmap at the same capacity, and
    each layer keeps its own-config k-means key ``seed + local_layer``
    (tests/test_lern_batched.py pins this), so the per-config caches are
    interchangeable.  The same profiler spans as ``train_model_batched``,
    with one ``lern.assemble`` per model."""
    with jax.profiler.TraceAnnotation("lern.train"):
        n_ls = [max(len(tr.layer_names), 1) for tr in traces]
        bounds = np.concatenate([[0], np.cumsum(n_ls)])
        seeds = [seed + li for n_l in n_ls for li in range(n_l)]
        with jax.profiler.TraceAnnotation("lern.extract"):
            lines_parts, layer_parts = [], []
            for ci, tr in enumerate(traces):
                lines, layer = _layer_sorted(tr)
                lines_parts.append(lines)
                layer_parts.append(layer + bounds[ci])
            lines_all = (np.concatenate(lines_parts) if traces
                         else np.zeros(0, np.int64))
            layer_all = (np.concatenate(layer_parts) if traces
                         else np.zeros(0, np.int64))
            if hash_fn is not None and lines_all.size:
                lines_all = hash_fn(lines_all)
            extracted = _extract_flat(lines_all, layer_all, int(bounds[-1]))
        flat = _fit_flat(extracted, seeds, use_kernel, fit_engine)
        return [_assemble(flat, int(bounds[ci]), int(bounds[ci + 1]),
                          hash_fn)
                for ci in range(len(traces))]


def train_host_numpy(trace: Trace, hash_fn: Optional[Callable] = None,
                     seed: int = 0) -> LernModel:
    """The pre-refactor host pipeline, kept as the perf baseline.

    Faithful to the seed-era ``train``: a Python loop over layers, numpy
    feature extraction, two k-means fits per layer at that layer's *exact*
    point count (a distinct compiled program per layer shape), and the
    O(n^2) silhouette computed inline.  ``benchmarks/fig05_clustering.py``
    times this against ``train_model_batched`` for bench_lern.json; it is
    not bitwise-comparable to the batched path (the fit shapes differ), so
    parity tests use ``train`` instead."""
    layers = []
    for li in range(len(trace.layer_names)):
        lines = trace.line[trace.layer == li]
        if hash_fn is not None:
            lines = hash_fn(lines)
        sig = reuse_signature_np(lines)
        f_ri, f_rc = ri_histogram_np(lines, sig)
        n = sig["uniq"].shape[0]
        rc_cluster = np.full(n, -1, dtype=np.int64)
        ri_cluster = np.full(n, -1, dtype=np.int64)
        multi = f_rc > 1
        sil = 0.0
        rc_centers = np.zeros(4, np.float32)
        ri_centers = np.zeros((4, NUM_RI_BINS), np.float32)
        if int(multi.sum()) >= MIN_MULTI:
            xrc = jnp.asarray(np.log1p(f_rc[multi]).astype(np.float32))[:, None]
            xn, lo, hi = km.normalize(xrc)
            res = km.kmeans_fit(xn, k=4, seed=seed + li)
            label_of = km.annotate_rc(np.asarray(res.centers))
            rc_cluster[multi] = label_of[np.asarray(res.assign)]
            denorm = np.asarray(res.centers) * np.asarray(hi - lo) \
                + np.asarray(lo)
            rc_centers = np.expm1(denorm.reshape(-1))[np.argsort(label_of)]
            xri_raw = f_ri[multi].astype(np.float32)
            xri = xri_raw / np.maximum(xri_raw.sum(1, keepdims=True), 1e-9)
            res = km.kmeans_fit(jnp.asarray(xri), k=4, seed=seed + li)
            assign = np.asarray(res.assign)
            centers_d = np.stack([
                xri_raw[assign == c].mean(0) if (assign == c).any()
                else np.zeros(NUM_RI_BINS) for c in range(4)])
            label_ri = km.annotate_ri(centers_d)
            ri_cluster[multi] = label_ri[assign]
            ri_centers = centers_d[np.argsort(label_ri)]
            sil = km.silhouette_score(xri, assign)
        layers.append(LayerClusters(
            uniq=sig["uniq"], rc_cluster=rc_cluster, ri_cluster=ri_cluster,
            rc_centers=rc_centers, ri_centers=ri_centers,
            features_ri=f_ri[multi] if multi.any()
            else np.zeros((0, NUM_RI_BINS), np.int64), _sil=sil))
    return LernModel.from_layers(layers, hash_fn=hash_fn)


def prediction_accuracy(model: LernModel, trace: Trace) -> float:
    """§IV-D: fraction of actual reuse intervals whose bin matches the
    cluster's correct-bin set (No-Reuse lines: correct iff truly single)."""
    e0, e1, e2 = RI_BIN_EDGES
    total = 0
    correct = 0
    for li, lc in enumerate(model.layers):
        mask = trace.layer == li
        lines = trace.line[mask]
        if model.hash_fn is not None:
            lines = model.hash_fn(lines)
        sig = reuse_signature_np(lines)
        ri, inv = sig["ri"], sig["inv"]
        # map this trace's unique set onto the trained unique set
        pos = np.searchsorted(lc.uniq, sig["uniq"])
        pos = np.clip(pos, 0, max(0, lc.uniq.shape[0] - 1))
        known = (lc.uniq.shape[0] > 0) & (lc.uniq[pos] == sig["uniq"])
        ri_cl = np.where(known, lc.ri_cluster[pos], -1)[inv]
        valid = ri >= 0  # occurrences that have an actual next-reuse
        bins = np.where(ri <= e0, 0, np.where(ri <= e1, 1,
                        np.where(ri <= e2, 2, 3)))
        for lbl, ok_bins in _CORRECT_BINS.items():
            m = valid & (ri_cl == lbl)
            total += int(m.sum())
            correct += int(np.isin(bins[m], ok_bins).sum())
        # No-Reuse predictions are correct when the line truly has no reuse:
        m = (ri_cl == -1)
        total += int(m.sum())
        correct += int((ri[m] < 0).sum())
    return correct / max(1, total)


def cluster_distribution(model: LernModel, trace: Trace) -> Dict[str, np.ndarray]:
    """Fig. 6: per-layer % of memory *accesses* in each RI / RC cluster."""
    n_layers = model.n_layers
    ri_dist = np.zeros((n_layers, 5))  # Immediate..Remote, NoReuse
    rc_dist = np.zeros((n_layers, 5))  # Cold..Hot, NoReuse
    for li, lc in enumerate(model.layers):
        mask = trace.layer == li
        lines = trace.line[mask]
        if model.hash_fn is not None:
            lines = model.hash_fn(lines)
        uniq, inv, cnt = np.unique(lines, return_inverse=True,
                                   return_counts=True)
        pos = np.searchsorted(lc.uniq, uniq)
        pos = np.clip(pos, 0, max(0, lc.uniq.shape[0] - 1))
        known = (lc.uniq.shape[0] > 0) & (lc.uniq[pos] == uniq)
        ri_cl = np.where(known, lc.ri_cluster[pos], -1)[inv]
        rc_cl = np.where(known, lc.rc_cluster[pos], -1)[inv]
        for k in range(4):
            ri_dist[li, k] = (ri_cl == k).mean()
            rc_dist[li, k] = (rc_cl == k).mean()
        ri_dist[li, 4] = (ri_cl == -1).mean()
        rc_dist[li, 4] = (rc_cl == -1).mean()
    return {"ri": ri_dist, "rc": rc_dist}
