"""K-Means clustering + semantic cluster annotation (paper §IV-C).

JAX Lloyd's algorithm with k-means++ init.  The canonical implementation is
``kmeans_fit_masked``: fixed-shape and mask-aware, so it vmaps into the
batched LERN training program (``lern.train_model_batched``) — all layers of
a model fit as one padded device call (``kmeans_fit_batched``).  The
assignment hot loop runs through the Pallas TPU kernel
(``repro.kernels.kmeans_assign``) when it would compile (TPU backend); on
interpret-mode backends the identical-math jnp decomposition is used
(cross-checked in tests).  ``kmeans_fit`` is the unmasked convenience
wrapper.

Annotation (paper §IV-C):
* RC clusters: rank 1-D centers ascending -> Cold(0) Light(1) Moderate(2) Hot(3)
* RI clusters: rank centers by expected-bin index E[c] = sum_k f_k*k / sum_k f_k
  ascending -> Immediate(0) Near(1) Far(2) Remote(3).  This realizes the
  paper's prose rules (dominant f1 -> Immediate; f1-with-f2 -> Near; f2/f3
  mass -> Far; f3/f4 dominant -> Remote) as a total order, which is what the
  bypass table consumes.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class KMeansResult(NamedTuple):
    centers: jnp.ndarray     # [K, D] (in the normalized feature space)
    assign: jnp.ndarray      # [N] cluster index per point
    inertia: jnp.ndarray     # [] sum of squared distances (masked)
    n_iter: int


class SegmentedKMeansResult(NamedTuple):
    centers: jnp.ndarray     # [S, K, D] per-segment centroids
    assign: jnp.ndarray      # [P] cluster index per flat point (pad: garbage)
    n_iter: int


# Flat segmented layout granularity (canonical value lives next to the
# kernel that depends on it: repro.kernels.common.SEG_BLOCK).
from repro.kernels.common import SEG_BLOCK  # noqa: E402

# Full-f32 contraction for the centre sums (``one_hot.T @ x``): a TPU's
# default precision rounds f32 operands to bf16, which moves centres and
# flips labels (RC counts above 256 are not bf16-exact).  The CPU computes
# f32 either way.
_HIGHEST = jax.lax.Precision.HIGHEST


def _xc(x: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """x [N, D] . c ([K, D], or [N, K, D] per point) -> [N, K], as an
    elementwise product summed over the feature axis: the same f32
    rounding in every fit engine.  A dot or einsum orders its sums by its
    own lowering, and integer features make exact distance ties common —
    the bucketed and segmented engines then broke them differently."""
    return jnp.sum(x[:, None, :] * c, -1)


def assign_jnp(x: jnp.ndarray, centers: jnp.ndarray) -> jnp.ndarray:
    """Nearest-center assignment via the -2 x.c + ||c||^2 expansion (the
    row-constant ||x||^2 term is dropped from the argmin — exactly the
    decomposition the Pallas kernel computes, so both paths agree)."""
    c2 = jnp.sum(centers * centers, -1)
    d2 = c2[None, :] - 2.0 * _xc(x, centers)
    return jnp.argmin(d2, axis=1).astype(jnp.int32)


def _default_use_kernel() -> bool:
    """Kernel where it compiles (TPU); jnp math elsewhere (interpret-mode
    Pallas inside a 50-iteration scan would dominate the fit)."""
    from repro.kernels.common import INTERPRET
    return not INTERPRET


def _pick_masked(key, weights):
    """Inverse-CDF draw from unnormalized ``weights`` (masked entries 0).

    Avoids jax.random.choice so the draw depends only on ``weights`` and
    ``key`` — identical under vmap and for any mask pattern."""
    cum = jnp.cumsum(weights)
    u = jax.random.uniform(key, (), weights.dtype) * cum[-1]
    idx = jnp.sum((cum < u).astype(jnp.int32))
    return jnp.clip(idx, 0, weights.shape[0] - 1)


def _plus_plus_init_masked(key, x, mask, k):
    """k-means++ seeding over the masked points (deterministic given key).

    The first center is drawn uniformly from the valid points; subsequent
    centers with probability proportional to the masked d² weights."""
    fmask = mask.astype(x.dtype)
    keys = jax.random.split(key, k)
    # uniform first pick: the t-th valid point, t ~ U{0..n_valid-1}
    n_valid = jnp.sum(mask.astype(jnp.int32))
    t = jnp.floor(jax.random.uniform(keys[0], (), x.dtype)
                  * n_valid.astype(x.dtype)).astype(jnp.int32)
    cm = jnp.cumsum(mask.astype(jnp.int32))
    idx0 = jnp.argmax(cm > t)        # first position with cm == t+1
    centers = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[idx0])

    def body(i, centers):
        d2 = jnp.min(
            jnp.sum((x[:, None, :] - centers[None, :, :]) ** 2, -1)
            + jnp.where(jnp.arange(k) < i, 0.0, jnp.inf)[None, :],
            axis=1)
        nxt = _pick_masked(keys[i], d2 * fmask)
        return centers.at[i].set(x[nxt])

    return jax.lax.fori_loop(1, k, body, centers)


@functools.partial(jax.jit, static_argnames=("k", "iters", "use_kernel"))
def kmeans_fit_masked(x: jnp.ndarray, mask: jnp.ndarray, key: jnp.ndarray,
                      k: int = 4, iters: int = 50,
                      use_kernel: Optional[bool] = None) -> KMeansResult:
    """Lloyd iterations over the points where ``mask`` is True.

    Fixed-shape and value-only in ``mask``/``key``, so it vmaps over a
    leading batch axis (``kmeans_fit_batched``).  Masked-out rows of ``x``
    should be zeroed by the caller (they never influence the fit, but keep
    the arithmetic NaN-free); their ``assign`` entries are meaningless.
    Empty clusters re-seed at the farthest valid point.
    """
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    if use_kernel:
        from repro.kernels.kmeans_assign import ops as _kops
        assign_fn = _kops.assign
    else:
        assign_fn = assign_jnp
    fmask = mask.astype(x.dtype)
    x2 = jnp.sum(x * x, -1)  # [N], constant across iterations
    centers0 = _plus_plus_init_masked(key, x, mask, k)

    def step(carry, _):
        centers = carry
        # scores via the product decomposition:
        # d2 = ||x||^2 - 2 x.c + ||c||^2; the ||x||^2 term only matters for
        # the farthest-point reseed, not the argmin
        c2 = jnp.sum(centers * centers, -1)
        sc = c2[None, :] - 2.0 * _xc(x, centers)            # [N, K]
        if use_kernel:
            a = assign_fn(x, centers)
        else:
            a = jnp.argmin(sc, axis=1).astype(jnp.int32)
        one_hot = jax.nn.one_hot(a, k, dtype=x.dtype) * fmask[:, None]
        counts = jnp.sum(one_hot, 0)                        # [K]
        sums = jnp.dot(one_hot.T, x, precision=_HIGHEST)    # [K, D]
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        # re-seed empty clusters at the farthest valid point
        far_score = jnp.where(mask, x2 + jnp.min(sc, 1), -jnp.inf)
        far = x[jnp.argmax(far_score)]
        new = jnp.where((counts > 0)[:, None], new, far[None, :])
        return new, None

    centers, _ = jax.lax.scan(step, centers0, None, length=iters)
    a = assign_fn(x, centers)
    d2 = jnp.sum((x - centers[a]) ** 2, -1)
    return KMeansResult(centers, a, jnp.sum(d2 * fmask), iters)


# ---------------------------------------------------------------------------
# flat-segmented fit: every segment's k-means over ONE flat point array
# ---------------------------------------------------------------------------
def segment_layout(counts, block: int = SEG_BLOCK):
    """Host helper: pack ragged segments into the flat blocked layout.

    ``counts[i]`` points for segment i -> ``(offsets, total)`` where segment
    i's rows occupy ``[offsets[i], offsets[i] + counts[i])`` and each run is
    padded to a multiple of ``block`` (pad rows carry segment id ``n_seg``).
    """
    offsets = []
    cur = 0
    for n in counts:
        offsets.append(cur)
        cur += ((int(n) + block - 1) // block) * block
    return np.asarray(offsets, np.int32), cur


def _seg_cumsum(w: jnp.ndarray, seg_off: jnp.ndarray) -> jnp.ndarray:
    """Per-segment prefix sums over the flat array: an associative scan
    that resets at the segment start positions (``seg_off`` scatters the
    reset flags, so pad runs between segments keep accumulating zeros and
    the value at a segment's last row is that segment's total)."""
    starts = jnp.zeros(w.shape[0], bool).at[seg_off].set(True)

    def comb(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, av + bv), af | bf

    out, _ = jax.lax.associative_scan(comb, (w, starts))
    return out


def _seg_pick(u: jnp.ndarray, w: jnp.ndarray, seg: jnp.ndarray,
              seg_off: jnp.ndarray, seg_cnt: jnp.ndarray,
              n_seg: int) -> jnp.ndarray:
    """Per-segment inverse-CDF draw (the segmented ``_pick_masked``):
    ``u[s]`` in [0, 1) picks the index whose within-segment cumulative
    weight first reaches ``u * total``; returns flat point indices [S]."""
    cum = _seg_cumsum(w, seg_off)
    nxt = jnp.concatenate([seg_off[1:], jnp.array([w.shape[0]], jnp.int32)])
    total = cum[nxt - 1]                           # [S] (pads add zero)
    segc = jnp.minimum(seg, n_seg - 1)
    below = (cum < (u * total)[segc]).astype(jnp.int32)
    cnt = jax.ops.segment_sum(jnp.where(seg < n_seg, below, 0), segc,
                              num_segments=n_seg)
    return seg_off + jnp.clip(cnt, 0, seg_cnt - 1)


def _plus_plus_init_segmented(keys, x, seg, seg_off, seg_cnt, n_seg, k):
    """k-means++ seeding for every segment at once (the segmented
    ``_plus_plus_init_masked``): per-segment keys drive the same draw
    sequence — uniform first pick, then d²-weighted inverse-CDF picks —
    so segment s reproduces the bucketed seeding given the same key."""
    valid = seg < n_seg
    fvalid = valid.astype(x.dtype)
    segc = jnp.minimum(seg, n_seg - 1)
    ks = jax.vmap(lambda kk: jax.random.split(kk, k))(keys)  # [S, k, 2]
    u0 = jax.vmap(lambda kk: jax.random.uniform(kk, (), x.dtype))(ks[:, 0])
    t = jnp.floor(u0 * seg_cnt.astype(x.dtype)).astype(jnp.int32)
    centers = jnp.zeros((n_seg, k, x.shape[1]), x.dtype)
    centers = centers.at[:, 0].set(x[seg_off + t])
    # masked min-d² maintained incrementally (min is exact, so this equals
    # the bucketed full re-min over the seeded prefix)
    dmin = jnp.sum((x - centers[segc, 0]) ** 2, -1)
    for i in range(1, k):
        ui = jax.vmap(lambda kk: jax.random.uniform(kk, (), x.dtype))(
            ks[:, i])
        pick = _seg_pick(ui, dmin * fvalid, seg, seg_off, seg_cnt, n_seg)
        centers = centers.at[:, i].set(x[pick])
        dmin = jnp.minimum(dmin, jnp.sum((x - centers[segc, i]) ** 2, -1))
    return centers


def assign_segmented_jnp(x: jnp.ndarray, centers: jnp.ndarray,
                         seg: jnp.ndarray) -> jnp.ndarray:
    """Per-point nearest-centroid over each point's own segment block,
    via the same -2 x.c + ||c||² decomposition as the Pallas kernel."""
    segc = jnp.minimum(seg, centers.shape[0] - 1)
    cg = centers[segc]                              # [P, K, D]
    c2 = jnp.sum(cg * cg, -1)                       # [P, K]
    d2 = c2 - 2.0 * _xc(x, cg)
    return jnp.argmin(d2, axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_seg", "k"))
def _pp_init_segmented(keys, x, seg, seg_off, seg_cnt, n_seg: int, k: int):
    return _plus_plus_init_segmented(keys, x, seg, seg_off, seg_cnt,
                                     n_seg, k)


@functools.partial(jax.jit,
                   static_argnames=("n_seg", "k", "iters", "use_kernel"))
def _lloyd_segmented(x: jnp.ndarray, seg: jnp.ndarray,
                     centers0: jnp.ndarray, n_seg: int, k: int, iters: int,
                     use_kernel: bool):
    """Up to ``iters`` segment-wise Lloyd sweeps from ``centers0``, exiting
    as soon as every segment reaches its fixed point.  Returns (centers,
    n_iter, converged [S] bool).  Per-segment math is segment-local, so a
    segment whose centers survive one sweep unchanged is at its fixed point
    forever — the flag lets the host re-dispatch only the stragglers."""
    valid = seg < n_seg
    fvalid = valid.astype(x.dtype)
    segc = jnp.minimum(seg, n_seg - 1)
    x2 = jnp.sum(x * x, -1)
    p, f = x.shape
    nb = p // SEG_BLOCK
    bseg = seg[::SEG_BLOCK]       # one segment per block (layout invariant)
    arange_p = jnp.arange(p, dtype=jnp.int32)
    if use_kernel:
        from repro.kernels.kmeans_assign import ops as _kops

    def body(carry):
        centers, i, _ = carry
        if use_kernel:
            a = _kops.assign_segmented(x, centers, seg)
            # nearest-centroid score without the [P, K, D] gather the
            # kernel exists to avoid: min_k sc == sc[a] by definition
            cga = centers[segc, a]                   # [P, D]
            min_sc = jnp.sum(cga * cga, -1) - 2.0 * jnp.sum(x * cga, -1)
        else:
            cg = centers[segc]                       # [P, K, D]
            c2 = jnp.sum(cg * cg, -1)
            sc = c2 - 2.0 * _xc(x, cg)
            a = jnp.argmin(sc, axis=1).astype(jnp.int32)
            min_sc = jnp.min(sc, 1)
        # two-stage segment reduction: dense per-block partial sums (the
        # layout guarantees one segment per block), then a scatter-add over
        # the SEG_BLOCK-fold smaller block table — no per-point scatter and
        # no one-hot [cap, K] matmul per capacity bucket
        oh = jax.nn.one_hot(a, k, dtype=x.dtype) * fvalid[:, None]
        pw = (oh[:, :, None] * x[:, None, :]).reshape(nb, SEG_BLOCK,
                                                      k * f).sum(1)
        pc = oh.reshape(nb, SEG_BLOCK, k).sum(1)
        sums = jax.ops.segment_sum(pw, bseg, num_segments=n_seg + 1,
                                   indices_are_sorted=True)[
            :n_seg].reshape(n_seg, k, f)
        counts = jax.ops.segment_sum(pc, bseg, num_segments=n_seg + 1,
                                     indices_are_sorted=True)[:n_seg]
        new = sums / jnp.maximum(counts, 1.0)[:, :, None]

        def reseed(nn):
            # re-seed empty clusters at the segment's farthest valid point
            far_score = jnp.where(valid, x2 + min_sc, -jnp.inf)
            bmax = far_score.reshape(nb, SEG_BLOCK).max(1)
            m = jax.ops.segment_max(bmax, bseg, num_segments=n_seg + 1,
                                    indices_are_sorted=True)[:n_seg]
            pos = jnp.where(valid & (far_score == m[segc]), arange_p, p)
            bmin = pos.reshape(nb, SEG_BLOCK).min(1)
            fi = jax.ops.segment_min(bmin, bseg, num_segments=n_seg + 1,
                                     indices_are_sorted=True)[:n_seg]
            far = x[jnp.clip(fi, 0, p - 1)]          # [S, D]
            return jnp.where((counts > 0)[:, :, None], nn, far[:, None, :])

        new = jax.lax.cond(jnp.any(counts == 0), reseed, lambda nn: nn, new)
        # Lloyd is a deterministic map of each segment's own centers; once
        # a segment repeats its centers bitwise it is at a fixed point and
        # every further sweep reproduces it, so exiting early is
        # result-identical to the oracle's full fixed-iteration sweeps
        conv = jnp.all(new == centers, axis=(1, 2))
        return new, i + 1, conv

    centers, n_iter, conv = jax.lax.while_loop(
        lambda c: (c[1] < iters) & ~jnp.all(c[2]),
        body, (centers0, jnp.int32(0), jnp.zeros(n_seg, bool)))
    return centers, n_iter, conv


def kmeans_fit_segmented(x: jnp.ndarray, seg: jnp.ndarray,
                         seg_off: np.ndarray, seg_cnt: np.ndarray,
                         keys: jnp.ndarray, n_seg: int, k: int = 4,
                         iters: int = 50,
                         use_kernel: Optional[bool] = None,
                         first_chunk: int = 6) -> SegmentedKMeansResult:
    """Every segment's Lloyd fit over ONE flat ``[P, D]`` point array.

    ``seg`` holds each row's segment id (``n_seg`` marks pad rows); each
    segment's rows are contiguous starting at ``seg_off[s]`` with
    ``seg_cnt[s]`` real points, runs padded to ``SEG_BLOCK`` multiples
    (``segment_layout``).  No power-of-two capacity padding anywhere, and
    no fixed 50-sweep scan either: a first ``first_chunk``-sweep dispatch
    settles most segments at their (bitwise) Lloyd fixed point, then the
    host compacts the unconverged segments' rows — block-aligned, so their
    FP trajectory is untouched — and only those re-dispatch for the
    remaining sweeps.  Seeding and update math mirror
    ``kmeans_fit_masked`` per segment, so the result is
    cluster-assignment-equal to the bucketed oracle (same labels up to
    centroid permutation; centroids agree to FP reassociation).

    The parity contract is empirical, not a float-for-float proof: the
    per-segment cumulative weights and centroid means are summed in a
    different association order than the bucketed path, so a k-means++
    draw landing within one ulp of an inverse-CDF boundary, or a point
    within one ulp of equidistant to two centroids, could in principle
    flip a label.  The parity suites (test_lern_batched/test_lern_props)
    pin that this never happens on real and hypothesis-random inputs.
    """
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    x = jnp.asarray(x)
    seg = jnp.asarray(seg)
    centers0 = _pp_init_segmented(jnp.asarray(keys), x, seg,
                                  jnp.asarray(seg_off),
                                  jnp.asarray(seg_cnt), n_seg, k)
    it1 = min(first_chunk, iters)
    centers, n1, conv = _lloyd_segmented(x, seg, centers0, n_seg, k, it1,
                                         use_kernel)
    total = int(n1)
    conv_np = np.asarray(conv)
    if it1 < iters and not conv_np.all():
        # compact the stragglers: copy each unconverged segment's padded
        # block run verbatim (block-aligned → bitwise-identical sweeps)
        stragglers = np.flatnonzero(~conv_np)
        xh = np.asarray(x)
        counts = np.asarray(seg_cnt)[stragglers]
        sub_off, sub_total = segment_layout(counts)
        n_sub = stragglers.shape[0]
        sub_p = max(((sub_total + 2047) // 2048) * 2048, SEG_BLOCK)
        xs = np.zeros((sub_p, xh.shape[1]), xh.dtype)
        segs = np.full(sub_p, n_sub, np.int32)
        for si, s in enumerate(stragglers):
            run = ((int(counts[si]) + SEG_BLOCK - 1)
                   // SEG_BLOCK) * SEG_BLOCK
            o = int(np.asarray(seg_off)[s])
            xs[sub_off[si]:sub_off[si] + run] = xh[o:o + run]
            segs[sub_off[si]:sub_off[si] + int(counts[si])] = si
        sub_centers, n2, _ = _lloyd_segmented(
            jnp.asarray(xs), jnp.asarray(segs),
            jnp.asarray(np.asarray(centers)[stragglers]),
            n_sub, k, iters - it1, use_kernel)
        total += int(n2)
        centers = centers.at[jnp.asarray(stragglers)].set(sub_centers)
    if use_kernel:
        from repro.kernels.kmeans_assign import ops as _kops
        a = _kops.assign_segmented(x, centers, seg)
    else:
        a = _assign_segmented_jit(x, centers, seg)
    return SegmentedKMeansResult(centers, a, total)


_assign_segmented_jit = jax.jit(assign_segmented_jnp)


@functools.partial(jax.jit, static_argnames=("k", "iters", "use_kernel"))
def kmeans_fit_batched(x: jnp.ndarray, mask: jnp.ndarray, keys: jnp.ndarray,
                       k: int = 4, iters: int = 50,
                       use_kernel: Optional[bool] = None) -> KMeansResult:
    """vmap of ``kmeans_fit_masked`` over a leading batch axis.

    x [B, N, D], mask [B, N], keys [B, 2] -> KMeansResult with a leading
    B axis on every field.  Each batch row assigns exactly as the
    single-problem fit at the same padded shape (centres equal up to the
    float reassociation vmap may introduce) — this is what lets the
    batched LERN trainer reproduce the per-layer pipeline's labels.
    """
    fit = functools.partial(kmeans_fit_masked, k=k, iters=iters,
                            use_kernel=use_kernel)
    return jax.vmap(fit)(x, mask, keys)


def kmeans_fit(x: jnp.ndarray, k: int = 4, iters: int = 50, seed: int = 0,
               use_kernel: Optional[bool] = None) -> KMeansResult:
    """Unmasked convenience wrapper over ``kmeans_fit_masked``."""
    return kmeans_fit_masked(x, jnp.ones(x.shape[0], bool),
                             jax.random.PRNGKey(seed), k=k, iters=iters,
                             use_kernel=use_kernel)


def normalize(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Feature normalization for K-means (per-dim min-max; the paper
    normalizes the RI histograms before clustering)."""
    lo = jnp.min(x, 0)
    hi = jnp.max(x, 0)
    return (x - lo) / jnp.maximum(hi - lo, 1e-9), lo, hi


def annotate_rc(centers: jnp.ndarray) -> np.ndarray:
    """Map RC cluster index -> semantic label 0..3 (Cold..Hot) by ascending
    center value. Returns int array label_of_cluster[K]."""
    c = np.asarray(centers).reshape(-1)
    order = np.argsort(c)
    label = np.empty_like(order)
    label[order] = np.arange(c.shape[0])
    return label


def annotate_ri(centers_denorm: np.ndarray) -> np.ndarray:
    """Map RI cluster index -> semantic label 0..3 (Immediate..Remote) by the
    expected-bin index of the de-normalized histogram center."""
    c = np.maximum(np.asarray(centers_denorm), 0.0)
    w = c / np.maximum(c.sum(axis=1, keepdims=True), 1e-9)
    score = w @ np.arange(c.shape[1])
    order = np.argsort(score)
    label = np.empty(c.shape[0], dtype=np.int64)
    label[order] = np.arange(c.shape[0])
    return label


def silhouette_score(x: np.ndarray, assign: np.ndarray,
                     max_points: int = 2000, seed: int = 0) -> float:
    """Mean silhouette coefficient (sampled for tractability)."""
    x = np.asarray(x, dtype=np.float64)
    assign = np.asarray(assign)
    n = x.shape[0]
    if n > max_points:
        rng = np.random.default_rng(seed)
        idx = rng.choice(n, max_points, replace=False)
    else:
        idx = np.arange(n)
    xs, as_ = x[idx], assign[idx]
    labels = np.unique(as_)
    if labels.shape[0] < 2:
        return 0.0
    d = np.sqrt(((xs[:, None, :] - xs[None, :, :]) ** 2).sum(-1))
    s = np.zeros(xs.shape[0])
    for i in range(xs.shape[0]):
        own = as_[i]
        same = (as_ == own)
        same[i] = False
        a = d[i][same].mean() if same.any() else 0.0
        b = np.inf
        for l in labels:
            if l == own:
                continue
            mask = as_ == l
            if mask.any():
                b = min(b, d[i][mask].mean())
        s[i] = 0.0 if max(a, b) == 0 else (b - a) / max(a, b)
    return float(s.mean())


def pca_2d(x: np.ndarray) -> np.ndarray:
    """2-D PCA projection (paper Fig. 5 feature-separability view)."""
    x = np.asarray(x, dtype=np.float64)
    xc = x - x.mean(0)
    cov = xc.T @ xc / max(1, x.shape[0] - 1)
    w, v = np.linalg.eigh(cov)
    return xc @ v[:, np.argsort(w)[::-1][:2]]
