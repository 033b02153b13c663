"""Hypothesis property tests for the device-resident LERN pipeline:
padded/ragged batches of the jitted feature extractor must match the
numpy oracle bitwise, the batched masked k-means must assign exactly as
the single-problem fit (centres within tolerance), and the flat-segmented
fit engine must stay cluster-assignment-equal to the
bucketed oracle over random ragged layer sets.
(Whole module skips where hypothesis is absent; CI installs it.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import kmeans as km, lern  # noqa: E402
from repro.core.tracegen import Trace  # noqa: E402
from test_lern_batched import (CENTER_ATOL, CENTER_RTOL,  # noqa: E402
                               _assert_labels_equal, _features_match_oracle)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 40), min_size=1, max_size=250),
       st.integers(0, 120))
def test_features_property_padding_invariant(lines, pad):
    """jitted reuse features == numpy oracle for any trace and padding."""
    _features_match_oracle(np.array(lines, np.int64), pad)


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(2, 48), min_size=1, max_size=4),
       st.integers(0, 2 ** 31 - 1))
def test_batched_kmeans_matches_single(sizes, seed):
    """Each row of the vmapped fit assigns every valid point exactly as
    the single masked fit at the same padded shape does, for ragged point
    counts; centres agree within the label-equality contract's tolerance
    (vmap may reassociate float sums — a few ulp under jax 0.9 CPU)."""
    rng = np.random.default_rng(seed)
    cap = max(sizes)
    b = len(sizes)
    x = np.zeros((b, cap, 4), np.float32)
    mask = np.zeros((b, cap), bool)
    for i, n in enumerate(sizes):
        x[i, :n] = rng.normal(size=(n, 4)).astype(np.float32)
        mask[i, :n] = True
    keys = jnp.stack([jax.random.PRNGKey(seed % 10_000 + i)
                      for i in range(b)])
    rb = km.kmeans_fit_batched(jnp.asarray(x), jnp.asarray(mask), keys,
                               k=4, iters=8)
    for i in range(b):
        rs = km.kmeans_fit_masked(jnp.asarray(x[i]), jnp.asarray(mask[i]),
                                  keys[i], k=4, iters=8)
        np.testing.assert_allclose(np.asarray(rs.centers),
                                   np.asarray(rb.centers[i]),
                                   rtol=CENTER_RTOL, atol=CENTER_ATOL)
        np.testing.assert_array_equal(np.asarray(rs.assign)[mask[i]],
                                      np.asarray(rb.assign[i])[mask[i]])


def _canon(centers: np.ndarray, assign: np.ndarray) -> np.ndarray:
    """Relabel ``assign`` by the lexicographic rank of each cluster's
    centroid (stable) — the permutation canonicalization the segmented
    parity story is pinned on."""
    order = np.lexsort(centers.T[::-1])
    rank = np.empty(centers.shape[0], np.int64)
    rank[order] = np.arange(centers.shape[0])
    return rank[assign]


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(8, 60), min_size=1, max_size=5),
       st.integers(0, 2 ** 31 - 1))
def test_segmented_kmeans_matches_masked(sizes, seed):
    """Each segment of the flat-segmented fit is assignment-equal
    (centroid-sort canonicalized) to the masked single fit at that
    segment's own power-of-two capacity, for ragged segment sets."""
    rng = np.random.default_rng(seed)
    seg_off, total = km.segment_layout(sizes)
    p = total
    s = len(sizes)
    x = np.zeros((p, 4), np.float32)
    seg = np.full(p, s, np.int32)
    for i, n in enumerate(sizes):
        x[seg_off[i]:seg_off[i] + n] = \
            rng.normal(size=(n, 4)).astype(np.float32)
        seg[seg_off[i]:seg_off[i] + n] = i
    keys = jnp.stack([jax.random.PRNGKey(seed % 10_000 + i)
                      for i in range(s)])
    res = km.kmeans_fit_segmented(jnp.asarray(x), jnp.asarray(seg),
                                  seg_off, np.asarray(sizes, np.int32),
                                  keys, n_seg=s, k=4)
    for i, n in enumerate(sizes):
        cap = max(8, 1 << (int(n) - 1).bit_length())
        xp = np.zeros((cap, 4), np.float32)
        xp[:n] = x[seg_off[i]:seg_off[i] + n]
        mask = np.zeros(cap, bool)
        mask[:n] = True
        rs = km.kmeans_fit_masked(jnp.asarray(xp), jnp.asarray(mask),
                                  keys[i], k=4)
        a_seg = np.asarray(res.assign)[seg_off[i]:seg_off[i] + n]
        c_seg = np.asarray(res.centers[i])
        a_ref = np.asarray(rs.assign)[:n]
        c_ref = np.asarray(rs.centers)
        np.testing.assert_array_equal(_canon(c_seg, a_seg),
                                      _canon(c_ref, a_ref))
        np.testing.assert_allclose(c_seg, c_ref, rtol=1e-4, atol=1e-5)


def _ragged_trace(layer_sizes, seed):
    """Random ragged multi-layer trace: per layer a hot-set/streaming mix
    so multi-occurrence counts vary wildly across layers."""
    rng = np.random.default_rng(seed)
    chunks = []
    for i, n in enumerate(layer_sizes):
        base = 10_000 * (i + 1)
        if n == 0:
            chunks.append(np.zeros(0, np.int64))
            continue
        hot = np.arange(rng.integers(1, 24)) + base
        seq = np.where(rng.random(n) < 0.7, rng.choice(hot, n),
                       base + 5000 + np.arange(n))
        chunks.append(seq.astype(np.int64))
    line = np.concatenate(chunks)
    layer = np.concatenate([np.full(len(c), i, np.int32)
                            for i, c in enumerate(chunks)])
    return Trace(line=line, write=np.zeros_like(line, bool),
                 cycle=np.arange(len(line)), layer=layer,
                 layer_names=[f"l{i}" for i in range(len(layer_sizes))],
                 compute_cycles=max(len(line), 1))


@settings(max_examples=8, deadline=None)
@given(st.lists(st.integers(0, 400), min_size=1, max_size=5),
       st.integers(0, 2 ** 31 - 1))
def test_segmented_vs_bucketed_trainer_property(layer_sizes, seed):
    """Segmented and bucketed trainers agree on every cluster-label table
    for random ragged layer sets (incl. empty and sub-MIN_MULTI layers)."""
    if not any(layer_sizes):
        return
    tr = _ragged_trace(layer_sizes, seed)
    a = lern.train_model_batched(tr, seed=seed % 1000,
                                 fit_engine="bucketed")
    b = lern.train_model_batched(tr, seed=seed % 1000,
                                 fit_engine="segmented")
    _assert_labels_equal(a, b, centers_exact=False)
