"""Device-resident LERN training: the batched pipeline must reproduce the
host-numpy reference (integer tables bitwise, cluster labels exactly,
centres within ``CENTER_RTOL``), and the flat-segmented fit engine must be
cluster-assignment-equal to that bucketed oracle.

Layers of parity:
* jitted ``reuse_features_jax`` == numpy oracle, for any padding amount
  and ragged layer batches (hypothesis property; integer-exact);
* ``kmeans_fit_batched`` row == single ``kmeans_fit_masked`` at the same
  padded shape (assignments exact, centres within tolerance);
* ``train_model_batched(fit_engine="bucketed")`` == ``train`` on a
  multi-layer trace (uniq sets and cluster tables exact, centres within
  tolerance),
  plus packed L-RPT images == per-layer ``load_layer`` tables;
* ``train_model_batched(fit_engine="segmented")`` == the bucketed oracle
  on the semantic cluster-label tables (the annotation step's
  centroid-sort IS the permutation canonicalization), with centers equal
  to FP reassociation — across ragged, empty, single-point, same-size,
  and one-giant-layer shapes.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lern, lrpt
from repro.core.reuse import (PAD_LINE, lines_to_device, reuse_features_jax,
                              reuse_signature_np, ri_histogram_np)
from repro.core.tracegen import Trace


def _features_match_oracle(arr: np.ndarray, pad: int) -> None:
    sig = reuse_signature_np(arr)
    f_ri, f_rc = ri_histogram_np(arr, sig)
    lx = np.concatenate([arr, np.zeros(pad, np.int64)])
    out = jax.jit(reuse_features_jax)(jnp.asarray(lines_to_device(lx)),
                                      jnp.int32(arr.shape[0]))
    nu = int(out["n_uniq"])
    assert nu == sig["uniq"].shape[0]
    np.testing.assert_array_equal(np.asarray(out["uniq"][:nu], np.int64),
                                  sig["uniq"])
    np.testing.assert_array_equal(np.asarray(out["f_ri"][:nu]), f_ri)
    np.testing.assert_array_equal(np.asarray(out["f_rc"][:nu]), sig["count"])
    assert np.all(np.asarray(out["uniq"][nu:]) == PAD_LINE)
    assert np.all(np.asarray(out["f_rc"][nu:]) == 0)


def test_features_match_oracle_table1():
    _features_match_oracle(np.array([1, 1, 1, 2, 2, 1, 1, 2], np.int64), 5)


def test_features_kernel_vs_jnp_binning():
    rng = np.random.default_rng(0)
    lx = jnp.asarray(rng.integers(0, 64, 1000).astype(np.int32))
    a = jax.jit(reuse_features_jax, static_argnames=("use_kernel",))(
        lx, jnp.int32(777), use_kernel=True)
    b = jax.jit(reuse_features_jax, static_argnames=("use_kernel",))(
        lx, jnp.int32(777), use_kernel=False)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


def _synthetic_trace(n_layers: int = 3, seed: int = 0) -> Trace:
    """Hot/warm/streaming mix per layer (ragged layer lengths)."""
    rng = np.random.default_rng(seed)
    chunks = []
    for i in range(n_layers):
        n = 1500 + 400 * i
        hot = np.arange(16) + 1000 * i
        warm = np.arange(100, 140) + 1000 * i
        seq = np.empty(n, np.int64)
        ci = 0
        for t in range(n):
            r = rng.random()
            if r < 0.5:
                seq[t] = rng.choice(hot)
            elif r < 0.7:
                seq[t] = rng.choice(warm)
            else:
                seq[t] = 50_000 * (i + 1) + ci
                ci += 1
        chunks.append(seq)
    line = np.concatenate(chunks)
    layer = np.concatenate([np.full(len(c), i, np.int32)
                            for i, c in enumerate(chunks)])
    return Trace(line=line, write=np.zeros_like(line, bool),
                 cycle=np.arange(len(line)), layer=layer,
                 layer_names=[f"l{i}" for i in range(n_layers)],
                 compute_cycles=len(line))


# Centre tolerance of the label-equality contract.  The label tables are
# the model LERN hands the simulator and must match exactly; centres are
# float32 sums whose association order XLA may change (vmap vs a single
# fit, segmented vs bucketed reductions, one jax release to the next) —
# observed differences are a few ulp (<= 2.4e-7 relative), so 1e-4 is
# loose enough to be stable and tight enough to catch a wrong cluster.
CENTER_RTOL = CENTER_ATOL = 1e-4


def _assert_labels_equal(a, b, centers_exact=True):
    """a (oracle) and b agree on every cluster-label table; centers are
    bitwise when ``centers_exact`` else within CENTER_RTOL/CENTER_ATOL."""
    assert a.n_layers == b.n_layers
    np.testing.assert_array_equal(a.n_uniq, b.n_uniq)
    for li in range(a.n_layers):
        n = int(a.n_uniq[li])
        np.testing.assert_array_equal(a.uniq[li, :n], b.uniq[li, :n])
        np.testing.assert_array_equal(a.rc_cluster[li, :n],
                                      b.rc_cluster[li, :n])
        np.testing.assert_array_equal(a.ri_cluster[li, :n],
                                      b.ri_cluster[li, :n])
        np.testing.assert_array_equal(a.features_ri[li], b.features_ri[li])
        if centers_exact:
            np.testing.assert_array_equal(a.rc_centers[li],
                                          b.rc_centers[li])
            np.testing.assert_array_equal(a.ri_centers[li],
                                          b.ri_centers[li])
        else:
            np.testing.assert_allclose(a.rc_centers[li], b.rc_centers[li],
                                       rtol=CENTER_RTOL, atol=CENTER_ATOL)
            np.testing.assert_allclose(a.ri_centers[li], b.ri_centers[li],
                                       rtol=CENTER_RTOL, atol=CENTER_ATOL)


def test_train_batched_matches_host_bitwise():
    """The batched (vmapped) trainer reproduces the per-layer host
    reference: integer features and uniq sets bitwise, cluster-label
    tables exactly, centres within the contract's tolerance — XLA CPU
    may reassociate the vmapped fit's float sums (a 1-ulp centre drift
    appeared with jax 0.9), which must never move a label."""
    tr = _synthetic_trace()
    a = lern.train(tr, seed=3)
    b = lern.train_model_batched(tr, seed=3, fit_engine="bucketed")
    _assert_labels_equal(a, b, centers_exact=False)


def test_train_segmented_matches_bucketed_labels():
    """The flat-segmented engine reproduces the bucketed oracle's cluster
    tables exactly (labels canonicalized by the annotation centroid sort)
    with centers equal up to FP reassociation."""
    tr = _synthetic_trace()
    a = lern.train_model_batched(tr, seed=3, fit_engine="bucketed")
    b = lern.train_model_batched(tr, seed=3, fit_engine="segmented")
    _assert_labels_equal(a, b, centers_exact=False)


def test_segmented_engine_shape_edge_cases():
    """Empty layer, single-point layer, all-same-size layers, and one
    giant layer among tiny ones — segmented == bucketed labels on all."""
    def mk(chunks):
        line = np.concatenate([np.asarray(c, np.int64) for c in chunks]) \
            if any(len(c) for c in chunks) else np.zeros(0, np.int64)
        layer = np.concatenate([np.full(len(c), i, np.int32)
                                for i, c in enumerate(chunks)]) \
            if any(len(c) for c in chunks) else np.zeros(0, np.int32)
        return Trace(line=line, write=np.zeros_like(line, bool),
                     cycle=np.arange(len(line)), layer=layer,
                     layer_names=[f"l{i}" for i in range(len(chunks))],
                     compute_cycles=max(len(line), 1))

    rng = np.random.default_rng(0)
    hot = lambda n, base: rng.choice(np.arange(24) + base, n)  # noqa: E731
    cases = [
        # empty middle layer
        [hot(400, 0), [], hot(300, 1000)],
        # single-point layer (and a single-line layer)
        [hot(500, 0), [7], [9] * 40],
        # all layers the same size
        [hot(256, 0), hot(256, 1000), hot(256, 2000)],
        # one giant segment among tiny ones
        [hot(20, 0), hot(5000, 1000), hot(12, 2000)],
    ]
    for chunks in cases:
        tr = mk(chunks)
        a = lern.train_model_batched(tr, seed=5, fit_engine="bucketed")
        b = lern.train_model_batched(tr, seed=5, fit_engine="segmented")
        _assert_labels_equal(a, b, centers_exact=False)


def test_resolve_engine():
    assert lern.resolve_engine("auto") == "segmented"
    assert lern.resolve_engine("bucketed") == "bucketed"
    assert lern.resolve_engine("segmented") == "segmented"
    try:
        lern.resolve_engine("nope")
        assert False, "expected ValueError"
    except ValueError:
        pass


def test_train_family_batched_matches_individual_bitwise():
    """One family dispatch over several configs' traces == per-config
    ``train_model_batched``, model for model, bit for bit — the property
    that makes the family fit cache-compatible with ``sim.load_lern``."""
    traces = [_synthetic_trace(n_layers=3, seed=11),
              _synthetic_trace(n_layers=2, seed=12),
              _synthetic_trace(n_layers=4, seed=13)]
    fam = lern.train_family_batched(traces, seed=7, fit_engine="bucketed")
    assert len(fam) == len(traces)
    segf = lern.train_family_batched(traces, seed=7,
                                     fit_engine="segmented")
    for got, seg in zip(fam, segf):
        _assert_labels_equal(got, seg, centers_exact=False)
    for tr, got in zip(traces, fam):
        want = lern.train_model_batched(tr, seed=7, fit_engine="bucketed")
        assert got.n_layers == want.n_layers
        np.testing.assert_array_equal(got.n_uniq, want.n_uniq)
        for li in range(want.n_layers):
            n = int(want.n_uniq[li])
            np.testing.assert_array_equal(got.uniq[li, :n],
                                          want.uniq[li, :n])
            np.testing.assert_array_equal(got.rc_cluster[li, :n],
                                          want.rc_cluster[li, :n])
            np.testing.assert_array_equal(got.ri_cluster[li, :n],
                                          want.ri_cluster[li, :n])
            np.testing.assert_array_equal(got.rc_centers[li],
                                          want.rc_centers[li])
            np.testing.assert_array_equal(got.ri_centers[li],
                                          want.ri_centers[li])
            np.testing.assert_array_equal(got.features_ri[li],
                                          want.features_ri[li])


def test_train_family_batched_hashed_variant():
    traces = [_synthetic_trace(n_layers=2, seed=21),
              _synthetic_trace(n_layers=2, seed=22)]
    hashed = lrpt.lrpt_train_hash("loptv3")
    fam = lern.train_family_batched(traces, hash_fn=hashed, seed=2,
                                    fit_engine="bucketed")
    for tr, got in zip(traces, fam):
        want = lern.train_model_batched(tr, hash_fn=hashed, seed=2,
                                        fit_engine="bucketed")
        np.testing.assert_array_equal(got.rc_cluster, want.rc_cluster)
        np.testing.assert_array_equal(got.ri_cluster, want.ri_cluster)


def test_train_batched_hashed_variant():
    """§VI-J hashed training goes through the same batched path — both
    fit engines."""
    tr = _synthetic_trace(n_layers=2, seed=5)
    hashed = lrpt.lrpt_train_hash("loptv3")
    a = lern.train(tr, hash_fn=hashed, seed=1)
    for engine in ("bucketed", "segmented"):
        b = lern.train_model_batched(tr, hash_fn=hashed, seed=1,
                                     fit_engine=engine)
        np.testing.assert_array_equal(a.rc_cluster, b.rc_cluster)
        np.testing.assert_array_equal(a.ri_cluster, b.ri_cluster)


def test_packed_tables_match_load_layer():
    tr = _synthetic_trace()
    model = lern.train_model_batched(tr, seed=0)
    for variant in ("full", "loptv1"):
        tables = lrpt.pack_tables(model, variant)
        t = lrpt.LRPT.create(variant)
        for li in range(model.n_layers):
            t.load_layer(model, li)
            np.testing.assert_array_equal(tables[li], t.table, variant)
        # whole-trace vectorized lookup == per-layer lookup
        rc, ri = lrpt.lookup_tables(tables, variant, tr.layer, tr.line)
        for li in range(model.n_layers):
            mask = tr.layer == li
            t.load_layer(model, li)
            rc_l, ri_l = t.lookup(tr.line[mask])
            np.testing.assert_array_equal(rc[mask], rc_l)
            np.testing.assert_array_equal(ri[mask], ri_l)


def test_replace_layers_swaps_tables():
    tr = _synthetic_trace()
    a = lern.train_model_batched(tr, seed=0)
    b = lern.train_model_batched(tr, seed=9)
    merged = a.replace_layers([1], b)
    n = int(merged.n_uniq[1])
    np.testing.assert_array_equal(merged.rc_cluster[1, :n],
                                  b.rc_cluster[1, :n])
    n0 = int(merged.n_uniq[0])
    np.testing.assert_array_equal(merged.rc_cluster[0, :n0],
                                  a.rc_cluster[0, :n0])
    np.testing.assert_array_equal(merged.rc_centers[0], a.rc_centers[0])
    np.testing.assert_array_equal(merged.rc_centers[1], b.rc_centers[1])


