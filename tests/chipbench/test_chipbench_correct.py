"""What decides ``correct``, checked on the CPU at a small size: sound
runs pass, the lower-precision controls fail, and a run whose timed path
is broken underneath comes out not correct, once for each fault a cell can
have.  (No cell crosses chips, so "the exchange between chips left out"
cannot occur.)"""
import dataclasses
import io
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import common  # noqa: E402

SMALL_LERN = {"traffic": {"kmeans_seeds": 1}}


def _run(workload, overrides, seed=11):
    from chipbench import run
    return run.run_cell(workload, seed, 0.05, trace=False,
                        require_tpu=False, overrides=overrides,
                        out=io.StringIO(), err=io.StringIO())


def test_lern_bfloat16_control_fails(env):
    import jax.numpy as jnp
    from chipbench import controls
    from chipbench.reference.compare import LernReference
    from repro.core import sim
    tr = sim.load_trace("config4", 300_000)
    ref = LernReference(np.asarray(tr.line, np.int64),
                        np.asarray(tr.layer), len(tr.layer_names))
    lim = common.load_json("traffic", "lern-full.json")["limits"]
    for seed in (1, 2):
        got = ref.compare(controls.lern_control_model(ref, seed,
                                                      jnp.bfloat16))
        assert any(got[k] > lim[k] for k in lim)
    got = ref.compare(controls.lern_control_model(ref, 1, jnp.float32))
    assert all(got[k] <= lim[k] for k in lim if k != "center_rel_gap")


def test_sound_lern_run_is_correct(env):
    assert _run("lern.config4", SMALL_LERN)["correct"]


def _patch_training(monkeypatch, change):
    from repro.core import lern
    orig = lern.train_model_batched

    def broken(*a, **kw):
        return change(orig(*a, **kw))

    monkeypatch.setattr(lern, "train_model_batched", broken)


def test_lern_answer_altered(env, monkeypatch):
    def relabel(model):
        rc = model.rc_cluster.copy()
        li = int(np.argmax((rc >= 0).sum(1)))
        j = int(np.flatnonzero(rc[li] >= 0)[0])
        rc[li, j] = (rc[li, j] + 2) % 4
        return dataclasses.replace(model, rc_cluster=rc)

    _patch_training(monkeypatch, relabel)
    assert not _run("lern.config4", SMALL_LERN)["correct"]


def test_lern_half_the_layers_left_out(env, monkeypatch):
    def drop(model):
        n = model.n_layers // 2
        rc, ri = model.rc_cluster.copy(), model.ri_cluster.copy()
        rc[:n], ri[:n] = -1, -1
        return dataclasses.replace(model, rc_cluster=rc, ri_cluster=ri)

    _patch_training(monkeypatch, drop)
    assert not _run("lern.config4", SMALL_LERN)["correct"]


def test_lern_step_returns_its_state_unchanged(env, monkeypatch):
    from repro.core import kmeans

    def frozen(x, seg, centers0, n_seg, k, iters, use_kernel):
        import jax.numpy as jnp
        return centers0, jnp.int32(iters), jnp.ones(n_seg, bool)

    monkeypatch.setattr(kmeans, "_lloyd_segmented", frozen)
    assert not _run("lern.config4", SMALL_LERN)["correct"]
