"""Declarative experiment API: registry protocol conformance, spec
expansion + transforms, columnar ResultSet + hydra-sweep/v3 round-trip,
bitwise parity of exp.run against the pre-redesign sequential path,
phase-drift workloads, and the serve-side online retrain hook."""
import dataclasses
import math

import numpy as np
import pytest

from _reference import run_reference
from repro import exp
from repro.core import sim, tracegen, workloads
from repro.exp.schema import validate_sweep
from repro.serve.hydra_scheduler import HydraKVScheduler, SessionProfile
from repro.serve.knobs import SchedulerKnobs

TINY = dataclasses.replace(sim.SimParams(), n_inputs=1, max_epochs=40,
                           subsample_target=50_000)


# ---------------------------------------------------------------------------
# registries: one uniform protocol across all four
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", sorted(exp.REGISTRIES))
def test_registry_protocol(kind):
    reg = exp.REGISTRIES[kind]
    names = reg.names()
    assert names == sorted(names) and len(names) == len(set(names))
    assert len(reg) == len(names) > 0
    assert list(reg) == names
    for n in names[:3]:
        assert n in reg
        assert reg.get(n) is not None
    assert "definitely-not-registered" not in reg
    with pytest.raises(KeyError) as ei:
        reg.get("definitely-not-registered")
    assert kind in str(ei.value)  # the error names its registry
    # idempotent re-registration is allowed...
    first = names[0]
    assert reg.register(first, reg.get(first)) == reg.get(first)
    # ...registering junk is type-checked and does not pollute the registry
    with pytest.raises(TypeError):
        reg.register("junk-entry", object())
    assert "junk-entry" not in reg


def test_params_presets_are_frozen_replacements():
    quick = exp.PARAMS.get("quick")
    smoke = exp.PARAMS.get("smoke")
    assert quick.n_inputs == 3 and quick.max_epochs == 1500
    assert smoke.n_inputs == 1 and smoke.max_epochs == 60
    assert smoke.subsample_target == 50_000
    with pytest.raises(dataclasses.FrozenInstanceError):
        smoke.n_inputs = 2  # the set_smoke() mutation pattern is dead


# ---------------------------------------------------------------------------
# spec expansion
# ---------------------------------------------------------------------------
def test_grid_expands_cross_product_with_named_axes():
    spec = exp.ExperimentSpec.grid(
        config="config1", mix=["moti1", "moti2"],
        policy=["fifo-nb", "hydra"], params="smoke",
        llc_size_bytes=[512 * 1024, 1024 * 1024])
    assert len(spec) == 8
    pts = spec.expand()
    assert len(pts) == 8
    sizes = {pt.params.llc_size_bytes for pt, _ in pts}
    assert sizes == {512 * 1024, 1024 * 1024}
    # axis rows carry JSON coordinates incl. the override axis
    _, row = pts[0]
    assert row["params"] == "smoke" and "llc_size_bytes" in row
    # points are frozen + hashable (usable as dedup keys)
    assert len({pt for pt, _ in pts}) == 8


def test_product_extends_and_rebinds_axes():
    spec = exp.ExperimentSpec.grid(config="config1", mix="moti1",
                                   policy="fifo-nb", params="smoke")
    wider = spec.product(mix=["moti1", "moti2"], seed=[0, 1])
    assert len(wider) == 4
    assert wider.axis("seed") == (0, 1)
    with pytest.raises(ValueError):
        spec.product(not_a_param=[1])
    with pytest.raises(ValueError):
        exp.ExperimentSpec.grid(bogus_axis=[1])


def test_policy_transforms_match_legacy_derivers():
    from repro.core import policies
    ol = exp.resolve_policy(("hydra", exp.online(50)))
    assert ol == policies.with_online(policies.get("hydra"), 50)
    wp = exp.resolve_policy(("fifo-nb", exp.way_partition(0xFFFC, 0x3)))
    assert wp == policies.with_way_partition(policies.get("fifo-nb"),
                                             0xFFFC, 0x3)
    lv = exp.resolve_policy(("hydra", exp.lrpt("v1")))
    assert lv == policies.with_lrpt(policies.get("hydra"), "v1")
    ap = exp.resolve_policy(("hydra", exp.with_apm(margin_high=0.07)))
    assert ap.apm.margin_high == 0.07 and ap.name == "hydra-margin_high0.07"
    # transforms chain, and unknown names fail through the registry
    both = exp.resolve_policy(("hydra", exp.online(50),
                               exp.way_partition(0xFFFC, 0x3)))
    assert both.name == "hydra-ol-wp"
    with pytest.raises(KeyError):
        exp.resolve_policy("no-such-policy")


# ---------------------------------------------------------------------------
# ResultSet: queries + hydra-sweep/v3 round-trip
# ---------------------------------------------------------------------------
def _toy_rs():
    rows = [{"config": "c1", "mix": m, "policy": p, "ipc": v,
             "dmr": d, "name": f"t/{p}/{m}", "us_per_call": 10,
             "derived": {"speedup": v}}
            for (m, p, v, d) in [("a", "x", 1.0, 0.0), ("b", "x", 2.0, 1.0),
                                 ("a", "y", 3.0, 0.0), ("b", "y", 5.0, 0.0)]]
    return exp.ResultSet.from_records(rows, keys=["config", "mix", "policy"])


def test_resultset_filter_group_mean():
    rs = _toy_rs()
    assert len(rs) == 4
    assert len(rs.filter(policy="x")) == 2
    assert rs.filter(policy="x", mix="a").one()["ipc"] == 1.0
    groups = rs.group_by("policy")
    assert set(groups) == {("x",), ("y",)}
    bars = rs.mean_over("mix")
    assert bars.filter(policy="x").one()["ipc"] == 1.5
    assert bars.filter(policy="y").one()["ipc"] == 4.0
    assert bars.filter(policy="y").one()["n"] == 2
    # mean_over drops the averaged axis from the keys
    assert "mix" not in bars.keys


def test_sweep_v2_round_trip_and_validation(tmp_path):
    rs = _toy_rs()
    path = str(tmp_path / "sweep.json")
    doc = rs.to_sweep_json(path, preset="smoke", modules=["toy"])
    assert validate_sweep(doc) == []
    back = exp.ResultSet.from_sweep_json(path)
    assert back.keys == rs.keys
    assert len(back) == len(rs)
    for a, b in zip(rs, back):
        for k in ("config", "mix", "policy", "ipc", "dmr", "name",
                  "us_per_call", "derived"):
            assert a[k] == b[k], k
    # serialization is stable: a round-tripped set re-serializes equal
    assert back.to_sweep_doc(preset="smoke", modules=["toy"]) == doc


def test_sweep_v2_validator_rejects_malformed():
    assert validate_sweep({"schema": "hydra-sweep/v1"})  # wrong version
    doc = _toy_rs().to_sweep_doc()
    doc["rows"][0].pop("point")
    assert any("point" in e for e in validate_sweep(doc))
    doc2 = _toy_rs().to_sweep_doc()
    doc2["rows"][0]["metrics"] = {"ipc": "fast"}
    assert any("metrics" in e for e in validate_sweep(doc2))


# ---------------------------------------------------------------------------
# bitwise parity: exp.run == the pre-redesign per-point path
# ---------------------------------------------------------------------------
def test_exp_run_bitwise_parity_with_legacy_path(tmp_path, monkeypatch):
    """Every row exp.run emits for the smoke cross-product equals the
    sequential reference loop with the calibrated deadline.  Fresh cache
    dir, so both sides really compute."""
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    spec = exp.ExperimentSpec.grid(config="config1", mix="moti1",
                                   policy=["fifo-nb", "arp-cs-as-d"],
                                   params=TINY)
    rs = exp.run(spec)
    assert len(rs) == 2
    for row in rs:
        pt, got = row["point"], row["result"]
        deadline = sim.calibrated_deadline(pt.config, pt.params, pt.dram)
        want = run_reference(pt.config, pt.mix, pt.policy, pt.params,
                             pt.dram, deadline_cycles=deadline)
        assert got.summary() == want.summary(), pt.policy.name
        assert got.completion_cycles == want.completion_cycles
        assert got.epochs == want.epochs
        assert got.history == want.history
        # the row landed in the shared disk cache under the same key
        cached = sim.cache_load(pt.cache_path())
        assert cached is not sim.MISS
        assert cached.summary() == got.summary()


def test_exp_run_uncached_matches_cached(tmp_path, monkeypatch):
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    spec = exp.ExperimentSpec.grid(config="config1", mix="moti1",
                                   policy=["fifo-nb"], params=TINY)
    fresh = exp.run(spec, plan=exp.ExecPlan(cache=False)).one()["result"]
    again = exp.run(spec, plan=exp.ExecPlan(cache=True)).one()["result"]
    assert fresh.summary() == again.summary()
    assert fresh.history == again.history


# ---------------------------------------------------------------------------
# ExecPlan: the unified execution-plan surface
# ---------------------------------------------------------------------------
def test_execplan_env_defaults_and_validation(monkeypatch):
    monkeypatch.delenv("REPRO_ENGINE", raising=False)
    monkeypatch.delenv("REPRO_LERN_FIT", raising=False)
    rp = exp.ExecPlan().resolve()
    assert (rp.engine, rp.jobs, rp.cache, rp.fit_engine) == \
        ("auto", 1, True, "auto")
    from repro.core import sweep
    assert rp.max_lanes == sweep.MAX_LANES
    # env vars are the defaults...
    monkeypatch.setenv("REPRO_ENGINE", "host")
    assert exp.ExecPlan().resolve().engine == "host"
    monkeypatch.setenv("REPRO_ENGINE", "bucketed")
    assert exp.ExecPlan().resolve().engine == "bucketed"
    monkeypatch.setenv("REPRO_LERN_FIT", "bucketed")
    assert exp.ExecPlan().resolve().fit_engine == "bucketed"
    # ...and explicit fields beat them
    assert exp.ExecPlan(engine="host").resolve().engine == "host"
    assert exp.ExecPlan(fit_engine="segmented").resolve().fit_engine == \
        "segmented"
    # junk rejected, eagerly and from the env; the per-group "fused"
    # engine is gone, and the plan has no pipeline switch
    for junk in ("fused", "warp"):
        with pytest.raises(ValueError):
            exp.ExecPlan(engine=junk)
    assert "pipeline" not in {f.name for f in dataclasses.fields(exp.ExecPlan)}
    with pytest.raises(ValueError):
        exp.ExecPlan(fit_engine="warp")
    monkeypatch.setenv("REPRO_ENGINE", "warp")
    with pytest.raises(ValueError):
        exp.ExecPlan().resolve()
    # frozen: plans are shareable constants
    with pytest.raises(dataclasses.FrozenInstanceError):
        exp.ExecPlan().engine = "host"


def test_execplan_jobs_refused_on_accelerator(monkeypatch):
    """A process pool needs the CPU backend: on an accelerator the parent
    holds the device, so ExecPlan(jobs>1) raises before any worker (or
    any LERN training, or calibration) starts."""
    import jax

    from repro.core import sweep
    spec = exp.ExperimentSpec.grid(config="config1", mix="moti1",
                                   policy=["fifo-nb"], params=TINY)
    started = []
    monkeypatch.setattr(sweep, "map_points",
                        lambda *a, **kw: started.append(a))
    monkeypatch.setattr(sweep, "_prepare_lern",
                        lambda *a, **kw: started.append(a))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match=r"jobs=2.*tpu"):
        exp.ExecPlan(jobs=2).resolve()
    with pytest.raises(ValueError, match="jobs=1"):
        exp.run(spec, plan=exp.ExecPlan(engine="host", jobs=2))
    assert not started
    assert exp.ExecPlan(jobs=1).resolve().jobs == 1
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert exp.ExecPlan(jobs=2).resolve().jobs == 2


def test_execplan_legacy_kwargs_removed():
    # the one-release deprecation grace for the pre-ExecPlan bare kwargs
    # is over: execution knobs live solely on ExecPlan now
    spec = exp.ExperimentSpec.grid(config="config1", mix="moti1",
                                   policy=["fifo-nb"], params=TINY)
    with pytest.raises(TypeError):
        exp.run(spec, jobs=1)
    with pytest.raises(TypeError):
        exp.run_points([], cache=False)


# ---------------------------------------------------------------------------
# phase-drift workloads (spec axis for the online-LERN study)
# ---------------------------------------------------------------------------
def test_phase_drift_trace_structure():
    base_cfg = workloads.CONFIGS["config10"]
    base = tracegen.generate_trace(base_cfg)
    name = workloads.with_drift("config10",
                                workloads.PhaseDrift(period=2, seed=3))
    assert name in exp.WORKLOADS  # registered through the shared backing
    d = tracegen.generate_trace(exp.WORKLOADS.get(name))
    n = base.num_accesses
    # replica 0 is the exact base schedule; replica 1 drifted
    assert np.array_equal(d.line[:n], base.line[:n])
    assert np.array_equal(d.layer[:n], base.layer[:n])
    assert d.num_accesses > n
    assert not np.array_equal(d.line[n:2 * n][:n], base.line[:n])
    # layer ids stay base-schedule indices
    assert set(np.unique(d.layer)) <= set(range(len(base.layer_names)))
    assert d.layer_names == base.layer_names
    # seed-controlled determinism
    d2 = tracegen.generate_trace(exp.WORKLOADS.get(name))
    assert np.array_equal(d.line, d2.line)
    assert np.array_equal(d.cycle, d2.cycle)
    # period=1 degenerates to the base trace exactly
    name1 = workloads.with_drift("config10", workloads.PhaseDrift(period=1))
    e = tracegen.generate_trace(exp.WORKLOADS.get(name1))
    assert np.array_equal(e.line, base.line)
    assert np.array_equal(e.cycle, base.cycle)


def test_resolve_config_rejects_name_collision():
    """An ad-hoc AccelConfig reusing a registered name with different
    contents must raise, not silently evaluate the registered one."""
    from repro.exp.spec import resolve_config
    clone = exp.WORKLOADS.get("config1")
    assert resolve_config(clone) == "config1"          # equal: no-op
    impostor = dataclasses.replace(clone, pe_rows=999)
    with pytest.raises(ValueError, match="already registered"):
        resolve_config(impostor)
    assert exp.WORKLOADS.get("config1").pe_rows != 999


def test_worker_init_reships_runtime_configs(tmp_path, monkeypatch):
    """Spawn workers re-import workloads.py, losing runtime-registered
    configs; _worker_init must re-register the shipped extras."""
    from repro.core import sweep
    old_cache = sim.CACHE_DIR
    name = "config-unit-test-ephemeral"
    cfg = dataclasses.replace(workloads.CONFIGS["config10"], name=name)
    assert name not in workloads.CONFIGS  # simulates the fresh import
    try:
        sweep._worker_init(str(tmp_path), {name: cfg})
        assert workloads.CONFIGS[name] == cfg
    finally:
        workloads.CONFIGS.pop(name, None)
        sim.CACHE_DIR = old_cache


def test_drift_config_is_a_spec_axis():
    name = workloads.with_drift("config10",
                                workloads.PhaseDrift(period=2, seed=3))
    spec = exp.ExperimentSpec.grid(config=["config10", name], mix="moti1",
                                   policy="fifo-nb", params="smoke")
    assert [pt.config for pt in spec.points()] == ["config10", name]
    # drift variants never perturb the base family's sampling ratio
    k_base = sim._family_k("config10", 50_000)
    assert sim._family_k(name, 50_000) == k_base


# ---------------------------------------------------------------------------
# serve: online retrain hook in HydraKVScheduler epochs
# ---------------------------------------------------------------------------
def _profile():
    return SessionProfile.fit(
        turns_per_session=np.array([1, 1, 2, 4, 6, 8, 8, 12] * 4),
        gaps=np.array([2, 4, 8, 16, 64, 256, 400, 800] * 4))


def _drive(sched, n=64, seed=0):
    rng = np.random.default_rng(seed)
    decisions = []
    for i in range(n):
        turns = float(rng.integers(1, 12))
        gap = float(rng.integers(2, 800))
        decisions.append(sched.keep_resident(turns, gap))
        if (i + 1) % 4 == 0:
            sched.epoch_update(decoded_rate=float(rng.random()),
                               required_rate=1.0,
                               hbm_pressure=float(rng.random()))
    return decisions


def test_kv_scheduler_infinite_period_is_offline_bitwise():
    """retrain_period=inf (the default) must be bitwise the offline-only
    scheduler: same decision sequence, same thresholds, zero refits."""
    profile = _profile()
    base = HydraKVScheduler(
        SchedulerKnobs(token_budget=2048, deadline_tokens=128),
        profile=profile)
    inf = HydraKVScheduler(
        SchedulerKnobs(token_budget=2048, deadline_tokens=128,
                       retrain_period=math.inf), profile=profile)
    assert _drive(base) == _drive(inf)
    assert base.stats() == inf.stats()
    assert inf.refits == 0 and inf.profile is profile


def test_kv_scheduler_finite_period_refits_from_observed_window():
    profile = _profile()
    sched = HydraKVScheduler(
        SchedulerKnobs(token_budget=2048, deadline_tokens=128,
                       retrain_period=4), profile=profile)
    _drive(sched, n=64)
    assert sched.refits >= 1
    assert sched.profile is not profile          # swapped in place
    assert sched.profile.rc_centers.shape == (4,)
    # deterministic: same stream of sessions -> same refit trajectory
    s2 = HydraKVScheduler(
        SchedulerKnobs(token_budget=2048, deadline_tokens=128,
                       retrain_period=4), profile=_profile())
    _drive(s2, n=64)
    assert np.allclose(sched.profile.rc_centers, s2.profile.rc_centers)
    assert np.allclose(sched.profile.ri_centers, s2.profile.ri_centers)
