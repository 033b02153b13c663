"""Chaos suite: deterministic fault injection (repro.exp.faults) and the
resilient sweep path.

Every recovery the execution layer takes — quarantining a corrupt cache
entry, respawning a crashed pool worker, watchdog-killing a hung task,
demoting a failing bucket down the bucketed→fused→host ladder — must be
bitwise-transparent: the results of a faulted run equal a clean run
exactly.  A hypothesis property randomizes whole fault plans over a
small sweep to hold that line beyond the hand-picked cases."""
import dataclasses
import json
import os
import pickle
import tempfile

import numpy as np
import pytest

try:        # property testing: hypothesis in CI, seeded fallback without
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from _reference import assert_bitwise
from repro import exp
from repro.core import fused, policies, sim, sweep
from repro.exp import faults
from repro.serve.hydra_scheduler import HydraKVScheduler, SessionProfile
from repro.serve.knobs import SchedulerKnobs

TINY = dataclasses.replace(sim.SimParams(), n_inputs=1, max_epochs=40,
                           subsample_target=50_000)
POLS = ("fifo-nb", "arp-cs-as")
MIXES = ("moti1", "moti2")


def _points(mixes=MIXES):
    return [sweep.SweepPoint("config1", mix, policies.get(n), TINY)
            for mix in mixes for n in POLS]


def _plan(*specs, **kw):
    return faults.FaultPlan.make([faults.FaultSpec(**s) for s in specs],
                                 **kw)


@pytest.fixture(scope="session")
def clean_baseline(tmp_path_factory):
    """The fault-free oracle: all 4 points (2 mixes x 2 policies) through
    inline map_points in a private cache dir."""
    d = tmp_path_factory.mktemp("clean_cache")
    old = sim.CACHE_DIR
    sim.CACHE_DIR = str(d)
    try:
        return sweep.map_points(_points(), jobs=1)
    finally:
        sim.CACHE_DIR = old


# ---------------------------------------------------------------------------
# cache envelope: checksums, quarantine, durability (satellites 1 + 2)
# ---------------------------------------------------------------------------
def test_envelope_roundtrip_and_quarantine(tmp_path, monkeypatch):
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    path = str(tmp_path / "entry.pkl")
    sim._atomic_dump({"a": 1}, path)
    assert sim.cache_load(path) == {"a": 1}
    assert sim.cache_load(str(tmp_path / "absent.pkl")) is sim.MISS

    qdir = tmp_path / "quarantine"

    # a pre-envelope legacy bare pickle: quarantined, reported as a miss
    legacy = str(tmp_path / "legacy.pkl")
    with open(legacy, "wb") as f:
        pickle.dump({"old": True}, f)
    assert sim.cache_load(legacy) is sim.MISS
    assert not os.path.exists(legacy)
    assert any(p.startswith("legacy.pkl.") for p in os.listdir(qdir))

    # bit rot in the payload: crc catches it
    sim._atomic_dump([1, 2, 3], path)
    with open(path, "r+b") as f:
        f.seek(-1, os.SEEK_END)
        b = f.read(1)
        f.seek(-1, os.SEEK_END)
        f.write(bytes([b[0] ^ 0xFF]))
    assert sim.cache_load(path) is sim.MISS
    assert not os.path.exists(path)

    # truncation (torn write survivor without the envelope's protection)
    sim._atomic_dump([4, 5, 6], path)
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size // 2)
    assert sim.cache_load(path) is sim.MISS
    assert len(os.listdir(qdir)) == 3


def test_corrupt_cache_entry_recomputed_bitwise(tmp_path, monkeypatch,
                                                clean_baseline):
    """Satellite 1: the sweep cache read path quarantines a damaged
    entry and recomputes the point instead of crashing the sweep."""
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    pts = _points()
    first = sweep.map_points(pts, jobs=1)
    for got, want in zip(first, clean_baseline):
        assert_bitwise(got, want, got.policy)
    # smash one committed result entry
    victim = pts[0].cache_path()
    with open(victim, "r+b") as f:
        f.seek(4)
        f.write(b"\x00\x00\x00\x00")
    report = faults.RunReport()
    again = sweep.map_points(pts, jobs=1, report=report)
    for got, want in zip(again, clean_baseline):
        assert_bitwise(got, want, got.policy)
    assert any(e["kind"] == "quarantine" for e in report.events)
    recs = report.points
    assert recs[sweep.point_key(victim)]["source"] == "computed"
    assert recs[sweep.point_key(pts[2].cache_path())]["source"] == "cache"
    # the recomputed entry is committed and clean again
    assert sim.cache_load(victim) is not sim.MISS


def test_injected_cache_read_fault_recovers(tmp_path, monkeypatch,
                                            clean_baseline):
    """The ``cache_read`` site damages entries on disk, driving the real
    quarantine/recompute machinery end to end."""
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    pts = _points()
    sweep.map_points(pts, jobs=1)
    report = faults.RunReport()
    plan = _plan({"site": "cache_read", "kind": "truncate",
                  "match": os.path.basename(pts[1].cache_path())})
    with faults.activate(plan):
        rs = sweep.map_points(pts, jobs=1, report=report)
    for got, want in zip(rs, clean_baseline):
        assert_bitwise(got, want, got.policy)
    kinds = [e["kind"] for e in report.events]
    assert "fault" in kinds and "quarantine" in kinds
    assert any(r["source"] == "computed" for r in report.points.values())


def test_atomic_dump_torn_write_preserves_committed(tmp_path, monkeypatch):
    """Satellite 2: a kill mid-write (fsync'd temp file, rename never
    runs) leaves the previously committed entry fully intact."""
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    path = str(tmp_path / "entry.pkl")
    sim._atomic_dump({"gen": 1}, path)
    with faults.activate(_plan({"site": "cache_dump", "kind": "torn"})):
        with pytest.raises(faults.InjectedFault):
            sim._atomic_dump({"gen": 2}, path)
    assert sim.cache_load(path) == {"gen": 1}
    # the half-written temp file exists (the simulated kill happened
    # mid-write) and never shadowed the committed path
    assert any(p.endswith(".tmp") for p in os.listdir(tmp_path))
    # a corrupt committed write is caught by the next read, not trusted
    with faults.activate(_plan({"site": "cache_dump", "kind": "corrupt"})):
        sim._atomic_dump({"gen": 3}, path)
    assert sim.cache_load(path) is sim.MISS  # quarantined
    sim._atomic_dump({"gen": 4}, path)
    assert sim.cache_load(path) == {"gen": 4}


# ---------------------------------------------------------------------------
# process-pool recovery: crash, hang, retry (tentpole)
# ---------------------------------------------------------------------------
def test_worker_crash_respawns_and_stays_bitwise(tmp_path, monkeypatch,
                                                 clean_baseline):
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    plan = _plan({"site": "task", "kind": "crash"})
    report = faults.RunReport()
    with faults.activate(plan):
        rs = sweep.map_points(_points(), jobs=2, report=report)
    for got, want in zip(rs, clean_baseline):
        assert_bitwise(got, want, got.policy)
    kinds = [e["kind"] for e in report.events]
    assert "worker_crash" in kinds
    assert report.summary()["points"] == 4
    assert all(r["source"] == "computed" for r in report.points.values())


def test_worker_fault_events_propagate_to_parent(tmp_path, monkeypatch,
                                                 clean_baseline):
    """Events fired inside pool workers ride back to the parent — with
    the result tuple on success (a ``cache_dump`` corruption while the
    worker commits one point), inside ``sweep.TaskError`` on failure
    (``task`` raise) — and land in the parent report tagged
    ``origin="worker"``."""
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(sweep, "RETRY_BACKOFF", 0.01)
    pts = _points()
    plan = _plan({"site": "task", "kind": "raise"},
                 {"site": "cache_dump", "kind": "corrupt",
                  "match": os.path.basename(pts[0].cache_path())})
    report = faults.RunReport()
    with faults.activate(plan):
        rs = sweep.map_points(pts, jobs=2, report=report)
    for got, want in zip(rs, clean_baseline):
        assert_bitwise(got, want, got.policy)
    wfaults = {e["site"] for e in report.events
               if e["kind"] == "fault" and e.get("origin") == "worker"}
    assert {"task", "cache_dump"} <= wfaults, report.events
    # the failed task's error surfaced as a retried TaskError
    assert any(e["kind"] == "task_retry" and e["cause"] == "task_error"
               for e in report.events)
    assert not any(e.get("origin") == "worker" for e in report.events
                   if e["kind"] == "task_retry")


def test_task_error_pickles_with_events():
    e = sweep.TaskError("ValueError", "boom", [{"kind": "fault",
                                                "site": "task"}])
    back = pickle.loads(pickle.dumps(e))
    assert isinstance(back, sweep.TaskError)
    assert back.cause == "ValueError" and "boom" in str(back)
    assert back.events == e.events


def test_task_timeout_watchdog_kills_and_retries(tmp_path, monkeypatch,
                                                 clean_baseline):
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    plan = _plan({"site": "task", "kind": "hang", "seconds": 600.0})
    report = faults.RunReport()
    with faults.activate(plan):
        rs = sweep.map_points(_points(), jobs=2, report=report,
                              task_timeout=20.0)
    for got, want in zip(rs, clean_baseline):
        assert_bitwise(got, want, got.policy)
    assert any(e["kind"] == "watchdog_kill" for e in report.events)


def test_inline_retry_with_backoff(tmp_path, monkeypatch, clean_baseline):
    """jobs<=1: a raising task retries (with backoff) and completes."""
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(sweep, "RETRY_BACKOFF", 0.01)
    plan = _plan({"site": "task", "kind": "raise", "max_fires": 2})
    report = faults.RunReport()
    with faults.activate(plan):
        rs = sweep.map_points(_points(), jobs=1, report=report)
    for got, want in zip(rs, clean_baseline):
        assert_bitwise(got, want, got.policy)
    assert any(e["kind"] == "task_retry" for e in report.events)
    assert any(r["attempts"] > 1 for r in report.points.values())


# ---------------------------------------------------------------------------
# degradation ladder: bucketed -> fused -> host (tentpole)
# ---------------------------------------------------------------------------
def test_bucket_demotes_to_fused_bitwise(tmp_path, monkeypatch,
                                         clean_baseline):
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    plan = _plan({"site": "bucket", "kind": "resource"})
    report = faults.RunReport()
    with faults.activate(plan):
        rs = sweep.run_bucketed(_points(), report=report)
    for got, want in zip(rs, clean_baseline):
        assert_bitwise(got, want, got.policy)
    degr = [e for e in report.events if e["kind"] == "degrade"]
    assert any(e["ladder"] == "bucketed->fused" for e in degr)
    assert any(r.get("engine") == "fused" for r in report.points.values())


def test_bucket_demotes_all_the_way_to_host(tmp_path, monkeypatch,
                                            clean_baseline):
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    plan = _plan({"site": "bucket", "kind": "resource"},
                 {"site": "fused", "kind": "resource", "max_fires": 8})
    report = faults.RunReport()
    with faults.activate(plan):
        rs = sweep.run_bucketed(_points(), report=report)
    for got, want in zip(rs, clean_baseline):
        assert_bitwise(got, want, got.policy)
    ladders = {e["ladder"] for e in report.events
               if e["kind"] == "degrade"}
    assert {"bucketed->fused", "fused->host"} <= ladders
    assert any(r.get("engine") == "host" for r in report.points.values())


def test_degradable_rejects_plain_runtime_errors():
    """Only exhausted device memory and injected faults walk the engine
    ladder.  Any other runtime error — a Mosaic compile refusal is one —
    is a bug: absorbing it would finish the run on the host and hide that
    the device path never ran."""
    from jax.errors import JaxRuntimeError
    assert not faults.degradable(JaxRuntimeError(
        "INVALID_ARGUMENT: Mosaic failed to compile TPU kernel"))
    assert not faults.degradable(ValueError("logic error"))
    assert faults.degradable(JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer"))
    assert faults.degradable(faults.InjectedFault("injected"))
    assert faults.degradable(faults.InjectedResourceExhausted(
        "RESOURCE_EXHAUSTED: injected"))


def test_bucket_runtime_error_propagates(tmp_path, monkeypatch):
    """A non-degradable failure inside a bucket is raised, not demoted."""
    from jax.errors import JaxRuntimeError
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))

    def refuse(*a, **kw):
        raise JaxRuntimeError("INTERNAL: Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(fused, "drive_lanes_bucketed", refuse)
    demoted = []
    monkeypatch.setattr(sweep, "_demote_batch",
                        lambda *a, **kw: demoted.append(a))
    with pytest.raises(JaxRuntimeError, match="Mosaic"):
        sweep.run_bucketed(_points(("moti1",)), report=faults.RunReport())
    assert not demoted


def test_inline_task_error_not_degradable_propagates(tmp_path, monkeypatch):
    """jobs<=1: a non-degradable task error is raised at once — no retry
    and no final attempt on the host engine."""
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    calls = []

    def broken(task, engine="auto"):
        calls.append(engine)
        raise ValueError("logic error")

    monkeypatch.setattr(sweep, "_group_task", broken)
    report = faults.RunReport()
    with pytest.raises(ValueError, match="logic error"):
        sweep.map_points(_points(("moti1",)), jobs=1, report=report)
    assert calls == ["auto"]
    assert not any(e["kind"] == "task_retry" for e in report.events)


def test_forced_bucket_overflow_demotion_bitwise(tmp_path, monkeypatch,
                                                 clean_baseline):
    """The ``bucket_overflow`` site forces the bucketed driver's real
    freeze/demote machinery on workloads that never overflow naturally."""
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(fused, "MAX_ROUNDS_CAP", 64)
    calls = []
    orig = fused.drive_lanes_fused

    def spy(lanes, *a, **kw):
        calls.append(len(lanes))
        return orig(lanes, *a, **kw)

    monkeypatch.setattr(fused, "drive_lanes_fused", spy)
    plan = _plan({"site": "bucket_overflow", "kind": "demote"})
    report = faults.RunReport()
    with faults.activate(plan):
        rs = sweep.run_bucketed(_points(), report=report)
    for got, want in zip(rs, clean_baseline):
        assert_bitwise(got, want, got.policy)
    assert calls, "forced overflow must route groups through the " \
                  "per-group fused driver"
    assert any(e["kind"] == "fault" and e["site"] == "bucket_overflow"
               for e in report.events)


def test_stage_evict_is_parity_safe(tmp_path, monkeypatch, clean_baseline):
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    plan = _plan({"site": "stage_evict", "kind": "evict"})
    with faults.activate(plan):
        rs = sweep.run_bucketed(_points())
    for got, want in zip(rs, clean_baseline):
        assert_bitwise(got, want, got.policy)


# ---------------------------------------------------------------------------
# manifest + resume (tentpole) and the ExecPlan(faults=) plumbing
# ---------------------------------------------------------------------------
def test_manifest_resume_runs_only_unfinished(tmp_path, monkeypatch):
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    manifest = str(tmp_path / "manifest.json")
    half = exp.ExperimentSpec.grid(config="config1", mix="moti1",
                                   policy=list(POLS), params=TINY)
    full = exp.ExperimentSpec.grid(config="config1", mix=list(MIXES),
                                   policy=list(POLS), params=TINY)
    rs1 = exp.run(half, manifest=manifest)
    with open(manifest) as f:
        doc = json.load(f)
    assert doc["schema"] == faults.MANIFEST_SCHEMA
    assert len(doc["completed"]) == 2
    from repro.exp import schema as schema_mod
    assert schema_mod.validate(doc) == []

    rs2 = exp.run(full, manifest=manifest, resume=True)
    rep = rs2.run_report
    resumed = {k for k, r in rep.points.items() if r["source"] == "resume"}
    computed = {k for k, r in rep.points.items()
                if r["source"] == "computed"}
    assert resumed == set(rs1.run_report.points)
    assert len(computed) == 2 and not (resumed & computed)
    # the merged manifest now covers the full sweep and still validates
    with open(manifest) as f:
        doc = json.load(f)
    assert len(doc["completed"]) == 4
    assert schema_mod.validate(doc) == []
    # the summary rides the sweep artifact header
    sweep_doc = rs2.to_sweep_doc()
    assert sweep_doc["run_report"]["by_source"] == {"resume": 2,
                                                    "computed": 2}

    with pytest.raises(ValueError, match="manifest"):
        exp.run(full, resume=True)
    with pytest.raises(ValueError, match="cache"):
        exp.run(full, plan=exp.ExecPlan(cache=False), manifest=manifest,
                resume=True)


def test_exec_plan_faults_field(tmp_path, monkeypatch):
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(sweep, "RETRY_BACKOFF", 0.01)
    with pytest.raises(ValueError, match="faults"):
        exp.ExecPlan(faults=123)
    plan_json = _plan({"site": "task", "kind": "raise"}).to_json()
    spec = exp.ExperimentSpec.grid(config="config1", mix="moti1",
                                   policy=list(POLS), params=TINY)
    rs = exp.run(spec, plan=exp.ExecPlan(engine="fused", faults=plan_json))
    kinds = [e["kind"] for e in rs.run_report.events]
    assert "fault" in kinds and "task_retry" in kinds
    assert rs.run_report.summary()["points"] == 2


# ---------------------------------------------------------------------------
# serve: refit failures degrade gracefully (satellite 3)
# ---------------------------------------------------------------------------
def _profile():
    return SessionProfile.fit(
        turns_per_session=np.array([1, 1, 2, 4, 6, 8, 8, 12] * 4),
        gaps=np.array([2, 4, 8, 16, 64, 256, 400, 800] * 4))


def _drive(sched, n=64, seed=0):
    rng = np.random.default_rng(seed)
    for i in range(n):
        sched.keep_resident(float(rng.integers(1, 12)),
                            float(rng.integers(2, 800)))
        if (i + 1) % 4 == 0:
            sched.epoch_update(decoded_rate=float(rng.random()),
                               required_rate=1.0,
                               hbm_pressure=float(rng.random()))


def test_refit_failure_keeps_stale_profile(monkeypatch):
    profile = _profile()
    sched = HydraKVScheduler(
        SchedulerKnobs(token_budget=2048, deadline_tokens=128,
                       retrain_period=4), profile=profile)

    def broken_fit(*a, **kw):
        raise ValueError("degenerate window")

    monkeypatch.setattr(SessionProfile, "fit", staticmethod(broken_fit))
    _drive(sched, n=64)          # must not propagate out of epoch_update
    assert sched.refit_failures >= 1
    assert sched.refits == 0
    assert sched.profile is profile              # still serving, stale
    assert sched.stats()["refit_failures"] == sched.refit_failures


def test_refit_injected_fault_counts_as_failure():
    profile = _profile()
    sched = HydraKVScheduler(
        SchedulerKnobs(token_budget=2048, deadline_tokens=128,
                       retrain_period=4), profile=profile)
    with faults.activate(_plan({"site": "refit", "kind": "raise"})):
        _drive(sched, n=64)
    assert sched.refit_failures == 1
    assert sched.refits >= 1     # later boundaries refit normally
    assert sched.profile is not profile


# ---------------------------------------------------------------------------
# fault-plan registry mechanics
# ---------------------------------------------------------------------------
def test_fault_plan_json_roundtrip_and_claims(tmp_path):
    plan = _plan({"site": "task", "kind": "raise", "at": 1,
                  "max_fires": 2, "match": "config1"},
                 seed=7)
    again = faults.FaultPlan.from_json(plan.to_json())
    assert again == plan
    with pytest.raises(ValueError, match="kind"):
        faults.FaultSpec(site="task", kind="nope")
    # at/max_fires/match semantics: skip the first arrival, fire twice,
    # only for matching keys
    with faults.activate(plan) as active:
        assert active.state is not None
        assert faults.fire("task", key="config2|m") is None  # no match
        assert faults.fire("task", key="config1|m") is None  # at: skipped
        for _ in range(2):
            with pytest.raises(faults.InjectedFault):
                faults.fire("task", key="config1|m")
        assert faults.fire("task", key="config1|m") is None  # spent
    faults.drain_events()


def test_crash_and_hang_suppressed_in_parent():
    with faults.activate(_plan({"site": "task", "kind": "crash"},
                               {"site": "task", "kind": "hang"})):
        assert faults.fire("task") is None    # would os._exit in a worker
        assert faults.fire("task") is None    # would sleep in a worker
    evs = faults.drain_events()
    assert sum(e["kind"] == "fault_suppressed" for e in evs) == 2


# ---------------------------------------------------------------------------
# hypothesis: random fault plans never perturb results
# ---------------------------------------------------------------------------
_FAULT_CHOICES = [
    ("task", "raise"), ("cache_read", "corrupt"),
    ("cache_read", "truncate"), ("cache_dump", "corrupt"),
    ("cache_dump", "truncate"), ("stage_evict", "evict"),
    ("bucket", "resource"), ("bucket", "raise"),
    ("fused", "resource"), ("bucket_overflow", "demote"),
]


def _check_random_plan(clean_baseline, specs, seed):
    pts = _points(mixes=("moti1",))
    cache = tempfile.mkdtemp(prefix="chaos-cache-")
    old_cache, old_backoff = sim.CACHE_DIR, sweep.RETRY_BACKOFF
    sim.CACHE_DIR, sweep.RETRY_BACKOFF = cache, 0.01
    try:
        plan = faults.FaultPlan(specs=tuple(specs), seed=seed)
        with faults.activate(plan):
            rs = sweep.run_bucketed(pts, report=faults.RunReport())
    finally:
        sim.CACHE_DIR, sweep.RETRY_BACKOFF = old_cache, old_backoff
    for got, want in zip(rs, clean_baseline[:len(pts)]):
        assert_bitwise(got, want, (got.policy, specs))


if HAVE_HYPOTHESIS:
    @settings(max_examples=5, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(specs=st.lists(
        st.builds(lambda sk, at, mf: faults.FaultSpec(
                      site=sk[0], kind=sk[1], at=at, max_fires=mf),
                  st.sampled_from(_FAULT_CHOICES),
                  st.integers(0, 2), st.integers(1, 2)),
        min_size=1, max_size=3),
           seed=st.integers(0, 2**31 - 1))
    def test_random_fault_plans_stay_bitwise(clean_baseline, specs, seed):
        _check_random_plan(clean_baseline, specs, seed)
else:
    @pytest.mark.parametrize("example", range(5))
    def test_random_fault_plans_stay_bitwise(clean_baseline, example):
        import random
        rng = random.Random(0xC4A05 + example)
        specs = [faults.FaultSpec(site=sk[0], kind=sk[1],
                                  at=rng.randint(0, 2),
                                  max_fires=rng.randint(1, 2))
                 for sk in rng.sample(_FAULT_CHOICES,
                                      rng.randint(1, 3))]
        _check_random_plan(clean_baseline, specs,
                           seed=rng.randint(0, 2**31 - 1))
