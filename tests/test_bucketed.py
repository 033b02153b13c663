"""Geometry-bucketed whole-sweep engine vs the host oracle.

Contract (core/fused.py::drive_lanes_bucketed and sweep.run_bucketed):
per-group results are bitwise-identical — integer stats and f64 float
histories — to the host epoch loop on each group alone
(``sweep.simulate_group(engine="host")``, itself pinned to
``sim.drive_lane``).  Covers mixed-geometry bucketing, the single-group
degenerate bucket, surgical overflow demotion of one group inside a
bucket to the host loop, `shard_map` over a multi-device group axis
(subprocess with forced host devices), and the ExecPlan
``engine="bucketed"`` end-to-end route.
"""
import dataclasses
import os
import subprocess
import sys

import numpy as np

from _reference import assert_bitwise
from repro import exp
from repro.core import fused, policies, sim, sweep

TINY = dataclasses.replace(sim.SimParams(), n_inputs=1, max_epochs=40,
                           subsample_target=50_000)
DEADLINE = 2.0e6  # explicit: skips the calibration run, keeps tests fast
POLS = [policies.get(n) for n in ("fifo-nb", "arp-cs-as")]


def _mk_group(config, mix, pols, p, dram=sim.DDR3_1600):
    art = sim.load_artifacts(config, mix, p, True)
    return [sim.Lane(config, mix, pol, p, dram, DEADLINE, art,
                     True) for pol in pols]


def _oracle(config, mix, pols, p, dram=sim.DDR3_1600):
    # dram pinned to match _mk_group's default — an env REPRO_DRAM override
    # must not split the oracle and the bucketed engine onto different models
    return sweep.simulate_group(config, mix, pols, p, dram,
                                deadline_cycles=DEADLINE, engine="host")


# ---------------------------------------------------------------------------
# bucket routing + bitwise parity across mixed geometries
# ---------------------------------------------------------------------------
def test_bucket_parity_mixed_geometry():
    """Four groups, three distinct static shapes: (a) two same-mix groups
    whose params differ only in data (max_epochs) share one bucket and
    run as a single vmapped program; (b) another mix (different core
    caps) and (c) a halved LLC (different geometry) each get their own.
    Every group must be bitwise the per-group oracle."""
    shorter = dataclasses.replace(TINY, max_epochs=25)
    small = dataclasses.replace(TINY, llc_size_bytes=TINY.llc_size_bytes // 2)
    gspecs = [("config1", "moti1", POLS, TINY),
              ("config1", "moti1", POLS, shorter),
              ("config1", "moti2", POLS, TINY),
              ("config1", "moti1", POLS, small)]
    groups = [_mk_group(*gs) for gs in gspecs]
    keys = [fused.bucket_key(g) for g in groups]
    assert keys[0] == keys[1]                      # shared bucket
    assert len({keys[0], keys[2], keys[3]}) == 3   # the others are alone

    buckets = {}
    for g, k in zip(groups, keys):
        buckets.setdefault(k, []).append(g)
    for batch_list in buckets.values():
        fused.drive_lanes_bucketed(batch_list)
    for (config, mix, pols, p), g in zip(gspecs, groups):
        for pol, lane, want in zip(pols, g, _oracle(config, mix, pols, p)):
            assert_bitwise(lane.result(), want, (mix, p.max_epochs, pol.name))


def test_bucket_mixed_policy_rosters_share_bucket():
    """Bucket-mates whose lane0 *policies* differ (the shape max_lanes
    chunking of a wide policy roster produces) still share one program:
    FusedDims.cfg is the incidental first lane's LLCConfig, but only its
    geometry_key feeds the compiled kernels — behaviour knobs ride as
    LaneKnobs data — so the groups must agree modulo cfg and stay
    bitwise."""
    rosters = [[policies.get(n) for n in ("fifo-nb", "arp-cs-as")],
               [policies.get(n) for n in ("arp-cs-as-d", "arp-al")]]
    groups = [_mk_group("config1", "moti1", r, TINY) for r in rosters]
    assert fused.bucket_key(groups[0]) == fused.bucket_key(groups[1])
    assert groups[0][0].llc_cfg != groups[1][0].llc_cfg  # the premise
    fused.drive_lanes_bucketed(groups)
    for pols, g in zip(rosters, groups):
        for pol, lane, want in zip(pols, g,
                                   _oracle("config1", "moti1", pols, TINY)):
            assert_bitwise(lane.result(), want, pol.name)


def test_bucket_single_group_degenerate():
    """A one-group bucket (the common tail case, and every device group
    of ``simulate_group``) has a unit group axis — still bitwise."""
    groups = [_mk_group("config1", "moti1", POLS, TINY)]
    fused.drive_lanes_bucketed(groups)
    for pol, lane, want in zip(POLS, groups[0],
                               _oracle("config1", "moti1", POLS, TINY)):
        assert_bitwise(lane.result(), want, pol.name)


def test_phase_times_are_also_profiler_spans(tmp_path):
    """Each block ``phase_times`` times is a profiler span too, so a trace
    places the bucketed engine's phases against the device; the split keeps its
    keys."""
    from test_lern_spans import _traced
    _, groups = _synthetic_group(3, 64)
    fused.reset_phase_times()
    _, events = _traced(lambda: fused.drive_lanes_bucketed(
        [groups], k_epochs=4, max_rounds=32), tmp_path)
    phases = fused.phase_times()
    assert sorted(phases) == ["device_s", "dispatch_s", "stage_s",
                              "writeback_s"]
    assert all(v > 0 for v in phases.values())
    assert {ev[0] for ev in events} == {"fused.stage", "fused.dispatch",
                                        "fused.device_wait",
                                        "fused.writeback"}


def test_bucket_sched_dram_mixed_policy_parity():
    """Scheduled-dram groups: the bank/rank geometry rides in bucket_key
    (the arbitration kind is SharedConsts data), so SQUASH and FR-FCFS
    variants of one part share a bucket — across mixed policy rosters —
    while fluid groups land elsewhere.  Bank state lives in the vmapped
    carry; every lane must stay bitwise the per-group oracle."""
    from repro.core.dram import DDR4_2400_FRFCFS, DDR4_2400_SQUASH
    rosters = [[policies.get(n) for n in ("fifo-nb", "arp-cs-as")],
               [policies.get(n) for n in ("arp-cs-as-d", "hydra")]]
    gspecs = [("config1", "moti1", rosters[0], TINY, DDR4_2400_SQUASH),
              ("config1", "moti1", rosters[1], TINY, DDR4_2400_SQUASH),
              ("config1", "moti1", rosters[0], TINY, DDR4_2400_FRFCFS)]
    groups = [_mk_group(*gs) for gs in gspecs]
    fluid = _mk_group("config1", "moti1", rosters[0], TINY)
    keys = [fused.bucket_key(g) for g in groups]
    assert len(set(keys)) == 1                       # one sched bucket
    assert fused.bucket_key(fluid) != keys[0]        # fluid stays apart
    fused.drive_lanes_bucketed(groups)
    for (config, mix, pols, p, dram), g in zip(gspecs, groups):
        for pol, lane, want in zip(pols, g,
                                   _oracle(config, mix, pols, p, dram)):
            assert_bitwise(lane.result(), want, (dram.name, pol.name))


# ---------------------------------------------------------------------------
# overflow: only the offending group leaves the bucket
# ---------------------------------------------------------------------------
HP = dataclasses.replace(sim.SimParams(), n_inputs=1, max_epochs=12,
                         accel_epoch_cap=400, subsample_target=50_000)


def _synthetic_group(seed, n_lines, length=2000, dram=sim.DDR3_1600):
    from test_fused import _synthetic_artifacts
    art = _synthetic_artifacts(seed, n_lines, length)
    return art, [sim.Lane("synthetic", "moti2", pol, HP, dram,
                          DEADLINE, art, True) for pol in POLS]


def _spy_demotion(monkeypatch):
    """Record every lane the bucketed driver demotes to the host loop."""
    demoted = []
    orig = fused.drive_lane

    def spy(lane, state=None):
        demoted.append(lane)
        return orig(lane, state=state)

    monkeypatch.setattr(fused, "drive_lane", spy)
    return demoted


def _only(demoted, group):
    """Some lanes were demoted, and every one of them belongs to ``group``."""
    return bool(demoted) and all(any(d is lane for lane in group)
                                 for d in demoted)


def test_bucket_overflow_demotes_offending_group_only(monkeypatch):
    """One group hammering 8 hot lines blows the round capacity; its
    bucket-mate with a spread-out trace must stay on the vmapped path.
    The hot group's lanes finish on the host loop (``sim.drive_lane``,
    which chunks the depth) from their frozen state, and both groups
    still match the sequential oracle."""
    demoted = _spy_demotion(monkeypatch)
    # measured: the tame trace fits in 64 rounds/set, the hot one needs
    # 128 — capping at 64 forces exactly one group over the edge
    monkeypatch.setattr(fused, "MAX_ROUNDS_CAP", 64)
    hot_art, hot = _synthetic_group(3, n_lines=8)
    tame_art, tame = _synthetic_group(4, n_lines=6000)
    assert fused.bucket_key(hot) == fused.bucket_key(tame)
    fused.drive_lanes_bucketed([hot, tame], k_epochs=4, max_rounds=32)
    assert _only(demoted, hot), "exactly the hot group must demote"
    for name, art, group in (("hot", hot_art, hot),
                             ("tame", tame_art, tame)):
        for pol, lane in zip(POLS, group):
            want = sim.drive_lane(
                sim.Lane("synthetic", "moti2", pol, HP, sim.DDR3_1600,
                         DEADLINE, art, True))
            assert_bitwise(lane.result(), want, (name, pol.name))


def test_bucket_overflow_demotion_with_sched_bank_state(monkeypatch):
    """Overflow demotion with the scheduled DRAM backend: the demoted
    group's in-flight bank state (open rows / backlog / rotor, mid-run in
    the vmapped carry) must survive the replay hand-off — both groups
    still match the sequential host oracle bitwise."""
    from repro.core.dram import DDR4_2400_SQUASH
    demoted = _spy_demotion(monkeypatch)
    monkeypatch.setattr(fused, "MAX_ROUNDS_CAP", 64)
    hot_art, hot = _synthetic_group(3, n_lines=8, dram=DDR4_2400_SQUASH)
    tame_art, tame = _synthetic_group(4, n_lines=6000,
                                      dram=DDR4_2400_SQUASH)
    assert fused.bucket_key(hot) == fused.bucket_key(tame)
    fused.drive_lanes_bucketed([hot, tame], k_epochs=4, max_rounds=32)
    assert _only(demoted, hot), "exactly the hot group must demote"
    for name, art, group in (("hot", hot_art, hot),
                             ("tame", tame_art, tame)):
        for pol, lane in zip(POLS, group):
            want = sim.drive_lane(
                sim.Lane("synthetic", "moti2", pol, HP, DDR4_2400_SQUASH,
                         DEADLINE, art, True))
            assert_bitwise(lane.result(), want, (name, pol.name))


def test_bucket_pipeline_donated_parity(monkeypatch):
    """The donated, double-buffered dispatch against the sequential
    oracle over >= 3 super-steps with an overflow-demotion in the
    middle: the hot group blows the capped round capacity and demotes
    while its tame bucket-mate keeps running donated super-steps —
    results must stay bitwise equal to ``sim.drive_lane``, and the
    donated executable must actually have carried the run."""
    monkeypatch.setattr(fused, "MAX_ROUNDS_CAP", 64)
    donated_calls = [0]
    orig_donated = fused._superstep_bucket_donated

    def donated_spy(*a, **kw):
        donated_calls[0] += 1
        return orig_donated(*a, **kw)

    monkeypatch.setattr(fused, "_superstep_bucket_donated", donated_spy)
    demoted = _spy_demotion(monkeypatch)
    hot_art, hot = _synthetic_group(3, n_lines=8)
    tame_art, tame = _synthetic_group(4, n_lines=6000)
    # max_epochs=12 at k_epochs=4 -> 3 super-steps for the survivor;
    # devices=1 pins the single-shard path — donation is disabled
    # under shard_map by design, and this test is about donation
    fused.drive_lanes_bucketed([hot, tame], k_epochs=4, max_rounds=32,
                               devices=1)
    assert donated_calls[0] >= 3, donated_calls
    # the demotion fired mid-run on the hot group alone
    assert _only(demoted, hot)
    for name, art, group in (("hot", hot_art, hot),
                             ("tame", tame_art, tame)):
        for pol, lane in zip(POLS, group):
            want = sim.drive_lane(
                sim.Lane("synthetic", "moti2", pol, HP, sim.DDR3_1600,
                         DEADLINE, art, True))
            assert_bitwise(lane.result(), want, (name, pol.name))


# ---------------------------------------------------------------------------
# staging cache: no re-upload across points sharing a bucket_key
# ---------------------------------------------------------------------------
def test_staging_cache_reuses_and_invalidates(tmp_path, monkeypatch):
    """Two ``run_bucketed`` passes over the same bucket (two groups, one
    ``bucket_key``) stage each group exactly once: the second pass rides
    ``sweep._STAGE_CACHE``.  An online-LERN retrain's table swap
    (``_Staged.refresh_clusters``) marks its entry stale, and only that
    entry re-stages on the next pass."""
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(sweep, "_STAGE_CACHE", type(sweep._STAGE_CACHE)())
    calls = []
    orig = fused.stage_group

    def spy(lanes, *a, **kw):
        calls.append(tuple(lane.policy.name for lane in lanes))
        return orig(lanes, *a, **kw)

    monkeypatch.setattr(fused, "stage_group", spy)
    shorter = dataclasses.replace(TINY, max_epochs=25)
    pts = [sweep.SweepPoint("config1", "moti1", pol, p)
           for p in (TINY, shorter) for pol in POLS]
    r1 = sweep.run_bucketed(pts, cache=False)
    assert len(calls) == 2, calls          # one upload per group
    r2 = sweep.run_bucketed(pts, cache=False)
    assert len(calls) == 2, calls          # both entries re-used
    for i, (a, b) in enumerate(zip(r1, r2)):
        assert_bitwise(a, b, i)            # re-use is bitwise-transparent
    staged = next(iter(sweep._STAGE_CACHE.values()))
    assert not staged.stale
    # the exact call the bucketed driver makes after an online retrain
    staged.refresh_clusters(_mk_group("config1", "moti1", POLS, TINY))
    assert staged.stale
    sweep.run_bucketed(pts, cache=False)
    assert len(calls) == 3, calls          # only the stale entry re-staged


# ---------------------------------------------------------------------------
# shard_map over the group axis (forced 2 host devices, subprocess)
# ---------------------------------------------------------------------------
_SHARD_SCRIPT = r"""
import dataclasses
import numpy as np
from repro.core import fused, policies, sim
from test_fused import _synthetic_artifacts
from test_bucketed import HP, DEADLINE, POLS
import jax
assert len(jax.devices()) == 2, jax.devices()

def mk(seed):
    art = _synthetic_artifacts(seed, 4000, 1500)
    return [sim.Lane("synthetic", "moti2", pol, HP, sim.DDR3_1600,
                     DEADLINE, art, True) for pol in POLS]

groups = [mk(11), mk(12)]
oracle = [mk(11), mk(12)]
fused.drive_lanes_bucketed(groups, devices=2)
for got_g, want_g in zip(groups, oracle):
    for got, want in zip(got_g, want_g):
        want = sim.drive_lane(want)
        assert got.result().summary() == want.summary()
        assert got.result().history == want.history
print("SHARDED-OK")
"""


def test_bucket_shard_map_two_host_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src, os.path.dirname(os.path.abspath(__file__))]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    out = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    assert "SHARDED-OK" in out.stdout


# ---------------------------------------------------------------------------
# ExecPlan end-to-end: engine="bucketed" through exp.run
# ---------------------------------------------------------------------------
def test_execplan_bucketed_end_to_end(tmp_path, monkeypatch):
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))
    spec = exp.ExperimentSpec.grid(
        config="config1", mix=["moti1", "moti2"],
        policy=["fifo-nb", "arp-cs-as"], params=TINY)
    bucketed = exp.run(spec, plan=exp.ExecPlan(engine="bucketed",
                                               cache=False))
    oracle = exp.run(spec, plan=exp.ExecPlan(engine="host", cache=False))
    assert len(bucketed) == len(oracle) == 4
    for got, want in zip(bucketed, oracle):
        assert (got["mix"], got["policy"]) == (want["mix"], want["policy"])
        assert_bitwise(got["result"], want["result"],
                       (got["mix"], got["policy"]))


# ---------------------------------------------------------------------------
# host callbacks: one packed operand and one packed result per epoch half
# ---------------------------------------------------------------------------
def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_bucket_step_host_calls_take_one_packed_operand():
    """A bucketed super-step makes exactly two host callbacks per epoch
    (``_begin_host``, ``_finish_host``), each with ONE operand and ONE
    result: on a TPU every operand is its own serial host transfer, so
    per-operand callbacks would cost one transfer latency per leaf."""
    import jax
    import jax.numpy as jnp

    from repro.core import llc
    _, group = _synthetic_group(3, 64)
    staged = fused.stage_group(group, k_epochs=4, max_rounds=32,
                               pads=fused.bucket_pads([group]))
    dims = staged.dims
    with jax.enable_x64():
        carry = fused._init_carry(
            group, llc.stack_states(dims.cfg, dims.n_lanes), dims.n_inputs)
        sh_g, lc_g, carry_g = (fused._stack_trees([t]) for t in
                               (staged.sh, staged.lc, carry))
        jaxpr = jax.make_jaxpr(fused._bucket_run(dims, 1))(
            sh_g, lc_g, carry_g, jnp.asarray([HP.max_epochs], jnp.int64))
    calls = [e for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "pure_callback"]
    assert len(calls) == 2
    for eqn in calls:
        assert len(eqn.invars) == 1 and len(eqn.outvars) == 1, eqn
        assert eqn.invars[0].aval.dtype == jnp.uint32
