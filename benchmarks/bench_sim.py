"""``bench-sim`` — simulation-path throughput: engines and whole sweeps.

The perf-trajectory artifact for the device-resident epoch loop
(core/fused.py), sibling to ``bench_lern.json``.  Two entry kinds
(schema hydra-bench-sim/v3):

``kind="engine"`` — for every suite config it times the sequential host
loop (``sim.drive_lane``, one lane at a time — the oracle the device
engine is bitwise-pinned against) and the device super-step engine on
the same policy group (``simulate_group``, a one-group bucket), at
``lanes`` of 1 and 4, and records epochs/sec.

``kind="sweep"`` — sweep-level points/sec: the CI smoke sweep (a
deadline-factor axis x the 4-policy lane set, i.e. several geometry-
compatible groups in one bucket) is driven end to end through
``sweep.map_points(jobs=1)`` (the per-group host/process fallback path)
and through ``sweep.run_bucketed`` (the whole-sweep flat device
program), and ``pps_speedup = bucketed_pps / map_pps`` is recorded.
The flat (G*L) epoch step, the donated double-buffered super-step
dispatch and the staging cache make the bucketed engine the winner
even on a single-core single-device host (>= 1.15x, gated as an
absolute floor by check_trend), and each sweep row carries the
bucketed leg's per-phase split — ``stage_s`` / ``dispatch_s`` /
``device_s`` / ``writeback_s`` — so a regression is attributable to
one phase.  (Donation attribution quirk: with a donated carry the next
dispatch blocks until the donated input is free, so device time lands
in ``dispatch_s`` and ``device_s`` reads near zero; the sum is what
matters.)

Methodology: artifacts (trace, LERN tables, deadline calibration) are
loaded/warmed first so both engines measure pure simulation — the
sweep legs link the warmed artifact caches into the scratch cache dir
and wipe only the sim-result cache per rep; each engine then runs the
full bounded simulation (fresh lanes, fresh LLC state, fresh result
cache) ``REPS`` times and the best time is reported — rep 1 carries
this shape's jit compilation, so min() excludes it (the same best-of
convention as bench_lern).
"""
import dataclasses
import json
import os
import shutil
import tempfile
import time

import numpy as np

from repro.core import policies, sim, sweep
from repro.core.dram import DDR3_1600

from .common import BENCH_SIM_PATH, Suite, emit

LANE_SETS = {
    1: ("hydra",),
    4: ("fifo-nb", "arp-cs", "arp-cs-as", "hydra"),
}
# the sweep-level shape: a deadline-factor axis over the 4-policy lane
# set — distinct groups sharing one bucket (the common figure sweep)
SWEEP_FACTORS = (1.0, 1.05, 1.1, 1.15)
# bounded epoch budget: full per-epoch work at the suite's scale, but a
# capped horizon so the bench stays minutes, not the full sweep's hours
BENCH_INPUTS = 2
BENCH_EPOCHS = 120
REPS = 3  # best-of: rep 1 pays jit compilation, rep 2+ is the measure


def _params(suite: Suite) -> sim.SimParams:
    return dataclasses.replace(suite.params, n_inputs=BENCH_INPUTS,
                               max_epochs=BENCH_EPOCHS)


def _run_host(config: str, mix: str, pols, p: sim.SimParams,
              deadline: float) -> int:
    art = sim.load_artifacts(config, mix, p, True)
    epochs = 0
    for pol in pols:
        lane = sim.Lane(config, mix, pol, p, DDR3_1600, deadline, art, True)
        epochs += sim.drive_lane(lane).epochs
    return epochs


def _run_fused(config: str, mix: str, pols, p: sim.SimParams,
               deadline: float) -> int:
    rs = sweep.simulate_group(config, mix, list(pols), p,
                              deadline_cycles=deadline, engine="auto")
    return sum(r.epochs for r in rs)


def _best_of(fn, reps: int = REPS):
    """(best seconds, epochs) over ``reps`` identical full runs — the
    first rep carries jit compilation for this shape, later reps are the
    measurement (matching bench_lern's warm-measurement convention)."""
    best, epochs = float("inf"), 0
    for _ in range(reps):
        t0 = time.time()
        epochs = fn()
        best = min(best, time.time() - t0)
    return best, epochs


def _sweep_points(cfg: str, mix: str, p: sim.SimParams):
    """The CI smoke sweep: deadline-factor axis x the 4-policy lane set."""
    pts = []
    for f in SWEEP_FACTORS:
        pf = dataclasses.replace(p, deadline_factor=f)
        sim.calibrated_deadline(cfg, pf, DDR3_1600)  # warm (shared quotient)
        for name in LANE_SETS[4]:
            pts.append(sweep.SweepPoint(cfg, mix, policies.get(name), pf))
    return pts


def _bench_sweep(pts, fn):
    """(best seconds, best rep's fused phase split) for one sweep leg.

    The result cache is redirected to a scratch dir whose artifact
    caches (trace / lern / deadline) are symlinks to the warmed real
    ones, and only the sim-result cache is wiped per rep — every rep
    simulates the full sweep, neither leg pays artifact (re)builds, so
    the measurement is pure engine time (the kind="engine" convention).
    """
    from repro.core import fused
    scratch = tempfile.mkdtemp(prefix="bench-sweep-")
    keep = sim.CACHE_DIR
    for kind in ("trace", "lern", "deadline"):
        src = os.path.join(keep, kind)
        os.makedirs(src, exist_ok=True)
        os.symlink(src, os.path.join(scratch, kind))
    best, best_ph = float("inf"), dict.fromkeys(
        ("stage_s", "dispatch_s", "device_s", "writeback_s"), 0.0)
    try:
        for _ in range(REPS):
            shutil.rmtree(os.path.join(scratch, "sim"),
                          ignore_errors=True)
            sim.CACHE_DIR = scratch
            fused.reset_phase_times()
            t0 = time.time()
            fn()
            dt = time.time() - t0
            if dt < best:
                best, best_ph = dt, fused.phase_times()
    finally:
        sim.CACHE_DIR = keep
        shutil.rmtree(scratch, ignore_errors=True)
    return best, best_ph


def run(suite: Suite):
    rows = []
    entries = []
    mix = suite.mixes[0]
    p = _params(suite)
    for cfg in suite.configs:
        deadline = sim.calibrated_deadline(cfg, suite.params, DDR3_1600)
        sim.load_artifacts(cfg, mix, p, True)  # trace/stream caches warm
        for lanes, pols in LANE_SETS.items():
            pol_objs = [policies.get(n) for n in pols]
            t1 = time.time()
            host_s, eh = _best_of(
                lambda: _run_host(cfg, mix, pol_objs, p, deadline))
            fused_s, ef = _best_of(
                lambda: _run_fused(cfg, mix, pol_objs, p, deadline))
            host_eps = eh / max(host_s, 1e-9)
            fused_eps = ef / max(fused_s, 1e-9)
            speedup = fused_eps / max(host_eps, 1e-9)
            rows.append(emit(
                f"bench_sim/{cfg}-{mix}-l{lanes}", t1,
                {"host_eps": host_eps, "fused_eps": fused_eps,
                 "speedup": speedup, "epochs": ef}))
            entries.append({
                "kind": "engine",
                "config": cfg, "mix": mix, "lanes": lanes,
                "epochs": int(ef),
                "host_s": round(host_s, 4), "fused_s": round(fused_s, 4),
                "host_eps": round(host_eps, 2),
                "fused_eps": round(fused_eps, 2),
                "speedup": round(speedup, 3)})
        # sweep-level points/sec: map_points --jobs 1 vs the bucketed
        # whole-sweep device program, same points, same cache handling
        pts = _sweep_points(cfg, mix, p)
        t1 = time.time()
        map_s, _ = _bench_sweep(pts, lambda: sweep.map_points(pts, jobs=1))
        bucketed_s, phases = _bench_sweep(
            pts, lambda: sweep.run_bucketed(pts))
        map_pps = len(pts) / max(map_s, 1e-9)
        bucketed_pps = len(pts) / max(bucketed_s, 1e-9)
        pps_speedup = bucketed_pps / max(map_pps, 1e-9)
        rows.append(emit(
            f"bench_sim/sweep-{cfg}-{mix}", t1,
            {"map_pps": map_pps, "bucketed_pps": bucketed_pps,
             "pps_speedup": pps_speedup, "points": len(pts)}))
        entries.append({
            "kind": "sweep", "config": cfg, "mix": mix,
            "lanes": len(LANE_SETS[4]), "points": len(pts),
            "groups": len(SWEEP_FACTORS), "epochs": BENCH_EPOCHS,
            "map_s": round(map_s, 4), "bucketed_s": round(bucketed_s, 4),
            "map_pps": round(map_pps, 3),
            "bucketed_pps": round(bucketed_pps, 3),
            "pps_speedup": round(pps_speedup, 3),
            **{k: round(v, 4) for k, v in phases.items()}})
    if entries:
        geo = {}
        for lanes in LANE_SETS:
            sp = [e["speedup"] for e in entries
                  if e["kind"] == "engine" and e["lanes"] == lanes]
            geo[str(lanes)] = round(float(np.exp(np.mean(np.log(sp)))), 3)
        pp = [e["pps_speedup"] for e in entries if e["kind"] == "sweep"]
        geo_pps = round(float(np.exp(np.mean(np.log(pp)))), 3)
        with open(BENCH_SIM_PATH, "w") as f:
            json.dump({"schema": "hydra-bench-sim/v3",
                       "geomean_speedup_by_lanes": geo,
                       "geomean_pps_speedup": geo_pps,
                       "entries": entries}, f, indent=1)
        print(f"# wrote {len(entries)} entries to {BENCH_SIM_PATH} "
              f"(geomean fused speedup: "
              + ", ".join(f"{k} lanes {v}x" for k, v in geo.items())
              + f"; sweep pps speedup {geo_pps}x)", flush=True)
    return rows
