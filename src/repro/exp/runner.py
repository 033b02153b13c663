"""``run(spec, plan=ExecPlan(...)) -> ResultSet`` — the single public
entry point for evaluating anything.

``ExecPlan`` routes points to an engine: ``bucketed`` (and the ``auto``
default when ``jobs <= 1``) batches whole sweeps on device through
``sweep.run_bucketed``; otherwise points go through ``sweep.map_points``
(``simulate_group`` per group — a one-group device bucket under
``auto``, the host epoch loop under ``host`` — + process pool +
disk-cache dedup).
Every engine is bitwise-identical on integer stats and f64 histories —
pinned by tests/test_exp.py and tests/test_bucketed.py.

Execution knobs live solely on ``ExecPlan`` — the pre-ExecPlan bare
kwargs (``jobs=``, ``cache=``, ``max_lanes=``) completed their
one-release deprecation grace and are gone.
"""
from __future__ import annotations

import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

from repro.core import lern as lern_mod
from repro.core import sim, sweep

from . import faults as faults_mod
from .faults import RunReport
from .plan import ExecPlan
from .resultset import ResultSet
from .spec import ExperimentSpec, Point

SpecLike = Union[ExperimentSpec, Iterable[ExperimentSpec]]


def _record(point: Point, axes: Dict, res: sim.SimResult) -> Dict:
    rec = dict(axes)
    rec.update(res.summary())
    rec["core_hit_rate"] = res.core_hit_rate
    rec["accel_hit_rate"] = res.accel_hit_rate
    rec["epochs"] = res.epochs
    rec["point"] = point
    rec["result"] = res
    return rec


def _run_points_uncached(points: Sequence[Point], rp: ExecPlan
                         ) -> List[sim.SimResult]:
    """Cache-off host path: lane-batched ``simulate_group`` per (config,
    mix, params, dram) group, never touching the result cache — fresh
    numbers every call (artifact caches for traces/LERN still apply)."""
    results: List[sim.SimResult] = [None] * len(points)  # type: ignore
    groups: Dict[Tuple, List[int]] = {}
    for i, p in enumerate(points):
        groups.setdefault((p.config, p.mix, p.params, p.dram), []).append(i)
    for (config, mix, params, dram), idxs in groups.items():
        uniq: Dict[Point, List[int]] = {}
        for i in idxs:
            uniq.setdefault(points[i], []).append(i)
        members = list(uniq.items())
        for lo in range(0, len(members), rp.max_lanes):
            chunk = members[lo:lo + rp.max_lanes]
            rs = sweep.simulate_group(config, mix,
                                      [pt.policy for pt, _ in chunk],
                                      params, dram, engine=rp.engine)
            for (pt, twin_idxs), res in zip(chunk, rs):
                for i in twin_idxs:
                    results[i] = res
                faults_mod.point_done(
                    sweep.point_key(pt.sweep_point().cache_path()),
                    source="computed", engine=rp.engine)
    return results


def run_points(points: Sequence[Point], plan: Optional[ExecPlan] = None,
               report: Optional[RunReport] = None) -> List[sim.SimResult]:
    """Evaluate resolved points in order; the engine behind ``run``.

    ``plan`` picks the engine (see :class:`ExecPlan`).
    ``engine="bucketed"`` (and ``"auto"`` with ``jobs <= 1``) batches
    geometry-compatible groups into single device programs; other
    engines go through ``sweep.map_points``.  ``plan.faults`` activates
    a deterministic fault-injection plan for the run; ``report``
    collects per-point completion records and fault/recovery events."""
    rp = (plan or ExecPlan()).resolve()
    sps = [p.sweep_point() for p in points]
    with lern_mod.fit_engine_override(rp.fit_engine), \
            faults_mod.activate(faults_mod.as_plan(rp.faults)), \
            faults_mod.reporting(report):
        if rp.engine == "bucketed" or (rp.engine == "auto" and rp.jobs <= 1):
            return sweep.run_bucketed(sps, max_lanes=rp.max_lanes,
                                      devices=rp.devices, cache=rp.cache,
                                      report=report)
        if rp.cache:
            return sweep.map_points(sps, jobs=rp.jobs, max_lanes=rp.max_lanes,
                                    engine=rp.engine,
                                    fit_engine=rp.fit_engine, report=report)
        return _run_points_uncached(points, rp)


def run(spec: SpecLike, plan: Optional[ExecPlan] = None, *,
        manifest: Optional[str] = None,
        resume: Optional[bool] = None) -> ResultSet:
    """Expand ``spec`` (one ExperimentSpec or several, concatenated) and
    evaluate every point under ``plan``; returns a columnar ResultSet
    whose key columns are the spec's axes and whose ``result`` column
    holds the full SimResults.

    ``manifest`` (default: env ``REPRO_MANIFEST``) names an incremental
    sweep manifest (``hydra-manifest/v1``) updated after every finished
    point and fault event.  ``resume`` defaults from env
    ``REPRO_RESUME`` (``benchmarks.run --resume`` sets both env vars so
    every figure module's ``exp.run`` picks the prior manifest up
    without threading arguments).  ``resume=True`` re-opens a prior manifest
    and re-executes only the unfinished points — the completed ones load
    from the result cache (a missing or corrupt cache entry simply
    recomputes) and are recorded with ``source="resume"``.  Requires
    ``manifest`` and a cache-enabled plan.  The structured
    :class:`~repro.exp.faults.RunReport` is attached to the returned
    ResultSet as ``rs.run_report`` and summarized in its sweep doc."""
    if manifest is None:
        manifest = os.environ.get("REPRO_MANIFEST") or None
    if resume is None:
        resume = os.environ.get("REPRO_RESUME", "").lower() \
            not in ("", "0", "false")
    if resume:
        if not manifest:
            raise ValueError("resume=True requires a manifest path "
                             "(argument or REPRO_MANIFEST)")
        rp = (plan or ExecPlan()).resolve()
        if not rp.cache:
            raise ValueError("resume=True requires a cache-enabled plan "
                             "(completed points are served from the "
                             "result cache)")
    report = RunReport(manifest=manifest, resume=resume)
    specs = [spec] if isinstance(spec, ExperimentSpec) else list(spec)
    expanded: List[Tuple[Point, Dict]] = []
    keys: List[str] = []
    for s in specs:
        expanded.extend(s.expand())
        for name, _ in s.axes:
            if name not in keys:
                keys.append(name)
    report.n_points = len(expanded)
    results = run_points([pt for pt, _ in expanded], plan, report=report)
    report.flush()
    records = [_record(pt, axes, res)
               for (pt, axes), res in zip(expanded, results)]
    rs = ResultSet.from_records(records, keys=keys)
    rs.run_report = report
    return rs
