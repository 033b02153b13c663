"""Batched multi-policy sweep engine.

The paper's evaluation is a large cross-product — policies x configs x
mixes x DRAM/LLC variants (Figs. 10-20) — and every point used to go
through the sequential single-point loop one at a time.  This module
batches that cross-product at three levels:

* **Within a (config, mix, params, dram) group** all requested policies
  are simulated in one pass: the trace, LERN clusters and core streams are
  loaded once (``sim.load_artifacts``), each policy advances as a
  ``sim.Lane``, and every epoch's LLC round chunks are pushed through a
  single vmapped dispatch (``llc.simulate_epoch_lanes``) instead of one
  dispatch per policy.  Lanes whose LLC geometry diverges (e.g. the
  SHIP_LARGE predictor-size study) are partitioned into geometry-compatible
  sub-batches, degenerating to a per-lane loop when nothing matches.
  Results are bitwise-identical to the sequential ``sim.drive_lane``
  reference (tests/test_sweep.py).

* **Across groups, on device** ``run_bucketed``/``simulate_bucket``
  bucket whole groups by device-engine static shape (``fused.bucket_key``)
  and drive each bucket as ONE device program with a leading group axis
  (``fused.drive_lanes_bucketed``) — thousands of sweep points become a
  handful of dispatch chains.  Bitwise-equal to per-group
  ``simulate_group`` (tests/test_bucketed.py).

Online-LERN lanes (``*-ol`` policies) ride the same batching: their
retrain hook lives inside ``Lane.finish_epoch`` (refit on the observed
window through ``lern.train_model_batched``, packed L-RPT images swapped
in place), so a group can mix offline and online policies freely and an
infinite retrain period stays bitwise-equal to the offline lane
(tests/test_sweep.py).

* **Across groups, across processes** ``map_points`` — the host/process
  fallback — fans independent groups over a spawn-based process pool.
  The existing sim disk cache is the dedup layer: cached points are
  skipped up front, finished groups are written back with atomic renames
  so concurrent workers (or concurrent benchmark invocations) never
  observe torn results.  Deadline calibrations — the one artifact shared
  *across* groups of one config — are precomputed first so workers don't
  race to simulate them redundantly.

Engine selection lives in ``repro.exp.ExecPlan`` — this module only
provides the mechanisms.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import time
from collections import OrderedDict
from concurrent.futures import ProcessPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import llc
from . import sim
from .dram import DramModel, default_model
from .policies import Policy


def _faults():
    # lazy: fault injection + run reporting live in repro.exp.faults
    # (stdlib-only); core modules import it on demand to stay cycle-safe
    from repro.exp import faults
    return faults

# Default lane width: keeps vmap working-set small and gives the process
# pool enough independent tasks to fill its workers even for single-mix
# figure sweeps.
MAX_LANES = 4
# Groups per bucketed device program: per-group SharedConsts (trace +
# core streams) are duplicated along the group axis, so a slab cap keeps
# the staged working set bounded on big sweeps.
BUCKET_GROUPS = int(os.environ.get("REPRO_BUCKET_GROUPS", "16"))
# Staged-buffer cache entries (one per group) kept alive across
# ``simulate_bucket`` calls: bench reps, policy-search generations and
# re-chunked rosters re-use the uploaded trace/stream/table constants
# instead of re-staging them.  Entries whose cluster tables an
# online-LERN retrain swapped in place are stale and re-stage.
STAGE_CACHE_CAP = int(os.environ.get("REPRO_STAGE_CACHE", "32"))
_STAGE_CACHE: "OrderedDict[Tuple, object]" = OrderedDict()

# Resilient-execution knobs (docs/resilience.md).  A failing pool task is
# retried TASK_RETRIES times with exponential backoff (base RETRY_BACKOFF
# seconds, doubled per attempt, capped at 5s) before the parent runs it
# inline on the host engine as a last resort.  TASK_TIMEOUT > 0 arms a
# per-task wall-clock watchdog: overrunning workers are killed, the pool
# respawned, and in-flight survivors re-dispatched.
TASK_RETRIES = int(os.environ.get("REPRO_TASK_RETRIES", "2"))
TASK_TIMEOUT = float(os.environ.get("REPRO_TASK_TIMEOUT", "0"))
RETRY_BACKOFF = float(os.environ.get("REPRO_RETRY_BACKOFF", "0.25"))


def point_key(path: str) -> str:
    """Manifest key of one sweep point: the md5 basename of its sim
    result cache path (stable across hosts and cache roots)."""
    base = os.path.basename(path)
    return base[:-4] if base.endswith(".pkl") else base


@dataclasses.dataclass(frozen=True)
class SweepPoint:
    """One cell of the evaluation cross-product."""
    config: str
    mix: str
    policy: Policy
    params: Optional[sim.SimParams] = None
    # default honors REPRO_DRAM (CI sched leg) — see dram.default_model
    dram: DramModel = dataclasses.field(default_factory=default_model)

    def resolved_params(self) -> sim.SimParams:
        return self.params or sim.SimParams()

    def cache_path(self) -> str:
        return sim.result_cache_path(self.config, self.mix, self.policy,
                                     self.resolved_params(), self.dram)


# ---------------------------------------------------------------------------
# one-pass multi-policy group simulation
# ---------------------------------------------------------------------------
def simulate_group(config: str, mix: str, pols: Sequence[Policy],
                   params: Optional[sim.SimParams] = None,
                   dram: Optional[DramModel] = None,
                   deadline_cycles: Optional[float] = None,
                   core_traffic: bool = True,
                   engine: str = "auto") -> List[sim.SimResult]:
    """Simulate several policies on one (config, mix) trace in one pass.

    Order of results matches ``pols``.  Bitwise-consistent with driving
    each point alone through the sequential ``sim.drive_lane`` loop —
    this is the sweep-level oracle ``simulate_bucket`` is pinned against.

    ``engine`` selects the epoch loop: ``"host"`` the per-epoch host
    loop, and ``"auto"`` (default) runs every device-eligible geometry
    batch as a one-group bucket of ``fused.drive_lanes_bucketed`` —
    results are bitwise-identical either way (tests/test_fused.py), so
    this is purely a performance switch.
    """
    if engine not in ("auto", "host"):
        raise ValueError(f"unknown engine {engine!r}")
    p = params or sim.SimParams()
    if dram is None:
        dram = default_model()
    if deadline_cycles is None:
        deadline_cycles = sim.calibrated_deadline(config, p, dram)
    art = sim.load_artifacts(config, mix, p, core_traffic)
    lanes = [sim.Lane(config, mix, pol, p, dram, float(deadline_cycles), art,
                      core_traffic) for pol in pols]
    # partition into geometry-compatible sub-batches (stable order)
    batches: Dict[Tuple, List[sim.Lane]] = {}
    for lane in lanes:
        batches.setdefault(llc.geometry_key(lane.llc_cfg), []).append(lane)
    for batch in batches.values():
        if engine == "auto":
            from . import fused  # deferred: keep host pool workers light
            if all(fused.lane_supported(lane) for lane in batch):
                fused.drive_lanes_bucketed([batch])
                continue
        _drive_lanes(batch)
    return [lane.result() for lane in lanes]


def _drive_lanes(lanes: List[sim.Lane]) -> None:
    """Advance a geometry-compatible batch of lanes to completion.

    Each epoch: every active lane builds its event list on the host, the
    per-lane round chunks are padded to a common [L, R, S] block, and one
    ``simulate_epoch_lanes`` dispatch advances all LLC states.  Padded
    rounds are invalid events (meta 0) — no-ops for cache content, so
    per-lane results match the unpadded sequential engine exactly.
    """
    import jax
    import jax.numpy as jnp  # deferred: keep module import light for the pool

    cfg0 = lanes[0].llc_cfg
    num_sets = cfg0.num_sets
    n_stats = len(llc.STAT_NAMES)
    pending = [lane for lane in lanes if lane.active]
    knobs = llc.lane_knobs([lane.llc_cfg for lane in pending])
    states = llc.stack_states(cfg0, len(pending))

    while pending:
        if len(pending) == 1:
            # lone survivor (or single-lane group): static engine, shared
            # kernels with sim.drive_lane, no vmap padding; continue from the
            # lane's current LLC content
            sim.drive_lane(pending[0], state=_lane_state(states, 0))
            return
        n_lanes = len(pending)
        evs = [lane.begin_epoch() for lane in pending]
        chunk_lists = [list(llc.build_rounds(cfg0, *ev))
                       if ev is not None else [] for ev in evs]
        stats = np.zeros((n_lanes, n_stats), np.int64)
        percore = np.zeros((n_lanes, llc.NUM_CORES, 2), np.int64)
        n_chunks = max((len(cl) for cl in chunk_lists), default=0)
        for c in range(n_chunks):
            r_pad = max(cl[c][0].shape[0]
                        for cl in chunk_lists if len(cl) > c)
            line_b = np.full((n_lanes, r_pad, num_sets), -1, np.int32)
            meta_b = np.zeros((n_lanes, r_pad, num_sets), np.int32)
            for i, cl in enumerate(chunk_lists):
                if len(cl) > c:
                    lm, mm = cl[c]
                    line_b[i, :lm.shape[0]] = lm
                    meta_b[i, :mm.shape[0]] = mm
            states, st_b, pc_b = llc.simulate_epoch_lanes(
                cfg0, knobs, states, jnp.asarray(line_b), jnp.asarray(meta_b))
            stats += np.asarray(st_b, np.int64)
            percore += np.asarray(pc_b, np.int64)
        for i, lane in enumerate(pending):
            lane_state = (_lane_state(states, i)
                          if lane.p.record_occupancy else None)
            lane.finish_epoch(stats[i], percore[i], llc_state=lane_state)
        # drop finished lanes so long-running survivors stop paying for
        # all-padding dispatches on the finished lanes' slots
        still = [i for i, lane in enumerate(pending) if lane.active]
        if len(still) < n_lanes:
            pending = [pending[i] for i in still]
            if pending:
                sel = np.asarray(still)
                knobs = jax.tree.map(lambda x: x[sel], knobs)
                states = jax.tree.map(lambda x: x[sel], states)


def _lane_state(states: llc.LLCState, i: int) -> llc.LLCState:
    import jax
    return jax.tree.map(lambda x: x[i], states)


# ---------------------------------------------------------------------------
# whole-sweep-on-device: geometry-bucketed vmap over groups
# ---------------------------------------------------------------------------
def _staged_for(batch_list: List[List[sim.Lane]]):
    """Staged device constants for one bucket slab, through the module
    staging LRU.  The key is everything that determines the staged
    buffers bit-for-bit: the bucket's static shape, the slab pads (array
    sizes), and each group's full point identity (config, mix, policy
    roster, params/dram, deadline).  A cached entry whose tables an
    online-LERN retrain swapped (``_Staged.stale``) re-stages."""
    from . import fused
    if _faults().fire("stage_evict", key=f"{len(batch_list)}g") is not None:
        # injected staging-buffer eviction: drop the LRU wholesale — a
        # pure perf event (everything re-stages from host copies)
        _STAGE_CACHE.clear()
    pads = fused.bucket_pads(batch_list)
    staged = []
    for batch in batch_list:
        lane0 = batch[0]
        key = (fused.bucket_key(batch), lane0.config, lane0.mix,
               tuple(repr(lane.policy) for lane in batch),
               _params_key(lane0.p, lane0.dram), float(lane0.deadline),
               pads, fused.DEFAULT_SUPERSTEP, fused.DEFAULT_MAX_ROUNDS)
        hit = _STAGE_CACHE.get(key)
        if hit is None or hit.stale:
            hit = fused.stage_group(batch, pads=pads)
            _STAGE_CACHE[key] = hit
        _STAGE_CACHE.move_to_end(key)
        while len(_STAGE_CACHE) > STAGE_CACHE_CAP:
            _STAGE_CACHE.popitem(last=False)
        staged.append(hit)
    return staged


def _make_task_lanes(task) -> List[sim.Lane]:
    """Fresh lanes (one per policy) for one group task, built from cached
    artifacts — cheap to rebuild, which is what makes mid-run engine
    demotion safe: a failed bucket never patches partially-advanced
    state, it recomputes the group from scratch."""
    config, mix, pols, params, dram, _paths = task
    p = params or sim.SimParams()
    deadline = sim.calibrated_deadline(config, p, dram)
    art = sim.load_artifacts(config, mix, p, True)
    return [sim.Lane(config, mix, pol, p, dram, float(deadline), art, True)
            for pol in pols]


def _demote_batch(task, poss: List[int]) -> List[sim.Lane]:
    """Degradation ladder, second rung: re-run the ``poss`` policy lanes
    of ``task`` on the host loop.  Starts from fresh lanes —
    recomputation is deterministic, so the bitwise contract holds
    whichever rung finishes the group."""
    lanes = _make_task_lanes(task)
    sel = [lanes[j] for j in poss]
    _drive_lanes(sel)
    return sel


def simulate_bucket(tasks: Sequence[Tuple], devices: Optional[int] = None,
                    task_keys: Optional[List[List[str]]] = None
                    ) -> List[List[sim.SimResult]]:
    """Simulate many ``(config, mix, pols, params, dram, paths)`` group
    tasks at once: groups are bucketed by fused-engine static shape
    (``fused.bucket_key``) and each bucket runs as one vmapped device
    program (``fused.drive_lanes_bucketed``), so a whole sweep is a
    handful of dispatch chains instead of one per group.

    Bitwise-equal to per-task ``simulate_group`` — the oracle it is
    pinned against (tests/test_bucketed.py).  Geometry batches the fused
    engine can't take fall back to the host loop, exactly like
    ``engine="auto"``; beyond that, a slab that fails **degradably**
    (``RESOURCE_EXHAUSTED`` or an injected fault) walks the ladder
    bucketed → host, recomputing the affected groups from fresh lanes so
    results stay bitwise-identical (docs/resilience.md).  Each finished
    point is dumped to its ``paths`` entry (pass empty paths to skip the
    cache).  Staged device constants ride the module staging LRU
    (``_staged_for``), so repeated sweeps over the same points skip the
    upload.  ``task_keys`` (parallel to ``tasks``) reports each finished
    point to the active run report.  Returns per-task result lists in
    task order."""
    flt = _faults()
    from . import fused
    task_lanes: List[List[sim.Lane]] = []
    task_engines: List[set] = []
    # bucket members carry (batch, task_idx, lane positions) so a demoted
    # batch can be rebuilt and re-installed into its task's lane roster
    buckets: Dict[Tuple, List[Tuple[List[sim.Lane], int, List[int]]]] = {}
    host_batches: List[List[sim.Lane]] = []
    for ti, task in enumerate(tasks):
        lanes = _make_task_lanes(task)
        task_lanes.append(lanes)
        task_engines.append(set())
        batches: Dict[Tuple, List[int]] = {}
        for j, lane in enumerate(lanes):
            batches.setdefault(llc.geometry_key(lane.llc_cfg),
                               []).append(j)
        for poss in batches.values():
            batch = [lanes[j] for j in poss]
            if all(fused.lane_supported(lane) for lane in batch):
                buckets.setdefault(fused.bucket_key(batch),
                                   []).append((batch, ti, poss))
                task_engines[ti].add("bucketed")
            else:
                host_batches.append(batch)
                task_engines[ti].add("host")
    for batch_list in buckets.values():
        for lo in range(0, len(batch_list), BUCKET_GROUPS):
            slab = batch_list[lo:lo + BUCKET_GROUPS]
            groups = [b for b, _ti, _poss in slab]
            try:
                flt.fire("bucket", key=f"{len(groups)}g")
                fused.drive_lanes_bucketed(groups, devices=devices,
                                           staged=_staged_for(groups))
            except Exception as e:
                if not flt.degradable(e):
                    raise
                flt.log_event("degrade", ladder="bucketed->host",
                              groups=len(groups), error=str(e)[:200])
                for _batch, ti, poss in slab:
                    for j, lane in zip(poss, _demote_batch(tasks[ti], poss)):
                        task_lanes[ti][j] = lane
                    task_engines[ti].add("host")
    for batch in host_batches:
        _drive_lanes(batch)
    out: List[List[sim.SimResult]] = []
    for ti, (task, lanes) in enumerate(zip(tasks, task_lanes)):
        results = [lane.result() for lane in lanes]
        for res, path in zip(results, task[5]):
            sim._atomic_dump(res, path)
        eng = "host" if "host" in task_engines[ti] else "bucketed"
        if task_keys is not None:
            for key in task_keys[ti]:
                flt.point_done(key, source="computed", engine=eng)
        out.append(results)
    return out


# ---------------------------------------------------------------------------
# cross-group orchestration (process pool + disk-cache dedup)
# ---------------------------------------------------------------------------
def _params_key(p: sim.SimParams, dram: DramModel) -> str:
    return json.dumps({"par": dataclasses.asdict(p), "d": dram.name},
                      sort_keys=True, default=str)


def _worker_init(cache_dir: str, extra_configs: Optional[Dict] = None,
                 fit_engine: Optional[str] = None) -> None:
    # sim is already imported (unpickling this initializer imports sweep),
    # so its import-time config came from the inherited env; propagate a
    # programmatic CACHE_DIR override (e.g. test monkeypatch) to the
    # artifact caches here.  The XLA cache stays where sim put it.
    sim.CACHE_DIR = cache_dir
    if fit_engine is not None:
        # ExecPlan.fit_engine: spawn workers don't see the parent's
        # lern.fit_engine_override, so pin the module default here
        from . import lern as lern_mod
        lern_mod.FIT_ENGINE = fit_engine
    # spawn re-imports workloads.py fresh, so configs registered at
    # runtime in the parent (phase-drift variants, ad-hoc AccelConfigs)
    # must be re-registered or CONFIGS[config] raises in every worker
    if extra_configs:
        from .workloads import CONFIGS
        for name, cfg in extra_configs.items():
            CONFIGS.setdefault(name, cfg)


def _calibrate_task(task) -> float:
    config, params, dram = task
    return sim.calibrated_deadline(config, params, dram)


def _prepare_lern(tasks) -> None:
    """Family-batched LERN training for every uncached (config, variant).

    Tiny configs are host-bound when trained one dispatch at a time
    (bench_lern.json); training whole config families in one device
    dispatch up front means workers (and inline groups) only read the
    cache for them.  Models are identical to per-config training, so
    this is purely a scheduling change.  Under the default segmented
    fit engine every uncached trace trains here (the family fit wins in
    both regimes — sim.family_cap() is unbounded); under the bucketed
    oracle engine only the small dispatch-bound traces do, and big
    uncached models stay with the workers, which train them in parallel
    as before."""
    fam: Dict[Tuple, List[str]] = {}
    for config, _mix, pols, params, _dram, _paths in tasks:
        for pol in pols:
            if pol.accel_predictor == "lern":
                # Lane loads clusters at the default training seed
                key = (pol.lrpt_variant, params.subsample_target)
                configs = fam.setdefault(key, [])
                if config not in configs:
                    configs.append(config)
    for (variant, sub), configs in fam.items():
        sim.load_lern_family(configs, variant, sub, family_only=True)


def _group_task(task, engine: str = "auto") -> List[sim.SimResult]:
    """Pool task: simulate one policy group and persist each point."""
    config, mix, pols, params, dram, paths = task
    # named injection site: crash/hang/raise faults land here, in the
    # worker (or inline caller), to exercise the retry/respawn machinery
    _faults().fire("task", key=f"{config}|{mix}")
    results = simulate_group(config, mix, list(pols), params, dram,
                             engine=engine)
    for res, path in zip(results, paths):
        sim._atomic_dump(res, path)
    return results


class TaskError(RuntimeError):
    """Picklable worker-task failure carrying the worker's buffered
    fault events (quarantines, injections, retries) back to the parent,
    so a failed task still contributes its fault log to the RunReport."""

    def __init__(self, cause: str, msg: str, events: List[Dict]):
        super().__init__(f"{cause}: {msg}")
        self.cause = cause
        self.events = events

    def __reduce__(self):
        return (TaskError, (self.cause,
                            str(self).split(": ", 1)[-1], self.events))


def _pool_task(task, engine: str = "auto"):
    """Spawn-pool wrapper around :func:`_group_task`: workers have no
    active RunReport, so their fault events buffer locally — drain the
    buffer and ship it with the result (or inside :class:`TaskError`),
    letting the parent fold worker-side events into its report."""
    flt = _faults()
    try:
        results = _group_task(task, engine=engine)
    except Exception as e:
        raise TaskError(type(e).__name__, str(e)[:500],
                        flt.drain_events()) from None
    return results, flt.drain_events()


def _plan_tasks(points: Sequence[SweepPoint], max_lanes: int,
                cache: bool = True):
    """The shared front half of ``map_points``/``run_bucketed``: cache
    reads (when ``cache``), duplicate-point dedup, grouping by (config,
    mix, params, dram) and chunking into <= ``max_lanes`` policy lanes.

    Returns ``(results, tasks, task_idxs, task_keys, calib, seen_paths)``
    — ``results`` pre-filled with cache hits, ``tasks`` as
    ``(config, mix, pols, params, dram, paths)`` tuples (empty paths
    when ``cache`` is off, so executors skip the dump), ``task_keys``
    the per-task manifest point keys.  Cache reads go through the
    checksummed envelope (``sim.cache_load``): corrupt or legacy entries
    are quarantined and the point recomputed."""
    flt = _faults()
    results: List[Optional[sim.SimResult]] = [None] * len(points)
    seen_paths: Dict[str, List[int]] = {}
    groups: Dict[str, List[Tuple[int, SweepPoint, str]]] = {}
    for idx, pt in enumerate(points):
        path = pt.cache_path()
        if path in seen_paths:          # duplicate point: fill from twin
            seen_paths[path].append(idx)
            continue
        seen_paths[path] = [idx]
        if cache:
            v = sim.cache_load(path)
            if v is not sim.MISS:
                results[idx] = v
                flt.point_done(point_key(path), source="cache")
                continue
        key = f"{pt.config}|{pt.mix}|{_params_key(pt.resolved_params(), pt.dram)}"
        groups.setdefault(key, []).append((idx, pt, path))

    tasks = []
    task_idxs: List[List[int]] = []
    task_keys: List[List[str]] = []
    calib: Dict[str, Tuple] = {}
    for members in groups.values():
        first = members[0][1]
        params, dram = first.resolved_params(), first.dram
        ck = f"{first.config}|{_params_key(params, dram)}"
        calib.setdefault(ck, (first.config, params, dram))
        for lo in range(0, len(members), max_lanes):
            chunk = members[lo:lo + max_lanes]
            tasks.append((first.config, first.mix,
                          tuple(pt.policy for _, pt, _ in chunk),
                          params, dram,
                          tuple(path for _, _, path in chunk) if cache
                          else ()))
            task_idxs.append([idx for idx, _, _ in chunk])
            task_keys.append([point_key(path) for _, _, path in chunk])
    return results, tasks, task_idxs, task_keys, calib, seen_paths


def _fill_twins(results, seen_paths) -> None:
    for _path, idxs in seen_paths.items():
        for idx in idxs[1:]:
            results[idx] = results[idxs[0]]


def _run_task_inline(task, engine: str, retries: int) -> Tuple:
    """Inline (jobs<=1) resilient execution of one group task: a
    degradable failure (``faults.degradable``) retries with exponential
    backoff on the requested engine, then makes a final attempt on the
    host engine; any other error propagates at once.  Returns (results,
    attempts, engine)."""
    flt = _faults()
    attempts = 0
    while True:
        attempts += 1
        eng = engine if attempts <= retries else "host"
        try:
            return _group_task(task, engine=eng), attempts, eng
        except Exception as e:
            if attempts > retries or not flt.degradable(e):
                raise
            flt.log_event("task_retry", task=f"{task[0]}|{task[1]}",
                          attempt=attempts, error=str(e)[:200])
            time.sleep(min(RETRY_BACKOFF * 2 ** (attempts - 1), 5.0))


def _run_pool(tasks, calib, engine: str, fit_engine: Optional[str],
              jobs: int, timeout: float, retries: int) -> List[Tuple]:
    """Spawn-pool execution of the group tasks with the full recovery
    stack: per-task retry with backoff, ``BrokenProcessPool`` detection
    with pool respawn + survivor re-dispatch, a wall-clock watchdog that
    kills overrunning workers (``timeout`` > 0), and an inline-host
    fallback in the parent once a task exhausts its retry budget.
    Returns per-task ``(results, attempts, engine)`` in task order."""
    import multiprocessing as mp
    from concurrent.futures import FIRST_COMPLETED, wait
    from concurrent.futures.process import BrokenProcessPool

    from .workloads import CONFIGS

    flt = _faults()
    ctx = mp.get_context("spawn")
    workers = min(jobs, len(tasks))
    # ship each task's config: runtime registrations (drift variants,
    # ad-hoc AccelConfigs) don't survive the spawn re-import;
    # setdefault makes statically-known ones a no-op
    extra = {t[0]: CONFIGS[t[0]] for t in tasks}

    results: List[Optional[Tuple]] = [None] * len(tasks)
    attempts = [0] * len(tasks)
    pending: List[int] = list(range(len(tasks)))
    running: Dict = {}          # future -> task index
    deadlines: Dict = {}        # future -> monotonic watchdog deadline
    ex: Optional[ProcessPoolExecutor] = None

    def discard_pool(kill: bool = False) -> None:
        nonlocal ex
        if ex is None:
            return
        if kill:
            # hung or wedged workers never drain the shutdown sentinel —
            # kill them outright so shutdown (and interpreter exit's
            # executor join) can't block behind a sleeping worker
            for proc in list(getattr(ex, "_processes", {}).values()):
                try:
                    proc.kill()
                except Exception:
                    pass
        ex.shutdown(wait=False, cancel_futures=True)
        ex = None

    def new_pool() -> None:
        nonlocal ex
        ex = ProcessPoolExecutor(max_workers=workers, mp_context=ctx,
                                 initializer=_worker_init,
                                 initargs=(sim.CACHE_DIR, extra, fit_engine))
        # phase 1: deadline calibration, one task per unique (config,
        # params, dram) — otherwise every group of a config would
        # redundantly simulate the standalone run.  Results land in the
        # disk cache, so the re-run after a pool respawn is free.
        try:
            list(ex.map(_calibrate_task, calib.values()))
        except Exception as e:
            flt.log_event("calibration_fallback", error=str(e)[:200])
            discard_pool(kill=True)
            for t in calib.values():
                _calibrate_task(t)
            ex = ProcessPoolExecutor(max_workers=workers, mp_context=ctx,
                                     initializer=_worker_init,
                                     initargs=(sim.CACHE_DIR, extra,
                                               fit_engine))

    def handle_failure(i: int, kind: str, err: str) -> None:
        if attempts[i] > retries:
            # retry budget exhausted: last resort is the parent itself,
            # on the always-available host engine
            flt.log_event("inline_fallback", task=f"{tasks[i][0]}|{tasks[i][1]}",
                          attempts=attempts[i], cause=kind)
            attempts[i] += 1
            results[i] = (_group_task(tasks[i], engine="host"),
                          attempts[i], "host")
        else:
            flt.log_event("task_retry", task=f"{tasks[i][0]}|{tasks[i][1]}",
                          attempt=attempts[i], cause=kind, error=err[:200])
            time.sleep(min(RETRY_BACKOFF * 2 ** (attempts[i] - 1), 5.0))
            pending.append(i)

    try:
        while pending or running:
            if ex is None and pending:
                new_pool()
            # one in-flight task per worker: with no executor-side
            # queueing, a submitted future is actually executing, so the
            # watchdog clock measures work, not queue wait
            while pending and len(running) < workers:
                i = pending.pop(0)
                attempts[i] += 1
                fut = ex.submit(functools.partial(_pool_task,
                                                  engine=engine), tasks[i])
                running[fut] = i
                if timeout > 0:
                    deadlines[fut] = time.monotonic() + timeout
            done, _ = wait(set(running), return_when=FIRST_COMPLETED,
                           timeout=0.25 if timeout > 0 else None)
            pool_broken = False
            for fut in done:
                i = running.pop(fut)
                deadlines.pop(fut, None)
                try:
                    rs, wevents = fut.result()
                    flt.merge_events(wevents)
                    results[i] = (rs, attempts[i], engine)
                    continue
                except BrokenProcessPool as e:
                    pool_broken = True
                    kind, err = "worker_crash", str(e)
                except TaskError as e:
                    flt.merge_events(e.events)
                    kind, err = "task_error", str(e)
                except Exception as e:
                    kind, err = "task_error", str(e)
                handle_failure(i, kind, err)
            if pool_broken:
                # a worker died mid-task: every in-flight future is
                # poisoned — respawn the pool and re-dispatch survivors
                # without charging their retry budgets
                flt.log_event("worker_crash", respawn=True,
                              inflight=len(running))
                for fut, i in list(running.items()):
                    attempts[i] -= 1
                    pending.append(i)
                running.clear()
                deadlines.clear()
                discard_pool(kill=True)
                continue
            now = time.monotonic()
            overdue = [fut for fut, dl in deadlines.items()
                       if dl < now and not fut.done()]
            if overdue:
                # watchdog: the pool API can't kill one worker, so kill
                # them all, fail the overdue tasks, and re-dispatch the
                # innocent in-flight survivors budget-free
                over_idx = {running[fut] for fut in overdue}
                flt.log_event(
                    "watchdog_kill", timeout=timeout,
                    tasks=[f"{tasks[i][0]}|{tasks[i][1]}"
                           for i in sorted(over_idx)])
                discard_pool(kill=True)
                survivors = [i for fut, i in running.items()
                             if i not in over_idx]
                running.clear()
                deadlines.clear()
                for i in survivors:
                    attempts[i] -= 1
                    pending.append(i)
                for i in sorted(over_idx):
                    handle_failure(i, "watchdog", "task exceeded "
                                   f"{timeout}s wall clock")
    finally:
        discard_pool()
    return results  # every slot is a (results, attempts, engine) triple


def map_points(points: Sequence[SweepPoint], jobs: int = 1,
               max_lanes: int = MAX_LANES, engine: str = "auto",
               fit_engine: Optional[str] = None,
               report=None, task_timeout: Optional[float] = None,
               retries: Optional[int] = None) -> List[sim.SimResult]:
    """Evaluate a list of sweep points, batched and (optionally) parallel
    — the host/process fallback behind ``exp.ExecPlan`` (the bucketed
    device path is ``run_bucketed``).

    Cached points are loaded and skipped; the remainder are grouped by
    (config, mix, params, dram), chunked into <= ``max_lanes`` policy
    lanes, and executed — inline for ``jobs <= 1``, else on a spawn-based
    process pool of ``jobs`` workers.  ``engine`` is the per-group epoch
    engine (``simulate_group``'s ``auto|host``); ``fit_engine``
    pins the LERN fit engine inside pool workers.  Every finished point
    is written to the sim disk cache, so concurrent sweeps (and later
    cached runs) are free.  Returns results in ``points`` order.

    Execution is resilient (docs/resilience.md): failing tasks retry
    with exponential backoff (``retries``, default ``REPRO_TASK_RETRIES``)
    and finish inline on the host engine as a last resort; a dead worker
    (``BrokenProcessPool``) respawns the pool and re-dispatches the
    in-flight survivors; ``task_timeout`` (default ``REPRO_TASK_TIMEOUT``,
    0 = off) arms a per-task wall-clock watchdog.  Recovery recomputes
    from cached artifacts, so results stay bitwise-identical to a
    fault-free run.  ``report`` (a ``faults.RunReport``) receives
    per-point completion records and every fault/recovery event; any
    active fault plan (``REPRO_FAULTS`` / ``ExecPlan(faults=)``) is
    honored.
    """
    flt = _faults()
    retries = TASK_RETRIES if retries is None else retries
    timeout = TASK_TIMEOUT if task_timeout is None else task_timeout
    with flt.activate(), flt.reporting(report):
        results, tasks, task_idxs, task_keys, calib, seen_paths = \
            _plan_tasks(points, max_lanes, cache=True)

        if tasks:
            _prepare_lern(tasks)
            if jobs <= 1 or len(tasks) == 1:
                task_results = [_run_task_inline(t, engine, retries)
                                for t in tasks]
            else:
                task_results = _run_pool(tasks, calib, engine, fit_engine,
                                         jobs, timeout, retries)
            for idxs, keys, (rs, n_att, eng) in zip(task_idxs, task_keys,
                                                    task_results):
                for idx, res in zip(idxs, rs):
                    results[idx] = res
                for key in keys:
                    flt.point_done(key, source="computed", engine=eng,
                                   attempts=n_att)

        _fill_twins(results, seen_paths)
    return results  # type: ignore[return-value]


def run_bucketed(points: Sequence[SweepPoint], max_lanes: int = MAX_LANES,
                 devices: Optional[int] = None, cache: bool = True,
                 report=None) -> List[sim.SimResult]:
    """Bucketed twin of ``map_points``: the same cache/dedup/grouping
    front half, but every uncached group executes together through
    ``simulate_bucket`` — whole-sweep-on-device instead of a process
    farm.  Degradable bucket failures walk the bucketed → host ladder
    inside ``simulate_bucket``.
    ``report`` receives per-point records + fault events.  Returns
    results in ``points`` order, bitwise-equal to ``map_points`` on the
    same points."""
    flt = _faults()
    with flt.activate(), flt.reporting(report):
        results, tasks, task_idxs, task_keys, calib, seen_paths = \
            _plan_tasks(points, max_lanes, cache=cache)
        if tasks:
            _prepare_lern(tasks)
            # resolve every unique (config, params, dram) deadline once
            # up front — same precompute phase as map_points — so
            # per-task lane construction (and any host-batch fallback)
            # only reads the calibration cache
            for t in calib.values():
                _calibrate_task(t)
            bucket_rs = simulate_bucket(tasks, devices, task_keys=task_keys)
            for idxs, rs in zip(task_idxs, bucket_rs):
                for idx, res in zip(idxs, rs):
                    results[idx] = res
        _fill_twins(results, seen_paths)
    return results  # type: ignore[return-value]
