"""ExecPlan — the one object that says *how* a spec is executed.

Historically execution knobs were scattered: ``REPRO_FUSED`` env var,
``engine=`` strings on ``sweep.simulate_group``, ``jobs=``/``cache=``
kwargs on ``exp.run``, ``REPRO_LERN_FIT`` for the k-means fit engine.
``ExecPlan`` unifies them:

    from repro import exp
    rs = exp.run(spec, plan=exp.ExecPlan(engine="bucketed", devices=4))

Fields left ``None`` resolve to the environment defaults (the old env
vars keep working, as documented below), so ``ExecPlan()`` is always a
valid "just do the right thing" plan.

Engine names:

* ``"auto"``    — bucketed whole-sweep-on-device when ``jobs <= 1``,
  else the process-pool host path with per-group fused scans.
* ``"host"``    — per-epoch host loop (the sequential oracle's engine).
* ``"fused"``   — per-group fused super-step scan, groups sequential.
* ``"bucketed"``— geometry-bucketed vmap of the fused engine: every
  sweep group with the same (sets, ways, rounds-cap, lane-count)
  geometry runs as one device program (``sweep.run_bucketed``).

All engines are bitwise-equal on integer stats and f64 float histories
(tests/test_sweep.py, tests/test_fused.py, tests/test_bucketed.py).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional, Union

from .faults import FaultPlan

_ENGINES = ("auto", "host", "fused", "bucketed")
_FIT_ENGINES = ("auto", "bucketed", "segmented")


def _check_pool_backend(jobs: int) -> None:
    """A process pool is a CPU-only tool: one process owns an accelerator,
    and spawned workers that import JAX would fight the parent for it
    (fail or hang).  Raise before any child starts."""
    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        raise ValueError(
            f"ExecPlan(jobs={jobs}) starts worker processes, but this "
            f"process holds the {backend} device and a worker cannot share "
            "it; use jobs=1 (the bucketed engine batches the whole sweep "
            "on the device)")


@dataclasses.dataclass(frozen=True)
class ExecPlan:
    """How to execute a spec.  ``None`` fields resolve to env defaults.

    engine:     "auto" | "host" | "fused" | "bucketed"
                (default: env ``REPRO_ENGINE``; legacy ``REPRO_FUSED=0``
                means "host"; else "auto")
    jobs:       process-pool width for the host fallback (default 1;
                ignored by the bucketed engine, which batches on device).
                ``jobs > 1`` needs the CPU backend: every worker process
                would need the accelerator this process already holds,
                so ``resolve`` raises on any other backend
    devices:    device count for ``shard_map`` over buckets (default:
                all visible devices)
    cache:      read/write the sim disk result cache (default True)
    fit_engine: "auto" | "bucketed" | "segmented" k-means fit engine
                (default: env ``REPRO_LERN_FIT``, else "auto")
    max_lanes:  lane cap per device batch (default ``sweep.MAX_LANES``)
    pipeline:   bucketed engine only: donate the super-step carry and
                double-buffer dispatch (default: env
                ``REPRO_BUCKET_PIPELINE``, on; ``False`` is the
                undonated one-dispatch-at-a-time reference path)
    faults:     deterministic fault-injection plan — a
                :class:`repro.exp.faults.FaultPlan` or its JSON string
                (default: env ``REPRO_FAULTS``; None = no injection).
                Recovery is bitwise-transparent; docs/resilience.md.
    """
    engine: Optional[str] = None
    jobs: Optional[int] = None
    devices: Optional[int] = None
    cache: Optional[bool] = None
    fit_engine: Optional[str] = None
    max_lanes: Optional[int] = None
    pipeline: Optional[bool] = None
    faults: Optional[Union[str, FaultPlan]] = None

    def __post_init__(self):
        if self.engine is not None and self.engine not in _ENGINES:
            raise ValueError(f"unknown engine {self.engine!r} "
                             f"(expected one of {_ENGINES})")
        if self.fit_engine is not None and self.fit_engine not in _FIT_ENGINES:
            raise ValueError(f"unknown fit_engine {self.fit_engine!r} "
                             f"(expected one of {_FIT_ENGINES})")
        if self.faults is not None and not isinstance(self.faults,
                                                      (str, FaultPlan)):
            raise ValueError("faults must be a FaultPlan or its JSON "
                             f"string, got {type(self.faults).__name__}")

    def resolve(self) -> "ExecPlan":
        """Fill every ``None`` field from the environment defaults,
        returning a fully-concrete plan (``devices`` may stay ``None`` =
        all visible)."""
        engine = self.engine or os.environ.get("REPRO_ENGINE")
        if engine is None:
            # legacy opt-out: REPRO_FUSED=0 forced the host epoch loop
            engine = ("host" if os.environ.get("REPRO_FUSED", "1") == "0"
                      else "auto")
        if engine not in _ENGINES:  # env var can carry junk
            raise ValueError(f"unknown engine {engine!r} from REPRO_ENGINE "
                             f"(expected one of {_ENGINES})")
        fit = self.fit_engine or os.environ.get("REPRO_LERN_FIT") or "auto"
        if fit not in _FIT_ENGINES:
            raise ValueError(f"unknown fit_engine {fit!r} from "
                             f"REPRO_LERN_FIT (expected one of {_FIT_ENGINES})")
        jobs = max(1, int(self.jobs if self.jobs is not None else 1))
        if jobs > 1:
            _check_pool_backend(jobs)
        from repro.core import sweep  # deferred: exp layers above core
        # mirrors fused.PIPELINE_DEFAULT without importing the (heavy)
        # fused module here — plan resolution must stay light
        pipeline = (os.environ.get("REPRO_BUCKET_PIPELINE", "1") != "0"
                    if self.pipeline is None else bool(self.pipeline))
        return dataclasses.replace(
            self, engine=engine,
            jobs=jobs,
            devices=self.devices,
            cache=True if self.cache is None else bool(self.cache),
            fit_engine=fit,
            max_lanes=(sweep.MAX_LANES if self.max_lanes is None
                       else int(self.max_lanes)),
            pipeline=pipeline,
            faults=(self.faults if self.faults is not None
                    else os.environ.get("REPRO_FAULTS")))
