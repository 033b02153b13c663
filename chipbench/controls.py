#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell, in
one process (the set-up is paid once):

- sound: the program's own answers on ``--seeds`` seeds, each compared
  with the reference exactly as a run compares them;
- control: ``--controls`` seeds on which the reference itself, with one
  part deliberately wrong, is put in the program's place.  For a LERN cell
  that is a plain k-means fitted in bfloat16 (the fit states float32); for
  a sweep cell each of three faults of the reference lane in turn: float32
  timing (the configuration states float64), the SHiP counter threshold
  off by one, and FIFO in place of LRU replacement.

    python3 chipbench/controls.py --workload <cell> --seed <n> [--seeds 12] [--controls 3]

Prints one JSON line per reading and, last, the largest sound and the
smallest control reading of each number.  The benchmark's runs never run
this; it needs the chip like a run does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from types import SimpleNamespace

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import common  # noqa: E402


def _plus_plus(x: np.ndarray, k: int, rng) -> np.ndarray:
    c = [x[rng.integers(x.shape[0])]]
    for _ in range(1, k):
        d = np.min(((x[:, None, :] - np.array(c)[None]) ** 2).sum(-1), 1)
        p = d / d.sum() if d.sum() > 0 else None
        c.append(x[rng.choice(x.shape[0], p=p)])
    return np.array(c)


def kmeans_low(x: np.ndarray, seed: int, dtype, k: int = 4,
               iters: int = 50):
    """Plain Lloyd k-means computed in ``dtype`` (points, centres,
    distances and means); returns (each point's cluster, the centres)."""
    import jax.numpy as jnp
    xs = jnp.asarray(x, dtype)
    c = jnp.asarray(_plus_plus(x, k, np.random.default_rng(seed)), dtype)
    a = None
    for _ in range(iters):
        d = ((xs[:, None, :] - c[None]) ** 2).sum(-1)
        a = jnp.argmin(d, 1)
        oh = (a[:, None] == jnp.arange(k)[None]).astype(dtype)
        cnt = oh.sum(0)
        new = jnp.where(cnt[:, None] > 0,
                        (oh.T @ xs) / jnp.maximum(cnt, 1)[:, None], c)
        if bool(jnp.all(new == c)):
            break
        c = new
    return np.asarray(a), c


def lern_control_model(ref, seed: int, dtype):
    """A model with the reference's feature tables and labels fitted by
    ``kmeans_low`` in ``dtype``, annotated in the paper's label order."""
    import jax.numpy as jnp
    from chipbench.reference.compare import _expected_bin
    n_l = len(ref.layers)
    n_tab = max(u.size for u, _, _ in ref.layers)
    uniq = np.full((n_l, n_tab), -1, np.int64)
    rc_t = np.full((n_l, n_tab), -1, np.int64)
    ri_t = np.full((n_l, n_tab), -1, np.int64)
    rc_c = np.zeros((n_l, 4))
    ri_c = np.zeros((n_l, 4, 4))
    feats, n_uniq = [], []
    for li, (u, f_ri, count) in enumerate(ref.layers):
        n = u.size
        uniq[li, :n] = u
        n_uniq.append(n)
        multi = count > 1
        feats.append(f_ri[multi])
        if multi.sum() < ref.MIN_MULTI:
            continue
        xrc = np.log1p(count[multi].astype(np.float64))
        xrc = ((xrc - xrc.min()) / max(xrc.max() - xrc.min(), 1e-9))[:, None]
        raw = f_ri[multi].astype(np.float64)
        xri = raw / np.maximum(raw.sum(1, keepdims=True), 1e-9)
        lo, hi = np.log1p(count[multi].min()), np.log1p(count[multi].max())
        a, c = kmeans_low(xrc, seed + 2 * li, dtype)
        cm = np.array([xrc[a == j, 0].mean() if (a == j).any() else np.inf
                       for j in range(4)])
        rank = np.empty(4, np.int64)
        rank[np.argsort(cm)] = np.arange(4)
        rc_t[li, :n][multi] = rank[a]
        # de-normalised in the same precision
        den = jnp.expm1(c[:, 0] * jnp.asarray(hi - lo, dtype)
                        + jnp.asarray(lo, dtype))
        rc_c[li, rank] = np.asarray(den, np.float64)
        a, _ = kmeans_low(xri, seed + 2 * li + 1, dtype)
        eb = np.nan_to_num(_expected_bin(raw, a), nan=np.inf)
        rank[np.argsort(eb)] = np.arange(4)
        ri_t[li, :n][multi] = rank[a]
        rw = jnp.asarray(raw, dtype)
        for j in range(4):
            m = a == j
            if m.any():
                ri_c[li, rank[j]] = np.asarray(
                    rw[m].sum(0) / jnp.asarray(m.sum(), dtype), np.float64)
    return SimpleNamespace(uniq=uniq, rc_cluster=rc_t, ri_cluster=ri_t,
                           n_uniq=np.asarray(n_uniq), features_ri=feats,
                           rc_centers=rc_c, ri_centers=ri_c)


def lern_readings(job, seeds, controls, emit):
    import jax.numpy as jnp
    from chipbench.reference.compare import LernReference
    tr = job.trace
    lines = np.asarray(tr.line, np.int64)
    if job.hash_fn is not None:
        lines = job.hash_fn(lines)
    ref = LernReference(lines, np.asarray(tr.layer),
                        max(len(tr.layer_names), 1))
    for i in range(seeds):
        seed = common.derive_seed(job.seed, i)
        emit("sound", seed, ref.compare(job._train(seed)))
    for i in range(controls):
        seed = common.derive_seed(job.seed, 500_000 + i)
        emit("control", seed,
             ref.compare(lern_control_model(ref, seed, jnp.bfloat16)))


SWEEP_FAULTS = {
    "float32_timing": dict(timing_dtype=np.float32),
    "ship_threshold_off_by_one": dict(dead_max=1),
    "fifo_replacement": dict(fifo=True),
}


def sweep_readings(job, seeds, controls, emit):
    """Sound: the program's groups on ``seeds`` fresh stream seeds against
    the reference, as a run compares them.  Control: each fault of the
    reference lane on ``controls`` seeds against the sound reference."""
    import dataclasses
    from repro import exp
    from repro.core import sim
    from chipbench.reference import sweep_lane
    as_lane = common.load_module("jobs", "sweep.py").as_lane
    lanes = list(job.traffic["lanes"])
    name, mix = job.config["name"], job.traffic["mix"]
    trace, model, lern, _ = job.reference_inputs()
    soc, cores = job.soc_and_cores()
    deadline = sweep_lane.standalone_deadline(soc, trace)
    occ = bool(job.config["params"]["record_occupancy"])

    def reference(seed, fault=sweep_lane.SOUND, deadline=None):
        p = dataclasses.replace(job.params[0], seed=seed)
        streams = sim.load_artifacts(name, mix, p).streams
        return sweep_lane.run_group(soc, lanes, trace, cores, streams, seed,
                                    model, deadline, fault), p

    def worst(pairs):
        m, g = 0, 0.0
        for got, want in pairs:
            mi, gi = sweep_lane.compare_lane(got, want, occ)
            m, g = m + mi, max(g, gi)
        return {"int_mismatch": m, "float_rel_gap": g}

    emit("sound", job.seed, {"lern_" + k: v for k, v in lern.items()})
    for i in range(seeds):
        seed = common.derive_seed(job.seed, 1000 + i)
        ref, p = reference(seed, deadline=deadline)
        rs = exp.run(exp.ExperimentSpec.grid(config=name, mix=mix,
                                             policy=lanes, params=p),
                     plan=job.plan)
        got = {n: rs.filter(policy=n).one()["result"] for n in lanes}
        emit("sound", seed, worst(
            (as_lane(got[n]), ref[n]) for n in lanes))
    for fname, kw in SWEEP_FAULTS.items():
        fault = sweep_lane.Fault(**kw)
        for i in range(controls):
            seed = common.derive_seed(job.seed, 500_000 + i)
            ref, _ = reference(seed, deadline=deadline)
            ctl, _ = reference(seed, fault)
            emit("control:" + fname, seed,
                 worst((ctl[n], ref[n]) for n in lanes))


READINGS = {"lern": lern_readings, "sweep": sweep_readings}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)
    from chipbench import run
    run._environment()
    c = common.cell(args.workload)
    run.device_info(c["workload"]["chips"])
    job = common.load_module("jobs", c["traffic"]["job"] + ".py").Job(
        c["config"], c["traffic"], args.seed)
    job.setup()
    out = {"sound": {}}

    def emit(kind, seed, numbers, **extra):
        print(json.dumps({"kind": kind, "seed": seed, **numbers, **extra}),
              flush=True)
        agg = out.setdefault(kind, {})
        for k, v in numbers.items():
            agg[k] = (max if kind == "sound" else min)(agg.get(k, v), v)

    READINGS[c["traffic"]["job"]](job, args.seeds, args.controls, emit)
    print(json.dumps({"largest_sound": out["sound"],
                      "smallest_control": {k: v for k, v in out.items()
                                           if k != "sound"}}), flush=True)
    return out


if __name__ == "__main__":
    main()
