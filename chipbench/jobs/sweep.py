"""Job kind ``sweep``: the paper's policy comparison for one SoC and one
core mix, one group after another, through the simulator's experiment API.

One iteration is one ``exp.run`` of one group (the configuration x the
traffic's core mix x its policy lanes) on the bucketed epoch engine with
no result cache, each lane from an empty LLC: the staging of the group,
the fused epoch loop and its LLC round loop on the device, and the float64
timing update in host callbacks.  ``SimParams.seed`` (the core streams and
write flags) cycles through a pool drawn from the run's seed; set-up runs
every seed of the pool once, so nothing compiles inside the window.

The end-to-end metric is the LLC requests the window's groups simulated
(core and accelerator, bypassed ones included, summed over lanes) per
second of window.  A group fails when any of its points ran on another
engine than ``bucketed`` or its run report holds a degrade or retry event.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import common
from chipbench.common import Checks, derive_seed
from chipbench.reference import sweep_lane

E2E = "sim_accesses_per_s"
# run-report events of a degraded or retried point (as ``chip_smoke.py``)
BAD_EVENTS = ("degrade", "task_retry", "inline_fallback", "serve_degrade")
LERN_CHECKS = ("feature_mismatch", "label_gap", "order_inversions",
               "center_rel_gap")


def requests(res) -> int:
    """LLC requests one lane simulated, from its result: the accelerator's
    admitted accesses (per-epoch ``accel_rate``) plus the cores' accesses,
    which the core miss rate gives from the core misses (DRAM fills less
    the accelerator's misses; the lanes prefetch nothing)."""
    acc = int(round(sum(res.history["accel_rate"])))
    accel_miss = acc - int(round(res.accel_hit_rate * acc))
    core_miss = int(round(res.dram_accesses)) - accel_miss
    if core_miss <= 0 or res.core_hit_rate >= 1.0:
        raise ValueError(f"{res.policy}: no core miss to count from")
    return acc + int(round(core_miss / (1.0 - res.core_hit_rate)))


def as_lane(res) -> dict:
    """A simulator result in the reference's form."""
    out = {k: getattr(res, k) for k in sweep_lane.FLOATS + (
        "epochs", "completion_cycles", "history", "dram_accesses")}
    out["requests"] = requests(res)
    out["occupancy"] = [tuple(o) for o in res.occupancy]
    return out


def check_traffic(traffic: dict) -> None:
    """The simulator must run the core mix and profiles the file states."""
    from repro.core import cores
    if list(cores.MIXES[traffic["mix"]]) != traffic["cores"]:
        raise ValueError(f"{traffic['mix']}: the simulator's mix differs "
                         "from the file's cores")
    for name, want in traffic["core_profiles"].items():
        pr = cores.PROFILES[name]
        for k, v in want.items():
            if getattr(pr, k) != v:
                raise ValueError(f"{name}.{k}: the simulator has "
                                 f"{getattr(pr, k)!r}, the file {v!r}")


class Job:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.kept = {}           # iteration -> (pool index, results)
        self.requests = 0
        self.attempted = 0
        self.failed = 0

    # -- set-up -----------------------------------------------------------
    def prepare(self) -> None:
        """Check the files against the simulator; build the seed pool's
        specs."""
        from repro import exp
        from repro.core import sim
        common.load_module("jobs", "lern.py").check_config(self.config)
        check_traffic(self.traffic)
        base = sim.SimParams(**self.config["params"])
        self.seeds = [derive_seed(self.seed, j)
                      for j in range(self.traffic["stream_seeds"])]
        self.params = [dataclasses.replace(base, seed=s) for s in self.seeds]
        self.specs = [exp.ExperimentSpec.grid(
            config=self.config["name"], mix=self.traffic["mix"],
            policy=list(self.traffic["lanes"]), params=p)
            for p in self.params]
        self.plan = exp.ExecPlan(engine="bucketed", cache=False)

    def setup(self) -> None:
        from repro import exp
        self.prepare()
        for spec in self.specs:
            exp.run(spec, plan=self.plan)

    # -- window -----------------------------------------------------------
    def iteration(self, i: int) -> None:
        from repro import exp
        j = i % len(self.specs)
        rs = exp.run(self.specs[j], plan=self.plan)
        rep = rs.run_report
        self.attempted += 1
        if any(rec["engine"] != "bucketed" for rec in rep.points.values()) \
                or any(e["kind"] in BAD_EVENTS for e in rep.events):
            self.failed += 1
        results = {name: rs.filter(policy=name).one()["result"]
                   for name in self.traffic["lanes"]}
        self.requests += sum(requests(r) for r in results.values())
        if i == 0 or derive_seed(self.seed, i, 16) % \
                self.traffic["check_one_in"] == 0:
            self.kept[i] = (j, results)

    def metrics(self, window_s: float) -> dict:
        return {E2E: (self.requests / window_s, "accesses/s")}

    # -- the reference ----------------------------------------------------
    def reference_inputs(self, pool=()):
        """The inputs both sides take as data: the accelerator trace, the
        LERN model with its readings against ``LernReference``, and the
        core streams of each pool index in ``pool``."""
        from repro.core import sim
        from chipbench.reference.compare import LernReference
        name = self.config["name"]
        sub = self.config["params"]["subsample_target"]
        tr = sim.load_trace(name, sub)
        model = sim.load_lern(name, self.traffic["lrpt_variant"], sub)
        lern = LernReference(np.asarray(tr.line, np.int64),
                             np.asarray(tr.layer),
                             max(len(tr.layer_names), 1)).compare(model)
        trace = {"line": tr.line, "write": tr.write, "layer": tr.layer}
        streams = {j: sim.load_artifacts(name, self.traffic["mix"],
                                         self.params[j]).streams
                   for j in pool}
        return trace, model, lern, streams

    def soc_and_cores(self):
        prof = self.traffic["core_profiles"]
        cores = [sweep_lane.Core(**prof[c]) for c in self.traffic["cores"]]
        return sweep_lane.Soc.from_config(self.config), cores

    # -- correctness ------------------------------------------------------
    def check(self) -> Checks:
        trace, model, lern, streams = self.reference_inputs(
            {j for j, _ in self.kept.values()})
        soc, cores = self.soc_and_cores()
        deadline = sweep_lane.standalone_deadline(soc, trace)
        occupancy = bool(self.config["params"]["record_occupancy"])
        refs = {j: sweep_lane.run_group(
            soc, list(self.traffic["lanes"]), trace, cores, streams[j],
            self.seeds[j], model, deadline) for j in streams}
        mism, gap = 0, 0.0
        for j, results in self.kept.values():
            for name, res in results.items():
                m, g = sweep_lane.compare_lane(as_lane(res), refs[j][name],
                                               occupancy)
                mism, gap = mism + m, max(gap, g)
        lim = self.traffic["limits"]
        checks = Checks()
        checks.add("groups_unchecked", 0 if self.kept else 1, 0)
        checks.add("int_mismatch", mism, lim["int_mismatch"])
        checks.add("float_rel_gap", gap, lim["float_rel_gap"])
        for k in LERN_CHECKS:
            checks.add("lern_" + k, lern[k], lim["lern_" + k])
        return checks
