"""Job kind ``lern``: train the configuration's LERN reuse predictor again
and again, cycling through a pool of k-means seeds drawn from the run's
seed.

One iteration is one ``lern.train_model_batched`` of the configuration's
sampled accelerator trace: feature extraction through the ``ri_histogram``
kernel, then the segmented k-means through ``kmeans_assign_segmented``.
The fit gives the segments still moving after its first sweeps a program
of their own shape, which depends on the seed; set-up trains every seed of
the pool once, so nothing compiles inside the window.  The end-to-end
metric is sampled trace accesses put through a complete training per
second of window.
"""
from __future__ import annotations

import numpy as np

from chipbench.common import Checks, derive_seed

E2E = "lern_accesses_per_s"


def check_config(config: dict) -> None:
    """The simulator must run the deployment the file states: its
    accelerator entry and its scale cuts."""
    from repro.core.llc import HW_SCALE
    from repro.core.workloads import CONFIGS, SIM_SCALE
    acc = CONFIGS[config["name"]]
    found = {k: getattr(acc, k) for k in config["accelerator"]}
    found.update(ifmap_hw_divisor=SIM_SCALE,
                 llc_bytes=config["source_values"]["llc_bytes"] // HW_SCALE)
    want = dict(config["accelerator"],
                ifmap_hw_divisor=config["ifmap_hw_divisor"],
                llc_bytes=config["llc_bytes"])
    for k, v in want.items():
        if found[k] != v:
            raise ValueError(f"{config['name']}.{k}: the simulator has "
                             f"{found[k]!r}, the file {v!r}")


class Job:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.kept = {}           # iteration -> model, sampled from the seed
        self.accesses = 0
        self.attempted = 0
        self._ref = None

    # -- set-up -----------------------------------------------------------
    def setup(self) -> None:
        from repro.core import sim
        from repro.core.lrpt import lrpt_train_hash
        check_config(self.config)
        self.trace = sim.load_trace(self.config["name"],
                                    self.config["params"]["subsample_target"])
        self.hash_fn = lrpt_train_hash(self.traffic["lrpt_variant"])
        self.seeds = [derive_seed(self.seed, j)
                      for j in range(self.traffic["kmeans_seeds"])]
        for s in self.seeds:
            self._train(s)

    def _train(self, seed: int):
        from repro.core import lern
        return lern.train_model_batched(self.trace, hash_fn=self.hash_fn,
                                        seed=seed)

    # -- window -----------------------------------------------------------
    def iteration(self, i: int) -> None:
        import jax
        with jax.profiler.TraceAnnotation("chipbench.train"):
            model = self._train(self.seeds[i % len(self.seeds)])
        self.attempted += 1
        self.accesses += self.trace.num_accesses
        if i == 0 or derive_seed(self.seed, i, 16) % \
                self.traffic["check_one_in"] == 0:
            self.kept[i] = model

    def metrics(self, window_s: float) -> dict:
        return {E2E: (self.accesses / window_s, "accesses/s")}

    @property
    def failed(self) -> int:
        return 0

    # -- the reference ----------------------------------------------------
    def reference(self):
        """The benchmark's own features of the trace, built once."""
        if self._ref is None:
            from chipbench.reference.compare import LernReference
            lines = np.asarray(self.trace.line, np.int64)
            if self.hash_fn is not None:
                lines = self.hash_fn(lines)
            self._ref = LernReference(lines, np.asarray(self.trace.layer),
                                      max(len(self.trace.layer_names), 1))
        return self._ref

    def kernel_work(self) -> dict:
        """The algorithm's work in one kernel call, from the reference's
        features of the trace: the reuse intervals one feature extraction
        bins, and the points and k-means problems of one assignment over
        every point (each eligible layer's multi-occurrence lines, once
        for its RC fit and once for its RI fit)."""
        ref = self.reference()
        intervals = sum(int(f_ri.sum()) for _, f_ri, _ in ref.layers)
        multi = [int((count > 1).sum()) for _, _, count in ref.layers]
        elig = [m for m in multi if m >= ref.MIN_MULTI]
        return {"ri_intervals_per_call": intervals,
                "kmeans_points_per_call": 2 * sum(elig),
                "kmeans_problems_per_call": 2 * len(elig)}

    # -- correctness ------------------------------------------------------
    def check(self) -> Checks:
        ref = self.reference()
        worst = {"feature_mismatch": 0, "label_gap": 0.0,
                 "order_inversions": 0, "center_rel_gap": 0.0}
        for model in self.kept.values():
            for k, v in ref.compare(model).items():
                worst[k] = max(worst[k], v)
        lim = self.traffic["limits"]
        checks = Checks()
        checks.add("trainings_unchecked", 0 if self.kept else 1, 0)
        for k in ("feature_mismatch", "label_gap", "order_inversions",
                  "center_rel_gap"):
            checks.add(k, worst[k], lim[k])
        return checks
