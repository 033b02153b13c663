#!/usr/bin/env python3
"""Bring-up smoke run of the HyDRA simulator on a TPU.

Drives the simulator's main path once through its public entry points and
checks what it produced, phase by phase:

  lern    config1's LERN model trained on the device (flat feature
          extraction through the ``ri_histogram`` kernel, segmented k-means
          through ``kmeans_assign_segmented``); its label tables must match
          the digest of the same tables trained on CPU from the same seed,
          and the fit's lowered program must hold the Mosaic kernel.
  sweep   ``exp.run`` of the paper's Fig. 10a cross-product (config1 x
          {moti1, mix3} x 12 policies) at the ``full`` params preset: every
          point computed on the bucketed device engine, with no degrade,
          retry or fallback event in the run report.
  oracle  one group (config1, moti1; fifo-nb, arp-al, hydra) at
          max_epochs=200: the bucketed engine against the host
          ``drive_lane`` oracle — integer stats bitwise, floats within
          rtol 1e-6; a mismatch names the first diverging epoch and lane.
  serve   ``serve.run`` of a kv-online Poisson trace with >= 1,000 peak
          concurrent sessions on the batched engine and on the host
          oracle — every counter and histogram bitwise equal.

Usage (from the root of a checkout; no install, no network):

    python chip_smoke.py             # one chip: the four phases above
    python chip_smoke.py --chips 4   # only the sharded bucket path

``--chips 4`` runs one bucket of four groups (config1..config4 x moti1, the
Fig. 12 configs; every mix has its own core slot layout and so its own
bucket) through ``ExecPlan(devices=4)`` and again with ``devices=1``, and
requires every group bitwise equal; it prints the device each group ran on.

Every run starts from an empty artifact cache (``.cache/chip_smoke``):
traces, LERN models, deadline calibrations and results all come from seeds
in this run.  The XLA compile cache stays where the package puts it
(``JAX_COMPILATION_CACHE_DIR`` if set, else ``.cache/xla``).  Each phase
prints one JSON line with its wall time, the part of it spent tracing and
compiling, and peak device memory.  The last line of stdout is
``{"ok": true, "device": {...}}``.  Without a TPU the script exits with
code 2 and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import hashlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SMOKE_CACHE = os.path.join(ROOT, ".cache", "chip_smoke")

SWEEP_MIXES = ("moti1", "mix3")
ORACLE_POLICIES = ("fifo-nb", "arp-al", "hydra")
ORACLE_EPOCHS = 200
SHARD_CONFIGS = ("config1", "config2", "config3", "config4")
SERVE_SESSIONS = 2400
SERVE_RATE = 8.0
MIN_PEAK_CONCURRENT = 1000
FLOAT_RTOL = 1e-6
BAD_EVENTS = ("degrade", "task_retry", "inline_fallback", "serve_degrade")
# sha256 of config1's LERN label tables (lrpt variant "full", full-preset
# subsample, seed 0, default segmented fit) as trained on CPU.
LERN_DIGEST = "0f513d5fce5120b84e505d65610328a4878270dfadbe55c519a885a0aa21a8dc"


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching from
    the persistent cache), backend compiles and persistent-cache hits, from
    JAX's own monitoring events.

    A nested jit's trace is reported inside its parent's, so the durations
    overlap: the clock measures the union of the reported intervals, which
    never exceeds the wall time it is read over."""
    DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self._spans = []              # (start, end) on time.perf_counter()
        self.compiles = 0             # backend compiles, cache fetches included
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        # JAX reports a duration when the timed block ends
        if event in self.DURATIONS:
            end = time.perf_counter()
            self._spans.append((end - duration, end))
            self.compiles += event == self.DURATIONS[2]

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def seconds(self, since: float) -> float:
        """Compile-side seconds after ``since`` (a perf_counter reading)."""
        total, cur = 0.0, None
        for s, e in sorted((max(s, since), e) for s, e in self._spans
                           if e > since):
            if cur is not None and s <= cur[1]:
                cur[1] = max(cur[1], e)
                continue
            if cur is not None:
                total += cur[1] - cur[0]
            cur = [s, e]
        return total + (cur[1] - cur[0] if cur is not None else 0.0)

    def mark(self):
        return time.perf_counter(), self.compiles, self.cache_hits

    def since(self, mark) -> dict:
        t0, n0, h0 = mark
        return {"wall_s": time.perf_counter() - t0,
                "compile_s": self.seconds(t0),
                "backend_compiles": self.compiles - n0,
                "cache_hits": self.cache_hits - h0}


def _peak_device_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _run_phase(name, fn, clock, **kw) -> bool:
    """Run one phase and print its line (timings, or the error); return
    whether it passed.  A failed phase does not stop the later ones."""
    mark = clock.mark()
    try:
        out, err = fn(**kw), None
    except Exception as e:  # reported, and the run fails at the end
        out, err = {}, f"{type(e).__name__}: {e}"
    line = {"phase": name, "ok": err is None, **clock.since(mark),
            "peak_device_bytes": _peak_device_bytes()}
    line.update(out.get("timing", {}))
    if err is not None:
        line["error"] = err[:2000]
    print(json.dumps(line), flush=True)
    return err is None


def _params(preset="full", **over):
    from repro import exp
    return dataclasses.replace(exp.PARAMS.get(preset), **over)


# ---------------------------------------------------------------------------
# phases (importable; each raises AssertionError on a wrong result)
# ---------------------------------------------------------------------------
def label_digest(model) -> str:
    """sha256 over a LernModel's integer label tables (per layer: the real
    unique lines and their RC/RI cluster labels)."""
    import numpy as np
    h = hashlib.sha256()
    h.update(np.asarray(model.n_uniq, np.int64).tobytes())
    for li, n in enumerate(np.asarray(model.n_uniq)):
        for tab in (model.uniq, model.rc_cluster, model.ri_cluster):
            h.update(np.asarray(tab[li, :n], np.int64).tobytes())
    return h.hexdigest()


def lern_phase(params, expect_digest=None, require_kernel=True):
    """Train config1's LERN model (the sweep's own ``sim.load_lern`` call)
    and check its label tables against ``expect_digest``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import kmeans as km
    from repro.core import sim

    t0 = time.perf_counter()
    sim.load_trace("config1", params.subsample_target)
    setup = time.perf_counter() - t0
    t0 = time.perf_counter()
    model = sim.load_lern("config1", "full", params.subsample_target)
    train = time.perf_counter() - t0
    digest = label_digest(model)
    if expect_digest is not None:
        assert digest == expect_digest, \
            f"LERN label digest {digest} != CPU-trained {expect_digest}"
    use_kernel = km._default_use_kernel()
    if require_kernel:
        assert use_kernel, "k-means assignment is not on the Pallas kernel"
        x = jax.ShapeDtypeStruct((2048, 4), jnp.float32)
        seg = jax.ShapeDtypeStruct((2048,), jnp.int32)
        c0 = jax.ShapeDtypeStruct((2, 4, 4), jnp.float32)
        hlo = km._lloyd_segmented.lower(x, seg, c0, n_seg=2, k=4, iters=6,
                                        use_kernel=use_kernel).as_text()
        assert "tpu_custom_call" in hlo, "segmented fit lowered no kernel"
    n_labeled = int(np.sum(np.asarray(model.rc_cluster) >= 0))
    return {"digest": digest,
            "timing": {"setup_s": setup, "train_s": train,
                       "labeled_lines": n_labeled}}


def sweep_phase(params, mixes=SWEEP_MIXES):
    """Fig. 10a through ``exp.run(spec, plan=ExecPlan())``."""
    from benchmarks.fig10_policies import POLICIES_10A
    from repro import exp
    from repro.core import fused, sim
    from repro.core.dram import default_model

    t0 = time.perf_counter()
    for mix in mixes:
        sim.load_artifacts("config1", mix, params, True)
    sim.calibrated_deadline("config1", params, default_model())
    setup = time.perf_counter() - t0
    spec = exp.ExperimentSpec.grid(config="config1", mix=list(mixes),
                                   policy=POLICIES_10A, params=params)
    fused.reset_phase_times()
    t0 = time.perf_counter()
    rs = exp.run(spec, plan=exp.ExecPlan())
    run = time.perf_counter() - t0
    rep = rs.run_report
    n_want = len(mixes) * len(POLICIES_10A)
    assert len(rep.points) == n_want, (len(rep.points), n_want)
    for key, rec in rep.points.items():
        assert rec["source"] == "computed" and rec["engine"] == "bucketed", \
            (key, rec)
    bad = [e for e in rep.events if e["kind"] in BAD_EVENTS]
    assert not bad, f"the sweep degraded or retried: {bad[:3]}"
    epochs = sum(int(r) for r in rs.column("epochs"))
    timing = {"setup_s": setup, "run_s": run, "points": n_want,
              "sim_epochs": epochs}
    timing.update(fused.phase_times())
    return {"rs": rs, "timing": timing}


_INT_HISTORY = ("accel_rate", "ri_th", "rc_th")


def _same(k, a, b) -> bool:
    """Integer-derived values bitwise, floats within FLOAT_RTOL."""
    import numpy as np
    if k in _INT_HISTORY or k == "occupancy":
        return a == b
    return bool(np.allclose(a, b, rtol=FLOAT_RTOL, atol=0))


def compare_results(got, want, lane: str):
    """Integer stats bitwise, floats within FLOAT_RTOL; the error names
    the lane, the first diverging epoch and a field diverging there (run
    totals are compared after the per-epoch records)."""
    import numpy as np
    per_epoch = {k: (list(got.history.get(k, [])), list(v))
                 for k, v in want.history.items()}
    per_epoch["occupancy"] = ([tuple(o) for o in got.occupancy],
                              [tuple(o) for o in want.occupancy])
    n = max(len(b) for _, b in per_epoch.values())
    for e in range(n):
        for k, (a, b) in per_epoch.items():
            if e >= len(a) or e >= len(b):
                assert len(a) == len(b), (
                    f"lane {lane}: {k} has {len(a)} entries on the device, "
                    f"{len(b)} on the host (first missing epoch {e})")
                continue
            assert _same(k, a[e], b[e]), (
                f"lane {lane}: {k} diverges at epoch {e}: "
                f"device {a[e]!r} vs host {b[e]!r}")
    totals = {"epochs": (got.epochs, want.epochs),
              "llc_accesses": (got.llc_accesses, want.llc_accesses),
              "dram_accesses": (got.dram_accesses, want.dram_accesses),
              "core_br": (got.core_br, want.core_br),
              "accel_br": (got.accel_br, want.accel_br),
              "core_hit_rate": (got.core_hit_rate, want.core_hit_rate),
              "accel_hit_rate": (got.accel_hit_rate, want.accel_hit_rate)}
    for k, (a, b) in totals.items():
        assert a == b, f"lane {lane}: total {k}: device {a!r} vs host {b!r}"
    floats = {"ipc": (got.ipc_total, want.ipc_total),
              "dmr": (got.dmr, want.dmr),
              "completion_cycles": (list(got.completion_cycles),
                                    list(want.completion_cycles))}
    for k, (a, b) in floats.items():
        assert np.shape(a) == np.shape(b) and _same(k, a, b), \
            f"lane {lane}: total {k}: device {a!r} vs host {b!r}"


def oracle_phase(params, mix="moti1"):
    """Bucketed engine vs the host oracle on one group, same process."""
    from repro import exp
    from repro.core import sweep

    spec = exp.ExperimentSpec.grid(config="config1", mix=mix,
                                   policy=list(ORACLE_POLICIES),
                                   params=params)
    t0 = time.perf_counter()
    rs = exp.run(spec, plan=exp.ExecPlan(engine="bucketed", cache=False))
    device = time.perf_counter() - t0
    engines = {rec["engine"] for rec in rs.run_report.points.values()}
    assert engines == {"bucketed"}, engines
    pols = [exp.POLICIES.get(p) for p in ORACLE_POLICIES]
    t0 = time.perf_counter()
    host = sweep.simulate_group("config1", mix, pols, params, engine="host")
    host_s = time.perf_counter() - t0
    for name, want in zip(ORACLE_POLICIES, host):
        got = rs.filter(policy=name).one()["result"]
        compare_results(got, want, name)
    return {"timing": {"bucketed_s": device, "host_s": host_s,
                       "sim_epochs": sum(r.epochs for r in host)}}


def serve_phase(sessions=SERVE_SESSIONS):
    """kv-online Poisson replay: batched engine == host oracle, bitwise."""
    import numpy as np
    from repro import serve
    from repro.exp import ExecPlan

    spec = serve.ServeSpec(
        trace=serve.TraceSpec(sessions=sessions, arrival="poisson",
                              rate=SERVE_RATE,
                              drift=serve.MixDrift(period=4, strength=0.5),
                              seed=0),
        knobs="kv-online", slots=128, max_steps=4096)
    out = {}
    timing = {}
    for engine in ("batched", "host"):
        t0 = time.perf_counter()
        rs = serve.run(spec, plan=ExecPlan(
            engine="host" if engine == "host" else "bucketed", cache=False))
        timing[f"{engine}_s"] = time.perf_counter() - t0
        row = rs.one()
        assert row["engine"] == engine, (engine, row["engine"])
        bad = [e for e in rs.run_report.events if e["kind"] in BAD_EVENTS]
        assert not bad, f"serve degraded: {bad[:3]}"
        out[engine] = row
    a, b = out["batched"]["result"], out["host"]["result"]
    assert a.counters == b.counters, "serve counters differ from the host"
    assert np.array_equal(a.wait_hist, b.wait_hist), "wait histogram differs"
    assert np.array_equal(a.lat_hist, b.lat_hist), "latency histogram differs"
    peak = out["batched"]["peak_concurrent"]
    assert peak >= MIN_PEAK_CONCURRENT, f"peak concurrency {peak}"
    timing.update(peak_concurrent=peak, steps=out["batched"]["steps"],
                  refits=out["batched"]["refits"])
    return {"timing": timing}


def shard_phase(params, n_devices, clock=None):
    """One bucket of four groups sharded over ``n_devices`` chips against
    the same bucket on one device: every group bitwise equal.  With a
    ``clock`` it prints one progress line after the set-up and after each
    leg."""
    from repro import exp
    from repro.core import fused, sim
    from repro.core.dram import default_model

    def progress(step, mark):
        if clock is not None:
            print(json.dumps({"shard_step": step, **clock.since(mark)}),
                  flush=True)

    mark = clock.mark() if clock else None
    t0 = time.perf_counter()
    for config in SHARD_CONFIGS:
        sim.load_artifacts(config, "moti1", params, True)
        sim.calibrated_deadline(config, params, default_model())
    sim.load_lern_family(list(SHARD_CONFIGS), "full",
                         params.subsample_target, family_only=True)
    timing = {"setup_s": time.perf_counter() - t0}
    progress("setup", mark)
    spec = exp.ExperimentSpec.grid(config=list(SHARD_CONFIGS), mix="moti1",
                                   policy=list(ORACLE_POLICIES),
                                   params=params)
    placement = {}
    orig = fused._superstep_bucket

    def spy(dims, n_shards, *args):
        carry, ys = orig(dims, n_shards, *args)
        if n_shards > 1 and not placement:
            leaf = carry.epoch
            for shard in leaf.addressable_shards:
                for g in range(*shard.index[0].indices(leaf.shape[0])):
                    placement[g] = str(shard.device)
        return carry, ys

    runs = {}
    fused._superstep_bucket = spy
    try:
        for dev in (n_devices, 1):
            mark = clock.mark() if clock else None
            t0 = time.perf_counter()
            runs[dev] = exp.run(spec, plan=exp.ExecPlan(
                engine="bucketed", devices=dev, cache=False))
            timing[f"devices{dev}_s"] = time.perf_counter() - t0
            progress(f"devices{dev}", mark)
    finally:
        fused._superstep_bucket = orig
    assert len(placement) == len(SHARD_CONFIGS), \
        f"the bucket did not run sharded: {placement}"
    for g, config in enumerate(SHARD_CONFIGS):
        print(json.dumps({"group": g, "config": config,
                          "device": placement[g]}), flush=True)
    assert len(set(placement.values())) == n_devices, placement
    for config in SHARD_CONFIGS:
        for pol in ORACLE_POLICIES:
            a = runs[n_devices].filter(config=config, policy=pol).one()
            b = runs[1].filter(config=config, policy=pol).one()
            ra, rb = a["result"], b["result"]
            assert (ra.summary(), ra.epochs, ra.completion_cycles,
                    ra.history, ra.occupancy, ra.llc_accesses,
                    ra.dram_accesses) == \
                (rb.summary(), rb.epochs, rb.completion_cycles, rb.history,
                 rb.occupancy, rb.llc_accesses, rb.dram_accesses), \
                f"{config}/{pol}: sharded != one device"
    return {"timing": timing}


# ---------------------------------------------------------------------------
def _fresh_cache() -> None:
    shutil.rmtree(SMOKE_CACHE, ignore_errors=True)
    os.makedirs(SMOKE_CACHE)
    os.environ["REPRO_CACHE"] = SMOKE_CACHE


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the sharded bucket path, on 4 chips")
    args = ap.parse_args(argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    _fresh_cache()                       # before repro reads REPRO_CACHE
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.core import sim  # noqa: F401  (places the compile cache)
    from repro.kernels import common
    if common.INTERPRET is not False:
        print("chip_smoke: Pallas kernels would run in interpret mode",
              file=sys.stderr)
        return 2

    clock = CompileClock()
    kind = devices[0].device_kind
    print(json.dumps({"platform": devices[0].platform, "kind": kind,
                      "count": len(devices), "jax": jax.__version__,
                      "compile_cache_dir":
                          jax.config.jax_compilation_cache_dir}),
          flush=True)
    if args.chips == 4:
        ok = _run_phase("shard", functools.partial(shard_phase, clock=clock),
                        clock, params=_params(max_epochs=ORACLE_EPOCHS),
                        n_devices=4)
    else:
        full = _params()
        ok = all([
            _run_phase("lern", lern_phase, clock, params=full,
                       expect_digest=LERN_DIGEST),
            _run_phase("sweep", sweep_phase, clock, params=full),
            _run_phase("oracle", oracle_phase, clock,
                       params=_params(max_epochs=ORACLE_EPOCHS)),
            _run_phase("serve", serve_phase, clock)])
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
