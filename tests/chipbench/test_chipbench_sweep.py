"""The sweep cell's yardstick, checked on the CPU at the ``smoke`` preset:
the reference lane against the simulator's bucketed and host engines, the
three controls against their limits, the reference's independence, the
readers of the ``fused.*`` spans and the callback waits on a synthetic and
on a recorded chip trace, and a run whose answers are altered where they
are produced."""
import ast
import gzip
import io
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import common, fused_spans, trace_reduce  # noqa: E402
from chipbench.reference import sweep_lane  # noqa: E402

CELL = "sweep.config1.moti1"
# every cell whose traffic drives the sweep job
SWEEP_CELLS = [w["name"] for w in common.benchmark()["workloads"]
               if common.cell(w["name"])["traffic"]["job"] == "sweep"]
SMOKE = {"n_inputs": 1, "max_epochs": 60, "subsample_target": 50_000}
SEED = 2 ** 33 + 21
READERS = ("fused.stage_ms", "fused.dispatch_ms", "fused.device_wait_ms",
           "fused.writeback_ms", "device.idle_share.sweep",
           "device.callback_wait_share.sweep")


@pytest.fixture(scope="module", params=SWEEP_CELLS)
def job(request):
    """The cell's job at the smoke preset, with its reference inputs."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    c = common.cell(request.param)
    c["config"]["params"].update(SMOKE)
    c["traffic"]["stream_seeds"] = 1
    mod = common.load_module("jobs", "sweep.py")
    j = mod.Job(c["config"], c["traffic"], SEED)
    j.prepare()
    j.inputs = j.reference_inputs({0})
    j.as_lane = mod.as_lane
    j.occupancy = bool(c["config"]["params"]["record_occupancy"])
    return j


@pytest.fixture(scope="module")
def reference(job):
    trace, model, _, streams = job.inputs
    soc, cores = job.soc_and_cores()
    return sweep_lane.run_group(soc, list(job.traffic["lanes"]), trace,
                                cores, streams[0], job.seeds[0], model)


@pytest.mark.parametrize("engine", ["bucketed", "host"])
def test_the_reference_matches_the_simulator(job, reference, engine):
    from repro import exp
    lanes = list(job.traffic["lanes"])
    rs = exp.run(exp.ExperimentSpec.grid(
        config=job.config["name"], mix=job.traffic["mix"], policy=lanes,
        params=job.params[0]),
        plan=exp.ExecPlan(engine=engine, cache=False))
    lim = job.traffic["limits"]
    for name in lanes:
        got = rs.filter(policy=name).one()["result"]
        mism, gap = sweep_lane.compare_lane(job.as_lane(got),
                                            reference[name], job.occupancy)
        assert mism == 0 and gap <= lim["float_rel_gap"], (name, mism, gap)
        assert got.epochs == reference[name]["epochs"] > 0
        assert job.as_lane(got)["requests"] == reference[name]["requests"]
        # a cell that records occupancy compares it, epoch by epoch
        assert len(job.as_lane(got)["occupancy"]) == (
            got.epochs if job.occupancy else 0)
    _, _, lern, _ = job.inputs
    for k, v in lern.items():
        assert v <= lim["lern_" + k], (k, v)


@pytest.mark.parametrize("fault", [
    dict(timing_dtype=np.float32), dict(dead_max=1), dict(fifo=True)],
    ids=["float32_timing", "ship_threshold_off_by_one", "fifo_replacement"])
def test_each_control_reads_over_its_limit(job, reference, fault):
    trace, model, _, streams = job.inputs
    soc, cores = job.soc_and_cores()
    ctl = sweep_lane.run_group(soc, list(job.traffic["lanes"]), trace,
                               cores, streams[0], job.seeds[0], model,
                               fault=sweep_lane.Fault(**fault))
    lim = job.traffic["limits"]
    mism = gap = 0
    for name, want in reference.items():
        m, g = sweep_lane.compare_lane(ctl[name], want, job.occupancy)
        mism, gap = mism + m, max(gap, g)
    assert mism > lim["int_mismatch"] or gap > lim["float_rel_gap"]


def test_the_reference_imports_nothing_of_the_simulator():
    path = os.path.join(common.HERE, "reference", "sweep_lane.py")
    tree = ast.parse(open(path).read())
    mods = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            mods.add((node.module or "").split(".")[0])
        elif isinstance(node, ast.Name):
            assert node.id not in ("__import__", "importlib"), node.id
    assert mods <= {"__future__", "dataclasses", "typing", "numpy"}, mods


def _synthetic():
    # window 0..100 ns; two groups; device ops with callback waits
    cb = ", is_host_transfer=true"
    ops = [["%fusion.1 = f32[8] fusion()", 10, 10],
           ["%recv-done.2 = (u32[3]) recv-done(token[] %r)" + cb, 20, 15],
           ["%send-done.1 = token[] send-done(token[] %s)" + cb, 30, 10],
           ["%recv-done.3 = (u32[3]) recv-done(token[] %r)" + cb, 60, 5],
           # a transfer between chips is no callback wait
           ["%recv-done.4 = (u32[3]) recv-done(token[] %r)", 40, 5],
           ["%fusion.2 = f32[8] fusion()", 80, 10]]
    host = [["chipbench.window", 0, 100],
            ["chipbench.iteration", 0, 50], ["chipbench.iteration", 50, 50],
            ["fused.stage", 1, 4], ["fused.dispatch", 5, 4],
            ["fused.device_wait", 10, 30], ["fused.writeback", 40, 5],
            ["fused.stage", 51, 2], ["fused.dispatch", 53, 4],
            ["fused.device_wait", 60, 10], ["fused.writeback", 70, 8]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops}],
            "host": host}


def _read(norm, metric):
    ctx = type("Ctx", (), {"trace": trace_reduce.Reduced(norm)})()
    return common.load_module("metrics", metric + ".py").read(ctx)


def test_the_readers_on_a_synthetic_trace():
    norm = _synthetic()
    want = {"fused.stage_ms": 3e-6, "fused.dispatch_ms": 4e-6,
            "fused.device_wait_ms": 20e-6, "fused.writeback_ms": 6.5e-6,
            # busy [10,45) [60,65) [80,90): 50 of 100 ns
            "device.idle_share.sweep": 50.0,
            # [20,40) and [60,65)
            "device.callback_wait_share.sweep": 25.0}
    for metric, v in want.items():
        assert _read(norm, metric) == pytest.approx(v, rel=1e-12), metric
    # no device plane: nothing to read; no fused span: nothing to read
    cpu = {"devices": [], "host": norm["host"]}
    assert all(_read(cpu, m) is None for m in READERS)
    bare = dict(norm, host=[h for h in norm["host"]
                            if not h[0].startswith("fused.")])
    assert _read(bare, "fused.stage_ms") is None
    # a phase missing where the others ran is an error
    gone = dict(norm, host=[h for h in norm["host"]
                            if h[0] != "fused.writeback"])
    with pytest.raises(ValueError):
        _read(gone, "fused.writeback_ms")


def test_the_readers_on_the_recorded_chip_trace():
    """One group of a traced run on a TPU v5 lite; device operations
    shorter than 0.5 ms are left out, except the callback waits, and each
    operation's text is cut to its head."""
    with gzip.open(os.path.join(common.HERE, "testdata",
                                "sweep_trace.json.gz"), "rt") as f:
        norm = json.load(f)
    red = trace_reduce.Reduced(norm)
    vals = {m: _read(norm, m) for m in READERS}
    assert all(v is not None and v >= 0 for v in vals.values()), vals
    assert vals["device.idle_share.sweep"] \
        + vals["device.callback_wait_share.sweep"] <= 100.0
    # the fused phases lie inside the groups, one after another, and
    # every group holds each of them
    its = sorted((s, s + d) for n, s, d in norm["host"]
                 if n == fused_spans.ITERATION)
    phases = sorted((s, s + d, n) for n, s, d in norm["host"]
                    if n in fused_spans.PHASES)
    for (_, e0, _), (s1, _, _) in zip(phases, phases[1:]):
        assert s1 >= e0
    for s, e in its:
        inside = {n for ps, pe, n in phases if s <= ps and pe <= e}
        assert inside == set(fused_spans.PHASES)
    assert all(any(s <= ps and pe <= e for s, e in its)
               for ps, pe, _ in phases)
    assert 0 < red.busy_s <= red.window_s


def _altered(monkeypatch, alter):
    """``exp.run`` with one answer altered where it is produced."""
    from repro import exp
    real = exp.run

    def run(spec, plan=None, **kw):
        rs = real(spec, plan, **kw)
        alter(rs.filter(policy="hydra").one()["result"])
        return rs
    monkeypatch.setattr(exp, "run", run)


def _bump_amal(res):
    res.history["amal"][2] *= 1.0 + 1e-6


def _cut_short(res):
    res.epochs -= 1
    for v in res.history.values():
        v.pop()


@pytest.mark.parametrize("alter", [_bump_amal, _cut_short],
                         ids=["timing_altered", "lane_cut_short"])
def test_a_run_with_altered_answers_is_not_correct(env, monkeypatch, alter):
    from chipbench import run
    _altered(monkeypatch, alter)
    out, err = io.StringIO(), io.StringIO()
    line = run.run_cell(CELL, SEED, 0.05, trace=False, require_tpu=False,
                        overrides={"params": SMOKE,
                                   "traffic": {"stream_seeds": 1}},
                        out=out, err=err)
    assert line["correct"] is False
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert "sim_accesses_per_s" in line["metrics"]
