"""The on-chip benchmark's harness, checked on the CPU: every cell resolves
its files by name, ``BENCHMARK.json`` keeps its contract, the roofline
counts and the trace reduction give known numbers, a run's last line
carries only the agreed keys, and the measurement entry refuses a CPU."""
import gzip
import io
import json
import os
import re
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import common, roofline, trace_reduce  # noqa: E402

BENCH = common.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_benchmark_keys_and_names():
    assert sorted(BENCH) == sorted(["command", "paths", "run_seconds",
                                    "configs", "workloads", "end_to_end",
                                    "per_layer"])
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for c in BENCH["configs"]:
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        assert c["file"].startswith("chipbench/")
    for w in BENCH["workloads"]:
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_its_files(workload):
    c = common.cell(workload, BENCH)
    common.check_cuts(c["config"])
    assert os.path.exists(os.path.join(common.HERE, "jobs",
                                       c["traffic"]["job"] + ".py"))
    job = common.load_module("jobs", c["traffic"]["job"] + ".py")
    e2e = [m["name"] for m in common.metrics_for("end_to_end", workload, BENCH)]
    assert "setup_s" in e2e and len(e2e) >= 2 and job.E2E in e2e
    layer = common.metrics_for("per_layer", workload, BENCH)
    assert layer
    for m in layer:
        assert m["moves"] in e2e
        mod = common.load_module("metrics", m["name"] + ".py")
        assert callable(mod.read)


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_every_configuration_has_what_each_job_reads(env, config):
    """Each job kind's checks and its reference take this configuration's
    file as it stands, whichever cell names it next."""
    file = {c["name"]: c["file"] for c in BENCH["configs"]}[config]
    with open(os.path.join(ROOT, file)) as f:
        cfg = json.load(f)
    assert cfg["name"] == config
    common.check_cuts(cfg)
    for tname in sorted(os.listdir(os.path.join(common.HERE, "traffic"))):
        traffic = common.load_json("traffic", tname)
        mod = common.load_module("jobs", traffic["job"] + ".py")
        if traffic["job"] == "lern":
            mod.check_config(cfg)
            assert cfg["params"]["subsample_target"] > 0
        else:
            assert traffic["job"] == "sweep", traffic["job"]
            job = mod.Job(cfg, traffic, 1)
            job.prepare()
            soc, cores = job.soc_and_cores()
            assert soc.sets > 0 and len(cores) == cfg["cores"]
            assert isinstance(cfg["params"]["record_occupancy"], bool)


def test_roofline_counts_on_known_shapes():
    assert roofline.ri_histogram_work(1000) == (5000, 8000)
    # 10 points, K=4 centres of D=4: 4*4*3 + 3 = 51 operations per point
    ops, byts = roofline.kmeans_assign_work(10, 2)
    assert ops == 510 and byts == 10 * 20 + 2 * 64
    pk = roofline.peaks("TPU v5 lite")
    assert pk["hbm_bytes_per_s"] == 819e9 and pk["flops_per_s"] == 197e12
    share, bound = roofline.share(0, 819e9, 2.0, "TPU v5 lite")
    assert share == pytest.approx(50.0) and bound == "memory"
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def _synthetic():
    # window 0..100 ns; device ops [10,20), [15,30), [50,60); host spans
    return {"devices": [{"name": "/device:TPU:0",
                         "ops": [["%fusion.1 = s32[8] fusion(s32[8] %p)", 10, 10],
                                 ["%histogram.2 = s32[8] custom-call(s32[8] %fusion.1)", 15, 15],
                                 ["%fusion.1 = s32[8] fusion(s32[8] %p)", 50, 10],
                                 ["outside", 200, 5]]}],
            "host": [["chipbench.window", 0, 100],
                     ["chipbench.iteration", 1, 98],
                     ["callback", 30, 20]]}


def test_reduction_on_a_synthetic_trace():
    red = trace_reduce.Reduced(_synthetic())
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(30e-9)          # [10,30) + [50,60)
    assert red.idle_share == pytest.approx(0.7)
    calls = red.kernel_calls("histogram")
    assert len(calls) == 1 and calls[0][1] == pytest.approx(15e-9)
    assert calls[0][0].startswith("%histogram.2 = s32[8] custom-call")
    gaps = dict(red.idle_gaps())
    # [0,10) and [60,100) under the iteration; [30,50) in the callback
    assert gaps["callback"] == pytest.approx(20e-9)
    assert gaps["chipbench.iteration"] == pytest.approx(50e-9)
    ops = dict(red.top_ops())
    assert ops["fusion.1"] == pytest.approx(20e-9) and "outside" not in ops


def _merged_busy(ops, w0, w1):
    """Straightforward interval merge, the test's own."""
    iv = sorted((max(s, w0), min(s + d, w1)) for _, s, d in ops
                if s + d > w0 and s < w1)
    total, cur = 0, None
    for s, e in iv:
        if cur and s <= cur[1]:
            cur[1] = max(cur[1], e)
        else:
            if cur:
                total += cur[1] - cur[0]
            cur = [s, e]
    return total + (cur[1] - cur[0] if cur else 0)


def test_reduction_on_the_recorded_trace():
    path = os.path.join(common.HERE, "testdata", "lern_trace.json.gz")
    with gzip.open(path, "rt") as f:
        norm = json.load(f)
    red = trace_reduce.Reduced(norm)
    win = [h for h in norm["host"] if h[0] == trace_reduce.WINDOW][0]
    want = _merged_busy(norm["devices"][0]["ops"], win[1], win[1] + win[2])
    assert red.busy_s == pytest.approx(want / 1e9, rel=1e-9)
    assert 0.0 < red.idle_share < 1.0
    for pattern in ("histogram", "assign_segmented", "while"):
        calls = red.kernel_calls(pattern)
        assert calls and all(t > 0 for _, t in calls)
    bd = red.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    gap_total = sum(v for _, v in red.idle_gaps(n=1000, longest=10 ** 9))
    assert gap_total == pytest.approx(red.window_s - red.busy_s, rel=1e-6)


def test_the_measurement_entry_refuses_a_cpu(env, capsys):
    from chipbench import run
    rc = run.main(["--workload", "lern.config4", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 3 and out.out == "" and "no TPU" in out.err


def test_a_run_prints_only_the_agreed_keys(env):
    from chipbench import run
    out, err = io.StringIO(), io.StringIO()
    line = run.run_cell("lern.config4", 2 ** 33 + 7, 0.2, trace=False,
                        require_tpu=False,
                        overrides={"traffic": {"kmeans_seeds": 1}},
                        out=out, err=err)
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    assert last == json.loads(json.dumps(line))
    assert list(last) == RESULT_KEYS + ["checks"]
    assert sorted(last["metrics"]) == ["lern_accesses_per_s", "setup_s"]
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert last["correct"] is True and last["attempted"] >= 1
    names = list(last["checks"])
    assert err.getvalue().strip().splitlines()[-len(names):] == [
        f"check {k}: {v['value']!r} (limit {v['limit']!r})"
        for k, v in last["checks"].items()]


def test_a_traced_run_reads_its_layers(env):
    from chipbench import run
    out, err = io.StringIO(), io.StringIO()
    line = run.run_cell("lern.config4", 5, 0.2, trace=True,
                        require_tpu=False,
                        overrides={"traffic": {"kmeans_seeds": 1}},
                        out=out, err=err)
    assert list(line) == RESULT_KEYS + ["breakdown", "checks"]
    # no TPU plane on the CPU: the device readers find nothing to read
    assert set(line["metrics"]) == {"setup.compile_s",
                                    "setup.backend_compiles"}
    assert line["device"]["busy_s"] == 0.0 and line["device"]["window_s"] > 0


def _reader_ctx(ops, work):
    norm = {"devices": [{"name": "/device:TPU:0", "ops": ops}],
            "host": [["chipbench.window", 0, 10 ** 9]]}
    return SimpleNamespace(trace=trace_reduce.Reduced(norm),
                           work=lambda: work, device_kind="TPU v5 lite")


def _assign(n, rows, start, dur):
    return [f"%assign_segmented.{n} = s32[1,{rows}]{{1,0}} custom-call("
            f"f32[{rows},128] %pad.1)", start, dur]


def test_kmeans_roofline_counts_only_the_calls_over_every_point():
    reader = common.load_module("metrics",
                                "kmeans_assign_segmented_roofline.py")
    work = {"kmeans_points_per_call": 1000, "kmeans_problems_per_call": 4}
    # two calls over all 1024 rows (1000 real points), one straggler call
    ctx = _reader_ctx([_assign(1, 1024, 0, 1000), _assign(1, 1024, 2000, 1000),
                       _assign(2, 256, 4000, 500)], work)
    ops, byts = roofline.kmeans_assign_work(2000, 8)
    want = roofline.share(ops, byts, 2000e-9, "TPU v5 lite")[0]
    assert reader.read(ctx) == pytest.approx(want)
    assert reader.read(_reader_ctx([], work)) is None


@pytest.mark.parametrize("metric,ops,work", [
    ("kmeans_assign_segmented_roofline", [_assign(1, 1024, 0, 1000)],
     {"kmeans_points_per_call": 0, "kmeans_problems_per_call": 0}),
    ("kmeans_assign_segmented_roofline", [_assign(1, 1024, 0, 1000)],
     {"kmeans_points_per_call": 2000, "kmeans_problems_per_call": 4}),
    ("ri_histogram_roofline",
     [["%histogram.1 = s32[8,128] custom-call(s32[8,128] %f)", 0, 10]],
     {"ri_intervals_per_call": 0}),
])
def test_a_kernel_that_ran_with_no_work_counted_is_an_error(metric, ops,
                                                            work):
    reader = common.load_module("metrics", metric + ".py")
    with pytest.raises(ValueError):
        reader.read(_reader_ctx(ops, work))


def test_lern_work_counts_fit_the_recorded_calls(env):
    """The reference's point and interval counts against the padded shapes
    of the kernel calls the chip ran on the same trace."""
    c = common.cell("lern.config4", BENCH)
    job = common.load_module("jobs", "lern.py").Job(c["config"],
                                                   c["traffic"], 1)
    from repro.core import sim
    job.trace = sim.load_trace("config4",
                               c["config"]["params"]["subsample_target"])
    job.hash_fn = None
    work = job.kernel_work()
    with gzip.open(os.path.join(common.HERE, "testdata",
                                "lern_trace.json.gz"), "rt") as f:
        red = trace_reduce.Reduced(json.load(f))
    reader = common.load_module("metrics",
                                "kmeans_assign_segmented_roofline.py")
    rows = max(int(reader.ROWS.search(t).group(1))
               for t, _ in red.kernel_calls("assign_segmented"))
    pts, probs = work["kmeans_points_per_call"], work["kmeans_problems_per_call"]
    # each problem's run is padded to 128 rows, each half to 2048
    assert pts <= rows < pts + 128 * probs + 2 * 2048
    assert 0 < work["ri_intervals_per_call"] < job.trace.num_accesses


SWEEP_SMOKE = {"params": {"n_inputs": 1, "max_epochs": 60,
                          "subsample_target": 50_000},
               "traffic": {"stream_seeds": 2}}


@pytest.mark.parametrize("workload,overrides", [
    ("lern.config4", {"traffic": {"kmeans_seeds": 3}}),
    ("sweep.config1.moti1", SWEEP_SMOKE),
    ("sweep.config4.moti1", SWEEP_SMOKE),
])
def test_nothing_compiles_inside_a_window(env, workload, overrides):
    """After set-up, two passes over the job's seed pool compile
    nothing."""
    from chipbench import run
    run._environment()
    c = common.cell(workload, BENCH)
    c["config"]["params"].update(overrides.get("params", {}))
    c["traffic"].update(overrides["traffic"])
    job = common.load_module("jobs", c["traffic"]["job"] + ".py").Job(
        c["config"], c["traffic"], 2 ** 40 + 3)
    clock = common.CompileClock()
    job.setup()
    mark = clock.mark()
    pool = max(c["traffic"].get(k, 0) for k in ("kmeans_seeds",
                                                 "stream_seeds"))
    for i in range(2 * pool):
        job.iteration(i)
    assert job.attempted == 2 * pool
    assert clock.since(mark)["backend_compiles"] == 0
