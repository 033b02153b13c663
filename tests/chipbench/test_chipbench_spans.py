"""The readers of the program's own spans (``chipbench/spans.py`` and the
seven metrics on it), checked on the CPU: exact readings on a synthetic
trace, no reading without a device plane or without the program's spans,
an error for a window that lost its ``lern.train`` spans, and the
recorded chip trace's phases against its idle share."""
import gzip
import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import common, spans, trace_reduce  # noqa: E402

US = 1000   # the synthetic trace counts in microseconds; traces in ns
PHASE_METRICS = ["lern.extract_ms", "lern.fit_ms", "lern.assemble_ms"]
IDLE_METRICS = ["device.idle_share.lern.extract",
                "device.idle_share.lern.fit",
                "device.idle_share.lern.assemble"]
METRICS = PHASE_METRICS + IDLE_METRICS + ["kmeans.redispatch_share"]


def _synthetic(devices=True):
    """A 1000 µs window with two trainings.  Device ops at [100,200),
    [300,350) and [600,700) µs leave the gaps [0,100), [200,300),
    [350,600) and [700,1000): 750 µs idle."""
    ops = [["%fusion.1 = s32[8] fusion(s32[8] %p)", 100 * US, 100 * US],
           ["%while.2 = s32[8] while(s32[8] %p)", 300 * US, 50 * US],
           ["%fusion.1 = s32[8] fusion(s32[8] %p)", 600 * US, 100 * US]]
    host = [["chipbench.window", 0, 1000 * US],
            ["chipbench.train", 40 * US, 420 * US],
            ["lern.train", 50 * US, 400 * US],
            ["lern.extract", 50 * US, 200 * US],
            ["np.asarray(jax.Array)", 210 * US, 30 * US],
            ["lern.fit", 250 * US, 150 * US],
            ["kmeans.stragglers", 300 * US, 80 * US],
            ["lern.assemble", 400 * US, 50 * US],
            ["lern.train", 500 * US, 400 * US],
            ["lern.extract", 500 * US, 150 * US],
            ["lern.fit", 650 * US, 200 * US],
            ["lern.assemble", 850 * US, 30 * US],
            ["lern.assemble", 880 * US, 20 * US],
            # after the window: not counted
            ["lern.train", 1100 * US, 100 * US],
            ["kmeans.stragglers", 1150 * US, 10 * US]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops}]
            if devices else [], "host": host}


def _ctx(norm):
    return SimpleNamespace(trace=trace_reduce.Reduced(norm))


def _read(metric, ctx):
    return common.load_module("metrics", metric + ".py").read(ctx)


@pytest.mark.parametrize("metric,want", [
    # extract 200 + 150 µs, fit 150 + 200, assemble 50 + 30 + 20; 2 trainings
    ("lern.extract_ms", 0.175),
    ("lern.fit_ms", 0.175),
    ("lern.assemble_ms", 0.05),
    # idle inside extract [50,100) [200,250) [500,600); fit [250,300)
    # [350,400) [700,850); assemble [400,450) [850,900); of 1000 µs
    ("device.idle_share.lern.extract", 20.0),
    ("device.idle_share.lern.fit", 25.0),
    ("device.idle_share.lern.assemble", 10.0),
    # one re-dispatch in two trainings
    ("kmeans.redispatch_share", 50.0),
])
def test_each_reading_on_a_synthetic_trace(metric, want):
    assert _read(metric, _ctx(_synthetic())) == pytest.approx(want,
                                                              rel=1e-12)


def test_phase_idle_and_the_rest_make_the_idle_share():
    ctx = _ctx(_synthetic())
    phases = sum(_read(m, ctx) for m in IDLE_METRICS)
    # idle outside the phases: [0,50), [450,500), [900,1000)
    assert phases + 20.0 == pytest.approx(
        _read("device.idle_share.lern", ctx), rel=1e-12)


@pytest.mark.parametrize("metric", METRICS)
def test_no_reading_without_a_device_or_without_the_programs_spans(metric):
    assert _read(metric, _ctx(_synthetic(devices=False))) is None
    norm = _synthetic()
    norm["host"] = [h for h in norm["host"]
                    if not h[0].startswith(spans.PROGRAM_PREFIXES)]
    assert _read(metric, _ctx(norm)) is None


@pytest.mark.parametrize("metric", METRICS)
def test_a_window_without_its_trainings_is_an_error(metric):
    norm = _synthetic()
    norm["host"] = [h for h in norm["host"] if h[0] != spans.TRAIN]
    with pytest.raises(ValueError, match="lern.train"):
        _read(metric, _ctx(norm))


@pytest.mark.parametrize("metric", PHASE_METRICS + IDLE_METRICS)
def test_a_training_without_a_phase_is_an_error(metric):
    phase = "lern." + metric.split(".")[-1].removesuffix("_ms")
    norm = _synthetic()
    norm["host"] = [h for h in norm["host"] if h[0] != phase]
    with pytest.raises(ValueError, match=phase):
        _read(metric, _ctx(norm))


def _outside(gaps, covered):
    """Length of the sorted disjoint ``gaps`` not covered by ``covered``
    (sorted, disjoint), by a plain walk."""
    total, j = 0.0, 0
    for gs, ge in gaps:
        cur = gs
        while j < len(covered) and covered[j][1] <= cur:
            j += 1
        k = j
        while cur < ge:
            if k < len(covered) and covered[k][0] < ge:
                cs, ce = covered[k]
                total += max(0.0, cs - cur)
                cur = max(cur, ce)
                k += 1
            else:
                total += ge - cur
                cur = ge
    return total


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(common.HERE, "testdata", "lern_trace_spans.json.gz")
    with gzip.open(path, "rt") as f:
        return trace_reduce.Reduced(json.load(f))


def test_the_recorded_phases_tile_their_trainings(recorded):
    n = spans.trainings(recorded)
    assert n >= 2
    ts, te = spans.spans(recorded, spans.TRAIN)
    phase = sum(float((e - s).sum())
                for s, e in (spans.spans(recorded, p) for p in spans.PHASES))
    assert phase >= 0.95 * float((te - ts).sum())
    ctx = SimpleNamespace(trace=recorded)
    assert all(_read(m, ctx) is not None for m in METRICS)


def test_the_recorded_phase_idle_and_the_rest_make_the_idle_share(recorded):
    ctx = SimpleNamespace(trace=recorded)
    idle = _read("device.idle_share.lern", ctx)
    per_phase = [_read(m, ctx) for m in IDLE_METRICS]
    assert all(0.0 <= v <= idle for v in per_phase)
    gs, ge = recorded.gaps(0)
    cov = sorted((float(s), float(e)) for p in spans.PHASES
                 for s, e in zip(*spans.spans(recorded, p)))
    rest = 100.0 * _outside(list(zip(gs, ge)), cov) / (recorded.w1
                                                       - recorded.w0)
    assert sum(per_phase) + rest == pytest.approx(idle, abs=1e-6)
