"""The LERN trainer's profiler spans, read back from a real profiler
session on the CPU: one ``lern.train`` per training, tiled in order by
``lern.extract``, ``lern.fit`` and ``lern.assemble`` (one per model);
``kmeans.stragglers`` inside the fit exactly when the segmented k-means
re-dispatched; and the same tables with the profiler on as off."""
import functools
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import kmeans as km
from repro.core import lern
from test_lern_batched import _synthetic_trace

PREFIXES = ("lern.", "kmeans.", "fused.")
PHASES = ["lern.extract", "lern.fit", "lern.assemble"]


def _traced(fn, trace_dir):
    """``fn()`` under a profiler session; (its result, the program's spans
    as (name, start_ns, end_ns) in start order)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith(PREFIXES)]
    return out, sorted(events, key=lambda ev: (ev[1], -ev[2]))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _assert_same_model(a, b):
    for f in ("uniq", "rc_cluster", "ri_cluster", "n_uniq", "rc_centers",
              "ri_centers"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    for fa, fb in zip(a.features_ri, b.features_ri, strict=True):
        np.testing.assert_array_equal(fa, fb)


@pytest.mark.parametrize("trainer", ["model", "family"])
def test_phases_tile_one_training(trainer, tmp_path):
    traces = [_synthetic_trace(n_layers=3, seed=1),
              _synthetic_trace(n_layers=2, seed=2)]
    if trainer == "model":
        traces = traces[:1]
        train = functools.partial(lern.train_model_batched, traces[0],
                                  seed=3)
    else:
        train = functools.partial(lern.train_family_batched, traces, seed=3)
    train()                       # compile outside the session
    _, events = _traced(train, tmp_path)
    outer = [ev for ev in events if ev[0] == "lern.train"]
    assert len(outer) == 1
    phases = [ev for ev in events if ev[0] in PHASES]
    assert [ev[0] for ev in phases] == PHASES[:2] + PHASES[2:] * len(traces)
    assert all(_inside(ev, outer[0]) for ev in phases)
    # siblings: each phase ends before the next one starts
    assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))


@pytest.mark.parametrize("first_chunk", [1, 50])
def test_stragglers_span_only_when_the_fit_redispatched(first_chunk,
                                                        monkeypatch,
                                                        tmp_path):
    """``first_chunk`` 1 leaves segments moving after the first dispatch;
    50 (every sweep) never re-dispatches."""
    lloyd = km._lloyd_segmented
    dispatches = []

    def counted(*args, **kwargs):
        dispatches.append(1)
        return lloyd(*args, **kwargs)

    monkeypatch.setattr(km, "_lloyd_segmented", counted)
    monkeypatch.setattr(km, "kmeans_fit_segmented", functools.partial(
        km.kmeans_fit_segmented, first_chunk=first_chunk))
    trace = _synthetic_trace(n_layers=3, seed=4)
    train = functools.partial(lern.train_model_batched, trace, seed=5,
                              fit_engine="segmented")
    train()
    dispatches.clear()
    _, events = _traced(train, tmp_path)
    redispatched = len(dispatches) == 2
    assert redispatched == (first_chunk == 1)
    stragglers = [ev for ev in events if ev[0] == "kmeans.stragglers"]
    assert len(stragglers) == int(redispatched)
    fit, = [ev for ev in events if ev[0] == "lern.fit"]
    assert all(_inside(ev, fit) for ev in stragglers)


def test_tables_are_the_same_with_the_profiler_on(tmp_path):
    trace = _synthetic_trace(n_layers=3, seed=6)
    train = functools.partial(lern.train_model_batched, trace, seed=7)
    off = train()
    on, events = _traced(train, tmp_path)
    assert [ev[0] for ev in events if ev[0] == "lern.train"] == ["lern.train"]
    _assert_same_model(off, on)
