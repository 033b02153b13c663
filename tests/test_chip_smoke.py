"""The chip smoke run's phases (``chip_smoke.py``), at CPU-sized params.

The chip run itself needs a TPU; its phase functions do not.  Running them
here keeps their checks live on every change: the sweep completes on the
bucketed engine without a degrade/retry/fallback event, the bucketed
engine agrees with the host oracle, the serve replay's batched engine
agrees with its host oracle bitwise at >= 1,000 concurrent sessions, and
the committed CPU digest of config1's LERN label tables still matches what
the trainer produces (the chip run compares its own tables against it).
"""
import copy
import dataclasses
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import chip_smoke  # noqa: E402
from repro import exp  # noqa: E402
from repro.core import sim  # noqa: E402

SMOKE = exp.PARAMS.get("smoke")


@pytest.fixture
def fresh_cache(tmp_path, monkeypatch):
    """Every artifact and result computed from seeds, as on the chip."""
    monkeypatch.setattr(sim, "CACHE_DIR", str(tmp_path))


def test_sweep_phase_all_computed_on_bucketed_engine(fresh_cache):
    out = chip_smoke.sweep_phase(SMOKE, mixes=("moti1",))
    assert out["timing"]["points"] == 12
    assert out["timing"]["sim_epochs"] > 0


def test_oracle_phase_bucketed_matches_host(fresh_cache):
    chip_smoke.oracle_phase(SMOKE)


def test_compare_results_names_first_divergence(fresh_cache):
    from repro.core import sweep
    pol = exp.POLICIES.get("hydra")
    (want,) = sweep.simulate_group("config1", "moti1", [pol], SMOKE,
                                   engine="host")
    chip_smoke.compare_results(want, want, "hydra")
    got = copy.deepcopy(want)
    got.history["amal"][3] *= 1 + 1e-5
    with pytest.raises(AssertionError, match="amal diverges at epoch 3"):
        chip_smoke.compare_results(got, want, "hydra")
    got = dataclasses.replace(copy.deepcopy(want),
                              llc_accesses=want.llc_accesses + 1)
    with pytest.raises(AssertionError, match="llc_accesses"):
        chip_smoke.compare_results(got, want, "hydra")


def test_serve_phase_batched_matches_host():
    chip_smoke.serve_phase()


def test_lern_phase_reproduces_committed_cpu_digest(fresh_cache):
    out = chip_smoke.lern_phase(chip_smoke._params(),
                                expect_digest=chip_smoke.LERN_DIGEST,
                                require_kernel=False)
    assert out["digest"] == chip_smoke.LERN_DIGEST


def test_compile_clock_counts_overlapping_spans_once():
    """Nested traces report durations inside their parent's: the clock
    takes the union of the spans, so compile seconds never exceed wall."""
    import jax
    import jax.numpy as jnp
    clock = chip_smoke.CompileClock()
    clock._spans[:] = [(0.0, 10.0), (2.0, 5.0), (8.0, 12.0), (20.0, 21.0)]
    assert clock.seconds(0.0) == 13.0
    assert clock.seconds(9.0) == 4.0
    clock._spans.clear()
    mark = clock.mark()

    @jax.jit
    def outer(x):
        return jax.jit(lambda y: y * 3)(x) + 1

    outer(jnp.arange(5.0)).block_until_ready()
    t = clock.since(mark)
    assert 0.0 < t["compile_s"] <= t["wall_s"]
    assert t["backend_compiles"] >= 1
