"""Fluid DRAM model constants + the scheduled-model registry surface.

Pins the single-sourced queueing-law constants (``dram.queue_delay_consts``
and the two stability floors), the host-vs-fused fluid implementation
parity, and the :class:`SchedDramModel` registry/validation/default-routing
contract the scheduled backend rides on.
"""
import dataclasses
from types import SimpleNamespace

import pytest

from repro.core import dram as dram_mod
from repro.core.dram import (DDR3_1600, DDR3_1600_SQUASH, DDR4_2400_FRFCFS,
                             DDR4_2400_SQUASH, MODELS, QUEUE_DELAY_CAP_X,
                             QUEUE_RHO_CAP, QUEUE_STAB_FLOOR,
                             QUEUE_TRAFFIC_FLOOR, DramModel, SchedDramModel,
                             default_model, dram_kind, queue_delay_consts)


# ---------------------------------------------------------------------------
# fluid queueing-law constants and edge cases
# ---------------------------------------------------------------------------
def test_stab_floor_is_non_binding():
    """The stability floor exists only as belt-and-braces: with rho capped
    at QUEUE_RHO_CAP the denominator ``2 * (1 - rho)`` can never reach it.
    A change that flips this relation silently changes every saturated
    queue delay in the repo — pin it."""
    assert 2.0 * (1.0 - QUEUE_RHO_CAP) > QUEUE_STAB_FLOOR


def test_queue_delay_rho_cap_saturates_to_delay_cap():
    """Overwhelming traffic saturates rho at the cap; for every registered
    model the capped-rho delay exceeds 25x unloaded, so the absolute delay
    cap is what comes out."""
    for m in MODELS.values():
        w_sat = (QUEUE_RHO_CAP
                 / max(2.0 * (1.0 - QUEUE_RHO_CAP), QUEUE_STAB_FLOOR)
                 ) / m.rate
        assert w_sat > QUEUE_DELAY_CAP_X * m.latency_cycles
        assert m.queue_delay(1e12, 50_000.0) == \
            QUEUE_DELAY_CAP_X * m.latency_cycles


def test_queue_delay_zero_traffic_is_zero():
    assert DDR3_1600.queue_delay(0.0, 50_000.0) == 0.0


def test_queue_delay_zero_window_saturates():
    """A zero-length window floors the capacity denominator at
    QUEUE_TRAFFIC_FLOOR, so any positive traffic rides the rho cap
    straight to the delay cap instead of dividing by zero."""
    assert DDR3_1600.queue_delay(1.0, 0.0) == \
        QUEUE_DELAY_CAP_X * DDR3_1600.latency_cycles
    assert DDR3_1600.utilization(1.0, 0.0) == 1.0


def test_queue_delay_consts_golden():
    denom, cap = queue_delay_consts(DDR3_1600, 50_000.0)
    assert denom == DDR3_1600.rate * 50_000.0
    assert cap == QUEUE_DELAY_CAP_X * DDR3_1600.latency_cycles
    denom0, _ = queue_delay_consts(DDR3_1600, 0.0)
    assert denom0 == QUEUE_TRAFFIC_FLOOR


def test_fused_queue_delay_matches_host():
    """fused._queue_delay over staged SharedConsts-style scalars (float64
    bits, as the fused engine's host callback reads them) must agree with
    DramModel.queue_delay exactly — both derive from queue_delay_consts
    and apply the same op order."""
    from repro.core import fused

    et = 50_000.0
    for m in (DDR3_1600, DDR4_2400_SQUASH):
        denom, cap = queue_delay_consts(m, et)
        f = fused._floats({"dram_denom": fused._bits(denom),
                           "dram_rate": fused._bits(m.rate),
                           "w_dram25": fused._bits(cap)})
        for traffic in (0.0, 17.0, 1234.5, 3e3, 1e7):
            got = float(fused._queue_delay(f, traffic))
            assert got == m.queue_delay(traffic, et), (m.name, traffic)


# ---------------------------------------------------------------------------
# scheduled-model registry surface
# ---------------------------------------------------------------------------
def test_sched_models_registered_with_fluid_envelope():
    for m in (DDR3_1600_SQUASH, DDR4_2400_FRFCFS, DDR4_2400_SQUASH):
        assert MODELS[m.name] is m
        assert isinstance(m, SchedDramModel)
        assert isinstance(m, DramModel)     # drops into fluid call sites
        assert m.rate > 0 and m.latency_cycles > 0
    assert DDR4_2400_FRFCFS.scheduler == "frfcfs"
    assert DDR4_2400_SQUASH.scheduler == "squash"
    # the FR-FCFS/SQUASH pair differs ONLY in arbitration — same part
    assert dataclasses.replace(DDR4_2400_FRFCFS, name="x") == \
        dataclasses.replace(DDR4_2400_SQUASH, name="x", scheduler="frfcfs")


def test_sched_model_geometry_validation():
    with pytest.raises(AssertionError):
        dataclasses.replace(DDR4_2400_SQUASH, banks=12)   # not a power of 2
    with pytest.raises(AssertionError):
        dataclasses.replace(DDR4_2400_SQUASH, banks=8, ranks=3)
    with pytest.raises(AssertionError):
        dataclasses.replace(DDR4_2400_SQUASH, scheduler="fcfs")


def test_dram_kind_tags():
    assert dram_kind(DDR3_1600) == "fluid"
    assert dram_kind(DDR4_2400_FRFCFS) == "sched:frfcfs"
    assert dram_kind(DDR4_2400_SQUASH) == "sched:squash"


def test_default_model_env_routing(monkeypatch):
    monkeypatch.delenv("REPRO_DRAM", raising=False)
    assert default_model() is DDR3_1600
    monkeypatch.setenv("REPRO_DRAM", "fluid")
    assert default_model() is DDR3_1600
    monkeypatch.setenv("REPRO_DRAM", "sched")
    assert default_model() is DDR3_1600_SQUASH
    monkeypatch.setenv("REPRO_DRAM", DDR4_2400_SQUASH.name)
    assert default_model() is DDR4_2400_SQUASH
    monkeypatch.setenv("REPRO_DRAM", "no_such_model")
    with pytest.raises(KeyError):
        default_model()


def test_fluid_constants_are_fuseds_source():
    """fused.py must reference the dram.py constants, not re-literal them
    (single-source satellite)."""
    from repro.core import fused
    assert fused.dram_mod is dram_mod
