"""Test-only sequential reference oracle.

``run_reference`` is the retired ``sim.run`` single-point path: load
artifacts, drive one Lane through the per-epoch host loop.  The parity
suites (test_sweep / test_fused / test_bucketed) pin every batched
engine against it; production code goes through ``exp.run`` instead.
"""
from typing import Optional

from repro.core import sim
from repro.core.dram import default_model
from repro.core.policies import Policy


def run_reference(config: str, mix: str, policy: Policy,
                  params: Optional[sim.SimParams] = None,
                  dram: Optional[sim.DramModel] = None,
                  deadline_cycles: Optional[float] = None,
                  core_traffic: bool = True) -> sim.SimResult:
    p = params or sim.SimParams()
    if dram is None:
        dram = default_model()
    if deadline_cycles is None:
        deadline_cycles = sim.calibrated_deadline(config, p, dram)
    art = sim.load_artifacts(config, mix, p, core_traffic)
    return sim.drive_lane(sim.Lane(config, mix, policy, p, dram,
                                   float(deadline_cycles), art,
                                   core_traffic))


def assert_bitwise(got: sim.SimResult, want: sim.SimResult, who):
    """Full bitwise equality, integer-derived counters and float timing
    alike: the contract every engine keeps with ``sim.drive_lane``."""
    assert got.summary() == want.summary(), who
    assert got.epochs == want.epochs, who
    assert got.completion_cycles == want.completion_cycles, who
    assert got.core_hit_rate == want.core_hit_rate, who
    assert got.accel_hit_rate == want.accel_hit_rate, who
    assert got.llc_accesses == want.llc_accesses, who
    assert got.dram_accesses == want.dram_accesses, who
    assert got.history == want.history, who
    assert got.occupancy == want.occupancy, who
