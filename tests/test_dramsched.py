"""Scheduled bank/rank DRAM backend: twin parity + behavioral pins.

``dramsched.epoch_compute`` is one function body run under numpy (host
oracle) and jax.numpy (inside the fused epoch scan) — the suite checks
the twins agree bitwise over chained epochs (fixed streams, a hypothesis
property over random bank/row sequences), pins the model's behavioral
contract (row hits cheaper than conflicts, periodic reset re-pays
activation, backlog carryover, SQUASH urgency ordering), and closes the
loop end-to-end: host ``drive_lane`` vs the fused engine, bitwise, across
the policy families with a scheduled model selected.
"""
import dataclasses

import numpy as np
import pytest

from _reference import assert_bitwise, run_reference
from test_fused import on_device  # noqa: F401 (fixture)
from repro.core import dramsched, policies, sim, sweep
from repro.core.dram import (DDR3_1600_SQUASH, DDR4_2400_FRFCFS,
                             DDR4_2400_SQUASH)

TINY = dataclasses.replace(sim.SimParams(), n_inputs=1, max_epochs=40,
                           subsample_target=50_000)
DEADLINE = 2.0e6


def _jnp():
    import jax.numpy as jnp
    return jnp


def _step(xp, model, state, samp, am, cm, pf, urgent, epoch,
          et_i=50_000):
    import contextlib

    import jax

    # the jnp twin runs under scoped x64, exactly as the fused engine
    # wraps its dispatches (the global x64 flag stays off repo-wide)
    scope = contextlib.nullcontext() if xp is np else jax.enable_x64()
    dims = dramsched.sched_dims(model)
    timing = dramsched.timing_tuple(model)
    with scope:
        orow, queue, rr = (xp.asarray(s) for s in state)
        out = dramsched.epoch_compute(
            xp, dims, timing, orow, queue, rr, xp.asarray(samp, np.int64),
            np.int64(am), np.int64(cm), np.int64(pf), urgent,
            np.int64(epoch), np.int64(et_i))
        num_a, den_a, num_c, den_c, orow2, queue2, rr2 = out
        return ((int(num_a), int(den_a), int(num_c), int(den_c)),
                (np.asarray(orow2, np.int64), np.asarray(queue2, np.int64),
                 np.int64(rr2)))


def _addr(model, bank, row):
    dims = dramsched.sched_dims(model)
    return (np.asarray(row, np.int64) << (dims.col_bits + dims.bank_bits)
            ) | (np.asarray(bank, np.int64) << dims.col_bits)


def _init(model):
    s = dramsched.host_init(model)
    return (s.row, s.queue, np.int64(s.rr))


# ---------------------------------------------------------------------------
# numpy-vs-jnp twin parity
# ---------------------------------------------------------------------------
def test_epoch_compute_twins_bitwise_chained():
    """25 chained epochs of a seeded random stream through both twins:
    every output scalar and every state array must agree exactly, with
    the state fed forward on each side independently."""
    jnp = _jnp()
    rng = np.random.default_rng(7)
    for model in (DDR4_2400_SQUASH, DDR4_2400_FRFCFS, DDR3_1600_SQUASH):
        st_np, st_j = _init(model), _init(model)
        for epoch in range(25):
            samp = rng.integers(0, 1 << 20, model.samples, dtype=np.int64)
            am = int(rng.integers(0, 3000))
            cm = int(rng.integers(0, 1500))
            pf = int(rng.integers(0, 400))
            urgent = bool(rng.integers(0, 2))
            out_np, st_np = _step(np, model, st_np, samp, am, cm, pf,
                                  urgent, epoch)
            out_j, st_j = _step(jnp, model, st_j, samp, am, cm, pf,
                                urgent, epoch)
            assert out_np == out_j, (model.name, epoch)
            for a, b in zip(st_np, st_j):
                assert np.array_equal(a, b), (model.name, epoch)


def test_epoch_compute_twins_property():
    """Hypothesis property over random bank/row/traffic sequences: the
    numpy and jnp twins agree exactly (CI's test extra installs
    hypothesis; skipped where it is absent)."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    jnp = _jnp()
    model = DDR4_2400_SQUASH
    dims = dramsched.sched_dims(model)

    @hyp.settings(max_examples=25, deadline=None)
    @hyp.given(st.data())
    def prop(data):
        banks = data.draw(st.lists(
            st.integers(0, dims.n_banks - 1),
            min_size=dims.n_samples, max_size=dims.n_samples))
        rows = data.draw(st.lists(
            st.integers(0, 7),
            min_size=dims.n_samples, max_size=dims.n_samples))
        samp = _addr(model, np.asarray(banks), np.asarray(rows))
        am = data.draw(st.integers(0, 5000))
        cm = data.draw(st.integers(0, 5000))
        pf = data.draw(st.integers(0, 1000))
        urgent = data.draw(st.booleans())
        epoch = data.draw(st.integers(0, 40))
        queue = np.asarray(data.draw(st.lists(
            st.integers(0, model.queue_cap),
            min_size=dims.n_banks, max_size=dims.n_banks)), np.int64)
        orow = np.asarray(data.draw(st.lists(
            st.integers(-1, 7),
            min_size=dims.n_banks, max_size=dims.n_banks)), np.int64)
        state = (orow, queue, np.int64(data.draw(st.integers(0, 31))))
        out_np, st_np = _step(np, model, state, samp, am, cm, pf,
                              urgent, epoch)
        out_j, st_j = _step(jnp, model, state, samp, am, cm, pf,
                            urgent, epoch)
        assert out_np == out_j
        for a, b in zip(st_np, st_j):
            assert np.array_equal(a, b)

    prop()


# ---------------------------------------------------------------------------
# behavioral pins (numpy twin)
# ---------------------------------------------------------------------------
def test_row_hits_cheaper_than_conflicts():
    """A same-row streaming pattern must cost strictly less service than
    the same traffic ping-ponging between two rows of one bank."""
    model = DDR4_2400_SQUASH
    ns = model.samples
    hit_samp = _addr(model, np.zeros(ns), np.zeros(ns))
    conf_samp = _addr(model, np.zeros(ns), np.arange(ns) % 2)
    kw = dict(am=ns, cm=0, pf=0, urgent=True, epoch=1)
    (num_hit, den, _, _), _ = _step(np, model, _init(model), hit_samp, **kw)
    (num_conf, den2, _, _), _ = _step(np, model, _init(model), conf_samp,
                                      **kw)
    assert den == den2 == ns
    assert num_hit < num_conf


def test_periodic_reset_repays_activation():
    """On a reset epoch the bank starts closed: the same single-row stream
    against a warm open row costs more than on a non-reset epoch."""
    model = DDR4_2400_SQUASH
    ns = model.samples
    samp = _addr(model, np.zeros(ns), np.full(ns, 5))
    warm_row = np.zeros(model.banks, np.int64)
    warm_row[0] = 5
    state = (warm_row, np.zeros(model.banks, np.int64), np.int64(0))
    kw = dict(am=ns, cm=0, pf=0, urgent=True)
    (num_warm, _, _, _), _ = _step(np, model, state, samp, epoch=1, **kw)
    (num_reset, _, _, _), (row2, _, _) = _step(
        np, model, state, samp, epoch=model.reset_period, **kw)
    # one activation: +t_rcd of service, halved by the urgent-wait law,
    # felt by all ns lines of the bank
    assert num_reset == num_warm + (model.t_rcd // 2) * ns
    assert row2[0] == 5   # the stream re-opens its row after the reset


def test_backlog_carries_into_next_epoch_and_clamps():
    """Service beyond the epoch window becomes next-epoch backlog (clamped
    at queue_cap); a second identical epoch then waits strictly longer."""
    model = dataclasses.replace(DDR4_2400_SQUASH, name="t", queue_cap=100)
    ns = model.samples
    samp = _addr(model, np.zeros(ns), np.arange(ns))   # all conflicts
    kw = dict(am=50_000, cm=0, pf=0, urgent=True, epoch=1, et_i=500)
    (num1, _, _, _), (_, queue2, _) = _step(np, model, _init(model), samp,
                                            **kw)
    assert queue2[0] == model.queue_cap            # clamped
    assert np.all(queue2[1:] == 0)                 # untouched banks stay 0
    state2 = (np.full(model.banks, -1, np.int64), queue2, np.int64(0))
    (num2, _, _, _), _ = _step(np, model, state2, samp, **kw)
    assert num2 > num1


def test_squash_urgency_ordering():
    """With both streams present: an urgent lane's accel wait is strictly
    below FR-FCFS's shared wait, non-urgent strictly above — and the core
    sees the mirror image."""
    sq, fr = DDR4_2400_SQUASH, DDR4_2400_FRFCFS
    ns = sq.samples
    samp = _addr(sq, np.arange(ns) % sq.banks, np.arange(ns))
    kw = dict(am=2000, cm=2000, pf=0, epoch=1)
    (a_urg, _, c_urg, _), _ = _step(np, sq, _init(sq), samp,
                                    urgent=True, **kw)
    (a_non, _, c_non, _), _ = _step(np, sq, _init(sq), samp,
                                    urgent=False, **kw)
    (a_fr, _, c_fr, _), _ = _step(np, fr, _init(fr), samp,
                                  urgent=True, **kw)
    assert a_urg < a_fr < a_non
    assert c_non < c_fr < c_urg


def test_sample_window_strided_gather():
    line = np.arange(100, dtype=np.int64) * 3
    got = dramsched.sample_window(line, pos=10, n_a=40, ns=4)
    assert np.array_equal(got, line[[10, 20, 30, 40]])


# ---------------------------------------------------------------------------
# host vs the device engine, end to end
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("pol_name", ["fifo-nb", "arp-cs-as", "hydra",
                                      "hydra-v1"])
def test_host_vs_fused_bitwise_squash(pol_name, on_device):
    pol = policies.get(pol_name)
    want = run_reference("config1", "moti1", pol, TINY, DDR4_2400_SQUASH,
                         deadline_cycles=DEADLINE)
    got = sweep.simulate_group("config1", "moti1", [pol], TINY,
                               DDR4_2400_SQUASH, deadline_cycles=DEADLINE,
                               engine="auto")[0]
    assert_bitwise(got, want, pol_name)
    assert on_device


@pytest.mark.parametrize("pol_name", ["fifo-nb", "hydra"])
def test_host_vs_fused_bitwise_frfcfs(pol_name, on_device):
    pol = policies.get(pol_name)
    want = run_reference("config1", "moti1", pol, TINY, DDR4_2400_FRFCFS,
                         deadline_cycles=DEADLINE)
    got = sweep.simulate_group("config1", "moti1", [pol], TINY,
                               DDR4_2400_FRFCFS, deadline_cycles=DEADLINE,
                               engine="auto")[0]
    assert_bitwise(got, want, pol_name)
    assert on_device
