"""Batched bank/rank DRAM scheduler (FR-FCFS / SQUASH-style) — the timing
backend behind :class:`repro.core.dram.SchedDramModel`.

The model is epoch-granularity but bank-accurate: each epoch the lane's
accelerator DRAM traffic is represented by ``samples`` strided line
addresses from its access window, with integer weights that partition the
epoch's miss count exactly.  Per bank the model tracks the open row and a
backlog counter (cycles of unserved service), charges row-buffer
hit / closed-row / conflict costs (tCAS / tRCD+tCAS / tRP+tRCD+tCAS),
spreads the core's misses round-robin across banks at conflict cost,
models rank-level bus contention over the epoch window, and resets the
row table every ``reset_period`` epochs (SNIPPETS.md's ramulator2 Hydra
plugin idiom).  Arbitration between the accelerator and core streams is
either shared FCFS (FR-FCFS approximation) or SQUASH-style: when the lane
is deadline-urgent the accelerator stream is served first and the core
waits behind it, otherwise the roles flip.

Everything is int64 until two final float64 divisions (exact — the
numerators stay far below 2^53), so the *same* ``epoch_compute`` function
body runs under numpy (host oracle) and jax.numpy (inside the fused epoch
``lax.scan``), giving bitwise host-vs-fused parity by construction.  The
jnp twin must run under the scoped ``jax.enable_x64()`` the
fused engine already wraps every dispatch in (this module deliberately
does NOT flip the global x64 flag — that would leak int64 promotion into
the Pallas kernels).  The per-lane state is three fixed-shape arrays that
live in the fused carry:

* ``row``   int64[banks]  — open row per bank, ``-1`` = closed
* ``queue`` int64[banks]  — backlog cycles carried into the next epoch
* ``rr``    int64 scalar  — round-robin rotor for spreading core misses
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from .dram import SchedDramModel


class SchedDims(NamedTuple):
    """Static (program-shape) geometry of a scheduled DRAM model.  Cycle
    costs and the scheduler kind are *data* (see ``timing_tuple``), so two
    models sharing a ``SchedDims`` share a compiled fused program."""
    n_banks: int
    n_ranks: int
    n_samples: int
    col_bits: int

    @property
    def bank_bits(self) -> int:
        return (self.n_banks - 1).bit_length()


def sched_dims(model: SchedDramModel) -> SchedDims:
    return SchedDims(n_banks=model.banks, n_ranks=model.ranks,
                     n_samples=model.samples, col_bits=model.col_bits)


def timing_tuple(model: SchedDramModel):
    """The model's data-side parameters, as plain ints in the order
    ``epoch_compute`` consumes them: (t_cas, t_rcd, t_rp, t_bus,
    reset_period, queue_cap, kind) with kind 0=frfcfs, 1=squash."""
    return (int(model.t_cas), int(model.t_rcd), int(model.t_rp),
            int(model.t_bus), int(model.reset_period), int(model.queue_cap),
            1 if model.scheduler == "squash" else 0)


def _scatter_add(xp, size, idx, vals):
    if xp is np:
        out = np.zeros(size, np.int64)
        np.add.at(out, idx, vals)
        return out
    return jnp.zeros(size, jnp.int64).at[idx].add(vals)


def _scatter_max(xp, size, fill, idx, vals):
    if xp is np:
        out = np.full(size, fill, np.int64)
        np.maximum.at(out, idx, vals)
        return out
    return jnp.full(size, fill, jnp.int64).at[idx].max(vals)


def epoch_compute(xp, dims: SchedDims, timing, orow, queue, rr,
                  samp, am, cm, pf, urgent, epoch, et_i):
    """One epoch of the bank/rank model for one lane.  Pure int64; ``xp``
    is ``numpy`` (host) or ``jax.numpy`` (fused) — every arithmetic op is
    shared, only the two scatter helpers dispatch (both order-free integer
    reductions), so the twins agree bitwise.

    Inputs: ``timing`` per :func:`timing_tuple` (scalars, int64 on device);
    ``orow``/``queue`` int64[banks], ``rr`` int64 scalar (lane state);
    ``samp`` int64[samples] line addresses sampled from the accel window;
    ``am``/``cm``/``pf`` int64 accel / core / prefetch DRAM lines this
    epoch; ``urgent`` bool (SQUASH deadline urgency); ``epoch`` int64;
    ``et_i`` int64 epoch length in cycles.

    Returns ``(num_a, den_a, num_c, den_c, orow', queue', rr')`` — the
    average extra DRAM wait per access is ``num / den`` (exact in f64).
    """
    nb, nr, ns = dims.n_banks, dims.n_ranks, dims.n_samples
    t_cas, t_rcd, t_rp, t_bus, reset_period, queue_cap, kind = timing
    squash = kind == 1

    # Periodic row-table reset (counter-table decay idiom): banks start the
    # epoch closed, so the first access per bank re-pays activation.
    do_reset = (epoch % reset_period) == 0
    orow = xp.where(do_reset, np.int64(-1), orow)

    # Exact integer partition of am over the samples: w_i sums to am, and
    # every sample with w_i > 0 is "present" this epoch.
    ii = xp.arange(ns, dtype=np.int64)
    w = ((ii + 1) * am) // ns - (ii * am) // ns
    present = w > 0

    bank = (samp >> dims.col_bits) & (nb - 1)
    srow = samp >> (dims.col_bits + dims.bank_bits)

    # Row seen by sample i = the last present earlier sample on the same
    # bank, else the bank's open row.  O(ns^2) mask instead of a sequential
    # scan — ns is small (32) and this keeps the body fully data-parallel.
    same_bank = bank[:, None] == bank[None, :]
    before = ii[None, :] < ii[:, None]
    lastj = xp.max(xp.where(same_bank & before & (w[None, :] > 0),
                            ii[None, :], np.int64(-1)), axis=1)
    prev = xp.where(lastj >= 0, srow[xp.clip(lastj, 0, ns - 1)], orow[bank])

    # Burst cost per sample: first line pays hit / closed / conflict, the
    # remaining w-1 lines of the burst stream at CAS rate.
    hit = (prev >= 0) & (prev == srow)
    first = xp.where(hit, t_cas,
                     xp.where(prev < 0, t_rcd + t_cas, t_rp + t_rcd + t_cas))
    cost = xp.where(present, first + (w - 1) * t_cas, np.int64(0))

    a_svc = _scatter_add(xp, nb, bank, cost)       # accel service, per bank
    a_load = _scatter_add(xp, nb, bank, w)         # accel lines, per bank

    # Core misses spread round-robin (rotor ``rr``) across banks, each at
    # conflict cost — the core's stride is opaque at this granularity, so
    # it is modeled as always closing the accelerator's rows.
    bidx = xp.arange(nb, dtype=np.int64)
    c_load = cm // nb + (((bidx - rr % nb) % nb) < cm % nb)
    c_svc = c_load * (t_rp + t_rcd + t_cas)

    # Rank-level bus contention: lines x t_bus over the epoch window; the
    # overflow beyond the window is charged back per line on that rank.
    # Prefetch fills ride the bus but skip the bank queues (issued early).
    rank_of = bidx // (nb // nr)
    pf_r = pf // nr + (xp.arange(nr, dtype=np.int64) < pf % nr)
    r_load = _scatter_add(xp, nr, rank_of, a_load + c_load) + pf_r
    over = xp.maximum(r_load * t_bus - et_i, np.int64(0))
    pen = (over // xp.maximum(r_load, np.int64(1)))[rank_of]

    # Arbitration.  FR-FCFS approximation: one shared queue per bank, the
    # average arrival waits behind the backlog plus half the epoch's
    # service.  SQUASH: the urgent stream goes first (waits behind backlog
    # + half its own service), the other waits behind all of it.
    shared = queue + (a_svc + c_svc) // 2
    wa_sq = xp.where(urgent, queue + a_svc // 2, queue + c_svc + a_svc // 2)
    wc_sq = xp.where(urgent, queue + a_svc + c_svc // 2, queue + c_svc // 2)
    wa = xp.where(squash, wa_sq, shared) + pen
    wc = xp.where(squash, wc_sq, shared) + pen

    num_a = xp.sum(wa * a_load)
    num_c = xp.sum(wc * c_load)
    den_a = xp.maximum(am, np.int64(1))
    den_c = xp.maximum(cm, np.int64(1))

    # State advance: backlog carries unserved cycles (clamped), the open
    # row per bank becomes the last present sample's row, rotor rotates.
    queue2 = xp.clip(queue + a_svc + c_svc - et_i, np.int64(0), queue_cap)
    last = _scatter_max(xp, nb, np.int64(-1), bank,
                        xp.where(present, ii, np.int64(-1)))
    orow2 = xp.where(last >= 0, srow[xp.clip(last, 0, ns - 1)], orow)
    rr2 = (rr + cm) % nb

    return num_a, den_a, num_c, den_c, orow2, queue2, rr2


@dataclasses.dataclass
class HostState:
    """Mutable per-lane host twin of the fused carry's bank-state block."""
    row: np.ndarray     # int64[banks], -1 = closed
    queue: np.ndarray   # int64[banks], backlog cycles
    rr: int             # round-robin rotor for core-miss spreading


def host_init(model: SchedDramModel) -> HostState:
    return HostState(row=np.full(model.banks, -1, np.int64),
                     queue=np.zeros(model.banks, np.int64), rr=0)


def sample_window(line: np.ndarray, pos: int, n_a: int, ns: int) -> np.ndarray:
    """``ns`` strided line addresses from the access window
    ``line[pos : pos + n_a]`` (host side; the fused twin gathers the same
    indices from the staged trace)."""
    si = np.arange(ns, dtype=np.int64)
    idx = pos + (si * np.int64(n_a)) // ns
    return np.asarray(line, np.int64)[idx]


def host_epoch(state: HostState, model: SchedDramModel, samp: np.ndarray,
               am: int, cm: int, pf: int, urgent: bool, epoch: int,
               et_i: int):
    """Advance ``state`` one epoch; returns the uncapped average extra
    DRAM wait ``(w_accel, w_core)`` as floats — bitwise-equal to the fused
    engine's ``num/den`` division (both exact below 2^53)."""
    num_a, den_a, num_c, den_c, row2, queue2, rr2 = epoch_compute(
        np, sched_dims(model), timing_tuple(model),
        state.row, state.queue, np.int64(state.rr),
        np.asarray(samp, np.int64), np.int64(am), np.int64(cm),
        np.int64(pf), bool(urgent), np.int64(epoch), np.int64(et_i))
    state.row = row2
    state.queue = queue2
    state.rr = int(rr2)
    return float(num_a) / float(den_a), float(num_c) / float(den_c)
