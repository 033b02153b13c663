"""Device-resident fused epoch loop (perf tentpole, PR 4).

The paper's evaluation is an epoch-driven feedback cycle — per-epoch
admission, APM threshold selection, LLC content simulation, fluid-timing
update (§III-C, §VI) — and the host engine (``sim.Lane`` +
``sweep._drive_lanes``) pays one numpy event-build, one ``build_rounds``
sort and one blocking device→host stats sync *per epoch*, up to
``max_epochs`` times per lane.  This module stages the whole
(config, mix, policy-lane-batch) simulation on device once and runs a
``lax.scan`` over epochs whose carry holds the LLC state *and* the lane
timing state (hit rates, AMAL, per-core IPC, input progress, APM
thresholds).  The host only syncs once per *super-step* of K epochs.

Parity contract (tests/test_fused.py):

* integer LLC stat counters are **bitwise-equal** to the sequential
  oracle ``sim.drive_lane``.  Event interleaving uses the exact integer
  keys of ``sim.when_keys`` on both sides, device round building is a
  composite (set, when) sort reproducing ``llc.build_rounds``'s
  per-set event order, and every round applies the very same shared
  ``llc.round_transition`` (on a depth-major prefix slice).
* float timing metrics are bitwise-equal too: the fluid timing update
  (``sim._mg1_delay``, ``dram.queue_delay``, ``cores.core_ipc``,
  ``apm.*``) runs on the host's own float64 — numpy, with the host's
  exact operation order, including numpy's pairwise summation tree for
  the 8-core IPC sum — through one ``jax.pure_callback`` per epoch half
  (``_begin_host``/``_finish_host``), for the whole lane batch at once.
  Each call's operands and its results cross as one packed uint32 word
  row per lane each way (``_host_call``): a TPU host transfer costs the
  same 0.1-0.6 ms whether it holds 3 bytes or 3 KB, and a call's
  transfers run one after another, so their count, not their bytes, is
  the cost.  No device float64 is involved: a TPU has none (XLA emulates
  it with f32 pairs and rounds even a plain copy), so every float64 leaf
  of the consts, the carry and the step outputs holds IEEE bits as a
  trailing pair of uint32 words (``f64b``), which only the host
  interprets.  The device keeps the integer work: event build, round
  loop, LLC state, scheduled-DRAM bank model.

Fallback contract: the per-epoch round matrix has a static round
capacity (``max_rounds``).  A hot set overflowing it — or an
online-LERN retrain boundary — raises a flag.  An overflowing epoch
never commits: the lane *freezes in place* on its pre-overflow carry
(``_finish_lane`` selects the old state, the sticky flag gates further
steps), so the carry is always valid and the driver can resume from it
directly — no rollback buffer is needed, which is what lets the driver
donate its carry.  ``drive_lanes_bucketed``, the one device driver,
escalates the bucket's capacity (re-jit, doubling up to the host's
largest round bucket) and, once that is exhausted, finishes only the
offending groups on the host: each still-active lane continues from its
frozen state through ``sim.drive_lane``, which chunks arbitrarily hot
sets.  ``sim.drive_lane`` survives unchanged as the sequential oracle;
``sweep.simulate_group`` and ``sweep.simulate_bucket`` route eligible
groups here.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import os
import time
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import dram as dram_mod
from . import dramsched
from . import llc as llc_mod
from .sim import PF_WHEN_OFF, WHEN_BITS, Lane, drive_lane

# Super-step length: epochs advanced per device dispatch (one host sync
# each).  Round capacity: static per-set event bound of the fused round
# matrix; hot epochs beyond it fall back to the host path's chunking.
DEFAULT_SUPERSTEP = int(os.environ.get("REPRO_FUSED_K", "32"))
# Static per-set round capacity.  The round loop's trip count follows
# the data; capacity only sizes the scatter target, so it starts small
# and the driver doubles it (re-jits) on overflow up to the host's
# largest ROUND_BUCKET — beyond that, the stretch falls back to the
# host path, which chunks arbitrarily hot sets.
DEFAULT_MAX_ROUNDS = int(os.environ.get("REPRO_FUSED_ROUNDS", "128"))
MAX_ROUNDS_CAP = llc_mod.ROUND_BUCKETS[-1]
# Active-set width above which a round is processed densely (full
# [S, W] transition) instead of on the compacted set list.  Round 0
# touches most sets; by round ~8 the per-round active-set count decays
# below this, and the sparse path does ~num_sets/cap times less work.
SPARSE_CAP = int(os.environ.get("REPRO_FUSED_SPARSE_CAP", "256"))

_HUGE_KEY = np.int64(1) << 62

# Wall-clock split of the bucketed driver, accumulated across calls:
# stage_s (host->device staging + carry init), dispatch_s (tracing,
# compilation and enqueue of super-steps), device_s (blocked fetching
# StepOut), writeback_s (host history/carry sync).  bench_sim resets
# before a leg and reports the split per kind="sweep" entry.  Each timed
# block is also a profiler span (``_SPANS``), on the device trace's clock.
_PHASES = {"stage_s": 0.0, "dispatch_s": 0.0, "device_s": 0.0,
           "writeback_s": 0.0}
_SPANS = {"stage_s": "fused.stage", "dispatch_s": "fused.dispatch",
          "device_s": "fused.device_wait", "writeback_s": "fused.writeback"}


@contextlib.contextmanager
def _phase(key: str):
    """Add the block's wall time to ``_PHASES[key]`` and trace it as the
    span ``_SPANS[key]``."""
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation(_SPANS[key]):
        yield
    _PHASES[key] += time.perf_counter() - t


def reset_phase_times() -> None:
    for k in _PHASES:
        _PHASES[k] = 0.0


def phase_times() -> dict:
    return dict(_PHASES)


@dataclasses.dataclass(frozen=True)
class FusedDims:
    """Static (compile-time) shape info for one lane batch."""
    cfg: llc_mod.LLCConfig          # shared geometry (knobs ride as data)
    n_lanes: int
    n_cores: int
    accel_cap: int                  # accel segment slots (accel_epoch_cap)
    core_caps: Tuple[int, ...]      # per-core slots (epoch demand at ipc0)
    has_dpcp: bool                  # prefetch segment allocated at all
    n_inputs: int
    k_epochs: int
    max_rounds: int
    sparse_cap: int                 # 0 = rounds always dense
    record_occ: bool                # emit per-epoch occupancy counters
    # scheduled-DRAM geometry (None = fluid model; timing rides as data
    # in SharedConsts so e.g. FR-FCFS and SQUASH share one program)
    sched: Optional[dramsched.SchedDims] = None


class SharedConsts(NamedTuple):
    """Device constants shared by every lane of the batch (traced).
    ``f64b`` leaves hold float64 values as IEEE bits — a trailing pair of
    uint32 words (``_bits``): only the host callbacks read them."""
    line: jnp.ndarray        # i32 [M] accel trace lines
    write: jnp.ndarray       # bool [M]
    layer: jnp.ndarray       # i32 [M]
    streams: jnp.ndarray     # i32 [C, WMAX] core address streams
    nominal: jnp.ndarray     # f64b [C] apkc/1000*et (epoch demand at ipc0)
    apkc1k: jnp.ndarray      # f64b [C] apkc/1000
    ipc0: jnp.ndarray        # f64b [C]
    inv_ipc0: jnp.ndarray    # f64b [C] 1/ipc0
    et: jnp.ndarray          # f64b [] epoch_cycles
    m_total: jnp.ndarray     # i64 []
    max_epochs: jnp.ndarray  # i64 []
    deadline: jnp.ndarray    # f64b []
    period: jnp.ndarray      # f64b []
    ma_global: jnp.ndarray   # f64b []
    llc_capacity: jnp.ndarray      # f64b []
    llc_capacity_int: jnp.ndarray  # i64 [] int(llc_capacity)
    s_llc: jnp.ndarray       # f64b []
    w_cap_s: jnp.ndarray     # f64b [] w_cap * s_llc
    w_cap_s_prio: jnp.ndarray      # f64b [] w_cap * s_llc * prio_cap
    prio_cap: jnp.ndarray    # f64b []
    hit_lat: jnp.ndarray     # f64b [] llc_hit_lat
    dram_lat: jnp.ndarray    # f64b []
    dram_rate: jnp.ndarray   # f64b []
    dram_cap: jnp.ndarray    # f64b [] rate * et
    dram_cap01: jnp.ndarray  # f64b [] 0.1 * dram_cap
    dram_denom: jnp.ndarray  # f64b [] max(rate * et, 1e-9)
    w_cap_dram: jnp.ndarray        # f64b [] w_cap * dram_lat
    w_cap_dram_prio: jnp.ndarray   # f64b [] (w_cap * dram_lat) * prio_cap
    w_dram25: jnp.ndarray    # f64b [] 25 * dram_lat
    mlp_et: jnp.ndarray      # f64b [] mlp_accel * et
    # scheduled-DRAM data (i64 scalars; zeros when dims.sched is None —
    # the sched branch is static, so they are never read then)
    sd_tcas: jnp.ndarray     # i64 [] row-hit (CAS) cost
    sd_trcd: jnp.ndarray     # i64 [] activate cost
    sd_trp: jnp.ndarray      # i64 [] precharge cost
    sd_tbus: jnp.ndarray     # i64 [] per-line rank bus occupancy
    sd_reset: jnp.ndarray    # i64 [] row-table reset period (epochs)
    sd_qcap: jnp.ndarray     # i64 [] per-bank backlog clamp (cycles)
    sd_kind: jnp.ndarray     # i64 [] 0 = frfcfs, 1 = squash
    sd_et: jnp.ndarray       # i64 [] epoch_cycles as an integer


class LaneConsts(NamedTuple):
    """Per-lane policy data (leading lane axis; vmapped)."""
    arp: jnp.ndarray          # bool [L]
    flash: jnp.ndarray        # bool [L]
    hydra: jnp.ndarray        # bool [L]
    dpcp: jnp.ndarray         # bool [L]
    accel_hint: jnp.ndarray   # bool [L] LERN hints active
    accel_rand: jnp.ndarray   # bool [L] AFRp hints active
    switch_point: jnp.ndarray  # i64 [L] §III-C1 deadline switch (-1 = off)
    knobs: llc_mod.LaneKnobs  # leaves [L, ...]
    rc: jnp.ndarray           # i8 [L, M] RC cluster per access
    ri: jnp.ndarray           # i8 [L, M]
    cold_le2: jnp.ndarray     # bool [L, NL] per-layer cold center <= 2.0
    afr: jnp.ndarray          # bool [L, M] pre-drawn AFRp decisions
    writes: jnp.ndarray       # bool [L, C, WMAX] pre-drawn core write flags
    # APM per-lane constants (lane's APMParams x shared ma_global)
    margin_high: jnp.ndarray  # f64b [L]
    margin_low: jnp.ndarray   # f64b [L]
    mr_th: jnp.ndarray        # f64b [L]
    behind_th: jnp.ndarray    # f64b [L] (1+alpha)*ma_global
    bands: jnp.ndarray        # f64b [L, 7] [ (1+b)mag, (1-b)mag .. (1-6b)mag ]
    t_a: jnp.ndarray          # f64b [L, 4] base T_A1..T_A4
    t_b: jnp.ndarray          # f64b [L]
    delta_a: jnp.ndarray      # f64b [L]
    delta_b: jnp.ndarray      # f64b [L]


class FusedCarry(NamedTuple):
    """Per-lane dynamic state carried across the epoch scan."""
    st: llc_mod.LLCState      # batched [L, ...]
    active: jnp.ndarray       # bool [L]
    hr_core: jnp.ndarray      # f64b [L]
    hr_accel: jnp.ndarray     # f64b [L]
    amal: jnp.ndarray         # f64b [L]
    ipc: jnp.ndarray          # f64b [L, C]
    stream_pos: jnp.ndarray   # i64 [L, C]
    pos: jnp.ndarray          # i64 [L]
    input_idx: jnp.ndarray    # i64 [L]
    input_start: jnp.ndarray  # f64b [L]
    now: jnp.ndarray          # f64b [L]
    ri_th: jnp.ndarray        # i64 [L]
    rc_th: jnp.ndarray        # i64 [L]
    special: jnp.ndarray      # bool [L]
    cm_prev: jnp.ndarray      # f64b [L]
    pf_prev: jnp.ndarray      # f64b [L]
    epoch: jnp.ndarray        # i64 [L]
    completions: jnp.ndarray  # f64b [L, n_inputs]
    totals: jnp.ndarray       # i64 [L, 7] ch cm cb ah am ab n_acc
    total_llc: jnp.ndarray    # f64b [L]
    total_dram: jnp.ndarray   # f64b [L]
    overflow: jnp.ndarray     # bool [L] sticky round-capacity flag
    # scheduled-DRAM bank state ([L, 0] / zeros when dims.sched is None,
    # keeping the carry tree uniform for stacking and donation)
    bank_row: jnp.ndarray     # i64 [L, NB] open row per bank, -1 = closed
    bank_queue: jnp.ndarray   # i64 [L, NB] backlog cycles per bank
    bank_rr: jnp.ndarray      # i64 [L] core-miss round-robin rotor


class StepOut(NamedTuple):
    """Per-epoch per-lane scan outputs (history write-back)."""
    active: jnp.ndarray       # bool — this step ran AND committed
    pos_before: jnp.ndarray   # i64  — accel window start (online-LERN)
    n_a: jnp.ndarray          # i64  — hist accel_rate
    req: jnp.ndarray          # f64b  — hist requirement
    ri_th: jnp.ndarray        # i64
    rc_th: jnp.ndarray        # i64
    core_ipc: jnp.ndarray     # f64b
    amal: jnp.ndarray         # f64b
    occ: jnp.ndarray          # int [2] core/accel occupancy (record_occ)
    alive: jnp.ndarray        # bool — lane still active after this step
    ovf: jnp.ndarray          # bool — sticky round-capacity flag after it


def _np_sum_order(terms: List[np.ndarray]) -> np.ndarray:
    """Sum ``terms`` in numpy's pairwise-summation order for n <= 128 —
    the host computes ``np.sum(ipc * shed)`` over one lane's cores; this
    sums the same terms, lane-batched, to the same float64 bits."""
    n = len(terms)
    if n < 8:
        s = 0.0
        for t in terms:
            s = s + t
        return s
    r = list(terms[:8])
    i = 8
    while i + 8 <= n:
        for j in range(8):
            r[j] = r[j] + terms[i + j]
        i += 8
    res = ((r[0] + r[1]) + (r[2] + r[3])) + ((r[4] + r[5]) + (r[6] + r[7]))
    while i < n:
        res = res + terms[i]
        i += 1
    return res


def _mg1(rho, s_llc):
    rho = np.minimum(rho, 0.98)
    return rho * s_llc / np.maximum(2.0 * (1.0 - rho), 1e-2)


def _queue_delay(f, traffic):
    # constants single-sourced from dram.py (dram.queue_delay_consts
    # stages dram_denom / w_dram25; the floors are the named module
    # constants) so host and fused fluid models cannot drift
    rho = np.minimum(traffic / f("dram_denom"), dram_mod.QUEUE_RHO_CAP)
    w = (rho / np.maximum(2.0 * (1.0 - rho), dram_mod.QUEUE_STAB_FLOOR)
         / f("dram_rate"))
    return np.minimum(w, f("w_dram25"))


def _bits(x) -> np.ndarray:
    """float64 values -> their IEEE bits as uint32 pairs [..., 2] (low
    word first).  As 32-bit words they cross neither the device's float64
    (a TPU's is an f32-pair emulation that rounds) nor a host callback's
    argument conversion (which narrows 64-bit types in threads where
    64-bit mode is off)."""
    return np.ascontiguousarray(
        np.asarray(x, np.float64)[..., None]).view(np.uint32)


def _floats(a: dict):
    """Accessor for the float64 values of a callback argument's bits."""
    return lambda k: np.ascontiguousarray(a[k]).view(np.float64)[..., 0]


def _words(x) -> jnp.ndarray:
    """Device non-negative int64 -> uint32 pairs [..., 2] (low word
    first), the host view of ``_bits``' layout as int64."""
    u = x.astype(jnp.uint64)
    return jnp.stack([(u & np.uint64(0xFFFFFFFF)).astype(jnp.uint32),
                      (u >> np.uint64(32)).astype(jnp.uint32)], axis=-1)


def _pack(args: dict):
    """Device operands -> one uint32 word row and its static layout
    ``((key, shape, dtype), ...)``: bool as 0/1, int32 bitcast, uint32
    (``f64b`` pairs included) as is, 64-bit integers as ``_words`` pairs."""
    layout, parts = [], []
    for k, v in args.items():
        v = jnp.asarray(v)
        dt = np.dtype(v.dtype)
        if dt == np.bool_:
            w = v.astype(jnp.uint32)
        elif dt in (np.int32, np.uint32):
            w = jax.lax.bitcast_convert_type(v, jnp.uint32)
        elif dt in (np.int64, np.uint64):
            w = _words(v)
        else:
            raise TypeError(f"host-call operand {k!r}: unsupported {dt}")
        layout.append((k, tuple(v.shape), dt))
        parts.append(w.reshape(-1))
    return jnp.concatenate(parts), tuple(layout)


def _unpack(rows: np.ndarray, layout) -> dict:
    """Host inverse of ``_pack`` for a batch of rows ``[n, words]``: each
    operand back in its dtype, as ``[n, *shape]``."""
    n, a, off = rows.shape[0], {}, 0
    for k, shape, dt in layout:
        size = math.prod(shape) * max(dt.itemsize // 4, 1)
        w = np.ascontiguousarray(rows[:, off:off + size])
        off += size
        a[k] = (w != 0 if dt == np.bool_ else w.view(dt)).reshape(
            (n,) + shape)
    return a


def _host_call(fn, out: dict, args: dict) -> dict:
    """Run ``fn`` — float64 math in numpy — on the host from inside the
    traced epoch step.  Under the lane vmap the callback sees the whole
    lane batch at once (leading axis) and returns every ``out`` leaf
    (bool, int32 or uint32) with the lane axis.

    Operands and results each cross as ONE packed uint32 word row per
    lane (``_pack``/``_unpack``; the layout is static, from the operands'
    shapes and dtypes).  On a TPU every host transfer costs 0.1-0.6 ms
    whatever its size, and a call's transfers run one after another, so
    their count, not their bytes, is what a step pays: one each way, not
    one per leaf.  Packed results are also the only form a callback inside
    ``shard_map`` lowers to on a TPU (jax 0.9 annotates every result's
    receive with the first result's rank).  Operands that are not
    batched travel broadcast to the lane batch: bytes, not transfers."""
    sizes = [math.prod(sd.shape) for sd in out.values()]
    row, layout = _pack(args)

    def call(row):
        # the callback receives a jax array: numpy from here on, so every
        # operation is the host's float64 (not jnp under 32-bit mode)
        row = np.asarray(row)
        rows = row.reshape(-1, row.shape[-1])
        n = rows.shape[0]
        with np.errstate(all="ignore"):  # unselected branches may divide by 0
            res = fn(_unpack(rows, layout))
        words = []
        for (k, sd), size in zip(out.items(), sizes):
            v = np.broadcast_to(np.asarray(res[k], sd.dtype), (n,) + sd.shape)
            v = v.astype(np.uint32) if sd.dtype == bool else v.view(np.uint32)
            words.append(v.reshape(n, size))
        return np.concatenate(words, axis=1).reshape(
            row.shape[:-1] + (sum(sizes),))

    words = jax.pure_callback(
        call, jax.ShapeDtypeStruct((sum(sizes),), jnp.uint32), row,
        vmap_method="expand_dims")
    res, off = {}, 0
    for (k, sd), size in zip(out.items(), sizes):
        w = words[off:off + size].reshape(sd.shape)
        res[k] = (w != 0 if sd.dtype == bool
                  else jax.lax.bitcast_convert_type(w, sd.dtype))
        off += size
    return res


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _f64(shape=()):
    """Result spec of float64 bits (``_bits`` layout)."""
    return jax.ShapeDtypeStruct(tuple(shape) + (2,), jnp.uint32)


def _pack_meta(is_accel, write, hint, prefetch, dlok, src):
    """jnp twin of llc.pack_meta (src may be a scalar segment id)."""
    return (llc_mod.M_VALID
            | jnp.where(is_accel, llc_mod.M_ACCEL, 0)
            | jnp.where(write, llc_mod.M_WRITE, 0)
            | jnp.where(hint, llc_mod.M_HINT, 0)
            | jnp.where(prefetch, llc_mod.M_PREFETCH, 0)
            | jnp.where(dlok, llc_mod.M_DLOK, 0)
            | (src << llc_mod.M_SRC_SHIFT)).astype(jnp.int32)


def _build_rounds_device(dims: FusedDims, sh: SharedConsts, lc, n_a, n_c,
                         pos, stream_pos, ri_th, rc_th, special, gid=None):
    """Build one epoch's round-major [R, S] event matrices on device.

    ``gid`` is the flat-bucket variant's group index: the big trace and
    stream arrays then carry a leading group axis (vmapped with
    ``in_axes=None``) and every access becomes a (group, element) gather
    — same elements, so values are unchanged — letting a bucket of G
    groups run begin/finish over one flat (G*L) lane axis with no group
    vmap.

    Reproduces the host pipeline's per-set event order exactly: static
    segment layout (accel, optional DPCP prefetch, core 0..C-1) with
    validity masks, the shared integer interleave keys
    (``sim.when_keys``), and ONE stable composite (set << 42 | when)
    sort — set-major with the host's when-order inside each set, ties
    resolving in segment order via stability — yielding each event's
    per-set rank, i.e. ``llc.build_rounds``'s (rank, set) coordinates.
    The §III-C1 deadline-switch bit is closed-form (only demand accel
    accesses are counted by the host's cumsum, and they are already
    when-ordered within their segment), so no global when-sort is
    needed; core/prefetch events carry dlok=0, which the transition
    never reads for them.  Events whose rank exceeds the static
    ``max_rounds`` capacity are dropped and flagged (the driver
    escalates the capacity, then falls back to the host path, which
    chunks hot sets instead).
    """
    num_sets = dims.cfg.num_sets
    na_safe = jnp.maximum(n_a, 1)
    ia = jnp.arange(dims.accel_cap, dtype=jnp.int64)
    when_a = (ia << WHEN_BITS) // na_safe
    idx_a = pos + ia
    valid_a = ia < n_a
    if gid is None:
        line_a = jnp.take(sh.line, idx_a)
        write_a = jnp.take(sh.write, idx_a)
        layer_now = jnp.take(sh.layer, pos)
    else:
        line_a = sh.line[gid, idx_a]
        write_a = sh.write[gid, idx_a]
        layer_now = sh.layer[gid, pos]
    # per-event bypass hint: LERN clusters x epoch thresholds, or AFRp
    cold_now = jnp.take(lc.cold_le2, layer_now)
    rc_a = jnp.take(lc.rc, idx_a)
    ri_a = jnp.take(lc.ri, idx_a)
    hint_lern = (ri_a > ri_th) | (rc_a < rc_th)
    hint_lern = hint_lern | (special & cold_now & (rc_a == 0))
    hint_a = jnp.where(lc.accel_hint, hint_lern,
                       jnp.where(lc.accel_rand, jnp.take(lc.afr, idx_a),
                                 False))
    # §III-C1 deadline switch, in closed form: the i-th demand accel
    # access is the (i+1)-th counted by the host's running cumsum (only
    # accel & ~prefetch events count), so its bit is just i >= switch.
    # Core and prefetch events get dlok=0 — the transition never reads
    # the bit for them (bypass is masked to demand accel accesses).
    dlok_a = ia >= lc.switch_point

    false_a = jnp.zeros(dims.accel_cap, bool)
    whens = [when_a]
    lines = [line_a]
    metas = [_pack_meta(jnp.ones(dims.accel_cap, bool), write_a, hint_a,
                        false_a, dlok_a, jnp.int32(0))]
    valids = [valid_a]
    if dims.has_dpcp:
        whens.append(when_a + PF_WHEN_OFF)
        lines.append(line_a + 1)
        metas.append(_pack_meta(jnp.ones(dims.accel_cap, bool), false_a,
                                false_a, jnp.ones(dims.accel_cap, bool),
                                false_a, jnp.int32(0)))
        valids.append(valid_a & lc.dpcp)
    for k, cap in enumerate(dims.core_caps):
        jk = jnp.arange(cap, dtype=jnp.int64)
        nk = n_c[k]
        whens.append((jk << WHEN_BITS) // jnp.maximum(nk, 1))
        idx_k = stream_pos[k] + jk
        lines.append(jnp.take(sh.streams[k], idx_k) if gid is None
                     else sh.streams[gid, k, idx_k])
        fk = jnp.zeros(cap, bool)
        metas.append(_pack_meta(fk, jnp.take(lc.writes[k], idx_k), fk, fk,
                                fk, jnp.int32(k)))
        valids.append(jk < nk)

    when = jnp.concatenate(whens)
    line = jnp.concatenate(lines)
    meta = jnp.concatenate(metas)
    valid = jnp.concatenate(valids)
    n_ev = when.shape[0]

    # one composite stable sort gives build_rounds' (set, when) order:
    # set-major, host event order within a set (when keys, ties in
    # segment order via stability), invalid slots last
    set_of = (line & (num_sets - 1)).astype(jnp.int64)
    key = jnp.where(valid, (set_of << (WHEN_BITS + 1)) | when, _HUGE_KEY)
    order2 = jnp.argsort(key, stable=True)
    seq = jnp.arange(n_ev, dtype=jnp.int64)
    valid_g = valid[order2]
    set_g = jnp.where(valid_g, key[order2] >> (WHEN_BITS + 1),
                      jnp.int64(num_sets))
    first = jnp.concatenate(
        [jnp.ones(1, bool), set_g[1:] != set_g[:-1]])
    grp_start = jax.lax.cummax(jnp.where(first, seq, jnp.int64(0)))
    rank_g = seq - grp_start
    ovf = jnp.any(valid_g & (rank_g >= dims.max_rounds))
    n_rounds = jnp.minimum(
        jnp.max(jnp.where(valid_g, rank_g, jnp.int64(-1))) + 1,
        jnp.int64(dims.max_rounds)).astype(jnp.int32)
    line_g = line[order2]
    meta_g = meta[order2]

    # depth-major column layout: relabel the columns of the round
    # matrices so sets sort by their epoch event depth, descending.
    # Round r's active sets (depth > r) are then exactly the first
    # counts[r] columns — every round can run on a contiguous
    # static-width *prefix slice* of the permuted state (no per-round
    # gathers or scatters), with one state permutation per epoch.
    # The transition is elementwise in the set dimension and its only
    # cross-set effects (SHCT scatter-adds, stat sums) are
    # order-independent, so the relabeling cannot change results.
    rank_sp = jnp.where(valid_g, rank_g, jnp.int64(dims.max_rounds))
    counts = jnp.zeros(dims.max_rounds, jnp.int32).at[rank_sp].add(
        valid_g.astype(jnp.int32), mode="drop")
    depth = jnp.zeros(num_sets, jnp.int32).at[set_g].add(
        valid_g.astype(jnp.int32), mode="drop")
    perm = jnp.argsort(-depth, stable=True).astype(jnp.int32)   # [S]
    inv_perm = jnp.zeros(num_sets, jnp.int32).at[perm].set(
        jnp.arange(num_sets, dtype=jnp.int32))
    col_g = inv_perm[jnp.minimum(set_g, num_sets - 1)]
    line_m = jnp.full((dims.max_rounds, num_sets), -1, jnp.int32).at[
        rank_sp, col_g].set(line_g, mode="drop")
    meta_m = jnp.zeros((dims.max_rounds, num_sets), jnp.int32).at[
        rank_sp, col_g].set(meta_g, mode="drop")
    return (line_m, meta_m, counts, perm, inv_perm, n_rounds, ovf)


def _prefix_round_step_fn(cfg, knobs, width: int):
    """``llc.round_transition`` on a depth-major prefix slice.

    With columns relabeled so sets sort by epoch event depth
    (descending), round r's active sets are exactly the first
    ``counts[r]`` columns — so a round whose count fits ``width``
    applies the shared transition to the contiguous ``[:width]`` slice
    of the permuted state, a static-shape slice update with no
    per-round gather or scatter.  Every skipped column's full-width
    contribution is a strict no-op (meta 0, delta-0 SHCT adds,
    untouched rows), as are padding columns inside the slice, so
    results are bitwise-equal to the full-width step.  The permuted
    sampler-set row rides along as data (the full-width step bakes it
    in by set index)."""
    def step(carry, ev):
        (tags_p, lru_p, owner_p, sig_p, reused_p, tick0, shct_core,
         shct_accel, stats, percore) = carry
        line_f, meta_f, sampler_p = ev          # [S] rows (permuted)
        tick = tick0 + 1
        rows, shct, upd, pc = llc_mod.round_transition(
            cfg, knobs, sampler_p[:width],
            (tags_p[:width], lru_p[:width], owner_p[:width],
             sig_p[:width], reused_p[:width]),
            (shct_core, shct_accel), line_f[:width], meta_f[:width], tick)
        return (tags_p.at[:width].set(rows[0]),
                lru_p.at[:width].set(rows[1]),
                owner_p.at[:width].set(rows[2]),
                sig_p.at[:width].set(rows[3]),
                reused_p.at[:width].set(rows[4]),
                tick, shct[0], shct[1], stats + upd, percore + pc)

    return step


def _run_rounds_batch(dims: FusedDims, knobs, states, bg):
    """Apply the shared round transition to every lane's populated rounds.

    One batch-level while-loop (trip count = the deepest lane's round
    count) whose body vmaps the per-lane transition on a depth-major
    prefix slice of the permuted state.  A three-tier ``lax.cond``
    (full width / SPARSE_CAP / 64) picks the narrowest static slice the
    round's widest lane fits — the loop sits outside vmap, so only one
    branch executes.  The state is permuted into column order once per
    epoch and un-permuted after the loop; see _prefix_round_step_fn for
    why this is transition-for-transition identical to the host engines
    (their tick advance on padded rounds only shifts absolute LRU tick
    values, never their per-way order)."""
    cfg = dims.cfg
    n_lanes = bg.line_m.shape[0]
    num_sets = cfg.num_sets
    max_r = jnp.max(bg.n_rounds).astype(jnp.int32)
    stats0 = jnp.zeros((n_lanes, len(llc_mod.STAT_NAMES)), jnp.int32)
    pc0 = jnp.zeros((n_lanes, llc_mod.NUM_CORES, 2), jnp.int32)
    sampler = (np.arange(num_sets) & ((1 << cfg.sampler_shift) - 1)) == 0
    sampler_p = jnp.asarray(sampler)[bg.perm]               # [L, S]

    def permute(x, idx):
        return jnp.take_along_axis(
            x, idx.astype(jnp.int32)[:, :, None], axis=1)

    carry0 = (permute(states.tags, bg.perm), permute(states.lru, bg.perm),
              permute(states.owner, bg.perm), permute(states.sig, bg.perm),
              permute(states.reused, bg.perm), states.tick,
              states.shct_core, states.shct_accel, stats0, pc0)

    widths = [num_sets]
    if dims.sparse_cap and dims.sparse_cap < num_sets:
        widths.append(dims.sparse_cap)
        if dims.sparse_cap > 64:
            widths.append(64)

    def cond(c):
        return c[0] < max_r

    def body(c):
        r, carry = c[0], c[1]
        line_r = jax.lax.dynamic_index_in_dim(bg.line_m, r, axis=1,
                                              keepdims=False)
        meta_r = jax.lax.dynamic_index_in_dim(bg.meta_m, r, axis=1,
                                              keepdims=False)

        def at_width(width):
            def run(carry):
                step = jax.vmap(
                    lambda kn, cr, lr, mr, sp:
                    _prefix_round_step_fn(cfg, kn, width)(cr, (lr, mr, sp)))
                return step(knobs, carry, line_r, meta_r, sampler_p)
            return run

        if len(widths) == 1:
            carry = at_width(num_sets)(carry)
        else:
            cnt = jnp.max(jax.lax.dynamic_index_in_dim(
                bg.counts, r, axis=1, keepdims=False))
            run = at_width(widths[0])
            for wdt in widths[1:]:
                run = (lambda run_wide, wdt:
                       lambda carry: jax.lax.cond(
                           cnt > wdt, run_wide, at_width(wdt), carry)
                       )(run, wdt)
            carry = run(carry)
        return (r + 1, carry)

    _, carry = jax.lax.while_loop(cond, body, (jnp.int32(0), carry0))
    (tags_p, lru_p, owner_p, sig_p, reused_p, tick, shct_core,
     shct_accel, stats, percore) = carry
    states = llc_mod.LLCState(
        permute(tags_p, bg.inv_perm), permute(lru_p, bg.inv_perm),
        permute(owner_p, bg.inv_perm), permute(sig_p, bg.inv_perm),
        permute(reused_p, bg.inv_perm), tick, shct_core, shct_accel)
    return states, stats, percore


class _Begin(NamedTuple):
    """Per-lane outputs of the admission/threshold/event-build half."""
    step_active: jnp.ndarray
    arrived: jnp.ndarray
    accel_prio: jnp.ndarray
    urgent: jnp.ndarray       # SQUASH urgency (scheduled DRAM)
    n_a: jnp.ndarray
    n_c: jnp.ndarray
    shed: jnp.ndarray         # f64b
    ri_th: jnp.ndarray
    rc_th: jnp.ndarray
    special: jnp.ndarray
    req_out: jnp.ndarray      # f64b
    line_m: jnp.ndarray       # [R, S] permuted (depth-major) columns
    meta_m: jnp.ndarray       # [R, S] permuted columns
    counts: jnp.ndarray       # [R] active sets per round
    perm: jnp.ndarray         # [S] column -> set
    inv_perm: jnp.ndarray     # [S] set -> column
    n_rounds: jnp.ndarray
    ovf: jnp.ndarray
    samp: jnp.ndarray         # i64 [NS] sched-DRAM window samples ([0]=off)


def _begin_host(accel_cap: int, a: dict) -> dict:
    """Lane.begin_epoch's float64 decisions for a lane batch, in numpy with
    the host's exact operation order: arbitration mode, accelerator
    admission, core demand shedding, and the HyDRA/APM thresholds."""
    f = _floats(a)
    now, start, et = f("now"), f("input_start"), f("et")
    ma_global, amal = f("ma_global"), f("amal")
    pos, m_total = a["pos"], a["m_total"]

    # ---- arbitration mode ---------------------------------------------
    arrived = now >= start
    remaining = m_total - pos
    done_rate = np.where(
        arrived, pos / np.maximum((now - start) / et, 1.0), ma_global)
    accel_prio = a["arp"] | (a["flash"] & (done_rate < ma_global))

    # ---- accelerator admission ----------------------------------------
    can_issue = arrived & (remaining > 0)
    miss_rate_a = np.maximum(1.0 - f("hr_accel"), 0.05)
    dram_cap = f("dram_cap")
    dram_share = np.where(
        accel_prio, dram_cap,
        np.maximum(dram_cap - f("cm_prev") - f("pf_prev"), f("dram_cap01")))
    ma_hat = f("mlp_et") / np.maximum(amal, 1.0)
    demand_a = np.minimum(
        np.minimum(remaining, ma_hat.astype(np.int64)),
        np.minimum((dram_share / miss_rate_a).astype(np.int64), accel_cap))
    demand_a = np.where(can_issue, demand_a, 0)

    # ---- core demand / LLC bandwidth shedding -------------------------
    n_c_dem = (f("nominal") * f("ipc") / f("ipc0")).astype(np.int64)
    core_sum = n_c_dem.sum(-1)
    total_demand = demand_a + core_sum
    llc_cap = f("llc_capacity")
    over_cap = total_demand > llc_cap
    n_a_p = np.minimum(demand_a, a["llc_capacity_int"])
    shed_p = np.minimum((llc_cap - n_a_p) / np.maximum(core_sum, 1), 1.0)
    f_f = llc_cap / total_demand
    n_a_f = (demand_a * f_f).astype(np.int64)
    n_a = np.where(over_cap, np.where(accel_prio, n_a_p, n_a_f), demand_a)
    shed = np.where(over_cap, np.where(accel_prio, shed_p, f_f), 1.0)
    n_c = (n_c_dem * shed[..., None]).astype(np.int64)

    # ---- HyDRA / APM epoch decision -----------------------------------
    hcond = a["hydra"] & can_issue
    deadline = f("deadline")
    rt = np.maximum((start + deadline) - now, et)
    elapsed = np.maximum(deadline - rt, 0.0)
    done = (m_total - remaining) * et
    ma_past = np.where(elapsed >= et, done / elapsed, ma_global)
    hc = (1.0 - f("hr_core")) > f("mr_th")
    behind = ma_past < f("behind_th")
    marg = np.where(hc & behind, f("margin_high"),
                    np.where(hc | behind, f("margin_low"), 0.0))
    eff_rt = np.maximum(rt - marg * deadline, et)
    ma_i = remaining / eff_rt * et
    # Algorithm 1 threshold scaling: band index d in {6, 5..1, 0}
    bands = f("bands")
    in_band = [(ma_i > bands[..., k + 1]) & (ma_i <= bands[..., k])
               for k in range(1, 6)]
    d = np.where(ma_i <= bands[..., 6], 6,
                 sum(np.where(b, k, 0) for k, b in zip(range(1, 6), in_band)))
    d_f = d.astype(np.float64)
    plus = (d == 0) & (ma_i > bands[..., 0])
    t_a0, delta_a = f("t_a"), f("delta_a")
    t_a = np.where((d > 0)[..., None],
                   np.maximum(t_a0 - (d_f * delta_a)[..., None], 1.0),
                   np.where(plus[..., None], t_a0 + delta_a[..., None],
                            t_a0))                                  # [4]
    t_b = np.where(d > 0, f("t_b") - d_f * f("delta_b"), f("t_b"))
    # Fig. 9 reuse-threshold selection
    c4 = ma_hat > t_a[..., 3] * ma_i
    c3 = ma_hat > t_a[..., 2] * ma_i
    c2 = ma_hat > t_a[..., 1] * ma_i
    c1 = ma_hat > t_a[..., 0] * ma_i
    cb = ma_hat > t_b * ma_i
    ri_sel = np.where(c4, -1, np.where(c3, 0, np.where(
        c2, 1, np.where(c1, 2, 3))))
    rc_sel = np.where(c4, 4, np.where(c3, 3, np.where(
        c2, 2, np.where(c1, 1, np.where(cb, 0, -1)))))
    sp_sel = (~c4) & (~c3) & (~c2) & (~c1) & cb
    req_out = np.where(hcond, ma_i, np.where(arrived, ma_global, 0.0))
    return {"arrived": arrived, "accel_prio": accel_prio,
            # SQUASH urgency: explicit accel priority, or a hydra lane
            # whose achievable rate falls short of this epoch's requirement
            "urgent": accel_prio | (a["hydra"] & (ma_hat < req_out)),
            "n_a": n_a, "n_c": n_c, "shed": _bits(shed),
            "ri_th": np.where(hcond, ri_sel, a["ri_th"]),
            "rc_th": np.where(hcond, rc_sel, a["rc_th"]),
            "special": np.where(hcond, sp_sel, a["special"]),
            "req_out": _bits(req_out)}


_SH_BEGIN = ("et", "m_total", "ma_global", "dram_cap", "dram_cap01",
             "mlp_et", "nominal", "ipc0", "llc_capacity", "llc_capacity_int",
             "deadline")
_LC_BEGIN = ("arp", "flash", "hydra", "mr_th", "behind_th", "margin_high",
             "margin_low", "bands", "t_a", "t_b", "delta_a", "delta_b")
_CY_BEGIN = ("now", "input_start", "pos", "hr_accel", "hr_core", "cm_prev",
             "pf_prev", "amal", "ipc", "ri_th", "rc_th", "special")


def _begin_lane(dims: FusedDims, sh: SharedConsts, stop_epoch, lc, cy,
                gid=None) -> _Begin:
    """Port of Lane.begin_epoch for one lane (the caller vmaps): epoch
    arbitration, admission, APM thresholds, and the on-device round
    build.  The float64 decisions run on the host (``_begin_host``), so
    the integer results are the host's own int() truncations.
    ``gid`` routes the flat-bucket variant's (group, element) trace
    gathers; see _build_rounds_device.
    """
    # ~overflow: an overflowed lane freezes in place (its last epoch
    # never committed) until the driver escalates capacity or demotes it
    step_active = cy.active & (cy.epoch < stop_epoch) & ~cy.overflow

    # every integer crossing the callback fits int32 (lane_supported
    # bounds the trace; counts are per epoch)
    i32 = jnp.int32
    args = {k: getattr(sh, k) for k in _SH_BEGIN}
    args.update({k: getattr(lc, k) for k in _LC_BEGIN})
    args.update({k: getattr(cy, k) for k in _CY_BEGIN})
    for k in ("m_total", "llc_capacity_int", "pos", "ri_th", "rc_th"):
        args[k] = args[k].astype(i32)
    fl = _host_call(
        functools.partial(_begin_host, dims.accel_cap),
        {"arrived": _sds((), bool), "accel_prio": _sds((), bool),
         "urgent": _sds((), bool), "n_a": _sds((), i32),
         "n_c": _sds((dims.n_cores,), i32), "shed": _f64(),
         "ri_th": _sds((), i32), "rc_th": _sds((), i32),
         "special": _sds((), bool), "req_out": _f64()},
        args)
    n_a = fl["n_a"].astype(jnp.int64)
    n_c = fl["n_c"].astype(jnp.int64)
    ri_th = fl["ri_th"].astype(jnp.int64)
    rc_th = fl["rc_th"].astype(jnp.int64)
    special = fl["special"]

    # ---- build the epoch event list (static segment layout) -----------
    (line_m, meta_m, counts, perm, inv_perm, n_rounds,
     ovf) = _build_rounds_device(
        dims, sh, lc, n_a, n_c, cy.pos, cy.stream_pos,
        ri_th, rc_th, special, gid)
    # frozen lanes contribute no rounds to the batch loop
    n_rounds = jnp.where(step_active, n_rounds, jnp.int32(0))
    counts = jnp.where(step_active, counts, jnp.int32(0))

    # ---- scheduled-DRAM window samples --------------------------------
    # strided line addresses from this epoch's accel window, same integer
    # indices as dramsched.sample_window on the host (n_a = 0 degenerates
    # to ns copies of line[pos], which carries zero weight in the model)
    if dims.sched is not None:
        ns = dims.sched.n_samples
        si = jnp.arange(ns, dtype=jnp.int64)
        s_idx = cy.pos + (si * n_a) // jnp.int64(ns)
        samp = (jnp.take(sh.line, s_idx) if gid is None
                else sh.line[gid, s_idx]).astype(jnp.int64)
    else:
        samp = jnp.zeros(0, jnp.int64)
    return _Begin(step_active=step_active, arrived=fl["arrived"],
                  accel_prio=fl["accel_prio"], urgent=fl["urgent"],
                  n_a=n_a, n_c=n_c, shed=fl["shed"],
                  ri_th=ri_th, rc_th=rc_th, special=special,
                  req_out=fl["req_out"], line_m=line_m, meta_m=meta_m,
                  counts=counts, perm=perm, inv_perm=inv_perm,
                  n_rounds=n_rounds, ovf=ovf, samp=samp)


def _finish_host(n_cores: int, sched: bool, a: dict) -> dict:
    """Lane.finish_epoch's float64 timing update for a lane batch, in
    numpy with the host's exact operation order: hit rates, LLC and DRAM
    queueing (fluid, or the scheduled bank model's exact num/den), core
    IPC, AMAL, and the float accumulators."""
    f = _floats(a)
    st = a["stats"]
    ch, cm, cb_ = st[..., 0], st[..., 1], st[..., 2]
    ah, am, ab = st[..., 3], st[..., 4], st[..., 5]
    awb, pf_fills = st[..., 6], st[..., 8]
    accel_prio = a["accel_prio"]
    hr_core = ch / np.maximum(ch + cm, 1)
    hr_accel = ah / np.maximum(ah + am, 1)
    llc_units = ((ch + cm + ah + am) - 0.7 * (cb_ + ab) - 0.3 * awb)
    llc_cap = f("llc_capacity")
    rho_llc = llc_units / llc_cap
    rho_a_llc = (ah + am) / llc_cap
    dram_traffic = cm + am + pf_fills
    s_llc, w_cap_s, prio_cap = f("s_llc"), f("w_cap_s"), f("prio_cap")
    # priority-arbitration branch (LLC-side waits stay fluid under the
    # scheduled backend — only the DRAM waits come from the bank model)
    w_llc_a_p = np.minimum(_mg1(rho_a_llc, s_llc), w_cap_s)
    prio = np.minimum(1.0 / np.maximum(1.0 - rho_a_llc, 1e-3), prio_cap)
    w_llc_c_p = np.minimum(_mg1(rho_llc, s_llc) * prio, f("w_cap_s_prio"))
    # FIFO branch
    w_fifo = np.minimum(_mg1(rho_llc, s_llc), w_cap_s)
    w_llc_a = np.where(accel_prio, w_llc_a_p, w_fifo)
    w_llc_c = np.where(accel_prio, w_llc_c_p, w_fifo)
    if not sched:
        w_dram_fifo = np.minimum(_queue_delay(f, dram_traffic),
                                 f("w_cap_dram"))
        rho_a_dram = np.minimum(am / f("dram_denom"), 1.0)
        w_dram_a_p = np.minimum(_queue_delay(f, am), f("w_cap_dram"))
        prio_d = np.minimum(1.0 / np.maximum(1.0 - rho_a_dram, 1e-3),
                            prio_cap)
        w_dram_c_p = np.minimum(w_dram_fifo * prio_d, f("w_cap_dram_prio"))
        w_dram_a = np.where(accel_prio, w_dram_a_p, w_dram_fifo)
        w_dram_c = np.where(accel_prio, w_dram_c_p, w_dram_fifo)
    else:
        # the bank model's waits are exact integer num/den pairs
        nd = np.ascontiguousarray(a["sched_nd"]).view(np.int64)[..., 0]
        nd = nd.astype(np.float64)
        w_dram_a = np.minimum(nd[..., 0] / nd[..., 1], f("w_cap_dram"))
        w_dram_c = np.minimum(nd[..., 2] / nd[..., 3], f("w_cap_dram_prio"))
    hit_lat, dram_lat = f("hit_lat"), f("dram_lat")
    miss_lat_c = hit_lat + w_llc_c + dram_lat + w_dram_c
    miss_lat_a = hit_lat + w_llc_a + dram_lat + w_dram_a
    pc = a["percore"][..., :n_cores, :]
    hk = pc[..., 0] / np.maximum(pc[..., 0] + pc[..., 1], 1)
    amat = (hk * (hit_lat + w_llc_c)[..., None]
            + (1 - hk) * miss_lat_c[..., None])
    ipc = 1.0 / (f("inv_ipc0") + f("apkc1k") * amat / 4.0)
    amal = np.where(
        a["n_a"] > 0,
        hr_accel * (hit_lat + w_llc_a) + (1 - hr_accel) * miss_lat_a,
        f("amal"))
    # total_instr (sum * et accumulated) stays in the write-back, which
    # accumulates it from the per-epoch core_ipc outputs op for op
    ipc_shed = ipc * f("shed")[..., None]
    core_ipc = _np_sum_order([ipc_shed[..., k] for k in range(n_cores)])
    now = f("now") + f("et")
    start = f("input_start")
    return {"hr_core": _bits(hr_core), "hr_accel": _bits(hr_accel),
            "amal": _bits(amal), "ipc": _bits(ipc),
            "core_ipc": _bits(core_ipc), "now": _bits(now),
            "comp_val": _bits(now - start),
            "input_start": _bits(np.where(
                a["completed"], np.maximum(start + f("period"), now), start)),
            "cm_prev": _bits(cm), "pf_prev": _bits(pf_fills),
            "total_llc": _bits(f("total_llc") + llc_units),
            "total_dram": _bits(f("total_dram") + dram_traffic)}


_SH_FINISH = ("llc_capacity", "s_llc", "w_cap_s", "prio_cap", "w_cap_s_prio",
              "w_cap_dram", "w_cap_dram_prio", "dram_denom", "dram_rate",
              "w_dram25", "hit_lat", "dram_lat", "inv_ipc0", "apkc1k", "et",
              "period")
_CY_FINISH = ("amal", "now", "input_start", "total_llc", "total_dram")


def _finish_lane(dims: FusedDims, sh: SharedConsts, lc, cy, bg: _Begin,
                 new_st, stats, percore):
    """Port of Lane.finish_epoch for one lane (the caller vmaps): fluid
    timing update (``_finish_host``), totals, progress bookkeeping — then
    a freeze select so a frozen step is an identity on the carry."""
    step_active = bg.step_active
    n_a, n_c = bg.n_a, bg.n_c
    ri_th, rc_th, special = bg.ri_th, bg.rc_th, bg.special
    st64 = stats.astype(jnp.int64)
    ch, cm, cb_ = st64[0], st64[1], st64[2]
    ah, am, ab = st64[3], st64[4], st64[5]
    pf_fills = st64[8]
    if dims.sched is None:
        sched_nd = jnp.zeros((4, 2), jnp.uint32)
        bank_row2, bank_queue2 = cy.bank_row, cy.bank_queue
        bank_rr2 = cy.bank_rr
    else:
        timing = (sh.sd_tcas, sh.sd_trcd, sh.sd_trp, sh.sd_tbus,
                  sh.sd_reset, sh.sd_qcap, sh.sd_kind)
        (num_a, den_a, num_c, den_c, bank_row2, bank_queue2,
         bank_rr2) = dramsched.epoch_compute(
            jnp, dims.sched, timing, cy.bank_row, cy.bank_queue,
            cy.bank_rr, bg.samp, am, cm, pf_fills, bg.urgent, cy.epoch,
            sh.sd_et)
        sched_nd = _words(jnp.stack([num_a, den_a, num_c, den_c]))
    pos2 = cy.pos + n_a
    completed = (n_a > 0) & (pos2 >= sh.m_total)
    fo = _host_call(
        functools.partial(_finish_host, dims.n_cores, dims.sched is not None),
        {k: _f64() for k in ("hr_core", "hr_accel", "amal", "core_ipc",
                             "now", "comp_val", "input_start", "cm_prev",
                             "pf_prev", "total_llc", "total_dram")}
        | {"ipc": _f64((dims.n_cores,))},
        dict({k: getattr(sh, k) for k in _SH_FINISH},
             **{k: getattr(cy, k) for k in _CY_FINISH},
             stats=stats.astype(jnp.int32),
             percore=percore.astype(jnp.int32),
             accel_prio=bg.accel_prio, n_a=n_a.astype(jnp.int32),
             shed=bg.shed, sched_nd=sched_nd, completed=completed))
    totals = cy.totals + jnp.stack([ch, cm, cb_, ah, am, ab, n_a])

    # ---- progress bookkeeping -----------------------------------------
    completions = cy.completions.at[
        jnp.where(completed, cy.input_idx, jnp.int64(dims.n_inputs))
    ].set(fo["comp_val"], mode="drop")
    input_idx = cy.input_idx + completed.astype(jnp.int64)
    pos = jnp.where(completed, jnp.int64(0), pos2)
    epoch = cy.epoch + 1
    active = (epoch < sh.max_epochs) & (input_idx < jnp.int64(dims.n_inputs))

    new = FusedCarry(
        st=new_st, active=active, hr_core=fo["hr_core"],
        hr_accel=fo["hr_accel"], amal=fo["amal"], ipc=fo["ipc"],
        stream_pos=cy.stream_pos + n_c, pos=pos, input_idx=input_idx,
        input_start=fo["input_start"], now=fo["now"], ri_th=ri_th,
        rc_th=rc_th, special=special, cm_prev=fo["cm_prev"],
        pf_prev=fo["pf_prev"], epoch=epoch,
        completions=completions, totals=totals,
        total_llc=fo["total_llc"], total_dram=fo["total_dram"],
        overflow=cy.overflow,
        bank_row=bank_row2, bank_queue=bank_queue2, bank_rr=bank_rr2)
    # per-epoch occupancy readback, fused (llc.occupancy's counts on the
    # epoch-end state; the write-back only consumes active steps)
    if dims.record_occ:
        occ_valid = new_st.tags != -1
        occ_accel = occ_valid & (new_st.owner == 1)
        occ = jnp.stack([jnp.sum(occ_valid & ~occ_accel),
                         jnp.sum(occ_accel)])
    else:
        occ = jnp.zeros(2, jnp.int32)

    # commit only steps that ran AND fit the round capacity: a frozen or
    # overflowing step is an identity on the carry, so the carry is
    # always a valid resume point (no rollback buffer — the bucketed
    # driver donates it) and the overflowing lane simply re-attempts the
    # same epoch after the driver escalates capacity
    commit = step_active & ~bg.ovf
    out_cy = jax.tree.map(
        lambda a, b: jnp.where(commit, a, b), new, cy)
    out_cy = out_cy._replace(
        overflow=cy.overflow | (step_active & bg.ovf))
    out = StepOut(active=commit, pos_before=cy.pos, n_a=n_a,
                  req=bg.req_out, ri_th=ri_th, rc_th=rc_th,
                  core_ipc=fo["core_ipc"], amal=out_cy.amal, occ=occ,
                  alive=out_cy.active, ovf=out_cy.overflow)
    return out_cy, out


# ---------------------------------------------------------------------------
# staging: host Lane objects -> device constants / carry
# ---------------------------------------------------------------------------
def lane_supported(lane: Lane) -> bool:
    """Can this lane run through the fused engine?  The host path stays
    authoritative for the core-traffic-free calibration runs and for any
    workload whose line addresses exceed the engine's int32 staging
    range — ``auto`` routing must degrade to the host loop for those,
    not crash in staging.  (Occupancy recording is fused: per-epoch [2]
    counters ride the scan outputs, see ``StepOut.occ``.)"""
    i32max = np.iinfo(np.int32).max
    return (lane.core_traffic
            and lane.n_cores <= llc_mod.NUM_CORES
            and lane.m_total < i32max
            # -1 headroom: DPCP prefetches stage line + 1
            and (lane.m_total == 0
                 or int(lane.tr.line.max()) < i32max - 1)
            and all(s.size == 0 or int(s.max()) < i32max
                    for s in lane.streams))


def _fb(x) -> jnp.ndarray:
    """Host float64 value(s) -> device IEEE bits: the values never pass
    through the device's float64, which on a TPU would round them."""
    return jnp.asarray(_bits(x))


# FusedCarry / StepOut leaves holding float64 bits
_CARRY_FLOATS = ("hr_core", "hr_accel", "amal", "ipc", "input_start", "now",
                 "cm_prev", "pf_prev", "completions", "total_llc",
                 "total_dram")
_STEP_FLOATS = ("req", "core_ipc", "amal")


def _to_host(nt, floats):
    """Device NamedTuple -> numpy, with ``floats`` leaves decoded from
    their bits."""
    h = jax.tree.map(np.asarray, nt)
    return h._replace(**{f: np.ascontiguousarray(getattr(h, f))
                         .view(np.float64)[..., 0] for f in floats})


def _i32(a: np.ndarray) -> np.ndarray:
    a = np.asarray(a, np.int64)
    if a.size and (a.min() < 0 or a.max() >= np.iinfo(np.int32).max):
        raise ValueError("line addresses out of int32 device range")
    return a.astype(np.int32)


class _Staged:
    """Everything the driver holds between super-steps.

    ``pads`` (m_pad, wmax_pad, nl_pad) sizes the trace/stream/layer
    staging arrays beyond this group's natural extents so several groups
    can stack along a leading group axis (drive_lanes_bucketed).  Padded
    slots are zeros behind the validity masks — ``jnp.take`` clips and
    no valid index ever reaches them, so padding cannot change results.
    """

    def __init__(self, lanes: List[Lane], k_epochs: int, max_rounds: int,
                 pads: Optional[Tuple[int, int, int]] = None):
        lane0 = lanes[0]
        p = lane0.p
        dram = lane0.dram
        et = lane0.et
        profiles = lane0.profiles
        n_cores = lane0.n_cores
        from . import cores as cores_mod
        core_caps = tuple(
            max(int(cores_mod.epoch_accesses(pr, pr.ipc0, et)), 0)
            for pr in profiles)
        num_sets = lane0.llc_cfg.num_sets
        sched = dram if isinstance(dram, dram_mod.SchedDramModel) else None
        self.dims = FusedDims(
            cfg=lane0.llc_cfg, n_lanes=len(lanes), n_cores=n_cores,
            accel_cap=int(p.accel_epoch_cap), core_caps=core_caps,
            has_dpcp=any(lane.policy.dpcp for lane in lanes),
            n_inputs=int(p.n_inputs), k_epochs=int(k_epochs),
            max_rounds=int(max_rounds),
            sparse_cap=SPARSE_CAP if num_sets > SPARSE_CAP else 0,
            record_occ=bool(p.record_occupancy),
            sched=(dramsched.sched_dims(sched)
                   if sched is not None else None))

        tr = lane0.tr
        m = tr.num_accesses
        wmax_nat = max([s.shape[0] for s in lane0.streams] or [1])
        nl_nat = len(tr.layer_names)
        m_pad, wmax, nl_pad = pads or (m, wmax_nat, nl_nat)
        assert m_pad >= m and wmax >= wmax_nat and nl_pad >= nl_nat
        streams = np.zeros((n_cores, wmax), np.int32)
        for k, s in enumerate(lane0.streams):
            streams[k, :s.shape[0]] = _i32(s)
        line = np.zeros(m_pad, np.int32)
        line[:m] = _i32(tr.line)
        write = np.zeros(m_pad, bool)
        write[:m] = np.asarray(tr.write, bool)
        layer = np.zeros(m_pad, np.int32)
        layer[:m] = np.asarray(tr.layer, np.int32)
        # fluid queueing constants from the single-source helper; the
        # sched timing tuple rides as data (zeros when fluid — never read)
        dram_denom, w_dram25 = dram_mod.queue_delay_consts(dram, et)
        sd = (dramsched.timing_tuple(sched) if sched is not None
              else (0, 0, 0, 0, 1, 0, 0))
        self.sh = SharedConsts(
            line=jnp.asarray(line),
            write=jnp.asarray(write),
            layer=jnp.asarray(layer),
            streams=jnp.asarray(streams),
            nominal=_fb([pr.apkc / 1000.0 * et for pr in profiles]),
            apkc1k=_fb([pr.apkc / 1000.0 for pr in profiles]),
            ipc0=_fb([pr.ipc0 for pr in profiles]),
            inv_ipc0=_fb([1.0 / pr.ipc0 for pr in profiles]),
            et=_fb(et), m_total=jnp.int64(lane0.m_total),
            max_epochs=jnp.int64(p.max_epochs),
            deadline=_fb(lane0.deadline),
            period=_fb(lane0.period),
            ma_global=_fb(lane0.apm.ma_global),
            llc_capacity=_fb(lane0.llc_capacity),
            llc_capacity_int=jnp.int64(int(lane0.llc_capacity)),
            s_llc=_fb(lane0.s_llc),
            w_cap_s=_fb(p.w_cap * lane0.s_llc),
            w_cap_s_prio=_fb(p.w_cap * lane0.s_llc * p.prio_cap),
            prio_cap=_fb(p.prio_cap),
            hit_lat=_fb(p.llc_hit_lat),
            dram_lat=_fb(dram.latency_cycles),
            dram_rate=_fb(dram.rate),
            dram_cap=_fb(lane0.dram_cap),
            dram_cap01=_fb(0.1 * lane0.dram_cap),
            dram_denom=_fb(dram_denom),
            w_cap_dram=_fb(p.w_cap * dram.latency_cycles),
            w_cap_dram_prio=_fb(p.w_cap * dram.latency_cycles * p.prio_cap),
            w_dram25=_fb(w_dram25),
            mlp_et=_fb(p.mlp_accel * et),
            sd_tcas=jnp.int64(sd[0]), sd_trcd=jnp.int64(sd[1]),
            sd_trp=jnp.int64(sd[2]), sd_tbus=jnp.int64(sd[3]),
            sd_reset=jnp.int64(sd[4]), sd_qcap=jnp.int64(sd[5]),
            sd_kind=jnp.int64(sd[6]),
            sd_et=jnp.int64(int(p.epoch_cycles)))

        self._wmax = wmax
        self._m = m
        self._m_pad = m_pad
        self._n_layers = nl_pad
        self.lc = self._stage_lanes(lanes)
        # flipped by refresh_clusters: an online retrain rewrote the
        # device tables, so a staging cache must not reuse this object
        # for a fresh point (sweep._staged_for checks it)
        self.stale = False

    def _stage_lanes(self, lanes: List[Lane]) -> LaneConsts:
        n_l, m, n_c = len(lanes), self._m, len(lanes[0].profiles)
        rc = np.zeros((n_l, self._m_pad), np.int8)
        ri = np.zeros((n_l, self._m_pad), np.int8)
        cold = np.zeros((n_l, max(self._n_layers, 1)))      # float64
        afr = np.zeros((n_l, self._m_pad), bool)
        writes = np.zeros((n_l, n_c, self._wmax), bool)
        mag = lanes[0].apm.ma_global
        apm_cols = {k: np.zeros(n_l) for k in (
            "margin_high", "margin_low", "mr_th", "behind_th",
            "t_b", "delta_a", "delta_b")}
        bands = np.zeros((n_l, 7))
        t_a = np.zeros((n_l, 4))
        switch = np.full(n_l, -1, np.int64)
        for i, lane in enumerate(lanes):
            if lane.clusters is not None:
                rc[i, :m] = lane.clusters["rc"]
                ri[i, :m] = lane.clusters["ri"]
                cc = lane.clusters["cold_center"]
                cold[i, :len(cc)] = cc
            if lane.afr_hints is not None:
                afr[i, :m] = lane.afr_hints
            for k, w in enumerate(lane.writes):
                writes[i, k, :w.shape[0]] = w
            ap = lane.apm.params
            apm_cols["margin_high"][i] = ap.margin_high
            apm_cols["margin_low"][i] = ap.margin_low
            apm_cols["mr_th"][i] = ap.mr_threshold
            apm_cols["behind_th"][i] = (1.0 + ap.alpha) * mag
            apm_cols["t_b"][i] = ap.t_b
            apm_cols["delta_a"][i] = ap.delta_a
            apm_cols["delta_b"][i] = ap.delta_b
            bands[i, 0] = (1.0 + ap.beta) * mag
            for k in range(1, 7):
                bands[i, k] = (1.0 - k * ap.beta) * mag
            t_a[i] = (ap.t_a1, ap.t_a2, ap.t_a3, ap.t_a4)
            pol = lane.policy
            if pol.deadline_aware and not pol.hydra:
                switch[i] = int(pol.asth_t * mag)
        pols = [lane.policy for lane in lanes]
        return LaneConsts(
            arp=jnp.asarray([p.arbitration == "arp" for p in pols]),
            flash=jnp.asarray([p.arbitration == "flash" for p in pols]),
            hydra=jnp.asarray([p.hydra for p in pols]),
            dpcp=jnp.asarray([p.dpcp for p in pols]),
            accel_hint=jnp.asarray(
                [p.accel_mode == llc_mod.A_HINT and lane.clusters is not None
                 for p, lane in zip(pols, lanes)]),
            accel_rand=jnp.asarray(
                [p.accel_mode == llc_mod.A_RAND for p in pols]),
            switch_point=jnp.asarray(switch),
            knobs=llc_mod.lane_knobs([lane.llc_cfg for lane in lanes]),
            rc=jnp.asarray(rc), ri=jnp.asarray(ri),
            cold_le2=jnp.asarray(cold <= 2.0),
            afr=jnp.asarray(afr), writes=jnp.asarray(writes),
            bands=_fb(bands), t_a=_fb(t_a),
            **{k: _fb(v) for k, v in apm_cols.items()})

    def refresh_clusters(self, lanes: List[Lane]) -> None:
        """Re-upload per-lane cluster tables (after an online retrain)."""
        self.lc = self._stage_lanes(lanes)
        self.stale = True


def bucket_pads(groups: List[List[Lane]]) -> Tuple[int, int, int]:
    """Common staging pads (m_pad, wmax_pad, nl_pad) for one bucket slab
    — every group's arrays are sized to the slab maxima so they stack
    along the leading group axis."""
    return (max(g[0].tr.num_accesses for g in groups),
            max(max([s.shape[0] for s in g[0].streams] or [1])
                for g in groups),
            max(len(g[0].tr.layer_names) for g in groups))


def stage_group(lanes: List[Lane], k_epochs: int = DEFAULT_SUPERSTEP,
                max_rounds: int = DEFAULT_MAX_ROUNDS,
                pads: Optional[Tuple[int, int, int]] = None) -> _Staged:
    """Build one group's staged device constants (the unit sweep's
    staging cache holds); time lands in the stage_s phase bucket."""
    with _phase("stage_s"), jax.enable_x64():
        staged = _Staged(lanes, k_epochs, max_rounds, pads=pads)
    return staged


def _init_carry(lanes: List[Lane], states: llc_mod.LLCState,
                n_inputs: int) -> FusedCarry:
    """Build the device carry from the lanes' current host state."""
    n_l = len(lanes)
    n_c = len(lanes[0].profiles)
    comp = np.zeros((n_l, n_inputs))
    for i, lane in enumerate(lanes):
        comp[i, :len(lane.completions)] = lane.completions[:n_inputs]
    col = np.array
    if lanes[0].dsched is not None:
        b_row = np.stack([lane.dsched.row for lane in lanes])
        b_queue = np.stack([lane.dsched.queue for lane in lanes])
        b_rr = col([lane.dsched.rr for lane in lanes], np.int64)
    else:
        b_row = np.zeros((n_l, 0), np.int64)
        b_queue = np.zeros((n_l, 0), np.int64)
        b_rr = np.zeros(n_l, np.int64)
    return FusedCarry(
        st=states,
        active=jnp.asarray(col([lane.active for lane in lanes])),
        hr_core=_fb([lane.hr_core for lane in lanes]),
        hr_accel=_fb([lane.hr_accel for lane in lanes]),
        amal=_fb([lane.amal for lane in lanes]),
        ipc=_fb(np.stack([np.asarray(lane.ipc, np.float64)
                          for lane in lanes])),
        stream_pos=jnp.asarray(np.stack(
            [np.asarray(lane.stream_pos, np.int64) for lane in lanes])),
        pos=jnp.asarray(col([lane.pos for lane in lanes], np.int64)),
        input_idx=jnp.asarray(col([lane.input_idx for lane in lanes],
                                  np.int64)),
        input_start=_fb([lane.input_start for lane in lanes]),
        now=_fb([lane.now for lane in lanes]),
        ri_th=jnp.asarray(col([lane.ri_th for lane in lanes], np.int64)),
        rc_th=jnp.asarray(col([lane.rc_th for lane in lanes], np.int64)),
        special=jnp.asarray(col([lane.special for lane in lanes], bool)),
        cm_prev=_fb([lane.cm_prev for lane in lanes]),
        pf_prev=_fb([lane.pf_prev for lane in lanes]),
        epoch=jnp.asarray(col([lane.epoch for lane in lanes], np.int64)),
        completions=_fb(comp),
        totals=jnp.asarray(np.stack([np.array(
            [lane.total_core_hits, lane.total_core_miss, lane.total_core_byp,
             lane.total_accel_hits, lane.total_accel_miss,
             lane.total_accel_byp, lane.total_accel_acc], np.int64)
            for lane in lanes])),
        total_llc=_fb([lane.total_llc for lane in lanes]),
        total_dram=_fb([lane.total_dram for lane in lanes]),
        overflow=jnp.zeros(n_l, bool),
        bank_row=jnp.asarray(b_row), bank_queue=jnp.asarray(b_queue),
        bank_rr=jnp.asarray(b_rr))


# ---------------------------------------------------------------------------
# write-back
# ---------------------------------------------------------------------------
def _write_back_carry(lanes: List[Lane], c) -> None:
    """Sync per-lane carry scalars into the host Lane objects — the exact
    fields (and python/numpy types) the sequential loop would have
    produced, so ``Lane.result()`` and any later host epochs are
    indistinguishable from a pure-host run.  ``c`` holds one group's
    non-state carry leaves as numpy; value-idempotent (a frozen lane
    writes back its unchanged values), so the bucketed driver can call
    it once at the end of the run and again at a demotion."""
    for i, lane in enumerate(lanes):
        lane.hr_core = float(c.hr_core[i])
        lane.hr_accel = float(c.hr_accel[i])
        lane.amal = float(c.amal[i])
        # np.array (not asarray): views of jax buffers are read-only, and
        # the host loop mutates these in place if it ever resumes
        lane.ipc = np.array(c.ipc[i], np.float64)
        lane.stream_pos = np.array(c.stream_pos[i], np.int64)
        lane.pos = int(c.pos[i])
        lane.input_idx = int(c.input_idx[i])
        lane.input_start = float(c.input_start[i])
        lane.now = float(c.now[i])
        lane.ri_th = int(c.ri_th[i])
        lane.rc_th = int(c.rc_th[i])
        lane.special = bool(c.special[i])
        lane.cm_prev = float(c.cm_prev[i])
        lane.pf_prev = float(c.pf_prev[i])
        lane.epoch = int(c.epoch[i])
        lane.completions = [float(v) for v in
                            c.completions[i][:lane.input_idx]]
        (lane.total_core_hits, lane.total_core_miss, lane.total_core_byp,
         lane.total_accel_hits, lane.total_accel_miss, lane.total_accel_byp,
         lane.total_accel_acc) = (int(v) for v in c.totals[i])
        lane.total_llc = float(c.total_llc[i])
        lane.total_dram = float(c.total_dram[i])
        if lane.dsched is not None:
            # np.array: the host twin mutates these on a later resume
            lane.dsched.row = np.array(c.bank_row[i], np.int64)
            lane.dsched.queue = np.array(c.bank_queue[i], np.int64)
            lane.dsched.rr = int(c.bank_rr[i])


def _write_back_steps(lanes: List[Lane], y: StepOut) -> None:
    """Append one super-step's committed epochs (``y`` = one group's
    StepOut as numpy) into the host lanes' histories.  Committed steps
    are a prefix of the scan — a freeze (stop boundary, completion or
    overflow) is sticky within a super-step — so row t is epoch t."""
    for i, lane in enumerate(lanes):
        steps = int(y.active[:, i].sum())
        if steps == 0:
            continue
        h = lane.hist
        et = lane.et
        for t in range(steps):
            h["accel_rate"].append(float(y.n_a[t, i]))
            h["requirement"].append(float(y.req[t, i]))
            h["ri_th"].append(float(y.ri_th[t, i]))
            h["rc_th"].append(float(y.rc_th[t, i]))
            h["core_ipc"].append(float(y.core_ipc[t, i]))
            h["amal"].append(float(y.amal[t, i]))
            if lane.p.record_occupancy:
                lane.occ.append([int(y.occ[t, i, 0]), int(y.occ[t, i, 1])])
            # the host's total_instr accumulation, op for op
            lane.total_instr += float(y.core_ipc[t, i] * et)
            if lane._retrain_every is not None and y.n_a[t, i] > 0:
                lane._win_ranges.append(
                    (int(y.pos_before[t, i]),
                     int(y.pos_before[t, i] + y.n_a[t, i])))


# ---------------------------------------------------------------------------
# whole-sweep bucketing: a leading group axis over compatible lane groups
# ---------------------------------------------------------------------------
def bucket_key(lanes: List[Lane]) -> Tuple:
    """Static-compatibility key for ``drive_lanes_bucketed``: two lane
    groups may share one bucketed device program iff every compile-time
    ``FusedDims`` field agrees — LLC geometry, lane count, core slot
    layout, accel capacity, the DPCP prefetch segment, input count, and
    the occupancy-record flag.  Everything else (traces, streams, knobs,
    deadlines, max_epochs) rides as data under the group axis."""
    lane0 = lanes[0]
    from . import cores as cores_mod
    core_caps = tuple(
        max(int(cores_mod.epoch_accesses(pr, pr.ipc0, lane0.et)), 0)
        for pr in lane0.profiles)
    sched = (dramsched.sched_dims(lane0.dram)
             if isinstance(lane0.dram, dram_mod.SchedDramModel) else None)
    return (llc_mod.geometry_key(lane0.llc_cfg), len(lanes),
            lane0.n_cores, core_caps, int(lane0.p.accel_epoch_cap),
            any(lane.policy.dpcp for lane in lanes),
            int(lane0.p.n_inputs), bool(lane0.p.record_occupancy), sched)


# SharedConsts leaves that keep their leading group axis in the flat
# bucket program (read via (group, element) gathers); every other leaf
# is group-constant and broadcasts to the flat lane axis up front.
_SH_GROUP_ARRAYS = frozenset({"line", "write", "layer", "streams"})
_SH_FLAT_AXES = SharedConsts(**{
    f: (None if f in _SH_GROUP_ARRAYS else 0) for f in SharedConsts._fields})


def _bucket_run(dims: FusedDims, n_shards: int):
    """Build the bucketed super-step program ``run(sh, lc, carry, stop)``.

    The (group, lane) axes flatten to ONE (G*L) lane axis outside the
    epoch scan, so a bucket of G groups runs the exact program one
    G*L-lane group would — no group-axis vmap anywhere.  Group-constant
    ``SharedConsts`` scalars and the stop epochs broadcast to the flat
    axis via one lane-indexed gather up front; the big per-group trace
    and stream arrays stay group-major and are read with (group,
    element) gathers inside the round build (``gid``), which touch the
    same elements as the per-group ``jnp.take``s and so cannot change
    values.  The round while-loop already ran flat — its trip count and
    width-tier predicates stay scalars.

    With ``n_shards > 1`` the group axis is ``shard_map``ped across
    devices first: groups are fully independent, so each shard flattens
    and runs its local slice with no cross-device communication (the
    round loop's trip count becomes a per-shard max, which only helps).
    """
    n_l = dims.n_lanes

    def run(sh, lc, carry, stop):
        n_g = stop.shape[0]
        gid = jnp.repeat(jnp.arange(n_g, dtype=jnp.int32), n_l)

        def flat(x):
            return x.reshape((n_g * n_l,) + x.shape[2:])

        sh_f = sh._replace(**{
            f: getattr(sh, f)[gid] for f in SharedConsts._fields
            if f not in _SH_GROUP_ARRAYS})
        stop_f = stop[gid]
        lc_f = jax.tree.map(flat, lc)
        begin = jax.vmap(
            lambda s, st, l, c, g: _begin_lane(dims, s, st, l, c, g),
            in_axes=(_SH_FLAT_AXES, 0, 0, 0, 0))
        finish = jax.vmap(
            lambda s, l, c, b, nst, sta, pc:
            _finish_lane(dims, s, l, c, b, nst, sta, pc),
            in_axes=(_SH_FLAT_AXES, 0, 0, 0, 0, 0, 0))

        def live_step(cy):
            bg = begin(sh_f, stop_f, lc_f, cy, gid)
            new_st, stats, percore = _run_rounds_batch(
                dims, lc_f.knobs, cy.st, bg)
            return finish(sh_f, lc_f, cy, bg, new_st, stats, percore)

        def body(cy, _):
            # epochs where every lane is frozen (done, at its stop, or
            # overflowed) skip the whole build+rounds+finish program —
            # a scalar cond, possible only because nothing vmaps over
            # groups anymore.  This is what makes a speculative
            # super-step past the end of the run (double-buffering) and
            # the post-completion tail of a final super-step ~free.
            # Frozen rows are identities: active=False rows are never
            # read by the write-back, and alive/ovf carry the real flags.
            y_sd = jax.eval_shape(live_step, cy)[1]

            def frozen_step(cy):
                y = jax.tree.map(
                    lambda sd: jnp.zeros(sd.shape, sd.dtype), y_sd)
                return cy, y._replace(alive=cy.active, ovf=cy.overflow)

            run_any = jnp.any(cy.active & (cy.epoch < stop_f)
                              & ~cy.overflow)
            return jax.lax.cond(run_any, live_step, frozen_step, cy)

        cy_end, ys = jax.lax.scan(
            body, jax.tree.map(flat, carry), None, length=dims.k_epochs)
        unflat = lambda x: x.reshape((n_g, n_l) + x.shape[1:])
        return (jax.tree.map(unflat, cy_end),
                jax.tree.map(
                    lambda y: y.reshape((y.shape[0], n_g, n_l)
                                        + y.shape[2:]), ys))

    if n_shards > 1:
        from jax.sharding import PartitionSpec as P

        run = jax.shard_map(run, mesh=_group_sharding(n_shards).mesh,
                            in_specs=(P("g"), P("g"), P("g"), P("g")),
                            out_specs=(P("g"), P(None, "g")),
                            check_vma=False)
    return run


def _group_sharding(n_shards: int):
    """The bucket's group axis split over the first ``n_shards`` devices."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:n_shards]), ("g",))
    return NamedSharding(mesh, P("g"))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _superstep_bucket(dims: FusedDims, n_shards: int, sh_g, lc_g, carry_g,
                      stop_g):
    """K epochs of every group in the bucket as one device program."""
    return _bucket_run(dims, n_shards)(sh_g, lc_g, carry_g, stop_g)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(4,))
def _superstep_bucket_donated(dims: FusedDims, n_shards: int, sh_g, lc_g,
                              carry_g, stop_g):
    """Donating twin of ``_superstep_bucket``: the carry buffers are
    donated to the next super-step (the driver never reads a dispatched
    carry again — StepOut carries everything the host needs)."""
    return _bucket_run(dims, n_shards)(sh_g, lc_g, carry_g, stop_g)


def _stack_trees(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def drive_lanes_bucketed(groups: List[List[Lane]],
                         k_epochs: int = DEFAULT_SUPERSTEP,
                         max_rounds: int = DEFAULT_MAX_ROUNDS,
                         devices: Optional[int] = None,
                         staged: Optional[List[_Staged]] = None) -> None:
    """Drive several static-compatible lane groups (equal ``bucket_key``)
    to completion as ONE flat fused program with a leading group axis.

    Every lane is bitwise-identical to ``sim.drive_lane`` on that lane
    alone (tests/test_fused.py, tests/test_bucketed.py): the flat (G*L)
    program computes the same per-lane values (see ``_bucket_run``), and
    a group of one is the plain per-group case.

    The driver tracks progress from the fetched ``StepOut`` alone
    (per-lane committed-epoch counts and alive flags ride the scan
    outputs), so between super-steps only the K-epoch history rows cross
    the device boundary — the carry stays on device until the run ends
    (or a group demotes), when its scalars sync once.  On one shard the
    carry is donated to the next super-step, and when no lane has an
    online-LERN retrain boundary (stop epochs constant) super-step N+1
    is dispatched before N's write-back runs, double-buffering host work
    against device work.

    Overflow handling demotes surgically and never rolls back: an
    overflowing lane freezes on its pre-overflow carry (see
    ``_finish_lane``), so committed epochs stand.  The shared round
    capacity is escalated first (one re-jit the whole bucket amortizes;
    the round loop's trip count follows the data, so shallow groups
    don't pay for the new depth), and once the capacity is exhausted
    only the *offending* groups leave — each syncs its carry scalars and
    finishes lane by lane on the host (``sim.drive_lane``, which chunks
    hot sets) from its frozen LLC state while its batch slot freezes, so
    one pathological group never knocks the whole bucket off the device.

    ``devices`` bounds the ``shard_map`` shard count for the group axis
    (None = all visible devices); sharding engages when more than one
    device is present and the group count divides evenly.  ``staged``
    reuses previously staged device constants (sweep's staging cache);
    entries must have been built with this bucket's ``bucket_pads`` and
    the same ``k_epochs``/``max_rounds``.
    """
    assert groups and len({bucket_key(g) for g in groups}) == 1
    for g in groups:
        assert all(lane_supported(lane) for lane in g)
    n_groups = len(groups)
    max_epochs = [int(g[0].p.max_epochs) for g in groups]
    if staged is None:
        pads = bucket_pads(groups)
        staged = [stage_group(g, k_epochs, max_rounds, pads=pads)
                  for g in groups]
    dims = staged[0].dims
    with _phase("stage_s"), jax.enable_x64():
        # Groups in one bucket agree on every static field except the
        # incidental choice of lane0's LLCConfig for ``cfg`` — behaviour
        # knobs ride as LaneKnobs data, so only geometry_key must match
        # (mixed-policy rosters chunked by max_lanes hit this: each
        # chunk's lane0 is a different policy's config).
        assert all(
            dataclasses.replace(s.dims, cfg=dims.cfg) == dims
            and llc_mod.geometry_key(s.dims.cfg)
            == llc_mod.geometry_key(dims.cfg)
            for s in staged)
        sh_g = _stack_trees([s.sh for s in staged])
        lc_g = _stack_trees([s.lc for s in staged])
        carry = _stack_trees([
            _init_carry(g, llc_mod.stack_states(dims.cfg, dims.n_lanes),
                        dims.n_inputs) for g in groups])
    n_dev = devices if devices else len(jax.devices())
    n_shards = n_dev if (n_dev > 1 and n_groups % n_dev == 0) else 1
    if n_shards > 1:
        # the group axis lives on the mesh: constants left on one device
        # would be resharded by every super-step
        g_sharding = _group_sharding(n_shards)
        with jax.enable_x64():
            sh_g, lc_g = jax.device_put((sh_g, lc_g), g_sharding)
    # donation needs the one-device path: under shard_map the stacked
    # inputs are resharded on the way in, and donating a buffer that is
    # about to be resharded is not aliasing-safe on every backend
    donate = n_shards == 1
    # speculative double-buffering needs constant stop epochs: an
    # online-LERN boundary requires a host refit (and table re-upload)
    # before the next super-step may start
    speculate = not any(
        lane._retrain_every is not None for g in groups for lane in g)

    # driver-local progress tracking, fed by the fetched StepOut — the
    # host Lane objects' scalars are stale until the final carry sync
    epochs = [[lane.epoch for lane in g] for g in groups]
    alive = [[lane.active for lane in g] for g in groups]
    live = [True] * n_groups       # False once demoted to the host
    # lanes that committed up to a retrain boundary whose refit hasn't
    # run yet (deferred while their group has an overflow to resolve —
    # the frozen lane must re-attempt its epoch under the OLD tables,
    # exactly as the sequential loop runs it)
    due = [set() for _ in range(n_groups)]

    def group_active(i: int) -> bool:
        return live[i] and any(alive[i])

    def next_stop(i: int) -> int:
        if not group_active(i):
            return 0
        stop = max_epochs[i]
        for j, lane in enumerate(groups[i]):
            r = lane._retrain_every
            if alive[i][j] and r is not None:
                e = epochs[i][j]
                # a due lane holds AT its boundary until the refit runs
                stop = min(stop, e if j in due[i] else e + r - e % r)
        return stop

    def dispatch():
        nonlocal carry
        stops = [next_stop(i) for i in range(n_groups)]
        before = [list(e) for e in epochs]
        with _phase("dispatch_s"), jax.enable_x64():
            step = _superstep_bucket_donated if donate else _superstep_bucket
            stop_g = jnp.asarray(stops, jnp.int64)
            if n_shards > 1:
                # the same input shardings on every call, or it compiles
                # again: the first carry sits on one device, and a
                # returned carry's empty leaves come back replicated
                carry, stop_g = jax.device_put((carry, stop_g), g_sharding)
            carry, ys = step(dims, n_shards, sh_g, lc_g, carry, stop_g)
            for leaf in jax.tree.leaves(ys):
                leaf.copy_to_host_async()
        return ys, before

    inflight: list = []
    depth = 2 if speculate else 1
    overflow_pending: set = set()
    while True:
        # fault-injection site "bucket_overflow" (repro.exp.faults):
        # force the surgical freeze/demote machinery as if every active
        # group had exhausted the round capacity at the cap.  Checked
        # before dispatch so it bites even on tiny workloads that finish
        # inside the first super-step.  Bitwise-safe by the same argument
        # as real overflow demotion — each group leaves from its
        # committed carry and finishes on the host.
        if any(group_active(i) for i in range(n_groups)):
            from repro.exp import faults as _flt
            if _flt.fire("bucket_overflow", key=f"g{n_groups}") is not None:
                dims = dataclasses.replace(dims, max_rounds=MAX_ROUNDS_CAP)
                overflow_pending.update(
                    i for i in range(n_groups) if group_active(i))
        while (not overflow_pending and len(inflight) < depth
               and any(group_active(i) for i in range(n_groups))):
            inflight.append(dispatch())
            if not speculate:
                break
        if not inflight:
            if not overflow_pending:
                break
            # every in-flight super-step is accounted for: escalate the
            # shared capacity first (committed epochs stand; the frozen
            # lanes re-attempt the same epoch at the new capacity) ...
            if dims.max_rounds < MAX_ROUNDS_CAP:
                dims = dataclasses.replace(
                    dims, max_rounds=min(dims.max_rounds * 2,
                                         MAX_ROUNDS_CAP))
                with jax.enable_x64():
                    carry = carry._replace(
                        overflow=jnp.zeros_like(carry.overflow))
                overflow_pending.clear()
                continue
            # ... and past the cap, demote only the offending groups:
            # sync their carry scalars and finish each still-active lane
            # on the host from its frozen LLC state (lanes share no LLC
            # state, so lane by lane is the group's own result)
            host_c = _to_host(carry._replace(st=None), _CARRY_FLOATS)
            for i in sorted(overflow_pending):
                if not live[i]:
                    continue
                live[i] = False
                _write_back_carry(groups[i],
                                  jax.tree.map(lambda x: x[i], host_c))
                # a deferred refit only touches the due lane's own
                # tables (it holds at its boundary), so fire it before
                # the host loop picks the lane up
                for j in sorted(due[i]):
                    groups[i][j]._online_retrain()
                due[i].clear()
                for j, lane in enumerate(groups[i]):
                    if lane.active:
                        drive_lane(lane, state=jax.tree.map(
                            lambda x: x[i, j], carry.st))
            with jax.enable_x64():
                dead = jnp.asarray(np.asarray([not a for a in live]))
                carry = carry._replace(
                    active=jnp.where(dead[:, None], False, carry.active),
                    overflow=jnp.zeros_like(carry.overflow))
            overflow_pending.clear()
            continue
        ys, before = inflight.pop(0)
        with _phase("device_s"):
            host_ys = _to_host(ys, _STEP_FLOATS)
        with _phase("writeback_s"):
            for i in range(n_groups):
                if not live[i]:
                    continue
                y_i = jax.tree.map(lambda y: y[:, i], host_ys)
                _write_back_steps(groups[i], y_i)
                for j in range(dims.n_lanes):
                    epochs[i][j] += int(y_i.active[:, j].sum())
                    alive[i][j] = bool(y_i.alive[-1, j])
                    r = groups[i][j]._retrain_every
                    if (r is not None and epochs[i][j] > before[i][j]
                            and epochs[i][j] % r == 0):
                        due[i].add(j)
                if y_i.ovf[-1].any():
                    overflow_pending.add(i)
        # online-LERN boundaries land at the super-step edge per group
        # (next_stop): run the host refit hooks and re-upload that
        # group's tables into its slot of the stacked constants.  A
        # group with an unresolved overflow defers (its frozen lane
        # re-attempts its epoch under the old tables first).
        for i in range(n_groups):
            if not live[i] or i in overflow_pending or not due[i]:
                continue
            for j in sorted(due[i]):
                groups[i][j]._online_retrain()
            due[i].clear()
            with _phase("stage_s"), jax.enable_x64():
                staged[i].refresh_clusters(groups[i])
                lc_g = jax.tree.map(
                    lambda full, new: full.at[i].set(new),
                    lc_g, staged[i].lc)
    # one final scalar sync per lane — everything epoch-by-epoch already
    # landed via _write_back_steps, and demoted groups were synced at
    # demotion (then driven to completion on the host)
    with _phase("writeback_s"):
        host_c = _to_host(carry._replace(st=None), _CARRY_FLOATS)
        for i in range(n_groups):
            if live[i]:
                _write_back_carry(groups[i],
                                  jax.tree.map(lambda x: x[i], host_c))
