"""Plain reference of the HyDRA sweep: one SoC (8 cores and an accelerator
sharing a 16-way LLC in front of DDR3-1600) run epoch by epoch under one
bypass and arbitration policy, in numpy and Python float64.  Imports
nothing of the simulator.

Written from the paper (arXiv:2605.08908) and the repository's
``docs/dram_model.md``:

- §III naming ``{FIFO|ARP}-{C}-{A}-{D}``: FIFO shares the LLC controller
  and DRAM queues, ARP serves the accelerator first on both; ``CS`` is
  SHiP-driven core response bypass, ``AS`` SHiP accelerator bypass, ``AL``
  LERN-hinted accelerator bypass, ``-D`` deadline awareness; HyDRA is
  ARP-CS-AL-D with the APM (§V-A) modulating its reuse thresholds.
- §III-C1 deadline switch: a ``-D`` lane without the APM bypasses the
  accelerator only after ``MA_global`` of its accesses in the epoch.
- §V-A APM (Fig. 8 margins, Algorithm 1 thresholds, Fig. 9 reuse
  thresholds) with the §VI-L parameters; §V-B L-RPT built here from the
  LERN model's label tables; §V-C bypass semantics (a bypassed accelerator
  write invalidates a cached copy; a read hit is served whatever the
  decision).
- The LLC: 16 ways, LRU replacement, SHiP (SHiP-Mem region signatures,
  saturating counters trained only in the sampler sets, a counter of 0
  predicts dead).  Events of an epoch interleave evenly per agent; the
  counters are updated once per round, a round holding the r-th access of
  every set (the model's batching of the predictor update).
- Timing: M/G/1 delay at the LLC controller, the fluid DDR3 queue law of
  ``docs/dram_model.md``, the cores' analytic IPC, the accelerator's
  admission bounded by its DMA queue, its DRAM share and its port.
- Deadline: ``deadline_factor`` x the accelerator's standalone completion
  time (ARP, no bypass, no core traffic), simulated here.

Inputs are data: the accelerator trace (line, write, layer), each core's
address stream, and the LERN label tables.  The core write flags are drawn
here from the run's seed, core by core.

Three deliberate faults serve as controls (``Fault``): float32 timing,
a SHiP counter threshold off by one, and FIFO in place of LRU.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

# APM parameters, paper §VI-L
MARGIN_HIGH, MARGIN_LOW, MR_TH = 0.05, 0.01, 0.30
ALPHA, BETA, DELTA_A, DELTA_B = 0.10, 0.05, 0.20, 0.10
T_A, T_B = (1.0, 1.2, 1.5, 2.0), 0.8
# fluid DRAM queue law (docs/dram_model.md)
RHO_CAP, STAB_FLOOR, TRAFFIC_FLOOR, DELAY_CAP_X = 0.999, 1e-3, 1e-9, 25.0
# interleave keys: slot i of an n-access segment sits at i/n of the epoch,
# kept exact as floor(i * 2^41 / n) (distinct for n < 2^13)
WHEN_BITS = 41
CORE_MLP = 4.0


@dataclasses.dataclass(frozen=True)
class Fault:
    """A control: the reference with one part deliberately wrong."""
    timing_dtype: type = float      # np.float32: float32 timing
    dead_max: int = 0               # 1: SHiP predicts dead at counter <= 1
    fifo: bool = False              # True: FIFO replacement, not LRU


SOUND = Fault()


@dataclasses.dataclass(frozen=True)
class LanePolicy:
    name: str
    arp: bool
    core_bypass: bool
    accel: str                      # "none" | "ship" | "lern"
    deadline: bool
    apm: bool


def parse_policy(name: str) -> LanePolicy:
    """§III naming; ``hydra`` is ARP-CS-AL-D with the APM."""
    if name == "hydra":
        return LanePolicy(name, True, True, "lern", True, True)
    arb, *parts = name.split("-")
    if arb not in ("fifo", "arp") or not parts or \
            not set(parts) <= {"nb", "cs", "as", "al", "d"}:
        raise ValueError(f"no reference for policy {name!r}")
    accel = "ship" if "as" in parts else "lern" if "al" in parts else "none"
    return LanePolicy(name, arb == "arp", "cs" in parts, accel, "d" in parts,
                      False)


@dataclasses.dataclass(frozen=True)
class Soc:
    """The deployment's sizes, from the configuration file."""
    epoch_cycles: float
    llc_rate: float
    llc_hit_lat: float
    w_cap: float
    prio_cap: float
    mlp_accel: float
    n_inputs: int
    deadline_factor: float
    max_epochs: int
    accel_epoch_cap: int
    sets: int
    ways: int
    dram_latency: float
    dram_rate: float
    ship_entries: int
    ship_max: int
    ship_init: int
    ship_region: int
    sampler_every: int

    @classmethod
    def from_config(cls, config: dict) -> "Soc":
        p, d, s = config["params"], config["dram_model"], config["ship"]
        return cls(
            epoch_cycles=float(p["epoch_cycles"]), llc_rate=p["llc_rate"],
            llc_hit_lat=p["llc_hit_lat"], w_cap=p["w_cap"],
            prio_cap=p["prio_cap"], mlp_accel=p["mlp_accel"],
            n_inputs=p["n_inputs"], deadline_factor=p["deadline_factor"],
            max_epochs=p["max_epochs"], accel_epoch_cap=p["accel_epoch_cap"],
            sets=p["llc_size_bytes"] // (config["line_bytes"]
                                         * p["llc_ways"]),
            ways=p["llc_ways"], dram_latency=d["latency_cycles"],
            dram_rate=d["peak_lines_per_cycle"] * d["efficiency"],
            ship_entries=s["entries"], ship_max=(1 << s["counter_bits"]) - 1,
            ship_init=s["init"], ship_region=s["region_lines"],
            sampler_every=s["sampler_every"])


@dataclasses.dataclass
class Core:
    apkc: float
    ipc0: float
    write_frac: float


def ship_signature(lines: np.ndarray, soc: Soc) -> np.ndarray:
    """SHiP-Mem: the region of ``ship_region`` lines, xor-folded and
    multiplied by the golden ratio in 32 bits; the top 16 bits index the
    table."""
    r = (lines // soc.ship_region).astype(np.uint32)
    h = (r ^ (r >> np.uint32(7)) ^ (r >> np.uint32(15))) \
        * np.uint32(0x9E3779B9)
    return (h >> np.uint32(16)).astype(np.int64) & (soc.ship_entries - 1)


def lrpt(model, n_layers: int, bits: int = 19):
    """The L-RPT of each layer (§V-B): a tagless direct-mapped table of
    2^bits entries indexed by the low line-address bits, holding (RC, RI)
    of the lines with reuse; on a collision the later line of the layer's
    sorted unique lines wins.  Returns [L, 2^bits] rc and ri, -1 = no
    reuse."""
    rc_t = np.full((n_layers, 1 << bits), -1, np.int8)
    ri_t = np.full((n_layers, 1 << bits), -1, np.int8)
    for li in range(n_layers):
        n = int(model.n_uniq[li])
        uniq = np.asarray(model.uniq[li, :n], np.int64)
        rc = np.asarray(model.rc_cluster[li, :n], np.int64)
        ri = np.asarray(model.ri_cluster[li, :n], np.int64)
        keep = rc >= 0
        # fancy assignment keeps the last of repeated indices
        rc_t[li, uniq[keep] & ((1 << bits) - 1)] = rc[keep]
        ri_t[li, uniq[keep] & ((1 << bits) - 1)] = ri[keep]
    return rc_t, ri_t


class Llc:
    """One lane's LLC: tags, recency, owner, inserting signature and
    reuse bit per way; one SHiP table per agent."""

    def __init__(self, soc: Soc, fault: Fault):
        s, w = soc.sets, soc.ways
        self.soc, self.fault = soc, fault
        self.tags = np.full((s, w), -1, np.int64)
        self.stamp = np.zeros((s, w), np.int64)   # last touch (LRU) or fill
        self.owner = np.zeros((s, w), np.int8)    # 1 = accelerator
        self.sig = np.zeros((s, w), np.int64)
        self.reused = np.zeros((s, w), bool)
        self.ship = np.full((2, soc.ship_entries), soc.ship_init, np.int64)
        self.tick = 0

    def occupancy(self):
        valid = self.tags != -1
        acc = valid & (self.owner == 1)
        return int(np.sum(valid & ~acc)), int(np.sum(acc))

    def epoch(self, line, isacc, write, hint, dlok, src, pol: LanePolicy):
        """Run one epoch's ordered events.  Returns the counts
        (core hits, misses, bypasses; accel hits, misses, bypasses,
        writes bypassed) and per-core [8, 2] hits and misses."""
        soc = self.soc
        sets = line & (soc.sets - 1)
        order = np.argsort(sets, kind="stable")
        ss = sets[order]
        first = np.ones(ss.size, bool)
        first[1:] = ss[1:] != ss[:-1]
        start = np.flatnonzero(first)
        rank = np.arange(ss.size) - np.repeat(start, np.diff(
            np.append(start, ss.size)))
        by_rank = np.argsort(rank, kind="stable")
        bounds = np.searchsorted(rank[by_rank], np.arange(rank.max() + 2))
        sig_e = ship_signature(line, soc)
        counts = np.zeros(7, np.int64)
        percore = np.zeros((8, 2), np.int64)
        for r in range(rank.max() + 1):
            ev = order[by_rank[bounds[r]:bounds[r + 1]]]
            s = sets[ev]
            self.tick += 1
            self._round(s, line[ev], isacc[ev], write[ev], hint[ev],
                        dlok[ev], src[ev], sig_e[ev], pol, counts, percore)
        return counts, percore

    def _round(self, s, ln, acc, wr, hint, dlok, src, sg, pol, counts,
               percore):
        """One round: at most one access in each set ``s``; the
        predictor reads the table as the round starts."""
        soc, f = self.soc, self.fault
        tags = self.tags[s]
        hv = tags == ln[:, None]
        hit = hv.any(1)
        way_hit = hv.argmax(1)
        dead = self.ship[:, sg] <= f.dead_max           # [2, n]
        # sampler sets train SHiP and never take a SHiP-driven bypass
        sampler = (s % soc.sampler_every) == 0
        if pol.accel == "ship":
            byp_a = dead[1] & ~sampler
        elif pol.accel == "lern":
            byp_a = hint
        else:
            byp_a = np.zeros_like(hit)
        byp_a = byp_a & dlok
        byp_c = dead[0] & ~sampler if pol.core_bypass else np.zeros_like(hit)
        bypass = np.where(acc, byp_a, byp_c)
        inval = acc & wr & bypass & hit
        served = hit & ~inval
        ins = ~hit & ~bypass
        empty = tags == -1
        has_empty = empty.any(1)
        victim = np.where(has_empty, empty.argmax(1),
                          self.stamp[s].argmin(1))
        evict = ins & ~has_empty
        core = ~acc
        counts += (np.sum(core & served), np.sum(core & ~hit),
                   np.sum(core & ~hit & bypass), np.sum(acc & served),
                   np.sum(acc & ~served), np.sum(acc & bypass & ~served),
                   np.sum(acc & wr & bypass))
        np.add.at(percore[:, 0], src[core & served], 1)
        np.add.at(percore[:, 1], src[core & ~hit], 1)

        # SHiP training in the sampler sets: a hit rewards the signature
        # that filled the line, an eviction of a line never reused punishes
        # it; the round's changes are summed, then saturated
        inc = served & sampler
        dec = evict & sampler & ~self.reused[s, victim]
        if inc.any() or dec.any():
            way = np.where(inc, way_hit, victim)[inc | dec]
            rows = s[inc | dec]
            delta = np.where(inc, 1, -1)[inc | dec]
            tbl = self.owner[rows, way].astype(np.int64)
            idx = self.sig[rows, way]
            d = np.zeros_like(self.ship)
            np.add.at(d, (tbl, idx), delta)
            touched = d != 0
            self.ship[touched] = np.clip(self.ship[touched] + d[touched], 0,
                                         soc.ship_max)

        # hits: recency (not under FIFO) and the reuse bit
        h = np.flatnonzero(served)
        if not f.fifo:
            self.stamp[s[h], way_hit[h]] = self.tick
        self.reused[s[h], way_hit[h]] = True
        # a bypassed accelerator write invalidates the cached copy
        v = np.flatnonzero(inval)
        self.tags[s[v], way_hit[v]] = -1
        # fills
        i = np.flatnonzero(ins)
        rs, wv = s[i], victim[i]
        self.tags[rs, wv] = ln[i]
        self.stamp[rs, wv] = self.tick
        self.owner[rs, wv] = acc[i]
        self.sig[rs, wv] = sg[i]
        self.reused[rs, wv] = False


def when_keys(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.int64) << WHEN_BITS) // n


def _mg1(rho, service):
    rho = min(rho, 0.98)
    return rho * service / max(2.0 * (1.0 - rho), 1e-2)


def _dram_delay(soc: Soc, traffic, window):
    rho = min(traffic / max(soc.dram_rate * window, TRAFFIC_FLOOR), RHO_CAP)
    w = (rho / max(2.0 * (1.0 - rho), STAB_FLOOR)) / soc.dram_rate
    return min(w, DELAY_CAP_X * soc.dram_latency)


def _sum8(x):
    """Sum of the eight cores' values in the order of numpy's pairwise
    reduction over eight elements, so that the total rounds alike."""
    if len(x) != 8:
        return sum(x)
    return ((x[0] + x[1]) + (x[2] + x[3])) + ((x[4] + x[5]) + (x[6] + x[7]))


class Lane:
    """One policy's run: per epoch, arbitration and admission, the APM's
    thresholds, the event list, the LLC, then the timing update."""

    def __init__(self, soc: Soc, pol: LanePolicy, trace, cores: List[Core],
                 streams: List[np.ndarray], writes: List[np.ndarray],
                 deadline, clusters=None, n_inputs: Optional[int] = None,
                 fault: Fault = SOUND):
        F = fault.timing_dtype
        self.F = F
        self.soc, self.pol, self.fault = soc, pol, fault
        self.line = np.asarray(trace["line"], np.int64)
        self.write = np.asarray(trace["write"], bool)
        self.layer = np.asarray(trace["layer"], np.int64)
        self.m = self.line.size
        self.cores, self.streams, self.writes = cores, streams, writes
        self.clusters = clusters
        self.n_inputs = soc.n_inputs if n_inputs is None else n_inputs
        self.et = F(soc.epoch_cycles)
        self.deadline = F(deadline)
        self.llc = Llc(soc, fault)
        self.ipc = [F(c.ipc0) for c in cores]
        self.hr_core, self.hr_accel, self.amal = F(0.5), F(0.3), F(200.0)
        self.spos = [0] * len(cores)
        self.input_idx = self.pos = 0
        self.input_start, self.now = F(0.0), F(0.0)
        self.completions = []
        self.ri_th, self.rc_th, self.special = (3, -1, False) if pol.apm \
            else (1, 2, False)
        self.cm_prev = self.pf_prev = F(0.0)
        self.total_instr, self.total_llc, self.total_dram = F(0), F(0), F(0)
        self.tot = dict(core_hits=0, core_misses=0, core_bypasses=0,
                        accel_hits=0, accel_misses=0, accel_bypasses=0,
                        accel_accesses=0)
        self.hist = {k: [] for k in ("accel_rate", "requirement", "ri_th",
                                     "rc_th", "core_ipc", "amal")}
        self.occupancy = []
        self.epoch = 0
        self.llc_cap = F(soc.llc_rate) * self.et
        self.s_llc = F(1.0) / F(soc.llc_rate)
        self.dram_cap = F(soc.dram_rate) * self.et
        self.ma_global = F(self.m) / self.deadline * self.et

    @property
    def active(self) -> bool:
        return self.epoch < self.soc.max_epochs and \
            self.input_idx < self.n_inputs

    # -- APM (§V-A) -------------------------------------------------------
    def _requirement(self, ra, rt, mr_i, ma_past):
        F = self.F
        hard = mr_i > MR_TH
        behind = ma_past < (F(1.0) + F(ALPHA)) * self.ma_global
        m = F(MARGIN_HIGH) if hard and behind else \
            F(MARGIN_LOW) if hard or behind else F(0.0)
        eff_rt = max(rt - m * self.deadline, self.et)
        return F(ra) / eff_rt * self.et

    def _thresholds(self, ma_i):
        F = self.F
        mag = self.ma_global
        t_a, t_b = [F(t) for t in T_A], F(T_B)
        if ma_i <= (F(1.0) - F(6.0) * F(BETA)) * mag:
            return [max(t - F(6.0) * F(DELTA_A), F(1.0)) for t in t_a], \
                t_b - F(6.0) * F(DELTA_B)
        for k in range(5, 0, -1):
            lo = (F(1.0) - F(k + 1) * F(BETA)) * mag
            hi = (F(1.0) - F(k) * F(BETA)) * mag
            if lo < ma_i <= hi:
                return [max(t - F(k) * F(DELTA_A), F(1.0)) for t in t_a], \
                    t_b - F(k) * F(DELTA_B)
        if ma_i > (F(1.0) + F(BETA)) * mag:
            return [t + F(DELTA_A) for t in t_a], t_b
        return t_a, t_b

    @staticmethod
    def _reuse_thresholds(ma_hat, ma_i, t_a, t_b):
        """Fig. 9: (RI_Th, RC_Th, special cases)."""
        if ma_hat > t_a[3] * ma_i:
            return -1, 4, False
        if ma_hat > t_a[2] * ma_i:
            return 0, 3, False
        if ma_hat > t_a[1] * ma_i:
            return 1, 2, False
        if ma_hat > t_a[0] * ma_i:
            return 2, 1, False
        if ma_hat > t_b * ma_i:
            return 3, 0, True
        return 3, -1, False

    # -- one epoch ----------------------------------------------------------
    def step(self) -> None:
        soc, pol, F, et = self.soc, self.pol, self.F, self.et
        arrived = self.now >= self.input_start
        remaining = self.m - self.pos
        if arrived and remaining > 0:
            miss_a = max(F(1.0) - self.hr_accel, F(0.05))
            share = self.dram_cap if pol.arp else max(
                self.dram_cap - self.cm_prev - self.pf_prev,
                F(0.1) * self.dram_cap)
            demand_a = min(remaining,
                           int(F(soc.mlp_accel) * et / max(self.amal,
                                                           F(1.0))),
                           int(share / miss_a), soc.accel_epoch_cap)
        else:
            demand_a = 0
        n_c = [int(F(c.apkc) / F(1000.0) * et * ipc / F(c.ipc0))
               for c, ipc in zip(self.cores, self.ipc)]
        total = demand_a + sum(n_c)
        shed = F(1.0)
        n_a = demand_a
        if total > self.llc_cap:
            if pol.arp:
                n_a = min(demand_a, int(self.llc_cap))
                shed = min((self.llc_cap - F(n_a)) / F(max(sum(n_c), 1)),
                           F(1.0))
            else:
                shed = self.llc_cap / F(total)
                n_a = int(F(demand_a) * shed)
        n_c = [int(F(n) * shed) for n in n_c]

        switch = -1
        if pol.deadline and not pol.apm:
            switch = int(self.ma_global)        # §III-C1 with t = 1
        if pol.apm and arrived and remaining > 0:
            rt = max(self.input_start + self.deadline - self.now, et)
            elapsed = max(self.deadline - rt, F(0.0))
            ma_past = (F(self.m - remaining) * et / elapsed
                       if elapsed >= et else self.ma_global)
            ma_i = self._requirement(remaining, rt, F(1.0) - self.hr_core,
                                     ma_past)
            t_a, t_b = self._thresholds(ma_i)
            ma_hat = F(soc.mlp_accel) * et / max(self.amal, F(1.0))
            self.ri_th, self.rc_th, self.special = self._reuse_thresholds(
                ma_hat, ma_i, t_a, t_b)
            self.hist["requirement"].append(ma_i)
        else:
            self.hist["requirement"].append(self.ma_global if arrived
                                            else F(0.0))

        seg_line, seg_acc, seg_wr, seg_hint, seg_src, seg_when = \
            [], [], [], [], [], []
        if n_a > 0:
            sl = slice(self.pos, self.pos + n_a)
            seg_line.append(self.line[sl])
            seg_acc.append(np.ones(n_a, bool))
            seg_wr.append(self.write[sl])
            if pol.accel == "lern":
                seg_hint.append(self._hints(sl))
            else:
                seg_hint.append(np.zeros(n_a, bool))
            seg_src.append(np.zeros(n_a, np.int64))
            seg_when.append(when_keys(n_a))
        for k, nk in enumerate(n_c):
            if nk == 0:
                continue
            sl = slice(self.spos[k], self.spos[k] + nk)
            seg_line.append(self.streams[k][sl])
            seg_acc.append(np.zeros(nk, bool))
            seg_wr.append(self.writes[k][sl])
            seg_hint.append(np.zeros(nk, bool))
            seg_src.append(np.full(nk, k, np.int64))
            seg_when.append(when_keys(nk))
            self.spos[k] += nk
        counts = np.zeros(7, np.int64)
        percore = np.zeros((8, 2), np.int64)
        if seg_line:
            # agents interleave evenly; equal keys keep the agent order
            # (accelerator, then cores 0..7)
            order = np.argsort(np.concatenate(seg_when), kind="stable")
            line = np.concatenate(seg_line)[order]
            acc = np.concatenate(seg_acc)[order]
            dlok = np.cumsum(acc) > switch
            counts, percore = self.llc.epoch(
                line, acc, np.concatenate(seg_wr)[order],
                np.concatenate(seg_hint)[order], dlok,
                np.concatenate(seg_src)[order], pol)
        self._finish(n_a, shed, counts, percore)

    def _hints(self, sl) -> np.ndarray:
        """§V-C / Fig. 9: bypass when RI > RI_Th or RC < RC_Th (no reuse
        is (-1, -1)); with the special cases also the Cold lines, when the
        current layer's Cold centre allows at most two reuses."""
        rc_t, ri_t, cold = self.clusters
        lay = self.layer[sl]
        idx = self.line[sl] & (rc_t.shape[1] - 1)
        rc = rc_t[lay, idx].astype(np.int64)
        ri = ri_t[lay, idx].astype(np.int64)
        byp = (ri > self.ri_th) | (rc < self.rc_th)
        if self.special and cold[self.layer[sl.start]] <= 2.0:
            byp = byp | (rc == 0)
        return byp

    def _finish(self, n_a, shed, counts, percore) -> None:
        soc, pol, F, et = self.soc, self.pol, self.F, self.et
        ch, cm, cb, ah, am, ab, awb = (int(x) for x in counts)
        self.hr_core = F(ch / max(ch + cm, 1))
        self.hr_accel = F(ah / max(ah + am, 1))
        # a bypassed fill costs the controller a tag lookup only; a
        # bypassed accelerator write goes around it
        llc_units = F(ch + cm + ah + am) - F(0.7) * F(cb + ab) \
            - F(0.3) * F(awb)
        rho = llc_units / self.llc_cap
        rho_a = F(ah + am) / self.llc_cap
        dram_traffic = cm + am
        w_cap_dram = F(soc.w_cap) * F(soc.dram_latency)
        if pol.arp:
            w_llc_a = min(_mg1(rho_a, self.s_llc), F(soc.w_cap) * self.s_llc)
            prio = min(F(1.0) / max(F(1.0) - rho_a, F(1e-3)),
                       F(soc.prio_cap))
            w_llc_c = min(_mg1(rho, self.s_llc) * prio,
                          F(soc.w_cap) * self.s_llc * F(soc.prio_cap))
        else:
            w_llc_a = w_llc_c = min(_mg1(rho, self.s_llc),
                                    F(soc.w_cap) * self.s_llc)
        w_fifo = min(_dram_delay(soc, F(dram_traffic), et), w_cap_dram)
        if pol.arp:
            rho_ad = min(F(am) / max(F(soc.dram_rate) * et,
                                     F(TRAFFIC_FLOOR)), F(1.0))
            w_dram_a = min(_dram_delay(soc, F(am), et), w_cap_dram)
            prio_d = min(F(1.0) / max(F(1.0) - rho_ad, F(1e-3)),
                         F(soc.prio_cap))
            w_dram_c = min(w_fifo * prio_d, w_cap_dram * F(soc.prio_cap))
        else:
            w_dram_a = w_dram_c = w_fifo
        hit_lat, lat = F(soc.llc_hit_lat), F(soc.dram_latency)
        miss_c = hit_lat + w_llc_c + lat + w_dram_c
        miss_a = hit_lat + w_llc_a + lat + w_dram_a
        self.cm_prev, self.pf_prev = F(cm), F(0.0)
        for k, c in enumerate(self.cores):
            h, m = int(percore[k, 0]), int(percore[k, 1])
            hk = F(h / max(h + m, 1))
            amat = hk * (hit_lat + w_llc_c) + (F(1) - hk) * miss_c
            stall = F(c.apkc) / F(1000.0) * amat / F(CORE_MLP)
            self.ipc[k] = F(1.0) / (F(1.0) / F(c.ipc0) + stall)
        if n_a > 0:
            self.amal = self.hr_accel * (hit_lat + w_llc_a) \
                + (F(1) - self.hr_accel) * miss_a
        ipc_sum = _sum8([ipc * shed for ipc in self.ipc])
        self.total_instr += ipc_sum * et
        t = self.tot
        for k, v in (("core_hits", ch), ("core_misses", cm),
                     ("core_bypasses", cb), ("accel_hits", ah),
                     ("accel_misses", am), ("accel_bypasses", ab),
                     ("accel_accesses", n_a)):
            t[k] += v
        self.total_llc += llc_units
        self.total_dram += F(dram_traffic)
        h = self.hist
        h["accel_rate"].append(n_a)
        h["ri_th"].append(self.ri_th)
        h["rc_th"].append(self.rc_th)
        h["core_ipc"].append(ipc_sum)
        h["amal"].append(self.amal)
        self.occupancy.append(self.llc.occupancy())
        self.now += et
        if n_a > 0:
            self.pos += n_a
            if self.pos >= self.m:
                self.completions.append(self.now - self.input_start)
                self.input_idx += 1
                self.pos = 0
                # inputs arrive one deadline apart (10-IPS style)
                self.input_start = max(self.input_start + self.deadline,
                                       self.now)
        self.epoch += 1

    def run(self) -> dict:
        while self.active:
            self.step()
        return self.result()

    def result(self) -> dict:
        t, F = self.tot, self.F
        core_acc = max(t["core_hits"] + t["core_misses"], 1)
        acc = max(t["accel_accesses"], 1)
        done = self.completions
        return {
            "policy": self.pol.name,
            "epochs": self.epoch,
            "requests": t["core_hits"] + t["core_misses"]
            + t["accel_accesses"],
            "totals": dict(t),
            "ipc_total": self.total_instr / (F(max(self.epoch, 1)) * self.et),
            "dmr": (sum(c > self.deadline for c in done) / len(done)
                    if done else 1.0),
            "core_br": t["core_bypasses"] / core_acc,
            "accel_br": t["accel_bypasses"] / acc,
            "core_hit_rate": t["core_hits"] / core_acc,
            "accel_hit_rate": t["accel_hits"] / acc,
            "completion_cycles": [float(c) for c in done],
            "deadline_cycles": float(self.deadline),
            "llc_accesses": float(self.total_llc),
            "dram_accesses": float(self.total_dram),
            "history": {k: [float(x) for x in v]
                        for k, v in self.hist.items()},
            "occupancy": [tuple(o) for o in self.occupancy],
        }


def standalone_deadline(soc: Soc, trace, fault: Fault = SOUND) -> float:
    """``deadline_factor`` x the accelerator's completion time alone: one
    input, ARP with no bypass, no core traffic, no deadline."""
    lane = Lane(soc, parse_policy("arp-nb"), trace, [], [], [], 1e12,
                n_inputs=1, fault=fault)
    lane.run()
    t0 = lane.completions[0] if lane.completions else 10 ** 9
    return lane.F(t0) * lane.F(soc.deadline_factor)


def core_writes(streams: List[np.ndarray], cores: List[Core],
                seed: int) -> List[np.ndarray]:
    """Each core's write flags, drawn from the run's seed core by core."""
    rng = np.random.default_rng(seed)
    return [rng.random(s.size) < c.write_frac for s, c in zip(streams, cores)]


def run_group(soc: Soc, policies: List[str], trace, cores: List[Core],
              streams: List[np.ndarray], seed: int, model=None,
              deadline: Optional[float] = None,
              fault: Fault = SOUND) -> Dict[str, dict]:
    """Every lane of one group from an empty LLC; ``model`` gives the
    LERN label tables for ``-AL`` lanes (and HyDRA)."""
    if deadline is None:
        deadline = standalone_deadline(soc, trace, fault)
    writes = core_writes(streams, cores, seed)
    clusters = None
    out = {}
    for name in policies:
        pol = parse_policy(name)
        if pol.accel == "lern" and clusters is None:
            n_l = int(np.asarray(model.n_uniq).size)
            rc_t, ri_t = lrpt(model, n_l)
            cold = np.asarray(model.rc_centers, np.float64)[:, 0]
            clusters = (rc_t, ri_t, cold)
        out[name] = Lane(soc, pol, trace, cores, streams, writes, deadline,
                         clusters, fault=fault).run()
    return out


INT_HISTORY = ("accel_rate", "ri_th", "rc_th")
FLOAT_HISTORY = ("requirement", "core_ipc", "amal")
FLOATS = ("ipc_total", "dmr", "core_br", "accel_br", "core_hit_rate",
          "accel_hit_rate", "llc_accesses")


def _rel(a: float, b: float) -> float:
    """|a - b| / |b| (1 where b is 0 and a is not; NaN reads 1e300)."""
    a, b = float(a), float(b)
    if a == b:
        return 0.0
    g = abs(a - b) / abs(b) if b != 0 else 1.0
    return g if g == g else 1e300


def _seq(got, want):
    """(entries that differ, counting a length difference, and the widest
    relative gap over the common entries)."""
    n = min(len(got), len(want))
    gaps = [_rel(a, b) for a, b in zip(got[:n], want[:n])]
    return (sum(g != 0 for g in gaps) + abs(len(got) - len(want)),
            max(gaps, default=0.0))


def compare_lane(got: dict, want: dict, occupancy: bool):
    """(int_mismatch, float_rel_gap) of one lane against the reference:
    integer totals, per-epoch integer histories and ``epochs`` must be
    equal (each difference counts 1); every float total and per-epoch
    timing history is held by its widest relative gap."""
    mism = sum(int(got[k] != want[k]) for k in
               ("epochs", "requests", "dram_accesses"))
    mism += int(len(got["completion_cycles"])
                != len(want["completion_cycles"]))
    for k in INT_HISTORY:
        mism += _seq(got["history"][k], want["history"][k])[0]
    if occupancy:
        g, w = got["occupancy"], want["occupancy"]
        mism += sum(a != b for a, b in zip(g, w)) + abs(len(g) - len(w))
    gap = max(_rel(got[k], want[k]) for k in FLOATS)
    gap = max(gap, _seq(got["completion_cycles"],
                        want["completion_cycles"])[1])
    for k in FLOAT_HISTORY:
        gap = max(gap, _seq(got["history"][k], want["history"][k])[1])
    return mism, gap
