"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is first normalised (``normalise``) to plain lists: per device,
the operations that ran on it as ``[name, start_ns, duration_ns]``; and
the host events (the benchmark's own ``TraceAnnotation`` spans, JAX's
runtime events, the host callbacks) in the same form.  Everything after
that (``Reduced``) works on the normalised form, which is what the
recorded test trace holds.

- window: the host span ``chipbench.window``;
- busy: the union of the intervals in which an operation ran on a device,
  inside the window, averaged over the devices used; idle share is
  1 - busy / window;
- kernel calls: the operations named after the kernel, each with its
  duration (an operation's name is its HLO instruction's, ``%name.N``; the
  whole event name is the instruction text, which also gives the shapes
  of its result and operands);
- idle gaps: each stretch of the window in which no device operation ran,
  named by the innermost host event that covers its middle.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional

import numpy as np

WINDOW = "chipbench.window"
DEVICE_PREFIX = "/device:TPU:"
# per device plane, the line that holds one event per operation executed
OPS_LINE = "XLA Ops"


def normalise(xplane_path: str, chips: int) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    devices: List[dict] = []
    host: List[list] = []
    for plane in pd.planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX):
            idx = name[len(DEVICE_PREFIX):]
            if not idx.isdigit() or int(idx) >= chips:
                continue
            ops = [[e.name, e.start_ns, e.duration_ns]
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices.append({"name": name, "ops": ops})
        elif name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events if e.duration_ns > 0)
    return {"devices": devices, "host": host}


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _union(starts: np.ndarray, ends: np.ndarray):
    """Merged, sorted intervals of the given ones."""
    if starts.size == 0:
        return starts, ends
    o = np.argsort(starts, kind="stable")
    s, e = starts[o], ends[o]
    run_end = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > run_end[:-1]
    grp = np.cumsum(new) - 1
    return s[new], np.maximum.reduceat(run_end, np.flatnonzero(new))[
        :grp[-1] + 1]


class Reduced:
    def __init__(self, norm: dict):
        host = norm["host"]
        win = [h for h in host if h[0] == WINDOW]
        if not win:
            raise ValueError(f"the trace has no {WINDOW!r} span")
        self.w0 = float(win[0][1])
        self.w1 = self.w0 + float(win[0][2])
        self.window_s = (self.w1 - self.w0) / 1e9
        self.devices = []
        for d in norm["devices"]:
            ops = d["ops"]
            s = np.array([o[1] for o in ops], np.float64)
            e = s + np.array([o[2] for o in ops], np.float64)
            keep = (e > self.w0) & (s < self.w1)
            self.devices.append({
                "texts": [o[0] for o, k in zip(ops, keep) if k],
                "names": [op_name(o[0]) for o, k in zip(ops, keep) if k],
                "s": np.clip(s[keep], self.w0, self.w1),
                "e": np.clip(e[keep], self.w0, self.w1)})
        hs = np.array([h[1] for h in host], np.float64)
        self.host_names = [h[0] for h in host]
        self.host_s = hs
        self.host_e = hs + np.array([h[2] for h in host], np.float64)

    # -- device time ------------------------------------------------------
    @property
    def busy_s(self) -> float:
        if not self.devices:
            return 0.0
        tot = []
        for d in self.devices:
            s, e = _union(d["s"], d["e"])
            tot.append(float(np.sum(e - s)) / 1e9)
        return float(np.mean(tot))

    @property
    def idle_share(self) -> Optional[float]:
        if not self.devices or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def kernel_calls(self, name: str):
        """``[(instruction text, device seconds)]`` of the operations named
        ``name`` (any ``.N`` suffix) inside the window, over all devices."""
        out = []
        for d in self.devices:
            for text, nm, s, e in zip(d["texts"], d["names"], d["s"],
                                      d["e"]):
                if nm == name or nm.startswith(name + "."):
                    out.append((text, float(e - s) / 1e9))
        return out

    def top_ops(self, n: int = 10):
        tot: Dict[str, float] = {}
        for d in self.devices:
            for nm, s, e in zip(d["names"], d["s"], d["e"]):
                tot[nm] = tot.get(nm, 0.0) + (e - s) / 1e9
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    # -- idle gaps ----------------------------------------------------------
    def gaps(self, device: int = 0):
        """Idle stretches of one device inside the window (ns pairs)."""
        d = self.devices[device]
        s, e = _union(d["s"], d["e"])
        starts = np.concatenate([[self.w0], e])
        ends = np.concatenate([s, [self.w1]])
        keep = ends > starts
        return starts[keep], ends[keep]

    def name_at(self, t: float) -> str:
        """The innermost host event covering time ``t``: the latest start
        among the covering ones, the shortest of those that share it."""
        cover = (self.host_s <= t) & (self.host_e >= t)
        if not cover.any():
            return "(no host event)"
        idx = np.flatnonzero(cover)
        idx = idx[self.host_s[idx] == self.host_s[idx].max()]
        return self.host_names[idx[np.argmin(self.host_e[idx])]]

    def idle_gaps(self, n: int = 10, longest: int = 500):
        """Idle seconds by what the host was doing, for the ``longest``
        gaps of device 0; the ``n`` largest totals."""
        if not self.devices:
            return []
        gs, ge = self.gaps(0)
        order = np.argsort(gs - ge)[:longest]
        tot: Dict[str, float] = {}
        for k in order:
            nm = self.name_at((gs[k] + ge[k]) / 2)
            tot[nm] = tot.get(nm, 0.0) + (ge[k] - gs[k]) / 1e9
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_dir(trace_dir: str, chips: int) -> Reduced:
    return Reduced(normalise(find_xplane(trace_dir), chips))
