"""The program's own profiler spans in a reduced trace (``trace_reduce``).

The LERN trainer marks each training ``lern.train``, tiled by its three
phases ``lern.extract``, ``lern.fit`` and ``lern.assemble`` (one per model
assembled), and the segmented k-means marks a straggler re-dispatch
``kmeans.stragglers``.  The readers here count the spans that start inside
the traced window and credit device 0's idle time, over every gap of the
window, to the phase whose span covers it.  Imports nothing of the
simulator.

A trace with no device plane gives no reading (a CPU run).  Neither does
one in which the program marked no span of the trainer at all (a program
from before the spans); but where it marked some, a window without a
``lern.train`` span is an error, so a span dropped or renamed fails the
run instead of leaving a reading empty.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from chipbench.trace_reduce import Reduced, _union

TRAIN = "lern.train"
PHASES = ("lern.extract", "lern.fit", "lern.assemble")
STRAGGLERS = "kmeans.stragglers"
PROGRAM_PREFIXES = ("lern.", "kmeans.")


def spans(red: Reduced, name: str) -> Tuple[np.ndarray, np.ndarray]:
    """(starts, ends) in ns of the host spans ``name`` that start inside
    the window, clipped to it."""
    names = np.asarray(red.host_names, dtype=object)
    s, e = red.host_s[names == name], red.host_e[names == name]
    keep = (s >= red.w0) & (s < red.w1)
    return s[keep], np.minimum(e[keep], red.w1)


def trainings(red: Reduced) -> Optional[int]:
    """The window's ``lern.train`` spans; None where there is nothing to
    read (module docstring)."""
    if not red.devices or not any(n.startswith(PROGRAM_PREFIXES)
                                  for n in red.host_names):
        return None
    n = spans(red, TRAIN)[0].size
    if n == 0:
        raise ValueError(f"the chip ran, but the traced window holds no "
                         f"{TRAIN!r} span")
    return n


def _measure(s: np.ndarray, e: np.ndarray) -> float:
    s, e = _union(s, e)
    return float(np.sum(e - s))


def idle_inside(red: Reduced, name: str) -> float:
    """Nanoseconds of device 0's idle time inside the spans ``name``."""
    gs, ge = red.gaps(0)
    ss, se = spans(red, name)
    both = _measure(np.concatenate([gs, ss]), np.concatenate([ge, se]))
    return _measure(gs, ge) + _measure(ss, se) - both


def phase_ms(red: Reduced, name: str) -> Optional[float]:
    """Milliseconds in the spans ``name`` per training of the window."""
    n = trainings(red)
    if n is None:
        return None
    s, e = spans(red, name)
    if s.size == 0:
        raise ValueError(f"the window holds {n} {TRAIN!r} spans and no "
                         f"{name!r} span")
    return float(np.sum(e - s)) / n / 1e6


def phase_idle_share(red: Reduced, name: str) -> Optional[float]:
    """Device 0's idle time inside the spans ``name``, in % of the
    window."""
    if phase_ms(red, name) is None:
        return None
    return 100.0 * idle_inside(red, name) / (red.w1 - red.w0)


def redispatch_share(red: Reduced) -> Optional[float]:
    """``kmeans.stragglers`` spans per training of the window, in %."""
    n = trainings(red)
    if n is None:
        return None
    return 100.0 * spans(red, STRAGGLERS)[0].size / n
