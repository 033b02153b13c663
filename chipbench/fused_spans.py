"""The fused epoch engine's profiler spans in a reduced trace
(``trace_reduce``), per group the sweep job runs.

The bucketed engine marks its host-side phases ``fused.stage`` (staging
and carry set-up), ``fused.dispatch`` (tracing, compiling and enqueueing a
super-step), ``fused.device_wait`` (blocked on a super-step's outputs, the
host callbacks of the timing update included) and ``fused.writeback``
(per-epoch history and carry sync on the host).  A group is one of the
harness's ``chipbench.iteration`` spans.  The device's waits on the host
callbacks' transfers are the operations named in ``CALLBACK_OPS``.
Imports nothing of the simulator.

A trace with no device plane gives no reading (a CPU run), nor does one in
which the program marked no ``fused.*`` span (a program from before the
spans); where it marked some, a phase missing from the window is an error.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from chipbench.spans import _measure, spans
from chipbench.trace_reduce import Reduced, _union

ITERATION = "chipbench.iteration"
PHASES = ("fused.stage", "fused.dispatch", "fused.device_wait",
          "fused.writeback")
# device operations that wait on a host callback's transfer: the TPU
# trace names them ``send-done.3`` / ``recv-done.1`` and marks their
# instruction ``is_host_transfer=true``
CALLBACK_OPS = ("recv-done", "send-done")
HOST_TRANSFER = "is_host_transfer=true"


def groups(red: Reduced) -> Optional[int]:
    """The window's groups; None where there is nothing to read."""
    if not red.devices or not any(n.startswith("fused.")
                                  for n in red.host_names):
        return None
    n = spans(red, ITERATION)[0].size
    if n == 0:
        raise ValueError(f"the traced window holds no {ITERATION!r} span")
    return n


def phase_ms(red: Reduced, name: str) -> Optional[float]:
    """Milliseconds in the spans ``name`` per group of the window."""
    n = groups(red)
    if n is None:
        return None
    s, e = spans(red, name)
    if s.size == 0:
        raise ValueError(f"the window holds {n} groups and no {name!r} "
                         "span")
    return float(np.sum(e - s)) / n / 1e6


def callback_wait_share(red: Reduced) -> Optional[float]:
    """The union of the callback-wait operations' intervals, in % of the
    window, averaged over the devices used; None where none ran."""
    if not red.devices:
        return None
    shares, found = [], False
    for d in red.devices:
        keep = np.array([HOST_TRANSFER in text and any(
            nm == op or nm.startswith(op + ".") for op in CALLBACK_OPS)
            for text, nm in zip(d["texts"], d["names"])], bool)
        found |= bool(keep.any())
        shares.append(_measure(*_union(d["s"][keep], d["e"][keep]))
                      if keep.any() else 0.0)
    if not found:
        return None
    return 100.0 * float(np.mean(shares)) / (red.w1 - red.w0)
