"""Pieces every job and reader of the benchmark shares: the compile clock,
seed derivation, the checks a run prints, and loading a file of the
benchmark by name.  Imports nothing of the simulator."""
from __future__ import annotations

import importlib.util
import json
import os
import time
from typing import Dict, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(ROOT, ".cache", "chipbench")


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching from
    the persistent cache), backend compiles and persistent-cache hits, from
    JAX's own monitoring events.

    A nested jit's trace is reported inside its parent's, so the durations
    overlap: the clock measures the union of the reported intervals, which
    never exceeds the wall time it is read over."""
    DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self._spans = []              # (start, end) on time.perf_counter()
        self.compiles = 0             # backend compiles, cache fetches included
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        # JAX reports a duration when the timed block ends
        if event in self.DURATIONS:
            end = time.perf_counter()
            self._spans.append((end - duration, end))
            self.compiles += event == self.DURATIONS[2]

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def seconds(self, since: float, until: float = float("inf")) -> float:
        """Compile-side seconds between two perf_counter readings."""
        total, cur = 0.0, None
        for s, e in sorted((max(s, since), min(e, until))
                           for s, e in self._spans if e > since and s < until):
            if cur is not None and s <= cur[1]:
                cur[1] = max(cur[1], e)
                continue
            if cur is not None:
                total += cur[1] - cur[0]
            cur = [s, e]
        return total + (cur[1] - cur[0] if cur is not None else 0.0)

    def mark(self) -> Tuple[float, int, int]:
        return time.perf_counter(), self.compiles, self.cache_hits

    def since(self, mark) -> Dict[str, float]:
        t0, n0, h0 = mark
        return {"compile_s": self.seconds(t0),
                "backend_compiles": self.compiles - n0,
                "cache_hits": self.cache_hits - h0}


def derive_seed(seed: int, index: int, bits: int = 31) -> int:
    """A seed for iteration ``index`` of a run started with ``seed``:
    splitmix64 of the pair, cut to ``bits`` bits (the simulator hands
    seeds to numpy and to ``jax.random.PRNGKey``)."""
    x = (int(seed) * 0x9E3779B97F4A7C15 + int(index) + 1) % (1 << 64)
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) % (1 << 64)
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) % (1 << 64)
    x ^= x >> 31
    return x % ((1 << bits) - 1024)


class Checks:
    """The numbers a run compares, each with its limit: ``correct`` holds
    when every number is at or under its limit."""

    def __init__(self):
        self.items: Dict[str, Tuple[float, float]] = {}

    def add(self, name: str, value: float, limit: float) -> None:
        self.items[name] = (float(value), float(limit))

    @property
    def correct(self) -> bool:
        return bool(self.items) and all(
            v <= lim for v, lim in self.items.values())

    def as_dict(self) -> Dict[str, Dict[str, float]]:
        return {k: {"value": v, "limit": lim}
                for k, (v, lim) in self.items.items()}

    def lines(self):
        return [f"check {k}: {v!r} (limit {lim!r})"
                for k, (v, lim) in self.items.items()]


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(*parts: str):
    """Import a file of the benchmark by its path (names may hold dots)."""
    path = os.path.join(HERE, *parts)
    name = "chipbench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str, bench: Optional[dict] = None) -> dict:
    """The workload entry with its configuration and traffic files."""
    bench = bench or benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = by_name[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(ROOT, cfg["file"])) as f:
        config = json.load(f)
    return {"workload": w, "config": config,
            "traffic": load_json("traffic", w["traffic"] + ".json")}


def check_cuts(config: dict) -> None:
    """The params a configuration runs with must carry the cuts it
    states."""
    p = config["params"]
    if (p["llc_size_bytes"] != config["llc_bytes"]
            or p["llc_ways"] != config["llc_ways"]
            or p["subsample_target"] != config["accel_accesses_per_input"]):
        raise ValueError(f"{config['name']}: params disagree with the "
                         "file's cuts")


def metrics_for(kind: str, name: str, bench: Optional[dict] = None):
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    that list it, and those that list no cells."""
    bench = bench or benchmark()
    every = [w["name"] for w in bench["workloads"]]
    return [m for m in bench[kind] if name in m.get("workloads", every)]
