#!/usr/bin/env python3
"""On-chip benchmark of the HyDRA simulator: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell is looked up in ``BENCHMARK.json``;
its configuration (``chipbench/configs/<config>.json``) and traffic
(``chipbench/traffic/<traffic>.json``) are data, and the traffic names the
job kind (``chipbench/jobs/<job>.py``) that drives the simulator.  A run
sets up and warms up every program the cell uses (``setup_s``, from process
start), then repeats the job's iteration until ``--seconds`` have passed
(the window ends at the first iteration boundary after that), then checks
a sample of the window's answers against the plain reference.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` records
a profiler trace of a window of at most ``TRACE_WINDOW_S`` and prints its
per-layer metrics, each read by its own reader
(``chipbench/metrics/<metric>.py``).  The last stdout
line is one JSON object; the compared numbers and their limits are also
the last lines on stderr.  Without a TPU (or with fewer chips than the
cell asks for) the run exits 3 and prints no result.
"""
import time

T_START = time.monotonic()      # set-up is measured from process start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import common  # noqa: E402


# A traced run traces a window of at most this many seconds, so that
# writing out and reading the trace keeps the run inside its time limit.
TRACE_WINDOW_S = 8.0


class NoChip(RuntimeError):
    pass


def _environment() -> None:
    """Fixed cache paths inside the checkout: artifacts (traces, LERN
    tables, deadline calibrations; never a simulated result) and JAX's
    persistent compilation cache."""
    os.environ["REPRO_CACHE"] = os.path.join(common.CACHE, "artifacts")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(common.CACHE,
                                                           "xla")
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def _memory_peak(chips: int):
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, overrides=None, out=sys.stdout,
             err=sys.stderr) -> dict:
    """One run; returns the result line (also printed).  ``overrides``
    (tests only) replaces keys of the configuration's params and of the
    traffic."""
    bench = common.benchmark()
    c = common.cell(workload, bench)
    chips = c["workload"]["chips"]
    dev = device_info(chips, require_tpu)
    common.check_cuts(c["config"])
    for part in ("params", "traffic"):
        for k, v in (overrides or {}).get(part, {}).items():
            (c["config"]["params"] if part == "params"
             else c["traffic"])[k] = v
    import jax
    clock = common.CompileClock()
    job_mod = common.load_module("jobs", c["traffic"]["job"] + ".py")
    job = job_mod.Job(c["config"], c["traffic"], seed)
    job.setup()
    setup_s = time.monotonic() - T_START
    setup = clock.since((0.0, 0, 0))

    tdir = os.path.join(common.CACHE, "trace", workload)
    window = min(seconds, TRACE_WINDOW_S) if trace else seconds
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        # host events are the benchmark's spans and JAX's runtime events;
        # a Python-function tracer would make the trace many times larger
        # and the run longer
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    mark = clock.mark()
    i = 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.window"):
        while True:
            with jax.profiler.TraceAnnotation("chipbench.iteration"):
                job.iteration(i)
            i += 1
            if time.perf_counter() - t0 >= window:
                break
    window_s = time.perf_counter() - t0
    in_window = clock.since(mark)
    if trace:
        jax.profiler.stop_trace()
    print(json.dumps({"window": {"iterations": i, "seconds": window_s,
                                 "backend_compiles":
                                     in_window["backend_compiles"],
                                 "compile_s": in_window["compile_s"],
                                 "cache_hits": in_window["cache_hits"],
                                 "setup_compile_s": setup["compile_s"],
                                 "setup_backend_compiles":
                                     setup["backend_compiles"],
                                 "setup_cache_hits": setup["cache_hits"]}}),
          file=out, flush=True)
    dev["memory_peak_bytes"] = _memory_peak(chips)

    metrics = {}
    result = {}
    if trace:
        from chipbench import trace_reduce
        t_red = time.perf_counter()
        red = trace_reduce.reduce_dir(tdir, chips)
        print(json.dumps({"trace": {"reduce_s": time.perf_counter() - t_red,
                                    "host_events": len(red.host_names)}}),
              file=out, flush=True)
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
        ctx = SimpleNamespace(setup=setup, window_s=window_s, trace=red,
                              work=getattr(job, "kernel_work", dict),
                              device_kind=dev["kind"])
        for m in common.metrics_for("per_layer", workload, bench):
            mod = common.load_module("metrics", m["name"] + ".py")
            v = mod.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = red.breakdown()
    else:
        e2e = dict(job.metrics(window_s))
        e2e["setup_s"] = (setup_s, "s")
        for m in common.metrics_for("end_to_end", workload, bench):
            v, unit = e2e[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": unit}

    checks = job.check()
    for line in checks.lines():
        print(line, file=err, flush=True)
    line = {"correct": checks.correct, "attempted": job.attempted,
            "failed": job.failed, "metrics": metrics, "device": dev}
    line.update(result)
    line["checks"] = checks.as_dict()
    print(json.dumps(line), file=out, flush=True)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _environment()
    try:
        run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
