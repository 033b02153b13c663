"""Benchmark harness — one module per paper table/figure (deliverable d).

    PYTHONPATH=src python -m benchmarks.run [--preset smoke|quick|full]
                                            [--only fig12,...] [--jobs N]
                                            [--engine auto|host|fused|bucketed]
                                            [--out sweep.json]
                                            [--resume] [--manifest M.json]

A thin CLI over the declarative experiment API: ``--preset`` resolves a
registered params preset + mix/config footprint into a frozen
``common.Suite`` that every figure module receives (no module-global
mutation), each module expresses its sweep as an ``ExperimentSpec`` run
under the suite's ``exp.ExecPlan`` (``suite.plan``), and the returned
rows are assembled into the machine-readable **sweep.json v3** artifact
(``hydra-sweep/v3``: every row embeds its point spec, including the
``dram_kind`` fluid/scheduled tag; validate with
``python -m repro.exp.schema sweep.json``).  Results are disk-cached
(.cache/sim); ``--jobs N`` fans uncached sweep points over N worker
processes, ``--engine`` pins the sweep engine (auto routes single-job
sweeps through the bucketed whole-sweep device program).

``fig05_clustering`` additionally times host-numpy vs device-batched LERN
training (the ``lern_train/*`` rows) and writes ``bench_lern.json``
(schema hydra-bench-lern/v3) — the perf-trajectory record for the
device-resident training pipeline; ``bench_sim`` does the same for the
main simulation path (``bench_sim.json``, schema hydra-bench-sim/v3:
host ``drive_lane`` vs the fused epoch engine, plus the sweep-level
map-vs-bucketed points/sec entries).  ``bench_serve`` runs the
multi-tenant trace-replay serving harness (``bench_serve.json``, schema
hydra-bench-serve/v1) and also writes the ``serve_replay.json``
hydra-serve/v1 row artifact.

``--resume`` re-opens the incremental ``hydra-manifest/v1`` ledger a
prior (killed) invocation left next to ``--out`` and re-executes only
the unfinished sweep points — completed ones load from the result cache
and are recorded with ``source="resume"`` (exp.run's PR-9 resume path,
wired through the ``REPRO_MANIFEST``/``REPRO_RESUME`` environment).
"""
import argparse
import importlib
import os
import sys
import time

MODULES = [
    "fig02_motivation", "fig05_clustering", "fig06_distribution",
    "tab_lern_accuracy", "fig10_policies", "fig11_access_rate",
    "fig12_configs", "fig14_occupancy", "fig15_afr_asth", "fig16_llc_sweep",
    "fig17_ddr", "fig18_waypart", "fig19_lrpt", "fig20_ship", "tab_params",
    "roofline", "bench_sim", "bench_serve",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="quick",
                    choices=["smoke", "quick", "full"],
                    help="registered suite footprint: smoke = CI-sized "
                         "(1 mix x 1 config, tiny params), quick = 2 mixes "
                         "x 5 configs, full = the paper's 12 x 10")
    ap.add_argument("--full", action="store_true",
                    help="deprecated alias for --preset full")
    ap.add_argument("--smoke", action="store_true",
                    help="deprecated alias for --preset smoke")
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset")
    ap.add_argument("--jobs", type=int, default=1,
                    help="worker processes for uncached sweep points")
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "host", "fused", "bucketed"],
                    help="sweep engine for every figure (ExecPlan.engine); "
                         "auto = bucketed device program when --jobs 1, "
                         "process pool otherwise")
    ap.add_argument("--out", default="sweep.json",
                    help="machine-readable results artifact path")
    ap.add_argument("--manifest", default=None,
                    help="incremental hydra-manifest/v1 ledger path "
                         "(default: <out>.manifest.json when --resume "
                         "is given)")
    ap.add_argument("--resume", action="store_true",
                    help="re-open the manifest from a prior (killed) "
                         "invocation: every sweep skips its finished "
                         "points, loading them from the result cache")
    args = ap.parse_args()
    preset = ("full" if args.full else
              "smoke" if args.smoke else args.preset)

    # the manifest/resume channel to every figure module's exp.run /
    # serve.run is the environment (the modules never thread manifest
    # arguments) — the runner reads REPRO_MANIFEST + REPRO_RESUME
    manifest = args.manifest or (args.out + ".manifest.json"
                                 if args.resume else None)
    if args.resume and not os.path.exists(manifest):
        ap.error(f"--resume: no prior manifest at {manifest!r} "
                 "(run once without --resume first, or pass --manifest)")
    if manifest:
        os.environ["REPRO_MANIFEST"] = manifest
        os.environ["REPRO_RESUME"] = "1" if args.resume else "0"

    from repro.exp import ResultSet
    from . import common
    suite = common.suite(preset=preset, jobs=args.jobs, engine=args.engine)
    try:
        suite.plan.resolve()
    except ValueError as e:  # e.g. --jobs > 1 on an accelerator
        ap.error(str(e))

    mods = args.only.split(",") if args.only else MODULES
    print("name,us_per_call,derived")
    t0 = time.time()
    failures = 0
    rows = []
    for name in mods:
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            mod.run(suite)
        except Exception as e:  # keep the suite going; report at the end
            failures += 1
            print(f"{name},0,ERROR={type(e).__name__}:{e}", flush=True)
        finally:
            # rows emitted before a failure still reach the artifact
            rows.extend(common.drain_rows())
    elapsed = time.time() - t0
    rs = ResultSet.from_records(rows)
    rs.to_sweep_json(args.out, preset=preset, modules=mods,
                     jobs=suite.jobs, elapsed_s=round(elapsed, 3),
                     failures=failures)
    print(f"# wrote {len(rows)} rows to {args.out}", flush=True)
    print(f"# total {elapsed:.0f}s, {failures} module failures", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
