"""The paper's design-space exploration in one command: evaluate the full
cache-policy zoo on one accelerator config + workload mix and print the
(IPC speedup, DMR, bypass-rate) table — Fig. 10a in CSV form.

    PYTHONPATH=src python examples/policy_explore.py --config config3 \
        --mix moti2 --jobs 4

One declarative spec, one batched ``exp.run``; pass ``--policies`` to
sweep any registered subset (``repro.exp.POLICIES.names()`` lists them).
"""
import argparse
import dataclasses
import sys

sys.path.insert(0, "src")

from repro import exp

POLS = ["fifo-nb", "fifo-cs", "arp-nb", "arp-cs", "arp-cas", "arp-cs-as",
        "arp-as-d", "arp-al", "arp-al-d", "arp-cs-as-d", "hydra",
        "dpcp", "flash"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="config7",
                    choices=exp.WORKLOADS.names())
    ap.add_argument("--mix", default="moti2")
    ap.add_argument("--inputs", type=int, default=3)
    ap.add_argument("--policies", default=None,
                    help="comma-separated policy names (default: the zoo)")
    ap.add_argument("--jobs", type=int, default=1,
                    help="worker processes for uncached points")
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "host", "fused", "bucketed"],
                    help="sweep engine (auto = bucketed device program "
                         "when --jobs 1, process pool otherwise)")
    args = ap.parse_args()
    pols = args.policies.split(",") if args.policies else POLS
    params = dataclasses.replace(exp.PARAMS.get("default"),
                                 n_inputs=args.inputs)
    spec = exp.ExperimentSpec.grid(config=args.config, mix=args.mix,
                                   policy=pols, params=params)
    plan = exp.ExecPlan(engine=args.engine, jobs=args.jobs)
    try:
        plan.resolve()
    except ValueError as e:  # e.g. --jobs > 1 on an accelerator
        ap.error(str(e))
    rs = exp.run(spec, plan=plan)
    print("policy,ipc_speedup,dmr,core_bypass_rate,accel_bypass_rate,"
          "core_hit_rate,accel_hit_rate")
    base = None
    for pol in pols:
        r = rs.filter(policy=pol).one()
        if base is None:
            base = r["ipc"]
        print(f"{pol},{r['ipc'] / base:.4f},{r['dmr']:.3f},"
              f"{r['core_br']:.3f},{r['accel_br']:.3f},"
              f"{r['core_hit_rate']:.3f},{r['accel_hit_rate']:.3f}")


if __name__ == "__main__":
    main()
