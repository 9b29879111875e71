"""The step time of a cell's job with keys of its configuration set
otherwise, beside the cell as it is: what a departure from the published
recipe that the comparison forces (dropout at 1e-7 where 0.1 is
published) is worth in time. No comparison is made and no reference is
run: a job with dropout 0.1 has none. One JSON object a variant goes to
standard output.

    python3 benchmark/tools/step_time.py --workload bert-base-s128 \
        --seed 5 --steps 12 \
        --set hidden_dropout_prob=0.1 --set attention_probs_dropout_prob=0.1
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="a key of the configuration and its value")
    ap.add_argument("--with-cell", action="store_true",
                    help="time the cell as it is too, after the variant")
    ap.add_argument("--base", default=None)
    ap.add_argument("--any-backend", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import harness

    harness.place_compile_cache()
    import jax

    base = args.base or harness.BENCH_DIR
    cell, config, traffic = harness.load_cell(args.workload, base=base)
    devices = jax.devices()[:int(cell["chips"])]
    if devices[0].platform != "tpu" and not args.any_backend:
        print("step_time: no tpu", file=sys.stderr)
        return 2
    family = harness.load_family(config)
    changed = {k: json.loads(v) for k, v in
               (item.split("=", 1) for item in args.set)}
    unknown = sorted(set(changed) - set(config))
    if unknown:
        raise KeyError("the configuration has no key %s" % unknown)
    feeds = family.make_ring(config, traffic, args.seed)
    for overrides in [changed] + ([{}] if args.with_cell else []):
        job = family.build(dict(config, **overrides), traffic, cell,
                           args.seed, devices)
        try:
            harness.run_steps(job, feeds,
                              lambda k, t: k >= harness.WARM_STEPS)
            w = harness.run_steps(job, feeds,
                                  lambda k, t: k >= args.steps)
        finally:
            job.free()
        print(json.dumps({
            "workload": args.workload, "set": overrides,
            "steps": w["completed"], "failed": w["failed"],
            "step_ms": 1e3 * w["elapsed_s"] / max(w["completed"], 1),
            "units_per_s": family.units_per_step(config, traffic)
            * w["completed"] / w["elapsed_s"],
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
