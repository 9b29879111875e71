"""The readings a cell's limits are set from, all in one process (set-up
is long, so a dozen seeds share it): for each seed the program's first
steps against the plain reference; for the first `--control-seeds` of
them also the control (the reference put in the program's place with
its matmul or convolution operands in the next precision down) and the
planted faults (half of the batch left out, the mean taken over the
rest; under Adam the bias corrected one step ahead, as the library's
does today; on several chips the exchange left out). Every reading is put
through `harness.verdict` against the cell's own limits, so that a line
says which held numbers it fails. One JSON object a line goes to
`--out`, with every leaf's norms and gradient error, so that a number
can be looked into afterwards.

    python3 benchmark/tools/readings.py --workload bert-base-s128 \
        --seeds 12 --first-seed 1001 --control-seeds 3 \
        --out chiprun_out/readings-bert-base-s128.jsonl
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
#: the nearest precision below the configurations' bfloat16
CONTROL_DTYPE = "float8_e4m3"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=1001)
    ap.add_argument("--seed-list", default="",
                    help="these seeds, comma-separated, instead of "
                         "--seeds consecutive ones from --first-seed")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--witness", default="",
                    help="also read the reference with operands rounded "
                         "to this type against itself (e.g. bfloat16)")
    ap.add_argument("--out", required=True)
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
        print("readings: no tpu", file=sys.stderr)
        return 2
    family = harness.load_family(config)
    n = int(cell["check_steps"])
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    out = open(args.out, "a")

    def scalars(side):
        return {k: v for k, v in side.items() if k != "grads"}

    def emit(kind, seed, got, want, t0, **more):
        numbers, where = harness.compare(got, want)
        compared, ok = harness.verdict(numbers, cell["limits"])
        fails = sorted(k for k, (v, lim) in compared.items() if not v <= lim)
        rec = {"cell": args.workload, "kind": kind, "seed": seed,
               "numbers": numbers, "where": where, "correct": ok,
               "fails": fails, "limits": cell["limits"],
               "grad_errs": harness.grad_errors(got, want),
               "seconds": round(time.perf_counter() - t0, 2),
               "got": scalars(got), **more}
        out.write(json.dumps(rec) + "\n")
        out.flush()
        print("readings: seed %d %-18s %s  (%s; %.1f s) -> %s" % (
            seed, kind, "  ".join("%s %.4g" % kv for kv in numbers.items()),
            "  ".join("%s@%s" % (k, where[k]) for k in numbers if k in where),
            rec["seconds"],
            "correct" if ok else "NOT correct: fails " + ", ".join(fails)),
            flush=True)

    def program(seed, feeds):
        job = family.build(config, traffic, cell, seed, devices)
        try:
            return harness.checked_steps(job, feeds, n)
        finally:
            job.free()

    seeds = ([int(x) for x in args.seed_list.split(",")] if args.seed_list
             else [args.first_seed + i for i in range(args.seeds)])
    for i, seed in enumerate(seeds):
        feeds = family.make_ring(config, traffic, seed)
        batches = [feeds[j % len(feeds)] for j in range(n)]
        t0 = time.perf_counter()
        got = program(seed, feeds)
        t1 = time.perf_counter()
        want = family.reference(config, traffic, cell, seed, batches)
        emit("program", seed, got, want, t0, reference=scalars(want),
             program_seconds=round(t1 - t0, 2))
        if i >= args.control_seeds:
            continue
        t0 = time.perf_counter()
        emit("control", seed, family.reference(
            config, traffic, cell, seed, batches, quant=CONTROL_DTYPE),
            want, t0)
        if args.witness:
            t0 = time.perf_counter()
            emit("witness_" + args.witness, seed, family.reference(
                config, traffic, cell, seed, batches, quant=args.witness),
                want, t0)
        t0 = time.perf_counter()
        half = int(traffic["batch"]) // 2
        emit("fault_half_batch", seed, family.reference(
            config, traffic, cell, seed, batches, keep=slice(0, half)),
            want, t0)
        if config["recipe"].get("optimizer") == "adam":
            # the library's Adam as it stands (PERF.md, Open questions):
            # the bias corrected one step ahead
            t0 = time.perf_counter()
            emit("fault_adam_ahead", seed, family.reference(
                config, traffic, cell, seed, batches, adam_ahead=1),
                want, t0)
        replicas = int((cell.get("parallel") or {}).get("dp", 1))
        if replicas > 1:
            # the exchange between chips left out: a replica steps on the
            # gradient of its own rows alone
            t0 = time.perf_counter()
            own = int(traffic["batch"]) // replicas
            emit("fault_no_exchange", seed, family.reference(
                config, traffic, cell, seed, batches, keep=slice(0, own)),
                want, t0)
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
