"""How many routing choices a lower precision flips, in the plain
reference: the cell's weights and first batch from each seed, the
forward pass in float32 and again with every matmul operand rounded to
`--dtype`, and for every routed layer the (token, expert) pairs chosen
in float32 and not in the lower precision, of all pairs and of the
pairs sent to an expert held here. A flip is a near-tie between the
6th and the 7th score that rounding upstream of the router decides the
other way: it moves a whole token between experts, which no limit on a
gradient's error should be loosened to hide.

    python3 benchmark/tools/routing_flips.py \
        --workload nemotron3-nano-ep16-s8192 --seed-list 1,2 \
        --out chiprun_out/routing-flips.jsonl
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def flips(a, b, held):
    """(pairs of `a` [S, k] not in `b`, those of them on a held
    expert, held pairs of `a`)"""
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    gone = ~(a[:, :, None] == b[:, None, :]).any(axis=2)
    on_held = (a >= held[0]) & (a < held[0] + held[1])
    return int(gone.sum()), int((gone & on_held).sum()), int(on_held.sum())


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed-list", required=True)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--out", required=True)
    ap.add_argument("--base", default=None)
    args = ap.parse_args(argv)

    from benchmark import harness

    harness.place_compile_cache()
    base = args.base or harness.BENCH_DIR
    cell, config, traffic = harness.load_cell(args.workload, base=base)
    family = harness.load_family(config)
    ref = family.ref
    held = ref.held_range(config)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as out:
        for seed in (int(x) for x in args.seed_list.split(",")):
            weights = family.make_weights(config, seed)
            ids = family.make_ring(config, traffic, seed)[0]["ids"]
            layers = []
            for row in ids:
                exact = ref.routings(weights, row, config)
                low = ref.routings(weights, row, config, quant=args.dtype)
                layers.append([flips(a, b, held)
                               for a, b in zip(exact, low)])
            total = [[sum(seq[i][j] for seq in layers) for j in range(3)]
                     for i in range(len(layers[0]))]
            rec = {"cell": args.workload, "seed": seed, "dtype": args.dtype,
                   "pairs_a_layer": int(ids.size) * int(
                       config["num_experts_per_tok"]),
                   "layers": [{"flipped": f, "flipped_on_held": fh,
                               "held_pairs": hp} for f, fh, hp in total]}
            out.write(json.dumps(rec) + "\n")
            print("routing_flips:", json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
