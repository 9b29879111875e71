"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell on the machine it is started on; the last line of
standard output is the result object. Exits non-zero and prints no
result where JAX finds no TPU or fewer chips than the cell asks for, or
where the program under test is not there."""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmark import harness

    harness.place_compile_cache()
    cell = harness.load_json("workloads", args.workload + ".json")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < int(cell["chips"]):
        print("bench: %d x %r found, the cell needs %d x tpu: nothing is "
              "measured" % (len(devices), devices[0].platform,
                            int(cell["chips"])), file=sys.stderr)
        return 2
    import paddle_tpu  # noqa: F401 - the program under test must be here

    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), devices, T_START)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
