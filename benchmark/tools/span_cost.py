"""What one `fluid.profiler.span` costs with no trace on, in nanoseconds
(host code only: run it with `JAX_PLATFORMS=cpu`, it needs no chip):

    JAX_PLATFORMS=cpu python3 benchmark/tools/span_cost.py [repeats]

Times an empty `with span("exe.bind"):` against an empty `with` of a
context manager that does nothing, and the executor's whole set of a
step (`exe.step` and eight spans, two of them into the step's account),
best of five rounds each; where the program has no `span`, says so."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


class _Nothing:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def best_ns(fn, n, rounds=5):
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        fn(n)
        best = min(best, time.perf_counter() - t0)
    return 1e9 * best / n


def main(n=200000):
    from paddle_tpu.fluid import profiler

    span = getattr(profiler, "span", None)
    if span is None:
        print("this program has no fluid.profiler.span: nothing to time")
        return 0

    def empty(n):
        for _ in range(n):
            with _Nothing():
                pass

    def one(n):
        for _ in range(n):
            with span("exe.bind"):
                pass

    def step(n):
        ph = {}
        for _ in range(n):
            with profiler.step_span("exe.step"):
                for name in ("exe.feed", "exe.feed", "exe.dispatch"):
                    with span(name, ph):
                        pass
                for name in ("exe.bind", "exe.bind", "exe.writeback"):
                    with span(name):
                        pass

    base = best_ns(empty, n)
    print("empty with: %.0f ns; span(): %.0f ns; a step's set (exe.step + "
          "6 spans): %.0f ns" % (base, best_ns(one, n),
                                 best_ns(step, n // 8)))
    profiler.reset_step_phases()
    return 0


if __name__ == "__main__":
    sys.exit(main(*(int(a) for a in sys.argv[1:2])))
