"""A/B microbench: Pallas flash attention vs the XLA reference path,
fwd+bwd, across sequence lengths — the measurement that sets
FLAGS_flash_attention_min_seq.

Run on the chip, in one process (it starts no child):

    python tools/attn_ab.py            # seq 512 1024 2048 4096
    python tools/attn_ab.py 1024 4096  # explicit seq list

Prints one JSON line per (seq, impl, dropout) with ms/step, and a final
`crossover` line naming the smallest measured seq where flash wins both
dropout settings — paste that into FLAGS_flash_attention_min_seq
(utils/flags.py).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bench_one(fn, args, iters=20, warmup=3):
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def main(seqs) -> int:
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import flash_attention, reference_attention

    plat = jax.devices()[0].platform
    if plat != "tpu":
        print(json.dumps({"error": "backend is %s, not tpu" % plat}))
        return 1

    B, H, D = 2, 12, 64
    r = np.random.RandomState(0)
    results = []
    for S in seqs:
        q, k, v = (jnp.asarray(
            r.randn(B, H, S, D).astype(np.float32)).astype(jnp.bfloat16)
            for _ in range(3))
        seed = jnp.int32(7)

        def loss_flash(q, k, v, p):
            return jnp.sum(flash_attention(
                q, k, v, dropout_p=p, dropout_seed=seed
                if p else None).astype(jnp.float32))

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v)
                           .astype(jnp.float32))

        per_impl = {}
        for name, fn in (
                ("flash", jax.jit(jax.grad(
                    lambda q, k, v: loss_flash(q, k, v, 0.0),
                    argnums=(0, 1, 2)))),
                ("flash_dropout", jax.jit(jax.grad(
                    lambda q, k, v: loss_flash(q, k, v, 0.1),
                    argnums=(0, 1, 2)))),
                ("xla", jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2))))):
            try:
                ms = _bench_one(fn, (q, k, v))
            except Exception as e:  # noqa: BLE001 - e.g. OOM at long S
                ms = None
                print(json.dumps({"seq": S, "impl": name,
                                  "error": repr(e)[:160]}), flush=True)
            if ms is not None:
                per_impl[name] = ms
                print(json.dumps({"seq": S, "impl": name,
                                  "ms_per_step": round(ms, 2)}),
                      flush=True)
        results.append((S, per_impl))

    crossover = crossover_min_seq(results)
    print(json.dumps({"crossover_min_seq": crossover,
                      "note": "set FLAGS_flash_attention_min_seq to "
                              "this (utils/flags.py:45)"}))
    return 0


def crossover_min_seq(results):
    """Smallest measured seq from which flash wins at EVERY measured
    length (both dropout settings); an XLA OOM counts as a flash win
    only when flash itself produced numbers there. results:
    [(seq, {impl: ms}), ...] ascending."""
    crossover = None
    for S, r_ in results:
        flash_ok = "flash" in r_ and "flash_dropout" in r_
        if not flash_ok:
            crossover = None  # flash itself unmeasured here: no claim
            continue
        if "xla" not in r_:
            # XLA path failed (OOM) while flash ran: flash wins here
            crossover = crossover or S
            continue
        if r_["flash"] < r_["xla"] and r_["flash_dropout"] < r_["xla"]:
            crossover = crossover or S
        else:
            crossover = None  # must win at every longer seq too
    return crossover


if __name__ == "__main__":
    seqs = sorted({int(a) for a in sys.argv[1:]}) \
        or [512, 1024, 2048, 4096]
    sys.exit(main(seqs))
