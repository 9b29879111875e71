"""Chip smoke: the quickest proof that the system still starts on the chip.

    python chip_smoke.py          one TPU chip; five phases, one process
    python chip_smoke.py --dp4    four chips: BERT-base data-parallel
                                  against the same steps on one chip,
                                  and no other phase

It drives the main paths through the entry points users call, at the
full width of the models the repo supports, with random weights made
from a seed, and checks what comes out by the repo's own means. Each
phase prints one JSON line (phase, seconds, compile seconds, cache
hits, what it checked); compile and step seconds are set-up facts of
this run, not benchmark numbers. Any phase that raises fails the
script: the last line is then `{"ok": false, ...}` and the exit code is
1. On success the last line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as JAX reports it. A backend that is not `tpu` fails in
the first phase and nothing runs on it. The builders are bench.py's —
this file holds no second copy of a model's set-up.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

# what `device` demands; the CPU rehearsal tests steer it to "cpu", and
# the compiled-kernel checks apply on "tpu" only (the interpreter the
# CPU backend uses leaves no custom call in the HLO)
EXPECT_PLATFORM = "tpu"
_KERNEL_MARK = "tpu_custom_call"


def _check(cond, what):
    if not cond:
        raise AssertionError(what)


def _on_expected_platform(array, what):
    plats = {d.platform for d in array.devices()}
    _check(plats == {EXPECT_PLATFORM},
           "%s lives on %s, not %s" % (what, sorted(plats), EXPECT_PLATFORM))


def _process_peak_hbm_gb(devices):
    """`memory_stats()["peak_bytes_in_use"]` of each device: the
    high-water mark of the whole PROCESS so far, so only the first
    phase that reads it can call it its own. On this runtime it counts
    live arrays, not a running program's temporaries (those are in
    `_step_memory_gb`)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(round(stats["peak_bytes_in_use"] / 1e9, 3))
    return peaks or "not reported by this backend"


def _step_memory_gb(exe, program, feed, loss, scope):
    """What the compiled step itself needs on one device, from the
    compiler's `memory_analysis()` of the executable that ran."""
    step = exe.step_memory(program, feed, [loss], scope)
    return {k: round(step[k] / 1e9, 3)
            for k in ("argument", "output", "alias", "temp")}


def _run_phase(name, fn, **kw):
    """Run one phase, print its JSON line, return what it checked. A
    phase that raises propagates: nothing is caught and carried past."""
    from paddle_tpu.fluid import compile_cache

    before = compile_cache.jax_stats()
    t0 = time.perf_counter()
    checked = fn(**kw)
    seconds = time.perf_counter() - t0
    d = compile_cache.stats_delta(before)
    print(json.dumps({
        "phase": name, "seconds": round(seconds, 2),
        "compile_seconds": round(d["backend_compile_s"], 2),
        "cache": {"backend_compiles": int(d["backend_compiles"]),
                  "persistent_hits": int(d["persistent_hits"])},
        "checked": checked}), flush=True)
    return checked


# -- phases ----------------------------------------------------------------

def _describe_devices():
    """The devices as JAX reports them (the contract's last-line keys)."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def phase_device(count, device=None):
    device = device or _describe_devices()
    _check(device["platform"] == EXPECT_PLATFORM,
           "jax found platform %r, not %r"
           % (device["platform"], EXPECT_PLATFORM))
    _check(device["count"] == count,
           "need %d device(s), jax reports %d" % (count, device["count"]))
    return device


def _train_steps(exe, program, feed, loss, steps, scope=None,
                 after_first=None):
    """`steps` runs on one fixed batch; returns (losses, first-run
    seconds, later-step seconds, last fetch as a device array). A
    data-parallel program fetches one loss per replica, each over its
    equal share of the batch: the step's loss is their mean.
    `after_first()` is called once the first update has been applied."""
    import numpy as np

    losses, times = [], []
    for i in range(steps):
        t0 = time.perf_counter()
        out = exe.run(program, feed=feed, fetch_list=[loss], scope=scope,
                      return_numpy=False)[0]
        losses.append(float(np.mean(np.asarray(out))))
        times.append(time.perf_counter() - t0)
        if i == 0 and after_first is not None:
            after_first()
    _check(all(np.isfinite(losses)), "loss not finite: %r" % (losses,))
    _check(min(losses[1:]) < losses[0] and losses[-1] < losses[0],
           "loss does not fall on a fixed batch: %r" % (losses,))
    return losses, times[0], times[1:], out.value


def _state_on_platform(scope, program):
    """Every persistable array of the program lives on the platform."""
    n = 0
    for var in program.list_vars():
        if not getattr(var, "persistable", False):
            continue
        val = scope.find_var(var.name)
        if val is not None and hasattr(val, "devices"):
            _on_expected_platform(val, "state %s" % var.name)
            n += 1
    _check(n > 0, "no state array found in the scope")
    return n


def _train_phase(main_p, startup_p, loss, feed, steps):
    """startup -> `steps` runs on one chip -> the checks both training
    phases share; returns their common report."""
    import jax

    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.scope import Scope

    scope = Scope()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup_p, scope=scope)
    losses, first_s, step_s, out = _train_steps(exe, main_p, feed, loss,
                                                steps, scope)
    _on_expected_platform(out, "fetched loss")
    return {
        "amp": "bfloat16",
        "losses": [round(v, 4) for v in losses],
        "first_run_seconds": round(first_s, 2),
        "step_seconds": [round(t, 4) for t in step_s],
        "state_arrays_on_device": _state_on_platform(scope, main_p),
        "step_memory_gb": _step_memory_gb(exe, main_p, feed, loss, scope),
        "process_peak_hbm_gb": _process_peak_hbm_gb(jax.devices()[:1]),
    }


def phase_bert_train(batch=256, seq_len=128, cfg=None, steps=5):
    """The main path: bert_pretrain_loss(scan_layers) -> AMP(Adam) ->
    minimize -> Executor(TPUPlace()) -> run(startup) -> run(main)."""
    import numpy as np

    import bench

    main_p, startup_p, total, cfg = bench.build_bert_train_program(
        seq_len, cfg)
    n_params = sum(int(np.prod(p.shape)) for p in main_p.all_parameters())
    report = {
        "model": "bert", "layers": cfg.num_hidden_layers,
        "hidden": cfg.hidden_size, "heads": cfg.num_attention_heads,
        "vocab": cfg.vocab_size, "params_m": round(n_params / 1e6, 1),
        "batch": batch, "seq_len": seq_len}
    report.update(_train_phase(main_p, startup_p, total,
                               bench.bert_feed(cfg, batch, seq_len), steps))
    return report


def phase_resnet_train(batch=128, depth=50, img=224, class_dim=1000,
                       steps=4):
    """bench.build_resnet_train_program (momentum + bf16 AMP) at a
    learning rate small enough that the loss on one fixed batch falls
    from the first step (the builder's 0.1 is for fresh batches)."""
    import bench

    main_p, startup_p, loss = bench.build_resnet_train_program(
        depth=depth, img_size=img, class_dim=class_dim,
        learning_rate=0.02)
    report = {"model": "resnet%d" % depth, "img": img,
              "classes": class_dim, "batch": batch}
    report.update(_train_phase(main_p, startup_p, loss,
                               bench.resnet_feed(batch, img, class_dim),
                               steps))
    return report


def phase_flash_attention(B=2, H=12, S=4096, D=64, dropout=0.1):
    """The SDPA op through its own dispatch (ops/nn_ops.py): forward and
    backward with dropout and a key bias, compiled by Mosaic; then the
    same op with dropout off against `reference_attention`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.rng import make_key
    from paddle_tpu.ops.pallas import reference_attention
    from paddle_tpu.ops.registry import get_op

    sdpa = get_op("scaled_dot_product_attention").compute
    r = np.random.RandomState(0)
    q, k, v = (jnp.asarray(r.randn(B, H, S, D) * 0.5, jnp.bfloat16)
               for _ in range(3))
    # a padding mask: the last eighth of the keys of sequence 1 is masked
    mask = np.ones((B, S), "float32")
    mask[-1, S - S // 8:] = 0.0
    bias = jnp.asarray((mask - 1.0) * 1e4)
    w = jnp.asarray(r.randn(B, H, S, D), jnp.float32)  # cotangent mix

    # bias and the cotangent mix `w` are ARGUMENTS, not closed over: a
    # closed-over array is baked into the executable as a constant (the
    # 25 MB `w` made each of these steps a 90 MB cache entry)
    def op_loss(q, k, v, bias, w, p_drop):
        out = sdpa({"Q": [q], "K": [k], "V": [v], "KeyBias": [bias]},
                   {"attn_dropout_prob": p_drop, "is_test": False,
                    "_rng_key": make_key(3)})["Out"]
        return jnp.sum(out.astype(jnp.float32) * w), out

    def ref_loss(q, k, v, bias, w):
        out = reference_attention(q, k, v, key_bias=bias)
        return jnp.sum(out.astype(jnp.float32) * w), out

    def grad_fn(f, **kw):
        return jax.jit(jax.value_and_grad(
            lambda *a: f(*a, **kw), argnums=(0, 1, 2), has_aux=True))

    args = (q, k, v, bias, w)
    checked = {"shape": [B, H, S, D], "dtype": "bfloat16",
               "dropout": dropout, "key_bias": True}
    for name, p in (("dropout", dropout), ("no_dropout", 0.0)):
        step = grad_fn(op_loss, p_drop=p)
        compiled = step.lower(*args).compile()
        if EXPECT_PLATFORM == "tpu":
            n = compiled.as_text().count(_KERNEL_MARK)
            # forward + dkv + dq kernels
            _check(n >= 3, "%s step holds %d Mosaic kernels, not the "
                   "flash forward and two backward kernels" % (name, n))
            checked["mosaic_kernels_" + name] = n
        (_, out), grads = compiled(*args)
        for a in (out,) + tuple(grads):
            _on_expected_platform(a, "flash %s output" % name)
            _check(bool(jnp.all(jnp.isfinite(a.astype(jnp.float32)))),
                   "flash %s output not finite" % name)
    # `out`/`grads` now hold the dropout-off run: same inputs, reference
    (_, ref_out), ref_grads = grad_fn(ref_loss)(*args)

    def rel_err(a, b):
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))

    errs = {"out": rel_err(out, ref_out)}
    for n_, g, rg in zip(("dq", "dk", "dv"), grads, ref_grads):
        errs[n_] = rel_err(g, rg)
    # bf16 inputs and outputs: 2^-8 relative steps, accumulated in f32
    tol = 3e-2
    _check(all(e < tol for e in errs.values()),
           "flash vs reference_attention beyond %g: %r" % (tol, errs))
    checked["max_rel_err_vs_reference"] = {k_: round(e, 5)
                                           for k_, e in errs.items()}
    checked["tolerance"] = tol
    return checked


def _serve(impl, kv_dtype, prompts, max_new):
    """Answer `prompts` through a fresh Engine; returns (streams, engine)."""
    import jax

    from paddle_tpu import serving

    model = serving.TinyDecoderLM(serving.TinyLMConfig())
    cfg = serving.EngineConfig.from_flags(
        num_pages=128, page_size=16, max_seqs=4, attention_impl=impl,
        kv_dtype=kv_dtype, prefix_cache=True)
    # f32 matmuls at full precision in BOTH engines: at the chip's
    # default (one bf16 pass) kernel and reference round differently
    # and a greedy argmax over random-weight logits may flip on a tie;
    # the phase is about the kernel and the engine loop
    with jax.default_matmul_precision("highest"):
        engine = serving.Engine(model, config=cfg, seed=0)
        engine.warmup()
        reqs = [engine.submit(p, max_new_tokens=max_new) for p in prompts]
        engine.run_until_idle()
    _check(all(r.state == "finished" for r in reqs),
           "unfinished requests: %r" % ([r.state for r in reqs],))
    return [list(r.output_tokens) for r in reqs], engine


def phase_serving(n_requests=6, max_new=12):
    """serving.Engine over TinyDecoderLM (the only served model the tree
    has): the ragged paged attention kernel against the gather
    reference, bf16 and int8 KV pages, equal token streams."""
    import jax
    import numpy as np

    r = np.random.RandomState(1)
    prompts = [[int(t) for t in r.randint(1, 64, size=n)]
               for n in r.randint(3, 40, size=n_requests)]
    checked = {"model": "TinyDecoderLM", "requests": n_requests,
               "prompt_lens": [len(p) for p in prompts],
               "max_new_tokens": max_new, "matmul_precision": "highest"}
    for kv_dtype in ("bfloat16", "int8"):
        got, engine = _serve("kernel", kv_dtype, prompts, max_new)
        want, _ = _serve("reference", kv_dtype, prompts, max_new)
        _check(all(len(s) == max_new for s in got),
               "short streams: %r" % ([len(s) for s in got],))
        _check(got == want, "kv_dtype=%s: kernel streams differ from the "
               "reference's:\n%r\n%r" % (kv_dtype, got, want))
        _on_expected_platform(jax.tree_util.tree_leaves(engine.pages)[0],
                              "KV pages")
        texts = [c.as_text() for c in engine._compiler._compiled.values()]
        if EXPECT_PLATFORM == "tpu":
            _check(texts and all(_KERNEL_MARK in t for t in texts),
                   "a %s bucket was compiled without the Mosaic kernel"
                   % kv_dtype)
        checked[kv_dtype] = {"streams_equal": True,
                             "buckets_compiled": len(texts),
                             "tokens": int(np.sum([len(s) for s in got]))}
    return checked


def _zero_dropout(cfg):
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_probs_dropout_prob = 0.0
    return cfg


#: data-parallel agreement tolerances. After ONE update from the same
#: seeded weights the Adam first moments are (1 - beta1) x the averaged
#: gradient: a sum taken for a mean moves them by three times their norm,
#: one replica's share lost from the sum by a quarter of it, bf16 rounding
#: by thousandths. The masters after that update can differ by at most two
#: learning rates (|Adam's first step| <= lr), whatever the gradients'
#: rounding. The losses (the mean over the replicas' shares against the
#: one chip's whole batch) are held to less than any step changes them.
DP_TOL = {"loss_rel": 2e-3, "moment1_rel_l2": 5e-2,
          "moment1_rel_l2_per_array": 0.25, "master_abs_in_lr": 2.02}


def _adam_state(program):
    """[(master or param name, moment1 name)] of the program's adam ops."""
    ops = [op for op in program.global_block().ops if op.type == "adam"]
    _check(ops, "the program holds no adam op")
    return [(op.input("Param")[0], op.input("Moment1")[0]) for op in ops]


def _host_flat(scope, name, size=None):
    """A state array as a flat host vector. The sharded update keeps its
    arrays flat and padded to a multiple of the device count: `size`
    cuts the padding off after checking that it is zero."""
    import numpy as np

    # a copy: on a CPU backend np.asarray may be a view of the buffer the
    # next step is given to overwrite
    flat = np.array(scope.find_var(name)).reshape(-1)
    if size is not None and flat.size != size:
        _check(flat.size > size and not flat[size:].any(),
               "state %s: %d elements, expected %d and zero padding"
               % (name, flat.size, size))
        flat = flat[:size]
    return flat


def _update_agreement(want, scope, lr, tol):
    """Compare the state after the FIRST update with `want` (the one-chip
    run's, {name: flat vector}); returns the report, raises beyond
    `tol`."""
    import numpy as np

    per_array = {}
    flips = nonzero = 0
    for name, ref in want["moment1"].items():
        got = _host_flat(scope, name, ref.size)
        per_array[name] = (float(np.sum((got - ref) ** 2)),
                           float(np.sum(ref ** 2)))
        live = ref != 0
        nonzero += int(live.sum())
        flips += int((np.sign(got[live]) != np.sign(ref[live])).sum())
    d2, n2 = (sum(v[i] for v in per_array.values()) for i in (0, 1))
    # array by array too, but only where there is signal: a bias whose
    # gradient cancels across the batch is all rounding in either run
    big = {k: (a / b) ** 0.5 for k, (a, b) in per_array.items()
           if b >= 1e-4 * n2}
    worst = max(big, key=big.get)
    master_abs = max(
        float(np.max(np.abs(_host_flat(scope, name, ref.size) - ref)))
        for name, ref in want["master"].items())
    report = {
        "moment1_rel_l2": round((d2 / n2) ** 0.5, 6),
        "moment1_rel_l2_worst_array": [worst, round(big[worst], 6)],
        "arrays_with_1pct_of_the_norm": len(big),
        "moment1_sign_flips": round(flips / max(nonzero, 1), 6),
        "master_max_abs_diff_in_lr": round(master_abs / lr, 4),
    }
    _check(report["moment1_rel_l2"] <= tol["moment1_rel_l2"] and
           big[worst] <= tol["moment1_rel_l2_per_array"],
           "averaged gradients (Adam first moments after one update) "
           "differ from the one-chip run's: %r" % (report,))
    _check(report["master_max_abs_diff_in_lr"] <= tol["master_abs_in_lr"],
           "master weights after one update differ by more than two "
           "learning rates: %r" % (report,))
    return report


def _loss_agreement(got, want, tol):
    diffs = [abs(a - b) / max(1.0, abs(b)) for a, b in zip(got, want)]
    _check(max(diffs) <= tol["loss_rel"],
           "per-step losses differ beyond %g: %r against one chip's %r"
           % (tol["loss_rel"], got, want))
    return [round(d, 6) for d in diffs]


def phase_bert_data_parallel(batch=256, seq_len=128, cfg=None, steps=4,
                             ndev=4, tol=None):
    """BERT-base through CompiledProgram.with_data_parallel on an
    `ndev`-device mesh (ZeRO-1 sharded update and bucketed collectives at
    their defaults) against the same seeded steps on ONE chip of the
    host. Dropout is off in both: replicas draw their own masks, so a
    data-parallel step with dropout cannot equal the one-chip step.

    Agreement is judged where an error in the update would show (see
    DP_TOL): the averaged gradient and the master weights after the
    first update, then the losses. A control run on the one chip feeds
    the same batch with its rows in another order, which changes nothing
    but the order of the gradient sums: how far IT drifts from the
    one-chip run says how much of the data-parallel run's drift is this
    program's own sensitivity to rounding."""
    import jax
    import numpy as np

    import bench
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.models import bert

    tol = dict(DP_TOL, **(tol or {}))

    def build():
        c = _zero_dropout(cfg() if cfg else bert.BertConfig.base())
        return bench.build_bert_train_program(seq_len, c)

    def update_agreement(report):
        return lambda sc: report.update(
            _update_agreement(want, sc, bench.BERT_LR, tol))

    def run(program, startup_p, total, feed, after_first):
        scope = Scope()
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup_p, scope=scope)
        return (exe, scope) + _train_steps(
            exe, program, feed, total, steps, scope,
            after_first=lambda: after_first(scope))

    # every run builds its programs anew: a Program carries its random
    # stream along, so only a fresh build lays down the seed's weights
    # -- the comparator: one chip ----------------------------------------
    main_p, startup_p, total, c = build()
    feed = bench.bert_feed(c, batch, seq_len)
    names = _adam_state(main_p)
    want = {}

    def keep(scope):
        want["master"] = {p: _host_flat(scope, p) for p, _ in names}
        want["moment1"] = {m: _host_flat(scope, m) for _, m in names}

    # ([2:]: the executor and the scope go when the run is over, and
    # their state leaves the chip)
    one, _, one_step_s, out = run(main_p, startup_p, total, feed, keep)[2:]
    _check(len(out.devices()) == 1, "comparator ran on several devices")

    # -- the control: the same batch, rows permuted, same chip -----------
    main_p, startup_p, total, c = build()
    order = np.random.RandomState(5).permutation(batch)
    control = {}
    perm = run(main_p, startup_p, total,
               {k: v[order] for k, v in feed.items()},
               update_agreement(control))[2]
    control["loss_rel_diff"] = _loss_agreement(perm, one, tol)
    control["losses"] = [round(v, 4) for v in perm]
    del out

    # -- the path across chips -------------------------------------------
    main_p, startup_p, total, c = build()
    compiled_p = fluid.CompiledProgram(main_p).with_data_parallel(
        loss_name=total.name)
    update = {}
    exe, scope, dp, first_s, dp_step_s, out = run(
        compiled_p, startup_p, total, feed, update_agreement(update))
    loss_diffs = _loss_agreement(dp, one, tol)
    _check(out.shape == (ndev,), "the data-parallel fetch is not one loss "
           "per replica: shape %r" % (out.shape,))

    entry, lowered, smut = exe._cached_lowerable(
        compiled_p, feed, [total], scope)[:3]
    _check(entry.mesh is not None and entry.mesh.devices.size == ndev,
           "mesh is not %d devices" % ndev)
    # a quarter of the batch on each device: the feeds' input shardings
    xla = exe._aot_compile(entry, lowered, smut)
    feed_sh = xla.input_shardings[0][0]
    for name, arr in feed.items():
        shard = feed_sh[name].shard_shape(tuple(arr.shape))
        _check(shard[0] * ndev == arr.shape[0] and
               len(feed_sh[name].device_set) == ndev,
               "feed %s is not split %d ways: %r" % (name, ndev, shard))
    # its shard of the optimizer state on each device, not all on device 0
    sharded = dict(entry.sharded_state or {})
    _check(sharded, "no optimizer state was sharded (ZeRO-1 is off?)")
    state_bytes = {}
    for name in sharded:
        arr = scope.find_var(name)
        shards = arr.addressable_shards
        _check(len({s.device.id for s in shards}) == ndev and
               all(s.data.shape[0] * ndev == arr.shape[0] for s in shards),
               "state %s is not held 1/%d per device" % (name, ndev))
        for s in shards:
            state_bytes[s.device.id] = state_bytes.get(s.device.id, 0) \
                + s.data.nbytes
    text = xla.as_text()
    n_coll = {k: text.count(k) for k in ("all-reduce", "reduce-scatter",
                                         "all-gather")}
    _check(n_coll["all-reduce"] + n_coll["reduce-scatter"] > 0,
           "the compiled step holds no all-reduce or reduce-scatter")
    return {
        "model": "bert", "layers": c.num_hidden_layers,
        "hidden": c.hidden_size, "global_batch": batch,
        "per_device_batch": batch // ndev, "devices": ndev,
        "mesh": {k: int(v) for k, v in entry.mesh.shape.items()},
        "losses_dp": [round(v, 4) for v in dp],
        "losses_one_chip": [round(v, 4) for v in one],
        "loss_rel_diff": loss_diffs,
        "after_first_update": update,
        "control_rows_permuted_one_chip": control,
        "tolerances": tol,
        "sharded_state_arrays": len(sharded),
        "sharded_state_gb_per_device": sorted(
            round(b / 1e9, 3) for b in state_bytes.values()),
        "collectives_in_hlo": n_coll,
        "step_memory_gb_per_device": _step_memory_gb(
            exe, compiled_p, feed, total, scope),
        "first_run_seconds": round(first_s, 2),
        "step_seconds_dp": [round(t, 4) for t in dp_step_s],
        "step_seconds_one_chip": [round(t, 4) for t in one_step_s],
    }


# -- entry -------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dp4", action="store_true",
                    help="four chips: the data-parallel phase and its "
                    "one-chip comparator, nothing else")
    args = ap.parse_args(argv)
    device = None
    try:
        # first, and before anything of the repo is imported: a wrong
        # backend runs no model. The failure line names the device too
        device = _describe_devices()
        phase_device(4 if args.dp4 else 1, device)
        from paddle_tpu.fluid import compile_cache

        print(json.dumps({
            "phase": "device", "checked": device,
            "compile_cache_dir": compile_cache.use_default_dir()}),
            flush=True)
        if args.dp4:
            _run_phase("bert_data_parallel", phase_bert_data_parallel)
        else:
            _run_phase("bert_train", phase_bert_train)
            _run_phase("resnet_train", phase_resnet_train)
            _run_phase("flash_attention", phase_flash_attention)
            _run_phase("serving", phase_serving)
    except Exception as e:  # noqa: BLE001 - the boundary: report, exit 1
        traceback.print_exc()
        print(json.dumps({"ok": False, "device": device, "error": "%s: %s"
                          % (type(e).__name__, str(e)[:500])}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
