"""Measurement bodies and program builders.

Each leg drives the full framework path (fluid static graph -> one
jitted XLA computation, bf16 AMP, donated buffers) in THIS process —
one process, one chip:

    python bench.py                      BERT-base pretraining, b256 seq128
    python bench.py --bert BATCH         the same at another batch
    python bench.py --longctx            BERT-base at seq 4096 (flash kernel)
    python bench.py --resnet [BATCH]     ResNet50 training, 224px
    python bench.py --serving [N]        serving.Engine over a request trace
    python bench.py --embedding [STEPS [ARCH]]   CTR model, sharded tables

Every leg prints one `BENCH_RESULT_JSON:{...}` line that names the
device its arrays were on. The command line refuses a backend that is
not `tpu` (exit 2, no result line) and a failing phase raises: there is
no CPU fallback and no re-emitted older number. The bodies stay
importable on the CPU so tests and tools/perf_analysis.py can build the
same programs; `chip_smoke.py` reuses the builders too.

Baseline denominators: BASELINE.md targets >=0.8x per-chip V100; the
in-repo reference publishes no numbers, so `vs_baseline` uses the widely
reported V100 FP16 figures (~25k tokens/s BERT-base seq128, ~900 img/s
ResNet50).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

V100_BERT_TOKENS_PER_SEC = 25000.0
V100_RESNET50_IMGS_PER_SEC = 900.0

# Published per-chip peaks, keyed by jax's `device_kind`. Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB
# of HBM at 819 GB/s). A device that is not listed is an error, never a
# default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}

BATCH = 256
SEQ_LEN = 128
BERT_LR = 1e-4
WARMUP = 3
STEPS = 10
# Long-context leg: BERT-base at seq 4096, where the Pallas flash kernel
# (with in-kernel prob dropout) is the attention path — its O(S) memory
# against the S^2 score buffer is what lets this length fit. No V100
# baseline exists for this config; it reports tokens/s and MFU only.
LONGCTX_SEQ = 4096
LONGCTX_BATCH = 2

_RESULT_TAG = "BENCH_RESULT_JSON:"


def device_peaks(device_kind: str) -> dict:
    """The peaks row of `device_kind`; an unknown kind raises."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            "no published peaks for device kind %r (known: %s) — add a "
            "sourced row to bench.DEVICE_PEAKS"
            % (device_kind, sorted(DEVICE_PEAKS))) from None


def require_tpu():
    """The command-line gate: the first device, or SystemExit(2) with
    the reason on stderr when the backend is not `tpu`."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("bench: backend is %r, not tpu — nothing is measured on it"
              % (dev.platform,), file=sys.stderr)
        raise SystemExit(2)
    return dev


def _device_of(array) -> dict:
    """Name the device(s) an output array actually lives on."""
    devs = sorted(array.devices(), key=lambda d: d.id)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _attach_blocks(result, exe, program, feed, fetch_list):
    """Attach every evidence block of the step that just ran — phases,
    collectives / opt_state_sharding / overlap (when data-parallel),
    precision (when AMP), attribution, static_checks, compile_cache,
    telemetry — assembled by the one registry-backed publisher
    (paddle_tpu/observability/publish.py)."""
    from paddle_tpu.observability import publish

    result.update(publish.bench_blocks(exe, program, feed, fetch_list))


def _mfu_pct(result, flops_per_sec):
    dev = result["device"]
    if dev["platform"] == "tpu":
        result["mfu_pct"] = round(
            100.0 * flops_per_sec
            / (device_peaks(dev["kind"])["bf16_flops"] * dev["count"]), 2)


# -- BERT ------------------------------------------------------------------

def _bert_flops_per_token(cfg, n_params, seq_len):
    """Training FLOPs/token: 6*N for the param matmuls plus the
    attention score/context matmuls (12*L*S*H per token: QK^T and AV are
    each 2*S*H MACs/token/layer forward, x3 for fwd+bwd). BERT-base
    (133.5 M parameters) at b256 seq128: 26.72 TFLOP per step."""
    attn = 12.0 * cfg.num_hidden_layers * seq_len * cfg.hidden_size
    return 6.0 * n_params + attn


def build_bert_train_program(seq_len: int = SEQ_LEN, cfg=None):
    """The canonical BERT pretraining program: scan-over-layers encoder
    with q/k/v fused into one projection, bf16 AMP (static loss scale)
    around Adam. One definition for bench.py, chip_smoke.py and
    tools/perf_analysis.py; seeded init, so two builds start from the
    same weights. Returns (main, startup, loss_var, cfg).

    Per-layer recompute inside the scan (`scan_remat`) is ON at every
    batch. The TPU compiler's `memory_analysis()` for BERT-base b256
    seq128 on a 16 GB v5e (15.75 GB usable): without remat the scan's
    stacked residuals need 24.53 GB and the step is refused; with it
    the step takes 1.87 GB of state (aliased in place) + 4.81 GB of
    temporaries, and 9.11 GB of temporaries at b512. The unrolled
    encoder without remat needs 14.11 GB + state and does not fit
    either."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework
    from paddle_tpu.fluid.contrib import mixed_precision
    from paddle_tpu.models import bert

    if cfg is None:
        cfg = bert.BertConfig.base()
    if seq_len > cfg.max_position_embeddings:
        cfg.max_position_embeddings = seq_len
    main_p, startup_p = framework.Program(), framework.Program()
    main_p.random_seed = startup_p.random_seed = 7
    with framework.program_guard(main_p, startup_p):
        with framework.unique_name_guard():
            total, _, _, _ = bert.bert_pretrain_loss(
                cfg, seq_len, is_test=False, scan_layers=True,
                scan_remat=True)
            opt = mixed_precision.decorate(
                fluid.optimizer.AdamOptimizer(learning_rate=BERT_LR),
                use_dynamic_loss_scaling=False)
            opt.minimize(total)
    return main_p, startup_p, total, cfg


def bert_feed(cfg, batch, seq_len):
    # the dense [B, max_pred] masked-LM feed (contract of
    # models/bert.bert_pretrain_loss) has one builder, in __graft_entry__
    from __graft_entry__ import _bert_feed

    return _bert_feed(cfg, batch, seq_len, max_pred=int(seq_len * 0.15))


def _timed_steps(exe, program, feed, loss, steps, warmup):
    """Compile on the first run, warm up, then time `steps` runs that
    end in a host read of the last loss. Returns (compile_s, dt, out)."""
    import numpy as np

    from paddle_tpu.fluid import profiler as _prof

    t0 = time.perf_counter()
    out = exe.run(program, feed=feed, fetch_list=[loss],
                  return_numpy=False)
    np.asarray(out[0])
    compile_time = time.perf_counter() - t0
    for _ in range(max(warmup - 1, 0)):
        out = exe.run(program, feed=feed, fetch_list=[loss],
                      return_numpy=False)
    np.asarray(out[0])
    _prof.reset_step_phases()
    t0 = time.perf_counter()
    for _ in range(steps):
        out = exe.run(program, feed=feed, fetch_list=[loss],
                      return_numpy=False)
    np.asarray(out[0])  # block on the final step
    return compile_time, time.perf_counter() - t0, out[0].value


def _bench_bert(batch: int = BATCH, steps: int = STEPS,
                warmup: int = WARMUP, seq_len: int = SEQ_LEN,
                cfg=None) -> dict:
    import numpy as np

    import paddle_tpu.fluid as fluid

    main_p, startup_p, total, cfg = build_bert_train_program(seq_len, cfg)
    n_params = sum(int(np.prod(p.shape)) for p in main_p.all_parameters())
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup_p)
    feed = bert_feed(cfg, batch, seq_len)
    compile_time, dt, out = _timed_steps(exe, main_p, feed, total, steps,
                                         warmup)
    tokens_per_sec = batch * seq_len * steps / dt
    longctx = seq_len >= LONGCTX_SEQ
    result = {
        "metric": ("bert_longctx4096_pretrain_throughput" if longctx
                   else "bert_base_pretrain_throughput"),
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec/chip",
        "device": _device_of(out),
        "steps_per_sec": round(steps / dt, 3),
        "compile_time_s": round(compile_time, 1),
        "params_m": round(n_params / 1e6, 1),
        "batch": batch,
        "seq_len": seq_len,
        "loss": round(float(np.asarray(out).reshape(-1)[0]), 4),
    }
    _attach_blocks(result, exe, main_p, feed, [total])
    if not longctx:
        result["vs_baseline"] = round(
            tokens_per_sec / V100_BERT_TOKENS_PER_SEC, 3)
    _mfu_pct(result, _bert_flops_per_token(cfg, n_params, seq_len)
             * tokens_per_sec)
    return result


# -- ResNet ----------------------------------------------------------------

def resnet_feed(batch: int, img_size: int = 224,
                class_dim: int = 1000) -> dict:
    import numpy as np

    r = np.random.RandomState(0)
    return {
        "image": r.randn(batch, 3, img_size, img_size).astype("float32"),
        "label": r.randint(0, class_dim, (batch, 1)).astype("int64"),
    }


def build_resnet_train_program(depth: int = 50, img_size: int = 224,
                               class_dim: int = 1000, seed: int = 11,
                               learning_rate: float = 0.1):
    """The canonical ResNet train program (momentum + bf16 AMP, static
    loss scaling), shared with chip_smoke.py and tools/perf_analysis.py.
    Seeded init keeps runs reproducible. Returns (main, startup, loss).

    The stages are unrolled. Run as `layers.Scan` (`scan_stages`) the
    stage tails save their backward residuals as stacked f32 arrays
    (f32[2,128,256,56,56] and friends, three per stage): the TPU
    compiler's `memory_analysis()` for ResNet50 b128 reports 20.83 GB
    of temporaries that way against 15.75 GB of HBM, 5.71 GB with the
    scan body rematerialized, and 4.31 GB unrolled — where XLA keeps
    the residuals in bf16 and nothing is recomputed. The unrolled
    compile took 50 s against the scan's 38 s."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework
    from paddle_tpu.fluid.contrib import mixed_precision
    from paddle_tpu.models import resnet as resnet_mod

    main_p, startup_p = framework.Program(), framework.Program()
    main_p.random_seed = startup_p.random_seed = seed
    with framework.program_guard(main_p, startup_p):
        with framework.unique_name_guard():
            img = fluid.layers.data("image",
                                    shape=[3, img_size, img_size],
                                    dtype="float32")
            label = fluid.layers.data("label", shape=[1], dtype="int64")
            logits = resnet_mod.resnet(img, class_dim=class_dim,
                                       depth=depth)
            loss = fluid.layers.mean(
                fluid.layers.loss.softmax_with_cross_entropy(logits,
                                                             label))
            opt = mixed_precision.decorate(
                fluid.optimizer.MomentumOptimizer(learning_rate,
                                                  momentum=0.9),
                use_dynamic_loss_scaling=False)
            opt.minimize(loss)
    return main_p, startup_p, loss


def _bench_resnet(batch: int = 128, steps: int = 8, warmup: int = 2,
                  depth: int = 50, img: int = 224,
                  class_dim: int = 1000) -> dict:
    """ResNet50 ImageNet training throughput (BASELINE.json config 2).
    depth/img/class_dim shrink only for CPU tests — the command line
    always runs the 50/224/1000 config."""
    import numpy as np

    import paddle_tpu.fluid as fluid

    main_p, startup_p, loss = build_resnet_train_program(
        depth=depth, img_size=img, class_dim=class_dim)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup_p)
    feed = resnet_feed(batch, img, class_dim)
    compile_time, dt, out = _timed_steps(exe, main_p, feed, loss, steps,
                                         warmup)
    imgs_per_sec = batch * steps / dt
    result = {
        "metric": "resnet50_train_throughput",
        "value": round(imgs_per_sec, 1),
        "unit": "images/sec/chip",
        "vs_baseline": round(imgs_per_sec / V100_RESNET50_IMGS_PER_SEC, 3),
        "device": _device_of(out),
        "compile_time_s": round(compile_time, 1),
        "batch": batch,
        "loss": round(float(np.asarray(out).reshape(-1)[0]), 4),
    }
    _attach_blocks(result, exe, main_p, feed, [loss])
    # ~4.1 GFLOPs fwd per 224x224 image, x3 for training
    _mfu_pct(result, 3 * 4.1e9 * imgs_per_sec)
    return result


# -- embeddings and serving ------------------------------------------------

def _bench_embedding(steps: int = 16, batch: int = 256,
                     vocab: int = 20000, arch: str = "wide_deep") -> dict:
    """Embedding leg: train the CTR model (wide&deep or dlrm_tiny)
    data-parallel over every device of the backend, each slot table
    vocab-sharded by paddle_tpu/embedding, and emit the
    registry-assembled "embedding" block — per-replica state bytes vs
    logical, modeled touched-rows sync bytes vs the dense reference's
    vocab-sized allreduce."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework
    from paddle_tpu.models import ctr

    cfg = ctr.CTRConfig(vocab_sizes=(vocab, vocab // 2, vocab // 4,
                                     vocab // 8),
                        embed_dim=32, arch=arch)
    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    with framework.unique_name_guard():
        framework.default_main_program().random_seed = 7
        framework.default_startup_program().random_seed = 7
        loss, _, _ = ctr.build_ctr_train(cfg)
        main_p = fluid.default_main_program()
        fluid.CompiledProgram(main_p).with_data_parallel(
            loss_name=loss.name)
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(fluid.default_startup_program())
        losses = []
        t0 = time.perf_counter()
        for i in range(steps):
            feed = ctr.synthetic_batch(cfg, batch, seed=i)
            out = exe.run(main_p, feed=feed, fetch_list=[loss],
                          return_numpy=False)[0]
            losses.append(float(np.asarray(out).mean()))
        dt = time.perf_counter() - t0
        plan = getattr(main_p, "_sparse_plan", None)
        result = {
            "metric": "ctr_examples_per_sec",
            "value": round(steps * batch / dt, 2),
            "unit": "examples/sec",
            "device": _device_of(out.value),
            "arch": arch,
            "steps": steps,
            "batch": batch,
            "loss_first": losses[0],
            "loss_last": losses[-1],
            "tables_sharded": len(plan.tables) if plan else 0,
        }
        # bench_blocks assembles (and publishes) the "embedding" block
        # along with every other evidence block
        _attach_blocks(result, exe, main_p, feed, [loss])
    return result


def _bench_serving(n_requests: int = 24, seed: int = 0) -> dict:
    """Serving leg: replay the synthetic multi-tenant request trace
    through a serving.Engine (continuous batching + paged KV cache +
    AOT-warmed step buckets) and emit the registry-assembled "serving"
    block — tokens/sec, request p50/p99 latency, queue depth, KV
    occupancy."""
    import jax

    from paddle_tpu import serving
    from paddle_tpu.observability import publish

    model = serving.TinyDecoderLM(serving.TinyLMConfig())
    engine = serving.Engine(model, config=serving.EngineConfig.from_flags(
        num_pages=256, page_size=8, max_seqs=8))
    # per-tenant system prompts exercise the prefix-cache lane, and a
    # priority class skew exercises the preemption path when the pool
    # is tight — the block's reuse ratio / preemption fields go live
    trace = serving.synthetic_trace(n_requests=n_requests, seed=seed,
                                    vocab=model.config.vocab,
                                    system_prompt_range=(12, 20),
                                    tenant_priorities=(1, 0, 0))
    summary = serving.run_trace(engine, trace)
    block = publish.serving_block()
    return {
        "metric": "serving_tokens_per_sec",
        "value": summary["tokens_per_sec"],
        "unit": "tokens/sec",
        "device": _device_of(jax.tree_util.tree_leaves(engine.params)[0]),
        "trace": summary,
        "serving": block,
    }


# -- command line ----------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    leg = ap.add_mutually_exclusive_group()
    leg.add_argument("--bert", type=int, metavar="BATCH", default=None)
    leg.add_argument("--longctx", action="store_true")
    leg.add_argument("--resnet", type=int, metavar="BATCH", nargs="?",
                     const=128, default=None)
    leg.add_argument("--serving", type=int, metavar="N", nargs="?",
                     const=24, default=None)
    leg.add_argument("--embedding", nargs="*", metavar="STEPS [ARCH]",
                     default=None)
    args = ap.parse_args(argv)

    # the gate sits here, at the command line: nothing below it runs,
    # and nothing is printed on stdout, on a backend that is not tpu
    require_tpu()
    from paddle_tpu.fluid import compile_cache

    compile_cache.use_default_dir()
    if args.resnet is not None:
        result = _bench_resnet(args.resnet)
    elif args.serving is not None:
        result = _bench_serving(args.serving)
    elif args.embedding is not None:
        steps = int(args.embedding[0]) if args.embedding else 16
        arch = args.embedding[1] if len(args.embedding) > 1 \
            else "wide_deep"
        result = _bench_embedding(steps=steps, arch=arch)
    elif args.longctx:
        result = _bench_bert(LONGCTX_BATCH, steps=6, warmup=2,
                             seq_len=LONGCTX_SEQ)
    else:
        result = _bench_bert(args.bert or BATCH)
    print(_RESULT_TAG + json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
