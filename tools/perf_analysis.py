"""Static evidence generator for the BERT-base train step: lowers the
EXACT bench train step (bench.build_bert_train_program: bf16 AMP +
Adam, fused linear-softmax-xent head) with jax.jit(...).lower() on the
CPU backend (StableHLO is backend-neutral), and writes
artifacts/bert_step_census.md with counts — never a time; the chip's
own `compiled.memory_analysis()` supersedes the HBM estimates:

- StableHLO op histogram + dot_general shape census per batch size,
- XLA's own pre-compile cost analysis (flops/bytes) when available,
- an analytical FLOPs / HBM-traffic / HBM-peak model for v5e
  (197 TFLOP/s bf16, 16 GB HBM) at batch 256 and 512, fused vs
  round-2 unfused head,
- the gzipped StableHLO committed alongside when small enough.

Usage: python tools/perf_analysis.py [--batches 256,512]
       python tools/perf_analysis.py --sharded-diff
       python tools/perf_analysis.py --quant
       python tools/perf_analysis.py --serving
       python tools/perf_analysis.py --embedding
       python tools/perf_analysis.py --overlap-audit [--bucket-mb 0.25]
       python tools/perf_analysis.py --hierarchy [--dcn 2]
       python tools/perf_analysis.py --attribution [--bucket-mb 0.25]
       python tools/perf_analysis.py --lint [tpu_lint args...]
       python tools/perf_analysis.py --stragglers \
           --telemetry-dir DIR [--window 32] [--xplane-dir DIR]
       python tools/perf_analysis.py --elastic --log-dir DIR
       python tools/perf_analysis.py --hang-report \
           --telemetry-dir DIR | --log-dir DIR [--attempt K]

`--hang-report` is the offline desync analyzer for a hang postmortem
(observability/watchdog.py): it aligns the per-rank in-flight
collective tables of a bundle's flightrec.rank*.json dumps by
collective key (the SAME schedule-key grammar the tpu-lint divergence
checker uses — the static and runtime checkers cannot disagree on what
"the same collective" means) and names the rank that never arrived —
state "inflight" (began, never contributed), or absent (stalled before
reaching it) — or the mismatched membership, as a structured verdict.
Point it at a telemetry dir with fresh dumps or at a collected
`<log_dir>/postmortem/attempt<K>` bundle (`--log-dir` picks the newest
attempt unless `--attempt` says otherwise). Exits 0 with a verdict,
1 when the bundle shows no hang, 2 when the dir has no dumps.

`--attribution` is the offline evidence for per-op resource
attribution (observability/attribution.py): it compiles the DP
BERT-tiny train step with ZeRO-1 + AMP-O2 masters + bucketed
collectives on the emulated CPU mesh, asserts that >= 90% of the
compiled `memory_analysis()` peak attributes to named framework
ops/classes, that the class totals match `donation_report` EXACTLY,
that every collective in the lowered module maps back to a fluid op /
bucket / gradient, and that `FLAGS_tpu_hbm_budget_mb` set below the
predicted peak fails PRE-dispatch with a structured error naming the
top consumers. Writes artifacts/attribution.json; exits nonzero when
any of those do not hold.

`--stragglers --xplane-dir DIR` additionally folds the device time of
a capture window (self times of the `XLA Ops` thread in the
trace.json.gz inside a PR 7 capture.py xplane dir) back through the
provenance markers to the step's regions (forward / recompute /
backward / update), fluid op types and per-layer / per-bucket device
time — the blame one level below the phase verdict.

`--hierarchy` is the offline evidence for the hierarchical DCN+ICI
grad collectives (FLAGS_tpu_dcn_replicas, hybrid multi-pod mesh): it
lowers the SAME data-parallel BERT-tiny train step flat and on an
emulated (dcn x ici) CPU hybrid mesh, splits the collective byte
census into ici/dcn lanes (lowering.collective_byte_census), asserts
every cross-pod grad-sync collective carries exactly 1/ici_size of
the flat-allreduce bytes, and writes artifacts/hierarchy_diff.json.
Exits nonzero when the cross-pod reduction does not hold.

`--elastic` reports the elastic-restart seams of a supervised run
(distributed/launch.py --min_ranks): every `elastic_transition` event
the supervisor published (old/new world, failed ranks, rank
reassignment map, recovery wall time) plus the per-attempt postmortem
index, from <log_dir>/telemetry/telemetry.supervisor.jsonl and
<log_dir>/postmortem/index.json. Exits 0 when transitions were found,
1 on a fixed-world run, 2 when the dir is missing.

`--stragglers` is the offline cross-rank straggler analysis over the
per-rank telemetry JSONL a run wrote (paddle_tpu/observability;
FLAGS_tpu_telemetry_dir): step records are aligned by step number
across ranks, each --window-step window names its slowest rank, and
the report ends with the overall offender + per-phase min/mean/max —
the "which host is dragging the pod" answer 1909.09756 calls the
dominant debugging cost at scale. Exits 0 with the report on stdout
(JSON after the human lines); exits 2 when the dir has fewer than 2
ranks of step records.

`--lint` is a thin alias onto tools/tpu_lint.py (the tpu-lint static
SPMD verifier, paddle_tpu/analysis) so one tool drives every audit:
remaining args pass through (e.g. `--lint --fail-on warning --json`);
writes artifacts/static_checks.json.

`--sharded-diff` is the offline check for the ZeRO-1 sharded weight
update (FLAGS_tpu_sharded_weight_update): it lowers the SAME
data-parallel BERT-tiny train step with the flag off and on, diffs the
per-collective byte census (lowering.collective_byte_census) and the
compiled per-replica optimizer-state bytes, asserts the grad-exchange
ICI bytes ~halve and the optimizer state ~1/N, and writes
artifacts/sharded_update_diff.json — the no-chip evidence the
acceptance criteria call for. Exits nonzero when the reduction does
not hold.

`--quant` is the offline evidence for the int8 serving tier: KV page
bytes per dtype, resident-batch admission under a fixed pool budget
(~2x bf16), PTQ weight bytes over the quantized subset (~4x), and the
int8-engine batched==sequential identity. Writes
artifacts/quant_diff.json; exits nonzero when any claim fails.

`--embedding` is the same-shape check for the vocab-sharded embedding
engine (FLAGS_tpu_sparse_embedding, paddle_tpu/embedding): it lowers
a CTR wide&deep train step with the engine off and on, asserts NO
sharded-path collective carries a vocab-sized payload (bytes scale
with touched rows) and the per-replica table+moment bytes are exactly
1/N, runs a Zipf-skewed cold-tier RowCache simulation for the
hit-rate/eviction numbers, and writes artifacts/embedding_diff.json.

`--overlap-audit` is the offline scheduling check for the bucketed,
backward-ordered grad collectives (FLAGS_tpu_comm_bucket_mb): it
compiles the SAME data-parallel BERT-tiny train step with bucketing on
(--bucket-mb, default 0.25 MB for the tiny model) and off (cap 0: the
per-variable single-exchange lowering), parses the OPTIMIZED scheduled
HLO (lowering.collective_overlap_audit), and asserts that >= 2 bucket
reduce-scatters have their dataflow-ready point BEFORE the final
backward compute op (transfer can overlap the remaining backward)
while the cap=0 lowering, under the collective-combiner model that
governs real-ICI behavior, has NOTHING schedulable after its combined
exchange (backward_after == 0 — the fully exposed collective gap this
PR closes). Writes artifacts/overlap_audit.json; exits nonzero when
the overlap is not there.
"""
from __future__ import annotations

import gzip
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if ("--sharded-diff" in sys.argv or "--overlap-audit" in sys.argv
        or "--hierarchy" in sys.argv or "--attribution" in sys.argv
        or "--embedding" in sys.argv) \
        and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    # the diff needs a multi-device mesh; must be set pre-jax-import
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_"
                               "count=8").strip()
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np

SEQ_LEN = 128
import bench  # noqa: E402 - the one peaks table lives with the benchmark

_V5E = bench.device_peaks("TPU v5 lite")
V5E_PEAK_BF16 = _V5E["bf16_flops"]
V5E_HBM = _V5E["hbm_bytes"]
V5E_HBM_BW = _V5E["hbm_bytes_per_s"]


def build_step(batch):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework, lowering
    from paddle_tpu.core.scope import global_scope
    from __graft_entry__ import _bert_feed

    # the bench's own builder: one definition of the program
    main_p, startup_p, total, cfg = bench.build_bert_train_program(SEQ_LEN)
    with framework.program_guard(main_p, startup_p):
        with framework.unique_name_guard():
            n_params = sum(int(np.prod(p.shape))
                           for p in main_p.all_parameters())
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup_p)
            feed_arrays = _bert_feed(cfg, batch, SEQ_LEN)
            block = main_p.global_block()
            state_in, _ = lowering.analyze_block(
                block, list(feed_arrays), [total.name])
            state_specs = {n: global_scope().find_var(n)
                           for n in state_in}
            entry = lowering.compile_block(
                main_p, block, feed_arrays, [total.name], state_specs)
            states_mut = {n: global_scope().find_var(n)
                          for n in entry.state_mut_names}
            states_ro = {n: global_scope().find_var(n)
                         for n in entry.state_ro_names}
    return cfg, n_params, entry, feed_arrays, states_mut, states_ro


def hlo_census(text):
    import re

    ops = {}
    dots = []
    for line in text.splitlines():
        m = re.search(r"=\s+\"?([a-z_]+\.[a-z_0-9]+)", line)
        if m:
            op = m.group(1)
            ops[op] = ops.get(op, 0) + 1
            if "dot_general" in op:
                shapes = re.findall(r"tensor<([^>]+)>", line)
                if shapes:
                    dots.append(shapes[-1])
    return ops, dots


def analytical(cfg, n_params, batch, remat=False):
    """FLOPs / bytes / HBM model for one train step. With remat (the
    bench's batch >= 384 path) only per-layer boundary activations stay
    resident plus one layer's internals during backward, and the
    forward runs again inside the vjp (~+1/3 FLOPs)."""
    tokens = batch * SEQ_LEN
    # 6N params matmul FLOPs/token + attention score/context
    attn = 12.0 * cfg.num_hidden_layers * SEQ_LEN * cfg.hidden_size
    flops = (6.0 * n_params + attn) * tokens
    if remat:
        flops *= 4.0 / 3.0  # fwd replayed inside the backward
    h, L, V = cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size
    max_pred = int(SEQ_LEN * 0.15)
    act_per_layer = 13 * tokens * h * 2  # bf16 activations kept (approx)
    weights_bf16 = n_params * 2
    master_fp32 = n_params * 4
    adam_state = n_params * 8
    grads_fp32 = n_params * 4
    if remat:
        # boundaries (L x [tokens, h] bf16) + one live layer's internals
        acts = L * tokens * h * 2 + act_per_layer
    else:
        acts = act_per_layer * L
    # head buffers: fused head streams [rows, V] in tiles; unfused
    # materializes fp32 logits + softmax for batch*max_pred rows
    unfused_head = 2 * (batch * max_pred) * V * 4
    fused_head = 0  # tiled inside the fused op
    peak = (weights_bf16 + master_fp32 + adam_state + grads_fp32
            + acts + fused_head)
    peak_unfused = peak + unfused_head
    return {
        "tokens": tokens,
        "train_flops": flops,
        "ideal_step_s": flops / V5E_PEAK_BF16,
        "ideal_tok_s": tokens / (flops / V5E_PEAK_BF16),
        "weights_bf16_gb": weights_bf16 / 1e9,
        "master_adam_gb": (master_fp32 + adam_state) / 1e9,
        "grads_gb": grads_fp32 / 1e9,
        "acts_gb": acts / 1e9,
        "head_unfused_gb": unfused_head / 1e9,
        "peak_gb": peak / 1e9,
        "peak_unfused_gb": peak_unfused / 1e9,
        "fits": peak < V5E_HBM,
        "fits_unfused": peak_unfused < V5E_HBM,
    }


def build_resnet_step(batch, img_size=224, class_dim=1000):
    """Lowers the EXACT bench ResNet50 train step without running it —
    the program comes from `bench.build_resnet_train_program` (one
    shared definition; this module never rebuilds its own copy)."""
    import bench
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import lowering
    from paddle_tpu.core.scope import global_scope

    main_p, startup_p, loss = bench.build_resnet_train_program(
        img_size=img_size, class_dim=class_dim)
    n_params = sum(int(np.prod(p.shape))
                   for p in main_p.all_parameters())
    # per-image activation elements, summed from the block's own
    # inferred var shapes (exact for this program, not a rule of
    # thumb); batch dim in var shapes is -1
    act_elems = 0
    block = main_p.global_block()
    param_names = {p.name for p in main_p.all_parameters()}
    for name, var in block.vars.items():
        shape = getattr(var, "shape", None)
        if not shape or name in param_names:
            continue
        if any(int(d) <= 0 for d in shape[1:]):
            continue
        if int(shape[0]) in (-1, 0):
            act_elems += int(np.prod([int(d) for d in shape[1:]]))
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup_p)
    r = np.random.RandomState(0)
    feed_arrays = {
        "image": r.randn(batch, 3, img_size,
                         img_size).astype("float32"),
        "label": r.randint(0, class_dim,
                           (batch, 1)).astype("int64"),
    }
    state_in, _ = lowering.analyze_block(
        block, list(feed_arrays), [loss.name])
    state_specs = {n: global_scope().find_var(n) for n in state_in}
    entry = lowering.compile_block(
        main_p, block, feed_arrays, [loss.name], state_specs)
    states_mut = {n: global_scope().find_var(n)
                  for n in entry.state_mut_names}
    states_ro = {n: global_scope().find_var(n)
                 for n in entry.state_ro_names}
    return n_params, act_elems, entry, feed_arrays, states_mut, states_ro


RESNET50_FWD_FLOPS_PER_IMG = 4.1e9  # 224x224, same figure bench.py uses


def analytical_resnet(batch, n_params, act_elems):
    """FLOPs / HBM model for one ResNet50 train step on v5e."""
    flops = RESNET50_FWD_FLOPS_PER_IMG * 3.0 * batch
    weights_bf16 = n_params * 2
    master_fp32 = n_params * 4
    momentum_fp32 = n_params * 4
    grads_fp32 = n_params * 4
    acts = act_elems * batch * 2  # bf16 activations held for backward
    peak = weights_bf16 + master_fp32 + momentum_fp32 + grads_fp32 + acts
    return {
        "train_flops": flops,
        "ideal_step_s": flops / V5E_PEAK_BF16,
        "ideal_img_s": batch / (flops / V5E_PEAK_BF16),
        "weights_bf16_gb": weights_bf16 / 1e9,
        "master_mom_gb": (master_fp32 + momentum_fp32) / 1e9,
        "grads_gb": grads_fp32 / 1e9,
        "acts_gb": acts / 1e9,
        "peak_gb": peak / 1e9,
        "fits": peak < V5E_HBM,
    }


def embedding_diff(batch=64, vocab=4096, dim=16, steps=3):
    """Lower a CTR train step with the vocab-sharded embedding engine
    off/on; diff the measured collective bytes (census) and the
    per-replica table+moment bytes, then run a small cold-tier
    simulation (in-process pserver + RowCache over Zipf-skewed
    batches) for the row-cache hit rate; write
    artifacts/embedding_diff.json. Returns 0 when the sharded form
    shows touched-rows (not vocab) collective scaling and ~1/N state,
    1 otherwise."""
    import json

    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework
    from paddle_tpu.models import ctr
    from paddle_tpu.utils.flags import set_flags

    cfg = ctr.CTRConfig(vocab_sizes=(vocab, vocab // 2),
                        embed_dim=dim, arch="wide_deep")

    def one(flag):
        from paddle_tpu.core import scope as scope_mod

        framework.switch_main_program(framework.Program())
        framework.switch_startup_program(framework.Program())
        scope_mod._global_scope = scope_mod.Scope()
        set_flags({"FLAGS_tpu_sparse_embedding": flag})
        with framework.unique_name_guard():
            framework.default_main_program().random_seed = 7
            framework.default_startup_program().random_seed = 7
            loss, _, _ = ctr.build_ctr_train(cfg)
            prog = fluid.default_main_program()
            fluid.CompiledProgram(prog).with_data_parallel(
                loss_name=loss.name)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            feed = ctr.synthetic_batch(cfg, batch)
            exe.run(prog, feed=feed, fetch_list=[loss])
            col = exe.collective_report(prog, feed=feed,
                                        fetch_list=[loss])
            plan = getattr(prog, "_sparse_plan", None)
            fallback = list(getattr(prog,
                                    "_sparse_embedding_fallback",
                                    None) or [])
        return col, plan, fallback

    col_off, _, _ = one(False)
    col_on, plan, fallback = one(True)
    itemsize = 4
    n_tables = len(plan.tables) if plan else 0
    state_logical = state_replica = 0
    for t in (plan.tables.values() if plan else ()):
        n_state = 1 + len(t.row_state)
        state_logical += t.info.vocab * t.info.dim * itemsize * n_state
        state_replica += (t.info.rows_local * t.info.dim * itemsize
                          * n_state)
    biggest_on = max(
        (v["tensor_bytes"] / max(v["count"], 1)
         for k, v in col_on.items()
         if isinstance(v, dict) and "tensor_bytes" in v), default=0)
    vocab_grad_bytes = min(
        t.info.vocab * t.info.dim * itemsize
        for t in plan.tables.values()) if plan else 0

    # cold-tier hit-rate simulation: Zipf-skewed ids against a capped
    # RowCache over an in-process pserver
    from paddle_tpu.distributed.ps import ParameterServer
    from paddle_tpu.distributed.rpc import RpcClient, RpcServer
    from paddle_tpu.embedding import RowCache
    from paddle_tpu.fluid import framework as fw

    ps = ParameterServer(fw.Program(), None, trainers=1, mode="async")
    srv = RpcServer("127.0.0.1", 0, ps.handle)
    srv.start()
    try:
        cli = RpcClient("127.0.0.1:%d" % srv.port)

        cap = batch + 32  # small enough that the tail evicts

        class _HostScope:
            def __init__(self):
                self._v = {"t": np.zeros((cap, dim), np.float32)}

            def find_var(self, n):
                return self._v.get(n)

            def set_var(self, n, v):
                self._v[n] = v

        cache = RowCache(cli, "t", vocab, dim, cap,
                         scope=_HostScope(), var_name="t")
        cache.seed_ps(np.zeros((vocab, dim), np.float32))
        r = np.random.RandomState(0)
        for _ in range(12):
            ids = r.zipf(1.3, size=(batch,)) % vocab
            cache.translate(ids)
        cache_stats = cache.stats()
    finally:
        srv.shutdown()
        ps.heartbeat.stop()

    out = {
        "model": "ctr wide_deep b%d vocab%d" % (batch, vocab),
        "ndev": col_on.get("ndev"),
        "tables_sharded": n_tables,
        "replicated": {"collectives": col_off},
        "sharded": {"collectives": col_on},
        "state_bytes": {"logical": state_logical,
                        "per_replica": state_replica},
        "largest_sharded_collective_bytes": biggest_on,
        "smallest_vocab_grad_bytes": vocab_grad_bytes,
        "row_cache": cache_stats,
        "fallback_reasons": fallback,
    }
    path = os.path.join(_REPO, "artifacts", "embedding_diff.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    ndev = max(int(col_on.get("ndev") or 1), 1)
    ok = (n_tables == 2 * len(cfg.vocab_sizes)
          and state_replica * ndev == state_logical
          # no sharded-path collective carries a vocab-sized payload
          and biggest_on < vocab_grad_bytes
          and 0.0 < cache_stats["hit_rate"] < 1.0
          and cache_stats["evicted_rows"] > 0)
    print("embedding diff: %d tables sharded %d-way, state %.2fMB -> "
          "%.2fMB/replica, largest sharded collective %.1fKB (vocab "
          "grad would be >= %.1fKB), cold-tier hit rate %.1f%% "
          "(%d evicted) -> %s; wrote %s"
          % (n_tables, ndev, state_logical / 1e6, state_replica / 1e6,
             biggest_on / 1e3, vocab_grad_bytes / 1e3,
             100 * cache_stats["hit_rate"],
             cache_stats["evicted_rows"],
             "OK" if ok else "MISMATCH", path))
    return 0 if ok else 1


def sharded_update_diff(batch=16, seq_len=32):
    """Lower the DP BERT-tiny train step with the sharded weight update
    off/on; diff collective bytes + per-replica optimizer-state bytes;
    write artifacts/sharded_update_diff.json. Returns 0 when the
    sharded form shows the expected reductions, 1 otherwise."""
    import json

    def one(flag):
        exe, prog, feed, total = _bert_tiny_step(
            batch, seq_len, {"FLAGS_tpu_sharded_weight_update": flag})
        col = exe.collective_report(prog, feed=feed, fetch_list=[total])
        don = exe.donation_report(prog, feed=feed, fetch_list=[total])
        # structured per-var fallback trail: why the planner declined /
        # degraded anything (empty = the whole update is sharded) —
        # surfaced here instead of silence (ROADMAP ZeRO-1 gap item)
        fallback = list(getattr(prog, "_sharded_update_fallback",
                                None) or [])
        return col, don, fallback

    col_off, don_off, _ = one(False)
    col_on, don_on, fallback = one(True)
    grad_off = col_off.get("all_reduce", {}).get("ici_bytes", 0)
    grad_on = col_on.get("reduce_scatter", {}).get("ici_bytes", 0)

    # third leg: the tensor-parallel planner on the same model (ZeRO-1
    # stays on; mp=2 over the intra-pod tier). Every weight the TP
    # planner touches is either PLANNED (model-sharded) or DECLINED
    # with a structured kind="tp_declined" reason — "unexplained" =
    # a weight-slot candidate that is neither, which should be empty
    exe_tp, prog_tp, feed_tp, total_tp = _bert_tiny_step(
        batch, seq_len, {"FLAGS_tpu_sharded_weight_update": True,
                         "FLAGS_tpu_model_parallel": 2})
    tpp = getattr(prog_tp, "_tp_plan", None)
    trail_tp = list(getattr(prog_tp, "_sharded_update_fallback",
                            None) or [])
    tp_declined = [e for e in trail_tp
                   if e.get("kind") == "tp_declined"]
    blk = prog_tp.global_block()
    cand = set()
    for op in blk.ops:
        slot = ("Y" if op.type in ("mul", "matmul", "matmul_v2")
                else "W" if op.type in ("lookup_table",
                                        "lookup_table_v2", "embedding")
                else None)
        if slot is None:
            continue
        for n in op.input_names.get(slot, []):
            v = blk._find_var_recursive(n)
            if v is not None and getattr(v, "persistable", False):
                cand.add(n)
    explained = set(getattr(tpp, "params", None) or ()) | \
        {e.get("var") for e in tp_declined}
    unexplained = sorted(cand - explained)
    mp_block = {
        "mp_degree": 2,
        "sharded_params": sorted(getattr(tpp, "params", None) or ()),
        "tp_declined": tp_declined,
        "unexplained_params": unexplained,
    }

    # fourth leg: a PipelineOptimizer program under the same ZeRO flag.
    # The pipeline engine owns the program partition, so plan_parallel
    # never runs — that bypass must be a structured
    # kind="pipeline_bypassed" decline on the trail, not silence
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.fluid import framework
    from paddle_tpu.utils.flags import set_flags

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    scope_mod._global_scope = scope_mod.Scope()
    set_flags({"FLAGS_tpu_sharded_weight_update": True})
    with framework.unique_name_guard():
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1],
                                  dtype="int64")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        logits = fluid.layers.fc(input=h, size=4)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.PipelineOptimizer(
            fluid.optimizer.SGDOptimizer(learning_rate=0.1),
            cut_list=[[h]], num_microbatches=2).minimize(loss)
        prog_pp = fluid.default_main_program()
        exe_pp = fluid.Executor(fluid.TPUPlace())
        exe_pp.run(fluid.default_startup_program())
        r = np.random.RandomState(0)
        exe_pp.run(prog_pp,
                   feed={"x": r.rand(8, 16).astype("float32"),
                         "label": r.randint(0, 4, (8, 1)).astype(
                             "int64")},
                   fetch_list=[loss])
    pp_trail = [dict(e) for e in
                (getattr(prog_pp, "_sharded_update_fallback", None)
                 or []) if e.get("kind") == "pipeline_bypassed"]

    out = {
        "model": "bert-tiny b%d s%d" % (batch, seq_len),
        "ndev": col_off.get("ndev"),
        "replicated": {"collectives": col_off,
                       "donation": don_off},
        "sharded": {"collectives": col_on, "donation": don_on},
        "grad_exchange_ici_bytes": {"replicated_allreduce": grad_off,
                                    "sharded_reduce_scatter": grad_on},
        "opt_state_bytes": {
            "replicated_per_replica":
                don_on.get("opt_state_logical_bytes"),
            "sharded_per_replica":
                don_on.get("opt_state_per_replica_bytes")},
        "fallback_reasons": fallback,
        "model_parallel": mp_block,
        "pipeline": {"bypassed": pp_trail},
    }
    path = os.path.join(_REPO, "artifacts", "sharded_update_diff.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    ok = (grad_off > 0 and grad_on > 0
          and grad_on <= 0.6 * grad_off
          and don_on.get("opt_state_sharded_vars", 0) > 0
          and don_on["opt_state_per_replica_bytes"]
          <= 0.2 * don_on["opt_state_logical_bytes"]
          and don_on.get("aliases_state")
          and mp_block["sharded_params"]
          and not unexplained
          and len(pp_trail) == 1)
    print("sharded-update diff (%s): grad ICI %d -> %d bytes "
          "(%.2fx), opt state/replica %s -> %s bytes; %s; wrote %s"
          % (out["model"], grad_off, grad_on,
             grad_off / max(grad_on, 1),
             out["opt_state_bytes"]["replicated_per_replica"],
             out["opt_state_bytes"]["sharded_per_replica"],
             "OK" if ok else "REDUCTION NOT MET", path))
    if fallback:
        print("sharded-update fallback reasons (%d):" % len(fallback))
        for f in fallback:
            print("  [%s] %s (var=%s op=%s)"
                  % (f["kind"], f["reason"], f["var"], f["op"]))
    else:
        print("sharded-update fallback reasons: none (fully planned)")
    print("tensor-parallel (mp=2): %d sharded, %d declined, "
          "%d unexplained%s"
          % (len(mp_block["sharded_params"]), len(tp_declined),
             len(unexplained),
             " <- " + ", ".join(unexplained) if unexplained else ""))
    for f in tp_declined:
        print("  [tp_declined] %s (var=%s op=%s)"
              % (f["reason"], f["var"], f["op"]))
    print("pipeline bypass: %d structured decline(s)%s"
          % (len(pp_trail),
             " <- " + pp_trail[0]["reason"] if pp_trail
             else " (MISSING — the bypass was silent)"))
    return 0 if ok else 1


def quant_diff():
    """Offline evidence for the int8 serving tier: the int8 KV page
    byte census vs f32/bf16 at fixed geometry, the resident-batch
    admission a fixed pool budget buys per dtype, the PTQ weight census
    over the quantized subset, and the int8-engine batched==sequential
    identity. Writes artifacts/quant_diff.json; exits nonzero when any
    reduction or identity does not hold."""
    import json

    import numpy as np
    from paddle_tpu.serving.engine import Engine, EngineConfig
    from paddle_tpu.serving.kv_cache import KVCacheConfig
    from paddle_tpu.serving.model import TinyDecoderLM, TinyLMConfig
    from paddle_tpu.serving.quantize import (is_quantized,
                                             quantize_weights_int8)

    geom = dict(num_pages=64, page_size=8, pages_per_seq=4,
                num_layers=2, num_kv_heads=2, head_dim=16)
    cfgs = {d: KVCacheConfig(dtype=d, **geom)
            for d in ("float32", "bfloat16", "int8")}
    budget = cfgs["float32"].pool_bytes
    pages = {d: c.pages_for_budget(budget) for d, c in cfgs.items()}
    page_bytes = {d: c.page_bytes for d, c in cfgs.items()}

    mcfg = TinyLMConfig()
    model = TinyDecoderLM(mcfg, attention_impl="reference")
    params = model.init_params(0)
    qparams = quantize_weights_int8(params)

    def subset(dense, quant):
        """(dense_bytes, quant_bytes) over the tensors PTQ replaced."""
        if is_quantized(quant):
            return (int(np.asarray(dense).nbytes),
                    int(np.asarray(quant["q"]).nbytes)
                    + int(np.asarray(quant["qscale"]).nbytes))
        if isinstance(dense, dict):
            pairs = [subset(dense[k], quant[k]) for k in dense]
        elif isinstance(dense, (list, tuple)):
            pairs = [subset(d, q) for d, q in zip(dense, quant)]
        else:
            return (0, 0)
        return (sum(p[0] for p in pairs), sum(p[1] for p in pairs))

    w_dense, w_quant = subset(params, qparams)

    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, mcfg.vocab, n)) for n in (5, 9, 3)]

    def run_engine(batched):
        m = TinyDecoderLM(mcfg, attention_impl="reference")
        eng = Engine(m, params=m.init_params(0),
                     config=EngineConfig.from_flags(
                         num_pages=64, page_size=8, max_seqs=4,
                         kv_dtype="int8", quantize_weights=True))
        outs = []
        if batched:
            reqs = [eng.submit(np.asarray(p, np.int32),
                               max_new_tokens=6) for p in prompts]
            eng.run_until_idle()
            outs = [list(r.output_tokens) for r in reqs]
        else:
            for p in prompts:
                r = eng.submit(np.asarray(p, np.int32),
                               max_new_tokens=6)
                eng.run_until_idle()
                outs.append(list(r.output_tokens))
        eng.close()
        return outs

    batched_eq_sequential = run_engine(True) == run_engine(False)
    int8_serving = {
        "kv_page_bytes": page_bytes,
        "pool_budget_bytes": budget,
        "resident_pages_at_budget": pages,
        "admission_ratio_int8_vs_bf16":
            pages["int8"] / max(pages["bfloat16"], 1),
        "weight_bytes_quantized_subset": {
            "dense": w_dense, "int8_plus_scales": w_quant},
        "engine_batched_eq_sequential": batched_eq_sequential,
    }

    out = {"model": "tiny-lm serving", "int8_serving": int8_serving}
    path = os.path.join(_REPO, "artifacts", "quant_diff.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    ok = (page_bytes["int8"] < page_bytes["bfloat16"]
          < page_bytes["float32"]
          and pages["int8"] >= 1.6 * pages["bfloat16"]
          and w_quant * 3.5 <= w_dense
          and batched_eq_sequential)
    print("quant diff: int8 pages %s B (f32/bf16/int8 "
          "admission %s), PTQ weights %d -> %d B (%.2fx), "
          "batched==sequential=%s -> %s; wrote %s"
          % ([page_bytes[d] for d in ("float32", "bfloat16", "int8")],
             [pages[d] for d in ("float32", "bfloat16", "int8")],
             w_dense, w_quant, w_dense / max(w_quant, 1),
             batched_eq_sequential,
             "OK" if ok else "MISMATCH", path))
    return 0 if ok else 1


def serving_prefix_diff():
    """Offline evidence for the serving prefix cache + priority
    preemption. Prefix lane: replays the SAME shared-system-prompt
    trace (serving/trace.synthetic_trace, per-tenant system prompts
    dominating the per-request remainder) against two engines — prefix
    cache ON vs OFF — asserts the per-request decoded streams are
    bit-identical, and that the cache-on engine actually PREFILLED at
    least 2x fewer prompt tokens (the cached-prefix chunks the engine
    skipped). Preemption lane: a low-priority request is evicted
    mid-decode by a higher class on a pool too small for both, and its
    recomputed-then-resumed stream must equal the never-preempted run.
    Writes artifacts/serving_prefix_diff.json; exits nonzero when the
    reduction or either identity does not hold."""
    import json

    import numpy as np
    from paddle_tpu.serving.engine import Engine, EngineConfig
    from paddle_tpu.serving.model import TinyDecoderLM, TinyLMConfig
    from paddle_tpu.serving.trace import synthetic_trace

    mcfg = TinyLMConfig()
    # system prompts ~32-40 tokens vs 2-6 unique body tokens: the
    # shared prefix dominates, so a working cache must cut prefill
    # well past 2x. Arrivals stagger (min 1 step) — registration
    # happens at prefill COMPLETION, so a same-step cold wave would
    # (correctly) share nothing.
    trace = synthetic_trace(
        n_requests=18, n_tenants=3, seed=3, vocab=mcfg.vocab,
        prompt_range=(2, 6), output_range=(4, 6),
        arrival_every=(1, 3), system_prompt_range=(32, 40))

    def replay(prefix_cache):
        model = TinyDecoderLM(mcfg, attention_impl="reference")
        eng = Engine(model, params=model.init_params(0),
                     config=EngineConfig.from_flags(
                         num_pages=96, page_size=8, max_seqs=6,
                         prefix_cache=prefix_cache))
        pending = sorted(trace, key=lambda tr: tr.arrival_step)
        reqs, i, step = [], 0, 0
        while i < len(pending) or not eng.scheduler.idle:
            while i < len(pending) and \
                    pending[i].arrival_step <= step:
                tr = pending[i]
                reqs.append(eng.submit(
                    tr.prompt, max_new_tokens=tr.max_new_tokens,
                    tenant=tr.tenant, priority=tr.priority))
                i += 1
            eng.step()
            step += 1
            if step > 4000:
                raise RuntimeError("trace failed to drain")
        outs = [list(r.output_tokens) for r in reqs]
        stats = eng.stats()
        hit = eng.kv.prefix_hit_tokens
        cow = eng.kv.cow_copies
        eng.close()
        return outs, stats, hit, cow

    outs_on, stats_on, hit_on, cow_on = replay(True)
    outs_off, stats_off, hit_off, _ = replay(False)
    prompt_tokens = sum(len(tr.prompt) for tr in trace)
    # actual prefill work = prompt tokens minus the cached-prefix
    # tokens the engine skipped (no preemption in this lane, so the
    # cumulative hit counter is exactly the skipped prefill)
    prefill_on = prompt_tokens - hit_on
    prefill_off = prompt_tokens - hit_off
    outputs_identical = outs_on == outs_off
    ratio = prefill_off / max(prefill_on, 1)

    # -- preemption identity lane ------------------------------------
    def decode_victim(with_rival):
        model = TinyDecoderLM(mcfg, attention_impl="reference")
        eng = Engine(model, params=model.init_params(0),
                     config=EngineConfig.from_flags(
                         num_pages=8, page_size=4, max_seqs=4))
        rng = np.random.default_rng(7)
        p_victim = rng.integers(1, mcfg.vocab, 8).astype(np.int32)
        p_rival = rng.integers(1, mcfg.vocab, 8).astype(np.int32)
        victim = eng.submit(p_victim, max_new_tokens=12, priority=0)
        for _ in range(4):                 # victim gets mid-decode
            eng.step()
        if with_rival:
            eng.submit(p_rival, max_new_tokens=12, priority=5)
        eng.run_until_idle()
        out = list(victim.output_tokens)
        n_pre = eng.scheduler.preemption_count
        eng.close()
        return out, n_pre

    out_preempted, n_preempt = decode_victim(True)
    out_baseline, _ = decode_victim(False)
    preempt_identical = out_preempted == out_baseline

    out = {
        "trace": {"requests": len(trace), "prompt_tokens":
                  prompt_tokens,
                  "system_prompt_range": [32, 40]},
        "prefix_cache_on": {
            "prefill_tokens": prefill_on,
            "prefix_hit_tokens": hit_on,
            "cow_copies": cow_on,
            "pages_cached": stats_on.get("kv_pages_cached", 0)},
        "prefix_cache_off": {
            "prefill_tokens": prefill_off,
            "prefix_hit_tokens": hit_off},
        "prefill_reduction_x": round(ratio, 3),
        "outputs_identical": outputs_identical,
        "preemption": {"preemptions": n_preempt,
                       "preempted_eq_baseline": preempt_identical},
    }
    path = os.path.join(_REPO, "artifacts", "serving_prefix_diff.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    ok = (outputs_identical and ratio >= 2.0 and hit_off == 0
          and n_preempt >= 1 and preempt_identical)
    print("serving prefix diff: prefill %d -> %d tokens (%.2fx, "
          "%d hit, %d cow), outputs identical=%s; preemptions=%d "
          "preempted==baseline=%s -> %s; wrote %s"
          % (prefill_off, prefill_on, ratio, hit_on, cow_on,
             outputs_identical, n_preempt, preempt_identical,
             "OK" if ok else "MISMATCH", path))
    return 0 if ok else 1


def _bert_tiny_step(batch, seq_len, flags, amp=False, run=True):
    """One compiled data-parallel BERT-tiny Adam step under `flags`;
    returns the serving Executor + program + feed (for the report
    APIs). Fresh programs/scope per call so flag changes recompile.
    `amp`: mixed_precision.decorate the optimizer (O2 masters, static
    scaling — the bench's AMP shape). `run=False` skips the train-step
    dispatch (the OOM pre-flight leg needs a program that FAILS before
    its first dispatch)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.fluid import framework
    from paddle_tpu.models import bert
    from paddle_tpu.utils.flags import set_flags
    from __graft_entry__ import _bert_feed

    cfg = bert.BertConfig.tiny()
    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    scope_mod._global_scope = scope_mod.Scope()
    set_flags(flags)
    with framework.unique_name_guard():
        framework.default_main_program().random_seed = 7
        framework.default_startup_program().random_seed = 7
        total, _, _, _ = bert.bert_pretrain_loss(
            cfg, seq_len, is_test=False)
        opt = fluid.optimizer.AdamOptimizer(learning_rate=1e-3)
        if amp:
            from paddle_tpu.fluid.contrib import mixed_precision

            opt = mixed_precision.decorate(
                opt, use_dynamic_loss_scaling=False)
        opt.minimize(total)
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=total.name)
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(fluid.default_startup_program())
        feed = _bert_feed(cfg, batch, seq_len)
        if run:
            exe.run(prog, feed=feed, fetch_list=[total])
    return exe, prog, feed, total


def hierarchy_diff(dcn=2, batch=16, seq_len=32, bucket_mb=0.25):
    """Lower the DP BERT-tiny train step flat and on an emulated
    (dcn x ici) hybrid CPU mesh; split the census into ici/dcn lanes
    and check the hierarchical contract — every cross-pod grad-sync
    collective carries flat-allreduce bytes / ici_size — then write
    artifacts/hierarchy_diff.json. Returns 0 when the cross-pod
    reduction holds, 1 otherwise."""
    import json

    def one(dcn_flag):
        exe, prog, feed, total = _bert_tiny_step(
            batch, seq_len,
            {"FLAGS_tpu_sharded_weight_update": True,
             "FLAGS_tpu_comm_bucket_mb": bucket_mb,
             "FLAGS_tpu_dcn_replicas": dcn_flag})
        col = exe.collective_report(prog, feed=feed, fetch_list=[total])
        return col, prog

    col_flat, _ = one(0)
    col_h, prog_h = one(dcn)
    hier = col_h.get("lanes") is not None
    ici_size = col_h.get("ici_size", 0)
    dcn_grad = [c for c in
                col_h.get("lanes", {}).get("dcn",
                                           {}).get("per_collective", [])
                if c["kind"] == "all_reduce"]
    dcn_bytes = sum(c["tensor_bytes"] for c in dcn_grad)
    # flat baseline: the bucketed reduce_scatter inputs (= what one
    # flat allreduce of the same grads would carry cross-pod)
    flat_bytes = sum(b["bytes"] for b in col_h.get("buckets", []))
    out = {
        "model": "bert-tiny b%d s%d" % (batch, seq_len),
        "dcn_replicas": dcn,
        "ici_size": ici_size,
        "flat": {"collectives": col_flat},
        "hierarchical": {"collectives": col_h},
        "cross_pod_grad_bytes": dcn_bytes,
        "flat_allreduce_bytes": flat_bytes,
        "per_bucket_ok": [
            {"dcn_collective_bytes": c["tensor_bytes"],
             "participants": c["participants"]} for c in dcn_grad],
    }
    path = os.path.join(_REPO, "artifacts", "hierarchy_diff.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    ok = (hier and ici_size > 1 and dcn_grad and flat_bytes > 0
          and dcn_bytes * ici_size == flat_bytes
          and all(c["participants"] == dcn for c in dcn_grad))
    print("hierarchy diff (%s): %dx%d (dcn x ici) mesh, cross-pod "
          "grad sync %d bytes vs %d flat (exactly 1/%d: %s); %d dcn "
          "collective(s); wrote %s"
          % (out["model"], dcn, ici_size, dcn_bytes, flat_bytes,
             max(ici_size, 1),
             "yes" if dcn_bytes * max(ici_size, 1) == flat_bytes
             else "NO", len(dcn_grad), path))
    return 0 if ok else 1


def overlap_audit(bucket_mb=0.25, batch=16, seq_len=32):
    """Compile the DP BERT-tiny step bucketed (bucket_mb) and
    single-exchange (cap 0); audit the optimized HLO schedules; write
    artifacts/overlap_audit.json. Returns 0 when >= 2 bucket
    reduce-scatters can overlap backward compute AND the cap=0 lowering
    has zero overlap under the collective-combiner model, 1 otherwise."""
    import json

    def one(mb):
        exe, prog, feed, total = _bert_tiny_step(
            batch, seq_len,
            {"FLAGS_tpu_sharded_weight_update": True,
             "FLAGS_tpu_comm_bucket_mb": mb})
        rep = exe.overlap_report(prog, feed=feed, fetch_list=[total])
        col = exe.collective_report(prog, feed=feed, fetch_list=[total])
        return rep, col

    rep_b, col_b = one(bucket_mb)
    rep_0, col_0 = one(0.0)
    rs_combined0 = rep_0["combined"].get("reduce-scatter", {})
    out = {
        "model": "bert-tiny b%d s%d" % (batch, seq_len),
        "bucket_mb": bucket_mb,
        "bucketed": {"overlap": rep_b, "collectives": col_b},
        "single_exchange": {"overlap": rep_0, "collectives": col_0},
    }
    path = os.path.join(_REPO, "artifacts", "overlap_audit.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    n_over = rep_b["overlappable_reduce_scatters"]
    ok = (n_over >= 2
          and rep_b.get("n_buckets", 0) >= 2
          and rep_b["is_scheduled"]
          and rs_combined0.get("backward_after", -1) == 0)
    rs_list = [c for c in rep_b["collectives"]
               if c["kind"] == "reduce-scatter"]
    print("overlap audit (%s): %d buckets -> %d/%d reduce-scatters "
          "ready before the final backward op (backward ops left to "
          "hide behind: %s); cap=0 combined exchange has %d backward "
          "ops after it; %s; wrote %s"
          % (out["model"], rep_b.get("n_buckets", 0), n_over,
             len(rs_list),
             [c["backward_after"] for c in rs_list],
             rs_combined0.get("backward_after", -1),
             "OK" if ok else "OVERLAP NOT MET", path))
    return 0 if ok else 1


def attribution_audit(batch=16, seq_len=32, bucket_mb=0.25):
    """The acceptance audit for per-op resource attribution: BERT-tiny
    DP + ZeRO-1 + AMP-O2 masters + bucketed collectives on the emulated
    CPU mesh. Asserts (1) >= 90% of the compiled memory_analysis()
    peak attributes to named framework ops/classes, (2) the class
    totals match donation_report EXACTLY, (3) every collective in the
    lowered module maps to a fluid op / bucket / gradient, and (4)
    FLAGS_tpu_hbm_budget_mb set below the predicted peak fails
    PRE-dispatch with a structured HbmBudgetExceeded naming the top
    consumers. Writes artifacts/attribution.json; returns the process
    exit code."""
    import json

    from paddle_tpu.observability.attribution import HbmBudgetExceeded
    from paddle_tpu.utils.flags import set_flags

    exe, prog, feed, total = _bert_tiny_step(
        batch, seq_len,
        {"FLAGS_tpu_sharded_weight_update": True,
         "FLAGS_tpu_comm_bucket_mb": bucket_mb},
        amp=True)
    rep = exe.attribution_report(prog, feed=feed, fetch_list=[total])
    mem = rep.get("memory", {})
    colls = rep.get("collectives", {})
    cross = rep.get("cross_check", {})
    coverage = float(mem.get("coverage") or 0.0)
    mapped_ok = colls.get("count", 0) > 0 and \
        colls.get("mapped") == colls.get("count")

    # OOM pre-flight: a budget below the predicted peak must fail the
    # NEXT program before its first dispatch, naming the consumers
    budget_mb = max(mem.get("peak_model_bytes", 0) / 1e6 / 2.0, 0.001)
    preflight = {"budget_mb": budget_mb, "raised": False}
    try:
        exe2, prog2, feed2, total2 = _bert_tiny_step(
            batch, seq_len,
            {"FLAGS_tpu_sharded_weight_update": True,
             "FLAGS_tpu_comm_bucket_mb": bucket_mb},
            amp=True, run=False)
        set_flags({"FLAGS_tpu_hbm_budget_mb": budget_mb})
        try:
            exe2.run(prog2, feed=feed2, fetch_list=[total2])
        except HbmBudgetExceeded as e:
            preflight.update({
                "raised": True,
                "predicted_bytes": e.predicted_bytes,
                "budget_bytes": e.budget_bytes,
                "top_consumers": e.top_consumers,
            })
    finally:
        set_flags({"FLAGS_tpu_hbm_budget_mb": 0})

    out = {
        "model": "bert-tiny b%d s%d (DP + ZeRO-1 + AMP-O2 + buckets)"
                 % (batch, seq_len),
        "bucket_mb": bucket_mb,
        "ndev": rep.get("ndev"),
        "classes": rep.get("classes"),
        "memory": mem,
        "coverage": coverage,
        "collectives": {"count": colls.get("count"),
                        "mapped": colls.get("mapped")},
        "cross_check": cross,
        "top_consumers": rep.get("top_consumers"),
        "activation_by_layer":
            rep.get("activation", {}).get("by_layer"),
        "preflight": preflight,
    }
    path = os.path.join(_REPO, "artifacts", "attribution.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    ok = (coverage >= 0.90 and cross.get("ok") and mapped_ok
          and preflight["raised"]
          and bool(preflight.get("top_consumers")))
    print("attribution audit (%s): %.0f%% of %.2f MB peak attributed, "
          "cross-check %s, %s/%s collectives mapped, pre-flight %s; "
          "%s; wrote %s"
          % (out["model"], 100.0 * coverage,
             mem.get("peak_model_bytes", 0) / 1e6,
             "ok" if cross.get("ok") else "FAILED",
             colls.get("mapped"), colls.get("count"),
             "raised pre-dispatch" if preflight["raised"]
             else "DID NOT RAISE",
             "OK" if ok else "ATTRIBUTION NOT MET", path))
    return 0 if ok else 1


def xplane_blame(xplane_dir):
    """Fold a capture window's device time (self times of the `XLA Ops`
    thread, over the traced executions of the step's module) through
    the provenance markers: where in the step it went (forward /
    recompute / backward / update / collective), by fluid op type, by
    op type crossed with region and with the parts an op's code names
    (`attribution.op_part_table`), and the per-layer / per-bucket blame
    (--stragglers --xplane-dir).
    Returns the attribution dict."""
    from paddle_tpu.observability import attribution as attr

    events = attr.load_trace_events(xplane_dir)
    t = attr.time_attribution(events)
    if not t["total_us"]:
        print("xplane dir %s: no device operation found (the fold reads "
              "the *.trace.json.gz sidecar's /device:TPU:<n> processes)"
              % xplane_dir)
        return t
    steps = max(t["steps"], 1)
    print("device-time attribution over %s (%d step(s) on %d device(s), "
          "%.1f ms a step, %.1f%% under provenance markers, %.1f%% with "
          "no scope path):"
          % (xplane_dir, t["steps"], t["devices"],
             t["total_us"] / steps / 1e3,
             100.0 * t["matched_us"] / t["total_us"],
             100.0 * t["unattributed_us"] / t["total_us"]))
    for region, us in t["by_region"].items():
        if us:
            print("  region %-27s %10.1f us/step %5.1f%%"
                  % (region, us / steps, 100.0 * us / t["total_us"]))
    for op_type, us in list(t["by_op_type"].items())[:12]:
        print("  op type %-26s %10.1f us/step" % (op_type, us / steps))
    for line in attr.op_part_table(t):
        print("  " + line)
    for layer, us in list(t["by_layer"].items())[:10]:
        print("  layer %-28s %10.1f us" % (layer, us))
    for b, us in t["by_bucket"].items():
        print("  bucket %-27d %10.1f us" % (b, us))
    return t


def stragglers(telemetry_dir, window=32):
    """Offline straggler report over a telemetry dir's per-rank JSONL
    (see module docstring). Returns the process exit code. Torn JSONL
    lines (the final-line artifact a killed rank leaves) are skipped
    and REPORTED, never a traceback."""
    import json

    from paddle_tpu.observability import aggregate

    torn = []
    by_rank = aggregate.load_telemetry_dir(telemetry_dir, errors=torn)
    steps = {r: sum(1 for rec in recs if rec.get("kind") == "step")
             for r, recs in by_rank.items()}
    print("telemetry dir %s: %d rank(s), step records per rank: %s"
          % (telemetry_dir, len(by_rank),
             {r: n for r, n in sorted(steps.items())}))
    for t in torn:
        print("skipped torn JSONL line: %s:%d%s (%r...)"
              % (t["file"], t["line_no"],
                 " [final line — a killed writer's artifact]"
                 if t["final_line"] else " [MID-FILE: corruption?]",
                 t["snippet"][:60]))
    report = aggregate.straggler_report(by_rank, window=window)
    if report["ranks"] < 2:
        print("need >= 2 ranks of step records for a cross-rank "
              "straggler report")
        return 2
    for w in report["windows"]:
        print("steps %d..%d: slowest rank %d (%.2fms/step mean, "
              "+%.2fms vs rank %d)"
              % (w["steps"][0], w["steps"][1], w["slowest_rank"],
                 w["slowest_total_ms_mean"], w["slack_ms"],
                 w["fastest_rank"]))
    print("straggler: rank %s (slowest in %d/%d windows)"
          % (report["straggler"], report["by_rank"].get(
              report["straggler"], 0), len(report["windows"])))
    # cross-rank per-phase spread over the whole run's step records
    summaries = [aggregate.window_summary(records=[
        rec for rec in recs if rec.get("kind") == "step"])
        for recs in by_rank.values()]
    agg = aggregate.aggregate_summaries(summaries)
    print(json.dumps({"stragglers": report, "cross_rank": agg},
                     indent=1, sort_keys=True))
    return 0


def compile_cache_report(telemetry_dir=None, log_dir=None,
                         cache_dir=None):
    """Compile-cache effectiveness report over a run's telemetry:
    aggregates the per-compile `compile_cache` events (hit rate,
    compile seconds actually paid vs compile seconds the persistent
    tier saved, per-rank breakdown), folds in the supervisor's
    elastic_transition coordination_s/compile_s split when present,
    and inventories the on-disk cache. Returns the process exit
    code."""
    import json

    from paddle_tpu.observability import aggregate

    if telemetry_dir is None and log_dir:
        telemetry_dir = os.path.join(log_dir, "telemetry")
    if cache_dir is None:
        # where the launcher keeps it: the environment's directory,
        # else the fixed <checkout>/.jax_cache
        from paddle_tpu.fluid import compile_cache as _cc

        cand = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
            _cc.default_dir()
        cache_dir = cand if os.path.isdir(cand) else None
    if not telemetry_dir or not os.path.isdir(telemetry_dir):
        print("no telemetry dir at %r" % telemetry_dir)
        return 2
    by_rank = aggregate.load_telemetry_dir(telemetry_dir)
    events = []
    for recs in by_rank.values():
        events.extend(r for r in recs
                      if r.get("event") == "compile_cache")
    # postmortem subdirs hold earlier attempts' streams (the launch
    # supervisor moves them between restarts) — a warm-restart proof
    # needs the cold attempt's misses next to the warm attempt's hits
    pm_root = os.path.join(os.path.dirname(telemetry_dir.rstrip("/")),
                           "postmortem")
    if log_dir:
        pm_root = os.path.join(log_dir, "postmortem")
    attempts = {}
    if os.path.isdir(pm_root):
        for aname in sorted(os.listdir(pm_root)):
            adir = os.path.join(pm_root, aname)
            if not (aname.startswith("attempt")
                    and os.path.isdir(adir)):
                continue
            arecs = aggregate.load_telemetry_dir(adir)
            aevs = [r for recs in arecs.values() for r in recs
                    if r.get("event") == "compile_cache"]
            if aevs:
                attempts[aname] = aevs
                events.extend(aevs)
    if not events:
        print("no compile_cache events under %s (persistent tier off — "
              "set JAX_COMPILATION_CACHE_DIR, or launch through "
              "paddle_tpu.distributed.launch)" % telemetry_dir)
        return 1
    hits = [e for e in events if e.get("status") == "hit"]
    misses = [e for e in events if e.get("status") == "miss"]
    paid_s = sum(float(e.get("compile_ms", 0.0)) for e in events) / 1e3
    saved_s = sum(float(e.get("saved_ms", 0.0)) for e in hits) / 1e3
    miss_bytes = sum(int(e.get("bytes", 0)) for e in misses)
    by_rank_tally = {}
    for e in events:
        t = by_rank_tally.setdefault(int(e.get("rank", -1)),
                                     {"hits": 0, "misses": 0})
        t["hits" if e.get("status") == "hit" else "misses"] += 1
    print("compile cache: %d hit(s) / %d miss(es) (hit rate %.0f%%), "
          "%.2fs compile paid, %.2fs compile saved, %.2f MB written "
          "on misses"
          % (len(hits), len(misses),
             100.0 * len(hits) / max(len(events), 1), paid_s, saved_s,
             miss_bytes / 1e6))
    for r, t in sorted(by_rank_tally.items()):
        print("  rank %d: %d hit(s) / %d miss(es)"
              % (r, t["hits"], t["misses"]))
    # by-source classification: training steps vs executor warmups vs
    # the serving engine's AOT-compiled decode/prefill step buckets
    # (source serving_decode / serving_prefill — an all-hit serving
    # restart shows up here as "serving_decode: N hit / 0 miss")
    by_source = {}
    for e in events:
        t = by_source.setdefault(str(e.get("source", "step")),
                                 {"hits": 0, "misses": 0})
        t["hits" if e.get("status") == "hit" else "misses"] += 1
    if len(by_source) > 1 or any(
            s.startswith("serving") for s in by_source):
        for s, t in sorted(by_source.items()):
            print("  source %s: %d hit(s) / %d miss(es)"
                  % (s, t["hits"], t["misses"]))
        sd = by_source.get("serving_decode")
        if sd:
            print("  serving decode buckets: %s"
                  % ("all-hit (warm restart)" if not sd["misses"]
                     else "%d cold compile(s)" % sd["misses"]))
    for aname, aevs in sorted(attempts.items()):
        ah = sum(1 for e in aevs if e.get("status") == "hit")
        print("  %s: %d hit(s) / %d miss(es)"
              % (aname, ah, len(aevs) - ah))
    transitions = []
    sup = os.path.join(telemetry_dir, "telemetry.supervisor.jsonl")
    if os.path.exists(sup):
        with open(sup) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("event") == "elastic_transition":
                    transitions.append(rec)
    for t in transitions:
        print("elastic transition %s -> %s: coordination %.2fs + "
              "compile %s = recovery %.2fs"
              % (t.get("old_world"), t.get("new_world"),
                 float(t.get("coordination_s",
                             t.get("recovery_s", 0.0))),
                 ("%.2fs" % t["compile_s"]) if "compile_s" in t
                 else "<no worker telemetry>",
                 float(t.get("recovery_s", 0.0))))
    inventory = None
    if cache_dir and os.path.isdir(cache_dir):
        files = [f for f in os.listdir(cache_dir)
                 if os.path.isfile(os.path.join(cache_dir, f))]
        inventory = {
            "dir": cache_dir,
            "entries": len(files),
            "bytes": sum(os.path.getsize(os.path.join(cache_dir, f))
                         for f in files),
            "index_entries": len(os.listdir(
                os.path.join(cache_dir, "index")))
            if os.path.isdir(os.path.join(cache_dir, "index")) else 0,
        }
        print("on-disk cache %s: %d entries, %.2f MB, %d index "
              "sentinel(s)"
              % (inventory["dir"], inventory["entries"],
                 inventory["bytes"] / 1e6, inventory["index_entries"]))
    print(json.dumps({
        "hits": len(hits), "misses": len(misses),
        "hit_rate": len(hits) / max(len(events), 1),
        "compile_paid_s": round(paid_s, 3),
        "compile_saved_s": round(saved_s, 3),
        "miss_bytes": miss_bytes,
        "by_rank": by_rank_tally,
        "by_source": by_source,
        "attempts": {a: len(v) for a, v in attempts.items()},
        "transitions": transitions,
        "cache": inventory,
    }, indent=1, sort_keys=True))
    return 0


def hang_report_cli(telemetry_dir=None, log_dir=None, attempt=None):
    """Offline hang/desync diagnosis over a postmortem bundle (see
    module docstring). Returns the process exit code."""
    import json

    from paddle_tpu.observability import watchdog as wd

    directory = telemetry_dir
    if directory is None and log_dir:
        pm = os.path.join(log_dir, "postmortem")
        if attempt is not None:
            directory = os.path.join(pm, "attempt%d" % attempt)
        else:
            attempts = sorted(
                (d for d in os.listdir(pm)
                 if d.startswith("attempt")),
                key=lambda d: int(d[len("attempt"):])
            ) if os.path.isdir(pm) else []
            directory = os.path.join(pm, attempts[-1]) if attempts \
                else os.path.join(log_dir, "telemetry")
    if not directory or not os.path.isdir(directory):
        print("no postmortem bundle at %r" % directory)
        return 2
    rep = wd.hang_report(directory)
    if not rep["n_docs"]:
        print("no flightrec.rank*.json dumps under %s" % directory)
        return 2
    for line in rep["lines"]:
        print(line)
    print(json.dumps({"hang": rep["verdict"]}, indent=1,
                     sort_keys=True))
    return 0 if rep["verdict"]["verdict"] != "no-hang" else 1


def _iter_jsonl_events(path, wanted):
    """Yield event records of the `wanted` types from one JSONL
    stream, skipping torn lines."""
    import json

    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn final line of a killed writer
                if rec.get("event") in wanted:
                    yield rec
    except OSError:
        return


def elastic_report(log_dir=None, telemetry_dir=None):
    """Elastic recovery report, both seam shapes side by side:

    - restart-shaped: the supervisor's `elastic_transition` events
      (telemetry.supervisor.jsonl — old/new world, reassignment map,
      recovery wall time) stitched with the per-attempt postmortem
      index;
    - live-shaped: the WORKERS' `elastic_transition(mode=live)` +
      `live_resize` events (telemetry.rank*.jsonl, current dir and
      postmortem attempts), each split into its
      notice -> snapshot -> rebuild -> resume spans.

    One command answers "what did the run lose at each seam — and did
    it pay a restart or a live resize for it". Returns the process
    exit code."""
    import glob as _glob
    import json

    if telemetry_dir is None and log_dir:
        telemetry_dir = os.path.join(log_dir, "telemetry")
    if not telemetry_dir or not os.path.isdir(telemetry_dir):
        print("no telemetry dir at %r" % telemetry_dir)
        return 2
    sup = os.path.join(telemetry_dir, "telemetry.supervisor.jsonl")
    transitions = list(_iter_jsonl_events(sup, ("elastic_transition",)))
    # live seams are worker-emitted: scan per-rank streams in the
    # telemetry dir and every postmortem attempt bundle
    pm_root = os.path.join(log_dir, "postmortem") if log_dir \
        else os.path.join(os.path.dirname(telemetry_dir), "postmortem")
    rank_streams = sorted(
        _glob.glob(os.path.join(telemetry_dir, "telemetry.rank*.jsonl"))
        + _glob.glob(os.path.join(pm_root, "attempt*",
                                  "telemetry.rank*.jsonl")))
    live, seen = [], set()
    for path in rank_streams:
        for rec in _iter_jsonl_events(
                path, ("elastic_transition", "live_resize")):
            if rec.get("event") == "elastic_transition" \
                    and rec.get("mode") != "live":
                continue
            # every survivor emits the same seam: dedup on the seam
            # identity, keep one representative per event type
            k = (rec["event"], rec.get("old_world"),
                 rec.get("new_world"), rec.get("generation"),
                 rec.get("status"))
            if k in seen:
                continue
            seen.add(k)
            rec["_stream"] = os.path.relpath(
                path, log_dir or telemetry_dir)
            live.append(rec)
    index = None
    pm_index = os.path.join(pm_root, "index.json")
    if os.path.exists(pm_index):
        with open(pm_index) as f:
            index = json.load(f)
    if not transitions and not live:
        print("no elastic_transition events under %s (fixed-world run, "
              "or the supervisor ran without --min_ranks)"
              % telemetry_dir)
    for t in transitions:
        degraded = " [degraded from live seam]" \
            if t.get("degraded_from_live") else ""
        print("attempt %s: restart world %s -> %s, dropped ranks %s, "
              "reassignment %s, recovery %.2fs%s"
              % (t.get("attempt"), t.get("old_world"),
                 t.get("new_world"), t.get("failed_ranks"),
                 t.get("reassignment"), float(t.get("recovery_s",
                                                    0.0)),
                 degraded))
    for t in (r for r in live if r.get("event") == "live_resize"):
        spans = " -> ".join(
            "%s %.3fs" % (name, float(t.get(name + "_s", 0.0)))
            for name in ("notice", "snapshot", "rebuild")
            if (name + "_s") in t)
        print("live seam: world %s -> %s (%s), coordination %.3fs%s"
              % (t.get("old_world"), t.get("new_world"),
                 t.get("status", "ok"),
                 float(t.get("coordination_s", 0.0)),
                 (" [%s]" % spans) if spans else ""))
    if transitions:
        total = sum(float(t.get("recovery_s", 0.0)) for t in transitions)
        print("total supervisor recovery wall time: %.2fs over %d "
              "restart transition(s)" % (total, len(transitions)))
    if live:
        lr = [r for r in live if r.get("event") == "live_resize"
              and r.get("status") == "ok"]
        if lr:
            total = sum(float(t.get("coordination_s", 0.0)) for t in lr)
            print("total live coordination wall time: %.3fs over %d "
                  "live seam(s)" % (total, len(lr)))
    print(json.dumps({"transitions": transitions, "live": live,
                      "postmortem_index": index},
                     indent=1, sort_keys=True))
    return 0 if (transitions or live) else 1


def _parse_mode_flags(mode, argv, spec):
    """One parser for the `--mode --flag VALUE|--flag=VALUE ...`
    subcommand shape --stragglers / --elastic / --hang-report all
    share: `spec` maps accepted flag name -> converter. Returns
    {flag: converted value}; unknown flags and missing values are
    loud SystemExits."""
    out = {}
    i = 0
    while i < len(argv):
        a = argv[i]
        if "=" in a:
            flag, val = a.split("=", 1)
        else:
            flag = a
            val = argv[i + 1] if i + 1 < len(argv) else ""
            if not val or val.startswith("--"):
                raise SystemExit("flag %s needs a value" % flag)
            i += 1
        if flag not in spec:
            raise SystemExit("unknown %s argument: %s" % (mode, flag))
        out[flag] = spec[flag](val)
        i += 1
    return out


def main():
    batches = [256, 512]
    resnet_batches = [128, 256]
    args = sys.argv[1:]
    if "--hang-report" in args:
        kv = _parse_mode_flags(
            "--hang-report", [a for a in args if a != "--hang-report"],
            {"--telemetry-dir": str, "--log-dir": str,
             "--attempt": int})
        if not (kv.get("--telemetry-dir") or kv.get("--log-dir")):
            raise SystemExit(
                "usage: --hang-report --telemetry-dir DIR | "
                "--log-dir DIR [--attempt K]")
        raise SystemExit(hang_report_cli(
            telemetry_dir=kv.get("--telemetry-dir"),
            log_dir=kv.get("--log-dir"),
            attempt=kv.get("--attempt")))
    if "--compile-cache" in args:
        kv = _parse_mode_flags(
            "--compile-cache",
            [a for a in args if a != "--compile-cache"],
            {"--telemetry-dir": str, "--log-dir": str,
             "--cache-dir": str})
        if not (kv.get("--telemetry-dir") or kv.get("--log-dir")):
            raise SystemExit(
                "usage: --compile-cache --telemetry-dir DIR | "
                "--log-dir DIR [--cache-dir DIR]")
        raise SystemExit(compile_cache_report(
            telemetry_dir=kv.get("--telemetry-dir"),
            log_dir=kv.get("--log-dir"),
            cache_dir=kv.get("--cache-dir")))
    if "--elastic" in args:
        kv = _parse_mode_flags(
            "--elastic", [a for a in args if a != "--elastic"],
            {"--log-dir": str, "--telemetry-dir": str})
        if not (kv.get("--log-dir") or kv.get("--telemetry-dir")):
            raise SystemExit(
                "usage: --elastic --log-dir DIR | --telemetry-dir DIR")
        raise SystemExit(elastic_report(
            log_dir=kv.get("--log-dir"),
            telemetry_dir=kv.get("--telemetry-dir")))
    if "--stragglers" in args:
        kv = _parse_mode_flags(
            "--stragglers", [a for a in args if a != "--stragglers"],
            {"--telemetry-dir": str, "--window": int,
             "--xplane-dir": str})
        if not kv.get("--telemetry-dir"):
            raise SystemExit(
                "usage: --stragglers --telemetry-dir DIR [--window N] "
                "[--xplane-dir DIR]")
        rc = stragglers(kv["--telemetry-dir"],
                        window=kv.get("--window", 32))
        if kv.get("--xplane-dir"):
            # per-layer / per-bucket device-time blame from a capture
            # window's trace, one level below the phase verdict
            xplane_blame(kv["--xplane-dir"])
        raise SystemExit(rc)
    if "--lint" in args:
        # alias into the tpu-lint static verifier; tools/ is not a
        # package, so import by path alongside this file
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import tpu_lint

        raise SystemExit(tpu_lint.main(
            [a for a in args if a != "--lint"]))
    if "--sharded-diff" in args:
        raise SystemExit(sharded_update_diff())
    if "--quant" in args:
        raise SystemExit(quant_diff())
    if "--serving" in args:
        raise SystemExit(serving_prefix_diff())
    if "--embedding" in args:
        raise SystemExit(embedding_diff())

    def _parse_bucket_mb(argv, default=0.25):
        mb = default
        for i, a in enumerate(argv):
            if not a.startswith("--bucket-mb"):
                continue
            val = (a.split("=", 1)[1] if "=" in a
                   else argv[i + 1] if i + 1 < len(argv) else "")
            try:
                mb = float(val)
            except ValueError:
                raise SystemExit(
                    "usage: --bucket-mb <float MB> (got %r)" % (val,))
        return mb

    if "--attribution" in args:
        raise SystemExit(attribution_audit(
            bucket_mb=_parse_bucket_mb(args)))
    if "--overlap-audit" in args:
        raise SystemExit(overlap_audit(
            bucket_mb=_parse_bucket_mb(args)))
    if "--hierarchy" in args:
        dcn = 2
        for i, a in enumerate(args):
            if not a.startswith("--dcn"):
                continue
            val = (a.split("=", 1)[1] if "=" in a
                   else args[i + 1] if i + 1 < len(args) else "")
            try:
                dcn = int(val)
            except ValueError:
                raise SystemExit("usage: --dcn <int> (got %r)" % (val,))
        raise SystemExit(hierarchy_diff(dcn=dcn))
    i = 0
    while i < len(args):
        a = args[i]
        # accept both --flag=1,2 and --flag 1,2
        if "=" in a:
            flag, val = a.split("=", 1)
        else:
            flag = a
            val = args[i + 1] if i + 1 < len(args) else ""
            if not val or val.startswith("--"):
                raise SystemExit("flag %s needs a value (e.g. %s=128,256)"
                                 % (flag, flag))
            i += 1
        if flag == "--batches":
            batches = [int(x) for x in val.split(",") if x]
        elif flag == "--resnet-batches":
            resnet_batches = [int(x) for x in val.split(",") if x]
        else:
            raise SystemExit("unknown argument: %s" % a)
        i += 1
    # lower the program the TPU bench would run: on chip
    # FLAGS_prng_impl=auto resolves to the hardware RngBitGenerator
    # (core/rng.py), so the analysis must force it here on the CPU
    # backend or the census would count threefry's extra ALU ops
    from paddle_tpu.utils.flags import set_flags

    set_flags({"FLAGS_prng_impl": "rbg"})
    report = ["# BERT-base train step: StableHLO census", "",
              "Counts from the CPU backend, no device time: "
              "`jax.jit(...).lower()` StableHLO + analytical "
              "FLOPs/bytes/HBM-peak for the EXACT bench train step "
              "(BERT-base seq128 bf16 AMP Adam, fused "
              "linear-softmax-xent head, models/bert.py:176; PRNG = "
              "rbg hardware bit-generator, FLAGS_prng_impl auto-on-TPU "
              "— core/rng.py). Switching dropout keys from threefry to "
              "rbg cut XLA cost-analysis bytes/step 28-31%% (b256: "
              "2603->1884 GB, b512: 9356->6479 GB) on this "
              "bandwidth-bound step.", ""]
    for batch in batches:
        t0 = time.time()
        (cfg, n_params, entry, feeds, smut, sro) = build_step(batch)
        lowered = entry.jitted.lower(
            {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in feeds.items()},
            {k: jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
             for k, v in smut.items()},
            {k: jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
             for k, v in sro.items()},
            np.uint32(0))
        text = lowered.as_text()
        ops, dots = hlo_census(text)
        try:
            cost = lowered.cost_analysis() or {}
        except Exception:
            cost = {}
        ana = analytical(cfg, n_params, batch, remat=batch >= 384)
        gz_path = os.path.join(
            _REPO, "artifacts", "bert_train_b%d.stablehlo.txt.gz" % batch)
        os.makedirs(os.path.dirname(gz_path), exist_ok=True)
        with gzip.open(gz_path, "wt") as f:
            f.write(text)
        gz_mb = os.path.getsize(gz_path) / 1e6

        report += [
            "## batch %d (seq %d, %.1fM params%s)" % (
                batch, SEQ_LEN, n_params / 1e6,
                ", per-layer remat" if batch >= 384 else ""), "",
            "- StableHLO: %d lines, %d distinct op kinds; dot_generals: "
            "%d; artifact: `artifacts/%s` (%.1f MB gz)" % (
                text.count("\n"), len(ops),
                sum(v for k, v in ops.items() if "dot_general" in k),
                os.path.basename(gz_path), gz_mb),
            "- lower+trace time: %.1fs" % (time.time() - t0),
        ]
        if cost:
            flops = cost.get("flops", 0.0)
            bts = cost.get("bytes accessed", 0.0)
            report += [
                "- XLA cost analysis: %.2f TFLOP/step, %.2f GB accessed "
                "(NOTE: with the scan-over-layers encoder XLA counts "
                "the scan BODY once, not x%d iterations — use the "
                "analytical FLOPs below for per-step totals)"
                % (flops / 1e12, bts / 1e9, cfg.num_hidden_layers),
            ]
        report += [
            "- analytical train FLOPs: %.2f TFLOP/step -> ideal %.0fk "
            "tok/s at 100%% MFU; >=45%% MFU target = %.0fk tok/s" % (
                ana["train_flops"] / 1e12, ana["ideal_tok_s"] / 1e3,
                0.45 * ana["ideal_tok_s"] / 1e3),
            "- HBM budget (GB): weights(bf16) %.2f + master+adam %.2f "
            "+ grads %.2f + acts(bf16, ~13/h/layer/token) %.2f = "
            "**%.2f peak** -> %s on 16G v5e" % (
                ana["weights_bf16_gb"], ana["master_adam_gb"],
                ana["grads_gb"], ana["acts_gb"], ana["peak_gb"],
                "FITS" if ana["fits"] else "OOM"),
            "- round-2 UNFUSED head added %.2f GB fp32 logits+softmax "
            "-> %.2f GB (%s) — the fused head (ops/fused_ops.py:258) "
            "removed exactly the buffers that made batch 512 OOM" % (
                ana["head_unfused_gb"], ana["peak_unfused_gb"],
                "fit" if ana["fits_unfused"] else "OOM at batch 512"),
            "",
            "Top-15 StableHLO ops: " + ", ".join(
                "%s x%d" % kv for kv in sorted(
                    ops.items(), key=lambda kv: -kv[1])[:15]),
            "",
        ]
    if resnet_batches:
        report += [
            "## ResNet50 (BASELINE config 2 — never measured on chip in "
            "any round; fallback evidence for the same bench program: "
            "bench.py _bench_resnet, 224x224x1000, momentum + bf16 AMP)",
            ""]
    for batch in resnet_batches:
        t0 = time.time()
        (n_params, act_elems, entry, feeds, smut,
         sro) = build_resnet_step(batch)
        lowered = entry.jitted.lower(
            {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in feeds.items()},
            {k: jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
             for k, v in smut.items()},
            {k: jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
             for k, v in sro.items()},
            np.uint32(0))
        text = lowered.as_text()
        ops, _ = hlo_census(text)
        try:
            cost = lowered.cost_analysis() or {}
        except Exception:
            cost = {}
        ana = analytical_resnet(batch, n_params, act_elems)
        gz_path = os.path.join(
            _REPO, "artifacts",
            "resnet50_train_b%d.stablehlo.txt.gz" % batch)
        os.makedirs(os.path.dirname(gz_path), exist_ok=True)
        with gzip.open(gz_path, "wt") as f:
            f.write(text)
        report += [
            "### batch %d (%.1fM params, %.1fM activation elems/img "
            "from the block's own inferred shapes)" % (
                batch, n_params / 1e6, act_elems / 1e6), "",
            "- StableHLO: %d lines, %d distinct op kinds; convolutions: "
            "%d; artifact: `artifacts/%s` (%.1f MB gz)" % (
                text.count("\n"), len(ops),
                sum(v for k, v in ops.items() if "convolution" in k),
                os.path.basename(gz_path),
                os.path.getsize(gz_path) / 1e6),
            "- lower+trace time: %.1fs" % (time.time() - t0),
        ]
        if cost:
            flops = cost.get("flops", 0.0)
            bts = cost.get("bytes accessed", 0.0)
            report += [
                "- XLA cost analysis: %.2f TFLOP/step, %.2f GB accessed"
                % (flops / 1e12, bts / 1e9)]
        report += [
            "- analytical train FLOPs (3x %.1f GFLOP fwd/img): %.2f "
            "TFLOP/step -> ideal %.0f img/s at 100%% MFU; BASELINE "
            "target 720 img/s = %.0f%% MFU" % (
                RESNET50_FWD_FLOPS_PER_IMG / 1e9,
                ana["train_flops"] / 1e12, ana["ideal_img_s"],
                100.0 * 720.0 / ana["ideal_img_s"]),
            "- HBM budget (GB): weights(bf16) %.2f + master+momentum "
            "%.2f + grads %.2f + acts(bf16, every intermediate = upper "
            "bound; XLA buffer reuse lowers the true peak) %.2f = "
            "**%.2f worst-case** -> %s on 16G v5e" % (
                ana["weights_bf16_gb"], ana["master_mom_gb"],
                ana["grads_gb"], ana["acts_gb"], ana["peak_gb"],
                "FITS" if ana["fits"] else
                "may OOM (the bench's on-chip fill pass therefore "
                "runs batch 128)"),
            "",
            "Top-10 StableHLO ops: " + ", ".join(
                "%s x%d" % kv for kv in sorted(
                    ops.items(), key=lambda kv: -kv[1])[:10]),
            "",
        ]

    out = os.path.join(_REPO, "artifacts", "bert_step_census.md")
    with open(out, "w") as f:
        f.write("\n".join(report) + "\n")
    print("wrote", out)


if __name__ == "__main__":
    main()
