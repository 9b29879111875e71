"""tpu-lint CLI: the static SPMD verifier (paddle_tpu/analysis) over
the repo's exemplar programs — a standing lint-regression harness that
turns "hangs 40 minutes into a chip run" into "fails in CI in 4
seconds".

Exemplars (each is a program the bench / tier-1 suite actually runs):

- ``bert_tiny``     — the data-parallel BERT-tiny Adam train step
                      (with the ZeRO-1 shard plan attached, so the
                      zero1-invariants checker has a plan to verify);
- ``bert_tiny_amp`` — the SAME model under bf16 AMP with ZeRO-sharded
                      fp32 master weights and bucketed (ZeRO-2) grad
                      collectives — the zero2-lifetimes leg plus the
                      AMP-aware dtype-contract checks, zero errors
                      required;
- ``bert_tiny_tp``  — the SAME AMP+ZeRO model 2-way TENSOR-PARALLEL
                      on a (dcn, ici, model) mesh: the one planner
                      assigns every axis (params over `model`, ZeRO
                      state + masters over the replica axis), and the
                      model-sharded zero1-invariants leg proves no
                      unguarded norm/optimizer/collective reads a TP
                      shard as if it were the full tensor;
- ``resnet_scan``   — ResNet50 with scan_stages (deep control-flow
                      nesting: host-sync + contract checkers descend
                      through the scan sub-blocks);
- ``embedding_ctr`` — the wide&deep CTR train step with every slot
                      table vocab-sharded by the sparse-embedding
                      engine (paddle_tpu/embedding): sparse-update
                      row-layout/exclusive-touch invariants, the
                      zero1 sparse-op skip, and `sparse_lookup`
                      divergence records;
- ``serving_decode``— the serving engine's greedy decode loop as a
                      scan (paddle_tpu/serving): the host-sync checker
                      proves NO per-token fetch/RPC/dynamic-shape op
                      in the body — the IR-level half of the serving
                      hot-loop contract;
- ``serving_decode_sampled`` — the SAME decode loop under SAMPLED
                      decoding (temperature scale -> softmax -> top-p
                      nucleus filter -> on-device ``sampling_id``):
                      the RNG key is threaded by the lowering from
                      ``program.random_seed`` + op index, so the
                      sampled path stays as device-resident as the
                      greedy one — zero host-sync errors required;
- ``fleet_ps_2rank``— the SAME model transpiled for 2 sync-PS
                      trainers; both rank programs are linted AND
                      cross-compared by the collective-divergence
                      checker.

Usage:
    python tools/tpu_lint.py [--fail-on {warning,error}] [--json]
                             [--out PATH] [--exemplar NAME[,NAME...]]
    python tools/tpu_lint.py --protocol [--protocol-budget N]
                             [--protocol-model NAME[,NAME...]]
                             [--fail-on {warning,error}] [--json]
                             [--out PATH]

Writes ``artifacts/static_checks.json`` (or --out) always; exits
nonzero when findings at/above --fail-on severity exist (default:
error). ``tools/perf_analysis.py --lint`` is a thin alias onto this
entry point so one tool drives all audits.

``--protocol`` switches from the IR exemplars to the PROTOCOL tier:
the explicit-state interleaving checker (analysis/protocol.py) drives
the real host-protocol implementations — RPC envelope retry/dedupe,
PS exactly-once apply across kill/restart, the elastic preemption
seam, serving drain->adopt and the paged-KV page ledger — through
every reachable interleaving up to ``--protocol-budget`` schedules
per model (default 1000) and reports invariant violations / deadlocks
as findings with replayable traces. Writes
``artifacts/protocol_checks.json`` (or --out).
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    # the DP exemplar needs a multi-device mesh; set pre-jax-import
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_"
                               "count=8").strip()
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

NDEV = 8


def _fresh():
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.fluid import framework

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    scope_mod._global_scope = scope_mod.Scope()


def build_bert_tiny():
    """Data-parallel BERT-tiny Adam step + ZeRO-1 shard plan."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework
    from paddle_tpu.models import bert
    from paddle_tpu.parallel import sharded_update as su

    _fresh()
    with framework.unique_name_guard():
        cfg = bert.BertConfig.tiny()
        framework.default_main_program().random_seed = 7
        total, _, _, _ = bert.bert_pretrain_loss(cfg, 32, is_test=False)
        fluid.optimizer.AdamOptimizer(
            learning_rate=1e-3).minimize(total)
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=total.name)
        prog._shard_plan = su.plan_sharded_update(
            prog, prog.global_block(), NDEV, "dp")
    return prog, None


def build_bert_tiny_amp():
    """BERT-tiny with bf16 AMP + ZeRO-sharded fp32 master weights +
    bucketed (ZeRO-2) gradient collectives: live params bf16, every
    optimizer op updates a ``@MASTER`` shard, grads bucket under a
    0.25 MB cap — the mixed-precision plan the zero1-invariants,
    zero2-lifetimes and (AMP-aware) dtype-contract checkers verify.
    Zero errors required."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework
    from paddle_tpu.fluid.contrib import mixed_precision
    from paddle_tpu.models import bert
    from paddle_tpu.parallel import sharded_update as su
    from paddle_tpu.utils.flags import get_flag, set_flags

    _fresh()
    with framework.unique_name_guard():
        cfg = bert.BertConfig.tiny()
        framework.default_main_program().random_seed = 7
        total, _, _, _ = bert.bert_pretrain_loss(cfg, 32, is_test=False)
        opt = mixed_precision.decorate(
            fluid.optimizer.AdamOptimizer(learning_rate=1e-3))
        opt.minimize(total)
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=total.name)
        old = get_flag("FLAGS_tpu_comm_bucket_mb")
        try:
            set_flags({"FLAGS_tpu_comm_bucket_mb": 0.25})
            prog._shard_plan = su.plan_sharded_update(
                prog, prog.global_block(), NDEV, "dp")
        finally:
            set_flags({"FLAGS_tpu_comm_bucket_mb": old})
        plan = prog._shard_plan
        assert plan is not None and plan.master_of and plan.buckets, \
            "AMP+ZeRO-2 exemplar failed to plan (fallback: %s)" % (
                getattr(prog, "_sharded_update_fallback", None),)
    return prog, None


def build_bert_tiny_tp():
    """BERT-tiny under bf16 AMP + ZeRO with 2-way TENSOR PARALLELISM
    on the (dcn, ici, model) mesh: `parallel.planner.plan_parallel`
    owns every axis — weight out-dims / vocab rows shard over `model`
    (via the logical-axis rules), fp32 masters + moments + buckets
    over the replica (ici) axis at TP-LOCAL shapes. The model-sharded
    zero1-invariants leg then proves no norm reader, fused optimizer
    or raw collective consumes a TP shard as the full tensor. Zero
    errors required."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework
    from paddle_tpu.fluid.contrib import mixed_precision
    from paddle_tpu.models import bert
    from paddle_tpu.parallel import env as penv
    from paddle_tpu.parallel import planner
    from paddle_tpu.utils.flags import get_flag, set_flags

    _fresh()
    with framework.unique_name_guard():
        cfg = bert.BertConfig.tiny()
        framework.default_main_program().random_seed = 7
        total, _, _, _ = bert.bert_pretrain_loss(cfg, 32, is_test=False)
        opt = mixed_precision.decorate(
            fluid.optimizer.AdamOptimizer(learning_rate=1e-3))
        opt.minimize(total)
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=total.name)
        old = {k: get_flag(k) for k in ("FLAGS_tpu_comm_bucket_mb",
                                        "FLAGS_tpu_model_parallel")}
        try:
            set_flags({"FLAGS_tpu_comm_bucket_mb": 0.25,
                       "FLAGS_tpu_model_parallel": 2})
            mesh = penv.create_hybrid_mesh(nranks=NDEV)
            pplan = planner.plan_parallel(
                prog, prog.global_block(), mesh, penv.ICI_AXIS)
        finally:
            set_flags(old)
        prog._mesh = mesh
        prog._sparse_plan = pplan.sparse_plan
        prog._tp_plan = pplan.tp_plan
        prog._model_axis = pplan.tp_plan.model_axis \
            if pplan.tp_plan is not None else None
        prog._shard_plan = pplan.shard_plan
        assert pplan.tp_plan is not None and pplan.tp_plan.params, \
            "TP exemplar failed to plan the model axis (trail: %s)" % (
                getattr(prog, "_sharded_update_fallback", None),)
        plan = pplan.shard_plan
        assert plan is not None and plan.master_of and plan.buckets, \
            "AMP+ZeRO exemplar failed to plan under TP (fallback: %s)" \
            % (getattr(prog, "_sharded_update_fallback", None),)
    return prog, None


def build_resnet_scan():
    """ResNet50 momentum step with scan_stages (32x32, 10 classes —
    the IR is what the checkers walk; image size only scales FLOPs)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework
    from paddle_tpu.models import resnet as resnet_mod

    _fresh()
    with framework.unique_name_guard():
        img = fluid.layers.data("image", shape=[3, 32, 32],
                                dtype="float32")
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        logits = resnet_mod.resnet(img, class_dim=10, depth=50,
                                   is_test=False, scan_stages=True)
        loss = fluid.layers.mean(
            fluid.layers.loss.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.MomentumOptimizer(
            0.1, momentum=0.9).minimize(loss)
        prog = fluid.default_main_program()
    return prog, None


def build_mlp_hier():
    """Data-parallel MLP Adam step on an emulated 2x2 hybrid
    (dcn, ici) CPU mesh with bucketed HIERARCHICAL collectives
    (FLAGS_tpu_dcn_replicas): the IR checkers verify the dcn-aware
    shard plan, and lint_exemplars adds the HLO-level two-level
    replica_groups audit (analysis.check_hierarchical_groups) over the
    actually-lowered module — zero errors is the standing claim for
    the hierarchical exemplar."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework
    from paddle_tpu.utils.flags import get_flag, set_flags

    _fresh()
    with framework.unique_name_guard():
        framework.default_main_program().random_seed = 7
        framework.default_startup_program().random_seed = 7
        img = fluid.layers.data(name="img", shape=[16],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1],
                                  dtype="int64")
        h = fluid.layers.fc(input=img, size=15, act="relu")
        logits = fluid.layers.fc(input=h, size=4)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.AdamOptimizer(1e-3).minimize(loss)
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name)
        import jax
        from jax.sharding import Mesh

        prog._mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                          ("dcn", "ici"))
        old = get_flag("FLAGS_tpu_comm_bucket_mb")
        try:
            set_flags({"FLAGS_tpu_comm_bucket_mb": 0.001})
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            r = np.random.RandomState(0)
            feed = {"img": r.rand(16, 16).astype("float32"),
                    "label": r.randint(0, 4, (16, 1)).astype("int64")}
            exe.run(prog, feed=feed, fetch_list=[loss])
            got = exe._cached_lowerable(prog, feed, [loss], None)
        finally:
            set_flags({"FLAGS_tpu_comm_bucket_mb": old})
        assert getattr(prog, "_shard_plan", None) is not None \
            and prog._shard_plan.dcn_axis is not None, \
            "hierarchical exemplar failed to plan (fallback: %s)" % (
                getattr(prog, "_sharded_update_fallback", None),)
        # stash the lowered module for the HLO-level hierarchy audit
        prog._lint_hlo = got[1].as_text() if got is not None else None
        prog._lint_ici_size = 2
    return prog, None


def build_serving_decode():
    """The serving engine's per-token decode loop expressed in Program
    IR: a greedy decode scan (hidden-state recurrence -> logits ->
    on-device argmax, token and state carried as loop state) with NO
    fetch / host RPC / dynamic-shape op in the body — the PR 5
    host-sync-in-hot-loop checker proves the loop never syncs per
    token. Zero errors is the standing claim (the deliberate-defect
    twin — a fetch seeded INTO the scan body — lives in
    tests/test_serving.py and must fire checker 3)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework

    HID, VOCAB, STEPS = 16, 32, 8
    _fresh()
    with framework.unique_name_guard():
        h0 = fluid.layers.data(name="h0", shape=[HID],
                               dtype="float32")
        w = fluid.layers.create_parameter(
            shape=[HID, HID], dtype="float32", name="dec.w")
        emb = fluid.layers.create_parameter(
            shape=[HID, VOCAB], dtype="float32", name="dec.emb")
        h = fluid.layers.fc(input=h0, size=HID)
        scan = fluid.layers.Scan(n=STEPS)
        with scan.block():
            nh = fluid.layers.tanh(fluid.layers.matmul(h, w))
            logits = fluid.layers.matmul(nh, emb)
            # greedy sampling stays ON DEVICE: the token feeds nothing
            # host-side inside the loop
            fluid.layers.argmax(logits, axis=1)
            fluid.layers.assign(nh, output=h)
        fluid.layers.matmul(h, emb)
        prog = fluid.default_main_program()
    return prog, None


def build_serving_decode_sampled():
    """The serving engine's SAMPLED decode loop (temperature + top-p)
    as a scan: temperature scale -> softmax -> top-p nucleus filter
    (sort descending, cumulative mass, where-mask) -> on-device
    ``sampling_id``. ``sampling_id`` is a needs_rng op — the lowering
    threads a jax PRNG key folded from ``program.random_seed`` and the
    op's position, so sampling needs NO per-token host round-trip and
    the host-sync checker must find the body exactly as clean as the
    greedy exemplar's. Zero errors is the standing claim."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework

    HID, VOCAB, STEPS = 16, 32, 8
    TEMPERATURE, TOP_P = 0.8, 0.9
    _fresh()
    with framework.unique_name_guard():
        framework.default_main_program().random_seed = 11
        h0 = fluid.layers.data(name="h0", shape=[HID],
                               dtype="float32")
        w = fluid.layers.create_parameter(
            shape=[HID, HID], dtype="float32", name="sdec.w")
        emb = fluid.layers.create_parameter(
            shape=[HID, VOCAB], dtype="float32", name="sdec.emb")
        h = fluid.layers.fc(input=h0, size=HID)
        scan = fluid.layers.Scan(n=STEPS)
        with scan.block():
            nh = fluid.layers.tanh(fluid.layers.matmul(h, w))
            logits = fluid.layers.matmul(nh, emb)
            probs = fluid.layers.softmax(
                fluid.layers.scale(logits, scale=1.0 / TEMPERATURE))
            # top-p nucleus filter, all on device: sort descending,
            # exclusive cumulative mass, zero out the tail past TOP_P
            sorted_probs, _order = fluid.layers.argsort(
                probs, axis=-1, descending=True)
            cum = fluid.layers.cumsum(sorted_probs, axis=-1,
                                      exclusive=True)
            keep = fluid.layers.less_than(
                cum, fluid.layers.scale(fluid.layers.ones_like(cum),
                                        scale=TOP_P))
            filtered = fluid.layers.where(
                keep, sorted_probs,
                fluid.layers.zeros_like(sorted_probs))
            # categorical draw over the nucleus (the lowering
            # re-normalizes via log + categorical); the sampled rank
            # stays on device, state carries through `h`
            fluid.layers.sampling_id(filtered)
            fluid.layers.assign(nh, output=h)
        fluid.layers.matmul(h, emb)
        prog = fluid.default_main_program()
    return prog, None


def build_embedding_ctr():
    """Data-parallel wide&deep CTR train step with every slot table
    vocab-sharded by the sparse-embedding engine
    (paddle_tpu/embedding): the sparse-update checker verifies the
    row layouts + exclusive-touch invariants, the zero1 checker skips
    the engine-owned optimizer ops, and the divergence vocabulary
    records one `sparse_lookup` per planned site. Zero errors is the
    standing claim (the deliberate-defect twins live in
    tests/test_tpu_lint.py)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.embedding import plan_sparse_tables
    from paddle_tpu.fluid import framework
    from paddle_tpu.models import ctr

    _fresh()
    with framework.unique_name_guard():
        framework.default_main_program().random_seed = 7
        cfg = ctr.CTRConfig()
        loss, _, feeds = ctr.build_ctr_train(cfg)
        prog = fluid.default_main_program()
        fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name)
        prog._sparse_plan = plan_sparse_tables(
            prog, prog.global_block(), NDEV, "dp", feed_names=feeds)
        assert prog._sparse_plan is not None and \
            len(prog._sparse_plan.tables) == 2 * len(cfg.vocab_sizes), \
            "embedding_ctr exemplar failed to plan (fallback: %s)" % (
                getattr(prog, "_sparse_embedding_fallback", None),)
    return prog, None


def build_fleet_ps_2rank():
    """One MLP classifier transpiled for 2 sync-PS trainers: returns
    (rank-0 program, [rank-1 program]) for the cross-rank pass."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework

    def one(tid):
        _fresh()
        with framework.unique_name_guard():
            img = fluid.layers.data(name="img", shape=[8],
                                    dtype="float32")
            label = fluid.layers.data(name="label", shape=[1],
                                      dtype="int64")
            h = fluid.layers.fc(input=img, size=8, act="relu")
            logits = fluid.layers.fc(input=h, size=4)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, label))
            fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
            t = fluid.DistributeTranspiler()
            t.transpile(tid,
                        pservers="127.0.0.1:6174,127.0.0.1:6175",
                        trainers=2, sync_mode=True)
            return t.get_trainer_program()

    return one(0), [one(1)]


EXEMPLARS = {
    "bert_tiny": build_bert_tiny,
    "bert_tiny_amp": build_bert_tiny_amp,
    "bert_tiny_tp": build_bert_tiny_tp,
    "mlp_hier": build_mlp_hier,
    "embedding_ctr": build_embedding_ctr,
    "resnet_scan": build_resnet_scan,
    "serving_decode": build_serving_decode,
    "serving_decode_sampled": build_serving_decode_sampled,
    "fleet_ps_2rank": build_fleet_ps_2rank,
}


def lint_exemplars(names=None):
    """Run all checkers over the named exemplars. Returns
    {name: (findings, summary)} in build order."""
    from paddle_tpu import analysis

    out = {}
    for name in (names or list(EXEMPLARS)):
        prog, rank_programs = EXEMPLARS[name]()
        labels = None
        if rank_programs:
            labels = ["%s/rank%d" % (name, i)
                      for i in range(1 + len(rank_programs))]
        findings = analysis.run_static_checks(
            prog, rank_programs=rank_programs, rank_labels=labels)
        if getattr(prog, "_lint_hlo", None):
            # hybrid-mesh exemplars: the HLO-level two-level
            # replica_groups audit over the lowered module
            findings = analysis.sort_findings(
                findings + analysis.check_hierarchical_groups(
                    prog._lint_hlo, prog._lint_ici_size, label=name))
        out[name] = (findings, analysis.summarize(findings))
    return out


def _main_protocol(fail_on, as_json, out_path, budget, models):
    """The --protocol leg: run the explicit-state interleaving checker
    over the registered host-protocol models and report violations /
    deadlocks as findings with replayable traces."""
    from paddle_tpu import analysis

    try:
        findings, report = analysis.run_protocol_checks(
            budget=budget, models=models)
    except ValueError as e:  # unknown --protocol-model: usage error
        raise SystemExit(str(e))
    summary = analysis.summarize(findings)
    report["fail_on"] = fail_on
    report["total_errors"] = summary["errors"]
    report["total_warnings"] = summary["warnings"]
    report["ok"] = not (summary["errors"] or
                        (fail_on == "warning" and summary["warnings"]))
    report["findings"] = [f.to_dict() for f in findings]
    if out_path is None:
        out_path = os.path.join(_REPO, "artifacts",
                                "protocol_checks.json")
    os.makedirs(os.path.dirname(os.path.abspath(out_path)),
                exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)

    if as_json:
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        for name, m in report["models"].items():
            print("== %s: %d schedule(s), %d state(s), %d error(s)%s"
                  % (name, m["schedules"], m["states"], m["errors"],
                     " [truncated]" if m["truncated"] else ""))
        for fnd in findings:
            print("   " + analysis.format_finding(fnd))
        print("tpu-lint --protocol: %d model(s), %d error(s), "
              "%d warning(s); %s; wrote %s"
              % (len(report["models"]), summary["errors"],
                 summary["warnings"],
                 "OK" if report["ok"] else "FAIL (--fail-on %s)"
                 % fail_on, out_path))
    return 0 if report["ok"] else 1


def main(argv=None):
    from paddle_tpu import analysis

    argv = list(sys.argv[1:] if argv is None else argv)
    fail_on = "error"
    as_json = "--json" in argv
    protocol = "--protocol" in argv
    proto_budget = 1000
    proto_models = None
    out_path = None
    names = None

    def value_of(flag, a, i):
        """The value of `--flag=v` / `--flag v`, or None when `a` is a
        different flag; a missing value is a usage error, not a crash."""
        if a == flag:
            if i + 1 >= len(argv):
                raise SystemExit("%s needs a value\nUsage:%s"
                                 % (flag, __doc__.split("Usage:")[1]))
            return argv[i + 1], i + 1
        if a.startswith(flag + "="):
            return a.split("=", 1)[1], i
        return None, i

    i = 0
    while i < len(argv):
        a = argv[i]
        fail_val, i = value_of("--fail-on", a, i)
        out_val, i = value_of("--out", a, i)
        ex_val, i = value_of("--exemplar", a, i)
        budget_val, i = value_of("--protocol-budget", a, i)
        model_val, i = value_of("--protocol-model", a, i)
        if fail_val is not None:
            if fail_val not in ("warning", "error"):
                raise SystemExit(
                    "--fail-on takes 'warning' or 'error', got %r"
                    % (fail_val,))
            fail_on = fail_val
        elif out_val is not None:
            out_path = out_val
        elif ex_val is not None:
            names = [n for n in ex_val.split(",") if n]
            unknown = set(names) - set(EXEMPLARS)
            if unknown:
                raise SystemExit("unknown exemplar(s) %s; have %s"
                                 % (sorted(unknown), list(EXEMPLARS)))
        elif budget_val is not None:
            try:
                proto_budget = int(budget_val)
            except ValueError:
                raise SystemExit("--protocol-budget takes an integer, "
                                 "got %r" % (budget_val,))
        elif model_val is not None:
            proto_models = [n for n in model_val.split(",") if n]
        elif a not in ("--json", "--protocol"):
            raise SystemExit(__doc__.split("Usage:")[1])
        i += 1

    if protocol:
        return _main_protocol(fail_on, as_json, out_path,
                              proto_budget, proto_models)

    if out_path is None:
        out_path = os.path.join(_REPO, "artifacts",
                                "static_checks.json")
    results = lint_exemplars(names)
    total_err = sum(s["errors"] for _, s in results.values())
    total_warn = sum(s["warnings"] for _, s in results.values())
    report = {
        "fail_on": fail_on,
        "checkers": list(analysis.CHECKERS),
        "total_errors": total_err,
        "total_warnings": total_warn,
        "ok": not (total_err or
                   (fail_on == "warning" and total_warn)),
        "programs": {name: s for name, (_, s) in results.items()},
    }
    os.makedirs(os.path.dirname(os.path.abspath(out_path)),
                exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)

    if as_json:
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        for name, (findings, s) in results.items():
            print("== %s: %d error(s), %d warning(s)"
                  % (name, s["errors"], s["warnings"]))
            for fnd in findings:
                print("   " + analysis.format_finding(fnd))
        print("tpu-lint: %d program(s), %d error(s), %d warning(s); "
              "%s; wrote %s"
              % (len(results), total_err, total_warn,
                 "OK" if report["ok"] else "FAIL (--fail-on %s)"
                 % fail_on, out_path))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
