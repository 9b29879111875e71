"""Block lowering: a Program block -> ONE jitted XLA computation.

This replaces the reference's entire execution stack — the op-by-op C++
Executor loop (`framework/executor.cc:471`), kernel dispatch
(`operator.cc:908-1030`), data transforms, memory-reuse passes
(`ir/memory_optimize_pass/`), fusion passes (`ir/*fuse*`), and the SSA
multi-device executors (`details/fast_threaded_ssa_graph_executor.cc`).
TPU-first: trace the op list once into a single jax function, let XLA fuse
and schedule it, cache the compiled executable keyed by
(program version, feed shapes); data-parallel programs wrap the same
function in `jax.shard_map` over a Mesh so collective ops emit ICI
collectives (SURVEY.md §3B "the whole SSA machinery collapses into XLA SPMD
partitioning").

Autodiff: `append_backward` plants a single `backward` pseudo-op; lowering
runs the forward segment under `jax.vjp` and binds each requested `X@GRAD`
(replacing per-op GradOpMakers, `grad_op_desc_maker.h`).

Mutable Scope semantics vs XLA purity (SURVEY.md §7 hard part (c)): the
lowered function is pure — scope-resident state (params, optimizer moments,
BN running stats) enters as inputs and leaves as outputs; variable rebinding
inside the block is SSA-ified by the name->value environment.
"""
from __future__ import annotations

import contextlib
from typing import Dict, List

import numpy as np

from . import framework
from .. import ops as ops_lib
from ..core.rng import make_key
from ..core.types import to_numpy_dtype

# Ops that exist only for runtime bookkeeping in the reference; under XLA
# they are no-ops (stream sync is dataflow; comm init is mesh construction).
_SKIP_OPS = frozenset({
    "feed", "fetch", "c_gen_nccl_id", "gen_nccl_id", "c_comm_init",
    "c_comm_init_all", "c_wait_compute", "c_wait_comm", "barrier",
    "nop",
    # PS-mode markers: the host-side PSCommunicator performs the actual
    # RPC around each jitted step (distributed/ps.py)
    "send", "recv", "send_barrier", "fetch_barrier", "checkpoint_notify",
})


class LoweredFunction:
    """A compiled block: callable (feeds, states_mut, states_ro, seed) ->
    (fetches, states'). states_mut (rebound by the block: params, moments,
    running stats) are donated so XLA updates them in place on HBM;
    feed_donate records whether the feed argument is donated too
    (FLAGS_tpu_donate_feed_buffers) — the executor then guards
    caller-owned device arrays before the call."""

    __slots__ = ("jitted", "state_in_names", "state_out_names",
                 "state_mut_names", "state_ro_names",
                 "fetch_names", "feed_names", "mesh", "dp_axis",
                 "auto_plan", "feed_donate", "sharded_state",
                 "sparse_tables", "aot_compiled", "cc_fingerprint",
                 "cc_prev")

    def __init__(self, jitted, feed_names, state_in_names, state_out_names,
                 state_mut_names, state_ro_names, fetch_names, mesh=None,
                 dp_axis=None, auto_plan=None, feed_donate=False,
                 sharded_state=None, sparse_tables=None):
        self.jitted = jitted
        self.feed_names = feed_names
        self.state_in_names = state_in_names
        self.state_out_names = state_out_names
        self.state_mut_names = state_mut_names
        self.state_ro_names = state_ro_names
        self.fetch_names = fetch_names
        self.mesh = mesh
        self.dp_axis = dp_axis
        self.auto_plan = auto_plan
        self.feed_donate = feed_donate
        # {name: parallel.sharded_update.ShardInfo} when the compiled
        # step keeps optimizer state sharded over the dp axis (ZeRO-1);
        # the executor lays those scope arrays out as flat 1/N buffers
        self.sharded_state = sharded_state
        # {name: embedding.RowShardInfo} when the step keeps embedding
        # tables (+ per-row moments) vocab-sharded over the dp axis;
        # the executor lays those scope arrays out as row-sharded
        # (padded_rows, dim) buffers (paddle_tpu/embedding)
        self.sparse_tables = sparse_tables
        # memoized AOT-compiled artifact for the report surfaces
        # (donation_report / overlap_report) — one XLA compile serves
        # every audit of this executable instead of one per call
        self.aot_compiled = None
        # persistent compile-cache classification (fluid/compile_cache,
        # JAX_COMPILATION_CACHE_DIR): the program fingerprint and the
        # prior compile's index sentinel (None = first-ever compile)
        self.cc_fingerprint = None
        self.cc_prev = None


def _sub_block_idxs(op):
    idxs = []
    for a in ("sub_block", "sub_block_t", "sub_block_f"):
        if a in op.attrs:
            idxs.append(op.attrs[a])
    idxs.extend(op.attrs.get("sub_blocks", []))
    return idxs


def _op_reads_writes(op):
    """(reads, writes) of an op, looking through control-flow sub-blocks
    (a var read only inside a while body is still block-level state).
    Sub-block writes to persistable vars also count as reads: the carry
    needs their incoming value so the functional loop can thread them."""
    reads = list(op.input_arg_names)
    writes = list(op.output_arg_names)
    prog = op.block.program
    for bi in _sub_block_idxs(op):
        blk = prog.block(bi)
        # scan xs slices (and the iteration-index var) are produced by
        # the loop machinery itself, not by any sub-block op — they are
        # never external reads
        produced_local = set(op.attrs.get("xs_slice", []))
        if op.attrs.get("iter_var"):
            produced_local.add(op.attrs["iter_var"])
        for sop in blk.ops:
            sr, sw = _op_reads_writes(sop)
            for n in sr:
                if n not in produced_local:
                    reads.append(n)
            for n in sw:
                v = blk._find_var_recursive(n)
                if v is not None and v.persistable \
                        and n not in produced_local:
                    reads.append(n)
                produced_local.add(n)
                writes.append(n)
    return reads, writes


def analyze_block(block, feed_names, fetch_names):
    """Dataflow analysis: which names are scope state in/out."""
    produced = set(feed_names)
    state_in: List[str] = []
    state_in_set = set()
    for op in block.ops:
        op_reads, op_writes = _op_reads_writes(op)
        for name in op_reads:
            if name not in produced and name not in state_in_set:
                state_in.append(name)
                state_in_set.add(name)
        for name in op_writes:
            produced.add(name)
    for name in fetch_names:
        if name not in produced and name not in state_in_set:
            state_in.append(name)
            state_in_set.add(name)

    # state outputs: names written by ops that are persistable vars or
    # rebind scope-resident inputs (param updates, running stats, ...)
    state_out: List[str] = []
    seen = set()
    for op in block.ops:
        for name in _op_reads_writes(op)[1]:
            if name in seen:
                continue
            persistable = False
            v = block._find_var_recursive(name)
            if v is not None and v.persistable:
                persistable = True
            if persistable or name in state_in_set:
                seen.add(name)
                state_out.append(name)
    return state_in, state_out


def _prov_scope(op, op_idx):
    """Provenance stamp of one traced op (FLAGS_tpu_op_provenance; see
    observability/attribution.py): a jax.named_scope whose marker rides
    the name stack into the StableHLO debug locations AND the optimized
    HLO's op_name metadata — zero runtime cost, one context manager per
    op at trace time. Control-flow sub-block ops nest inside their
    parent op's scope; the innermost marker is the true source."""
    from ..observability import attribution as _attr

    return _attr.op_scope(op, op_idx)


def _exec_op(op, env, key0, op_idx, amp_lists=None):
    t = op.type
    if t in _SKIP_OPS:
        return
    with _prov_scope(op, op_idx):
        return _exec_op_stamped(op, env, key0, op_idx,
                                amp_lists=amp_lists)


def _exec_op_stamped(op, env, key0, op_idx, amp_lists=None):
    import jax
    import jax.numpy as jnp

    t = op.type
    if t == "while":
        return _exec_while(op, env, key0, op_idx, amp_lists)
    if t == "scan":
        return _exec_scan(op, env, key0, op_idx, amp_lists)
    if t == "cond":
        return _exec_cond(op, env, key0, op_idx, amp_lists)
    if t == "switch_case":
        return _exec_switch_case(op, env, key0, op_idx, amp_lists)
    if t == "conditional_block":
        return _exec_conditional_block(op, env, key0, op_idx, amp_lists)
    # vocab-sharded embedding engine (paddle_tpu/embedding): under an
    # active sparse plan, lookup ops over TableShards and the sparse
    # optimizer ops route to the engine's trace rules; any OTHER op
    # touching an engine value fails loudly (no-op when no plan is
    # active — a single contextvar read)
    from ..embedding import engine as _emb_engine

    if _emb_engine.maybe_exec(op, env):
        return
    opdef = ops_lib.get_op(t)
    ins = {}
    for slot, names in op.input_names.items():
        if not names:
            continue
        try:
            ins[slot] = [env[n] for n in names]
        except KeyError as e:
            from ..core.errors import NotFoundError, attach_op_callstack

            attach_op_callstack(NotFoundError(
                "op %s: input var %s not materialized (feed it or run "
                "the startup program)" % (t, e)), op)
    # AMP policy (reference: fp16_utils.py cast insertion; here the
    # casts are applied at trace time and fused by XLA)
    if amp_lists is not None:
        ins = _apply_amp_casts(t, op, ins, amp_lists)
    attrs = dict(op.attrs)
    if opdef.needs_rng:
        attrs["_rng_key"] = jax.random.fold_in(key0, op_idx)
    try:
        # tensor parallelism (parallel/tensor_parallel.py): under an
        # active TP plan, an op consuming a model-sharded weight lowers
        # to the local partial compute + its model-axis collective —
        # same contextvar routing as the sparse engine above
        from ..parallel import tensor_parallel as _tp_engine

        tp_outs = _tp_engine.maybe_compute(op, ins, attrs)
        if tp_outs is not None:
            outs = ops_lib.normalize_outs(tp_outs)
        elif opdef.no_jit and any(
                isinstance(v, jax.core.Tracer)
                for vs in ins.values() for v in vs):
            outs = _host_callback_op(opdef, op, ins, attrs)
        else:
            outs = ops_lib.normalize_outs(opdef.compute(ins, attrs))
    except Exception as e:  # attach the op's python creation site
        from ..core.errors import attach_op_callstack

        attach_op_callstack(e, op)
    for slot, names in op.output_names.items():
        vals = outs.get(slot, [])
        for n, v in zip(names, vals):
            env[n] = v


class _AmpTracePolicy:
    """The AMP lowering 'pass', trace-time form: per-op white/black-list
    casts at list boundaries (white-list matmul/conv inputs drop to the
    16-bit compute dtype for the MXU; black-list softmax/norm/reduce
    inputs lift back to fp32), applied as the block traces so XLA fuses
    every inserted convert. Parameterized by `program._amp_dtype`
    (bf16 default, fp16 with loss scaling) and honoring the lists'
    `black_varnames` (vars pinned to fp32 by name). Gray-list ops
    follow their inputs — no casts — exactly the reference policy."""

    __slots__ = ("lists", "low")

    def __init__(self, lists, dtype_name):
        import jax.numpy as jnp

        self.lists = lists
        self.low = jnp.float16 if str(dtype_name) == "float16" \
            else jnp.bfloat16

    # duck-type the raw AutoMixedPrecisionLists surface for callers
    # that inspect the policy (analysis/contracts.py, tests)
    @property
    def white_list(self):
        return self.lists.white_list

    @property
    def black_list(self):
        return self.lists.black_list


def _amp_trace_policy(program):
    """program -> _AmpTracePolicy (or None when AMP is off)."""
    if not getattr(program, "_amp", False):
        return None
    lists = getattr(program, "_amp_lists", None)
    if lists is None:
        return None
    return _AmpTracePolicy(lists,
                           getattr(program, "_amp_dtype", "bfloat16"))


def _apply_amp_casts(t, op, ins, amp):
    """Insert the list-boundary casts for one op's inputs (see
    _AmpTracePolicy). `amp` may be an _AmpTracePolicy or a raw
    AutoMixedPrecisionLists (legacy callers: bf16, no black vars)."""
    import jax.numpy as jnp

    lists = amp.lists if isinstance(amp, _AmpTracePolicy) else amp
    low = amp.low if isinstance(amp, _AmpTracePolicy) else jnp.bfloat16
    black_vars = getattr(lists, "black_varnames", None) or ()

    def cast_ins(src, dst):
        out = {}
        for s, vs in ins.items():
            names = op.input_names.get(s, [])
            out[s] = [
                v.astype(dst)
                if hasattr(v, "dtype") and v.dtype == src
                and (i >= len(names) or names[i] not in black_vars)
                else v
                for i, v in enumerate(vs)]
        return out

    if t in lists.white_list:
        return cast_ins(jnp.float32, low)
    if t in lists.black_list:
        return cast_ins(low, jnp.float32)
    return ins


def _host_callback_op(opdef, op, ins, attrs):
    """Lower a host-side (`no_jit`) op inside a jitted block via
    jax.pure_callback. Reference parity: CPU-only kernels (e.g.
    bipartite_match_op.cc) run on host mid-graph with device transfers
    inserted by PrepareData (operator.cc:1120); pure_callback is the XLA
    equivalent. Output shapes are probed by running the op once at trace
    time on zero-filled inputs — ops whose OUTPUT SHAPE depends on input
    values (multiclass_nms-style) cannot run under jit, same as any XLA
    program, and keep working eagerly. No gradient flows through the
    callback (host ops produce matches/indices, not differentiable
    values)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    slot_order = sorted(ins)
    flat = [v for s in slot_order for v in ins[s]]
    layout = [(s, len(ins[s])) for s in slot_order]

    def rebuild(flat_vals):
        d, i = {}, 0
        for s, n in layout:
            d[s] = list(flat_vals[i:i + n])
            i += n
        return d

    if opdef.infer_shape is not None:
        # side-effecting host ops (print, assert) declare their output
        # shapes so the zero-filled probe below — which would EXECUTE
        # the side effect at trace time — is never run for them
        spec_in = {s: [(tuple(v.shape), str(np.dtype(v.dtype)))
                       for v in vs] for s, vs in ins.items()}
        inferred = opdef.infer_shape(spec_in, dict(attrs))
        out_slots = [(s, len(vs)) for s, vs in sorted(inferred.items())]
        result_spec = [jax.ShapeDtypeStruct(tuple(shape), np.dtype(dt))
                       for _, vs in sorted(inferred.items())
                       for shape, dt in vs]
    else:
        probe = [np.zeros(v.shape, v.dtype) for v in flat]
        # NOTE: under stackless tracing, jnp constants created inside
        # compute come back as tracers — only .shape/.dtype may be read.
        probe_out = ops_lib.normalize_outs(
            opdef.compute(rebuild(probe), dict(attrs)))
        out_slots = [(s, len(vs)) for s, vs in sorted(probe_out.items())]
        result_spec = [
            jax.ShapeDtypeStruct(tuple(v.shape), np.dtype(v.dtype))
            for _, vs in sorted(probe_out.items()) for v in vs]

    def host_fn(*flat_vals):
        outs = ops_lib.normalize_outs(opdef.compute(
            rebuild([np.asarray(v) for v in flat_vals]), dict(attrs)))
        return tuple(np.asarray(v) for _, vs in sorted(outs.items())
                     for v in vs)

    if op.type in ("print", "assert"):
        # observable effects with passthrough-or-no outputs: a debug
        # callback keeps the effect alive under jit AND autodiff
        # (pure_callback with unused outputs is DCE-able; io_callback
        # does not support vjp), and the outputs are synthesized as the
        # identity of the inputs instead of round-tripping to host
        def effect_fn(*flat_vals):
            opdef.compute(
                rebuild([np.asarray(v) for v in flat_vals]),
                dict(attrs))

        jax.debug.callback(effect_fn, *flat, ordered=True)
        outs = {}
        for s, n in out_slots:
            outs[s] = list(flat[:n])  # print: Out = its input
        return outs
    flat_out = jax.pure_callback(host_fn, tuple(result_spec), *flat)
    outs, i = {}, 0
    for s, n in out_slots:
        outs[s] = [jnp.asarray(v) for v in flat_out[i:i + n]]
        i += n
    return outs


def _run_ops(ops, env, key0, base_idx=0, amp_lists=None):
    for i, op in enumerate(ops):
        _exec_op(op, env, key0, base_idx + i, amp_lists=amp_lists)


# -- control-flow lowering (reference: operators/controlflow/while_op.cc:42,
# conditional_block_op.cc -> lax.while_loop / lax.cond / lax.switch;
# SURVEY.md §7 hard part (b): scope mutation becomes an explicit carry) --

def _sub_block_carry(sub_block, env):
    """Loop carry = sub-block writes that pre-exist in the enclosing env
    (paddle requires loop vars be created+initialized before the While).
    Includes writes made in NESTED control flow (a cond inside the while
    body assigning a loop var). Writes to loop-local temps are not
    carried."""
    carry, seen = [], set()
    for sop in sub_block.ops:
        for n in _op_reads_writes(sop)[1]:
            if n in env and n not in seen:
                carry.append(n)
                seen.add(n)
    return carry


def _exec_while(op, env, key0, op_idx, amp_lists):
    import jax
    import jax.numpy as jnp
    from jax import lax

    prog = op.block.program
    sub = prog.block(op.attrs["sub_block"])
    cond_name = op.attrs["cond_name"]
    carry_names = _sub_block_carry(sub, env)
    if cond_name not in carry_names:
        raise RuntimeError(
            "while: the loop body never rebinds condition var %r — the "
            "loop would not terminate" % cond_name)
    base_key = jax.random.fold_in(key0, op_idx)
    cond_pos = carry_names.index(cond_name)

    def cond_f(carry):
        return jnp.all(carry[1 + cond_pos])

    def body_f(carry):
        it = carry[0]
        e = dict(env)
        e.update(zip(carry_names, carry[1:]))
        # per-iteration rng so dropout etc. differs across iterations
        _run_ops(sub.ops, e, jax.random.fold_in(base_key, it),
                 amp_lists=amp_lists)
        return (it + 1,) + tuple(e[n] for n in carry_names)

    init = (jnp.int32(0),) + tuple(env[n] for n in carry_names)
    final = lax.while_loop(cond_f, body_f, init)
    env.update(zip(carry_names, final[1:]))


def _exec_scan(op, env, key0, op_idx, amp_lists):
    """`scan` op -> jax.lax.scan: fixed-trip loop whose body is traced
    and compiled ONCE regardless of depth — the TPU-native way to build
    deep identical-layer stacks (12-layer BERT encoder: one body in the
    HLO instead of 12 clones). Carry contract is the While contract
    (sub-block writes to pre-existing vars are threaded functionally);
    per-iteration slices of the stacked inputs arrive as scan xs; with
    attrs['remat'] the body is wrapped in jax.checkpoint, giving
    activation recompute per layer without RecomputeOptimizer's
    segment machinery. What the forward scan stacks for the backward
    is then the carry and the few values the ops named as cheaper to
    keep than to make again (ops/remat_names.py: dropout keep masks,
    narrow matmul products, the flash kernels' output and row
    statistics, which leave their forward kernel out of the second
    pass); everything else in the body is computed a second time.
    `program._remat_saved` holds what was kept, per scan
    op (Executor.remat_saved reads it). Reverse-mode grads fall out of
    the ordinary jax.vjp over lax.scan (no recurrent_grad op —
    contrast reference recurrent_op.cc's scope-mutation step loop)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..ops import remat_names

    prog = op.block.program
    sub = prog.block(op.attrs["sub_block"])
    n = int(op.attrs["n"])
    xs_stacked = list(op.attrs.get("xs_stacked", []))
    xs_slice = list(op.attrs.get("xs_slice", []))
    carry_names = _sub_block_carry(sub, env)
    if not carry_names:
        raise RuntimeError(
            "scan: the body never rebinds a pre-existing var — every "
            "iteration's results would be discarded. Rebind the carry "
            "with layers.assign(new_val, output=carried_var).")
    base_key = jax.random.fold_in(key0, op_idx)

    iter_name = op.attrs.get("iter_var") or None
    kept = [] if op.attrs.get("remat") else None

    def body(carry, xs):
        if kept is not None:
            kept.clear()  # a retrace of the body starts its list anew
        it = carry[0]
        e = dict(env)
        e.update(zip(carry_names, carry[1:]))
        e.update(zip(xs_slice, xs))
        if iter_name:
            e[iter_name] = jnp.reshape(it, (1,)).astype(jnp.int32)
        # per-iteration rng so dropout masks differ across layers
        _run_ops(sub.ops, e, jax.random.fold_in(base_key, it),
                 amp_lists=amp_lists)
        return ((it + 1,) + tuple(e[nm] for nm in carry_names)), None

    if kept is not None:
        # prevent_cse=False: forward and recompute sit in two loops, so
        # there is nothing to eliminate, and the barrier that would
        # prevent it makes XLA copy every stacked value out of its
        # buffer before the recompute may read it
        body = jax.checkpoint(
            body, prevent_cse=False,
            policy=jax.checkpoint_policies.save_only_these_names(
                *remat_names.KEPT))
    init = (jnp.int32(0),) + tuple(env[nm] for nm in carry_names)
    xs = tuple(env[nm] for nm in xs_stacked)
    with remat_names.collecting(kept):
        final, _ = lax.scan(body, init, xs, length=n)
    if kept is not None:
        from ..observability import attribution as _attr

        _record_remat_saved(prog, _attr.op_marker(op, op_idx), n, kept)
    env.update(zip(carry_names, final[1:]))


def _record_remat_saved(prog, marker, n, kept):
    """Trace-time account of what a checkpoint keeps, on the program
    under `marker` (a remat scan op's provenance marker, or
    `<backward op's marker>/seg<i>` for a segment of an unrolled stack
    under RecomputeOptimizer): the named values with shape, dtype and
    bytes, bytes a layer and over the `n` layers the checkpoint is
    applied to (a scan's carry, a segment's outputs are kept besides).
    Logged when it is new or has changed, so once a compile."""
    import logging

    rows = [{"name": name, "shape": list(shape), "dtype": str(dtype),
             "bytes": int(np.prod(shape, dtype=np.int64))
             * np.dtype(dtype).itemsize}
            for name, shape, dtype in kept]
    per_layer = sum(r["bytes"] for r in rows)
    record = {"n": n, "kept": rows, "bytes_per_layer": per_layer,
              "bytes_over_scan": per_layer * n}
    saved = getattr(prog, "_remat_saved", None)
    if saved is None:
        saved = prog._remat_saved = {}
    if saved.get(marker) != record:
        saved[marker] = record
        logging.getLogger(__name__).info(
            "%s keeps across its checkpoint, besides what it hands on: "
            "%s; %d bytes a layer, %d over %d layers", marker,
            ", ".join("%s %s%s" % (r["name"], r["dtype"], r["shape"])
                      for r in rows) or "nothing",
            per_layer, per_layer * n, n)


def _branch_out_names(op, env, blocks):
    """Names a branch op must return: its declared outputs PLUS any writes
    (incl. nested) to vars that pre-exist in env — so a branch assigning
    an outer var (e.g. a loop var from an enclosing While) propagates.
    Branches that don't write a given name return env's value unchanged,
    keeping lax.cond/switch branch signatures identical."""
    names = list(op.attrs["out_names"])
    seen = set(names)
    for blk in blocks:
        for sop in blk.ops:
            for n in _op_reads_writes(sop)[1]:
                if n in env and n not in seen:
                    names.append(n)
                    seen.add(n)
    return names


def _branch_fn(block, env, key, out_names, amp_lists):
    def f(_):
        e = dict(env)
        _run_ops(block.ops, e, key, amp_lists=amp_lists)
        return tuple(e[n] for n in out_names)

    return f


def _exec_cond(op, env, key0, op_idx, amp_lists):
    import jax
    import jax.numpy as jnp
    from jax import lax

    prog = op.block.program
    blk_t = prog.block(op.attrs["sub_block_t"])
    blk_f = prog.block(op.attrs["sub_block_f"])
    out_names = _branch_out_names(op, env, [blk_t, blk_f])
    pred = jnp.all(env[op.attrs["cond_name"]])
    key = jax.random.fold_in(key0, op_idx)
    outs = lax.cond(
        pred,
        _branch_fn(blk_t, env, key, out_names, amp_lists),
        _branch_fn(blk_f, env, key, out_names, amp_lists),
        None)
    env.update(zip(out_names, outs))


def _exec_conditional_block(op, env, key0, op_idx, amp_lists):
    """Reference conditional_block_op.cc: run the sub-block iff Cond is
    true. Functional form: lax.cond whose false branch returns the
    enclosing env's values unchanged, so every carried write must
    pre-exist (the same contract as While loop vars)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    prog = op.block.program
    blk = prog.block(op.attrs["sub_block"])
    out_names = _branch_out_names(op, env, [blk]) \
        if "out_names" in op.attrs else _sub_block_carry(blk, env)
    cond_vals = [env[n] for n in op.input_names.get("Cond", [])]
    pred = jnp.all(cond_vals[0]) if cond_vals else jnp.bool_(True)
    key = jax.random.fold_in(key0, op_idx)
    true_fn = _branch_fn(blk, env, key, out_names, amp_lists)
    # outputs first created INSIDE the block (no pre-existing env value)
    # are zeros on the skip path, like an unexecuted reference scope
    shapes = jax.eval_shape(true_fn, None)

    def false_fn(_):
        return tuple(
            env[n] if n in env else jnp.zeros(s.shape, s.dtype)
            for n, s in zip(out_names, shapes))

    outs = lax.cond(pred, true_fn, false_fn, None)
    env.update(zip(out_names, outs))


def _exec_switch_case(op, env, key0, op_idx, amp_lists):
    import jax
    import jax.numpy as jnp
    from jax import lax

    prog = op.block.program
    keys = op.attrs["keys"]
    blocks = [prog.block(b) for b in op.attrs["sub_blocks"]]  # default last
    out_names = _branch_out_names(op, env, blocks)
    key = jax.random.fold_in(key0, op_idx)
    idx_val = jnp.reshape(env[op.attrs["index_name"]], ()).astype(jnp.int32)
    # map the user's branch keys to positions; no match -> default (last)
    sel = jnp.full((), len(blocks) - 1, jnp.int32)
    for pos, k in enumerate(keys):
        sel = jnp.where(idx_val == k, jnp.int32(pos), sel)
    fns = [_branch_fn(blk, env, key, out_names, amp_lists)
           for blk in blocks]
    outs = lax.switch(sel, fns, None)
    env.update(zip(out_names, outs))


def _run_gradient_merge(ops, bwd_idx, gm, env, key0, amp_lists,
                        sync_fn=None, shard_plan=None, block=None):
    """k-step gradient accumulation (reference: gradient_merge strategy,
    `framework/ir/multi_batch_merge_pass.cc` / fleet 2.0 GradientMerge
    meta-optimizer). Each step adds the fresh grads into persistable
    accumulators; the optimizer section runs under lax.cond only on every
    k-th step (with the averaged accumulated grads), then the
    accumulators reset to zero. Off steps leave params/moments untouched.

    With a `shard_plan` (ZeRO-1 + gradient merge), the once-per-k sync
    on the MERGED grads is a (bucketed) reduce-scatter instead of an
    allreduce, and the post section inside the cond runs on flat 1/N
    shards — the merged-grad update path is sharded too. Sharded
    optimizer state is a ShardVal on BOTH branches (skip passes the
    incoming shard through), so the cond's pytrees agree; any other
    shard-space value is gathered back to its replicated form before
    leaving the branch."""
    import jax.numpy as jnp
    from jax import lax

    if shard_plan is not None:
        from ..parallel import sharded_update as _su

    k = int(gm["k_steps"])
    avg = bool(gm.get("avg", True))
    acc_map = dict(gm["acc_map"])  # grad name -> accumulator name
    counter_n = gm["counter"]
    post_ops = ops[bwd_idx + 1:]

    cnt = jnp.reshape(env[counter_n], ()).astype(jnp.int32)
    new_cnt = cnt + 1
    do_apply = (new_cnt % k) == 0
    for g, acc in acc_map.items():
        env[acc] = env[acc] + env[g].astype(env[acc].dtype)

    # cond-uniform outputs: post-section writes that pre-exist in env
    # (param/moment/lr updates), plus the accumulators
    out_names, seen = [], set()
    for op in post_ops:
        for n in _op_reads_writes(op)[1]:
            if n in env and n not in seen:
                out_names.append(n)
                seen.add(n)
    out_names.extend(a for a in acc_map.values() if a not in seen)

    def apply_branch(_):
        e = dict(env)
        if shard_plan is not None:
            # sharded merged-grad sync: reduce-scatter (per-bucket when
            # FLAGS_tpu_comm_bucket_mb > 0) ONCE per k steps — the
            # predicate is counter-driven, so every shard takes this
            # branch together and the collectives stay uniform
            gdict = {g: (e[acc] / k if avg else e[acc])
                     for g, acc in acc_map.items()
                     if g in shard_plan.grad_names}
            scattered = _su.bucketed_reduce_scatter(
                gdict, shard_plan, mean=True)
            for g, acc in acc_map.items():
                if g in scattered:
                    e[g] = scattered[g].astype(e[g].dtype)
                else:
                    merged = e[acc] / k if avg else e[acc]
                    if sync_fn is not None:
                        merged = sync_fn(merged, g)
                    e[g] = merged.astype(e[g].dtype)
            _su.run_sharded_post_ops(post_ops, e, key0, bwd_idx + 1,
                                     amp_lists, shard_plan, block)
        else:
            for g, acc in acc_map.items():
                merged = e[acc] / k if avg else e[acc]
                if sync_fn is not None:
                    # implicit-DP sync on the merged grad: one allreduce
                    # per k steps (the predicate is counter-driven, so
                    # every shard takes this branch together)
                    merged = sync_fn(merged, g)
                e[g] = merged.astype(e[g].dtype)
            _run_ops(post_ops, e, key0, base_idx=bwd_idx + 1,
                     amp_lists=amp_lists)
        for acc in acc_map.values():
            e[acc] = jnp.zeros_like(e[acc])
        if shard_plan is not None:
            # branch-exit normalization: sharded state stays a ShardVal
            # (the skip branch passes the incoming shard through, so
            # pytrees agree); every other shard-space value gathers back
            return tuple(
                (_su.gather_full(e[n], shard_plan, name=n)
                 if isinstance(e[n], _su.ShardVal)
                 and n not in shard_plan.sharded_state else e[n])
                for n in out_names)
        return tuple(e[n] for n in out_names)

    def skip_branch(_):
        return tuple(env[n] for n in out_names)

    outs = lax.cond(do_apply, apply_branch, skip_branch, None)
    env.update(zip(out_names, outs))
    env[counter_n] = jnp.reshape(new_cnt % k,
                                 env[counter_n].shape).astype(
                                     env[counter_n].dtype)


def _amp_found_inf(grads, axis_names):
    """Global non-finite indicator over this step's (synced) gradients.
    Counted on each replica's LOCAL values — under ZeRO the 1/N shard
    vecs, 1/N the work of a full-tensor scan — then psum'd over the dp
    axis/axes when live: the `lax.cond` that skips the weight update
    must see a replica-UNIFORM predicate (an overflow lands in exactly
    one replica's shard slots; without the psum the other replicas
    would run the update branch and its all-gathers alone — deadlock).
    On a hybrid mesh `axis_names` is the (ici, dcn) pair: the count
    psums over both so every pod agrees."""
    import jax.numpy as jnp

    from ..parallel import env as penv
    from ..parallel import sharded_update as _su

    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    total = jnp.zeros((), jnp.float32)
    for g in grads.values():
        v = g.vec if isinstance(g, _su.ShardVal) else g
        total = total + jnp.sum(
            (~jnp.isfinite(v.astype(jnp.float32))).astype(jnp.float32))
    axes = penv.active_axes() or {}
    from ..observability import attribution as _attr

    with _attr.marker_scope(_attr.amp_marker("found_inf")):
        for axis_name in axis_names:
            if axis_name is not None and axes.get(axis_name, 1) > 1:
                import jax

                total = jax.lax.psum(total, axis_name)
    return total > 0


def _amp_unscale(g, scale):
    """grad / loss_scale, computed in fp32 (an fp16 division would
    re-lose the low bits the scaling protected) then cast back."""
    import jax.numpy as jnp

    from ..parallel import sharded_update as _su

    if isinstance(g, _su.ShardVal):
        return _su.ShardVal(_amp_unscale(g.vec, scale), g.shape)
    return (g.astype(jnp.float32) / scale).astype(g.dtype)


def _run_loss_scaled_post(ops, bwd_idx, dls, env, key0, amp_lists,
                          shard_plan, block, found_inf,
                          fetch_names=()):
    """fp16 dynamic loss scaling (reference: decorator.py's
    amp_check_finite_and_scale + update_loss_scaling op pair). The whole
    post-backward section — optimizer update, clip, lr schedule —
    runs under ``lax.cond`` on the psum'd finite check: an overflow step
    leaves params/moments/counters untouched (the reference's
    found_inf short-circuit inside each optimizer kernel). The scale
    state machine updates OUTSIDE the cond with plain arithmetic:

      clean step:    good += 1; good == incr_every_n_steps
                     -> scale *= incr_ratio, good = 0
      overflow step: bad += 1, good = 0; bad == decr_every_n_nan_or_inf
                     -> scale *= decr_ratio, bad = 0

    ZeRO interplay mirrors _run_gradient_merge's branch normalization:
    values that are ShardVals on BOTH sides (sharded opt state / fp32
    masters, and the scattered grads themselves — their shards pass
    through, honoring the ZeRO-2 lifetime) stay sharded; a value the
    apply branch shards but the skip branch holds full (an updated
    param not covered by the deferred per-bucket gathers) gathers at
    branch exit so the cond's pytrees agree."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..parallel import sharded_update as _su

    post_ops = ops[bwd_idx + 1:]
    out_names, seen = [], set()
    post_writes = set()
    for op in post_ops:
        for n in _op_reads_writes(op)[1]:
            post_writes.add(n)
            if n in env and n not in seen:
                out_names.append(n)
                seen.add(n)
    # post-CREATED vars that are fetched (a regularizer term, the
    # global grad norm): they exist only inside the branch, so they
    # must ride the cond outputs or the fetch loop never sees them —
    # on a skipped (overflow) step they read as zeros, like an
    # unexecuted reference scope (the conditional_block contract)
    created = [n for n in fetch_names
               if n in post_writes and n not in env and n not in seen]
    out_names.extend(created)

    def _norm(n, v):
        """Align a branch output with the skip side's type: the apply
        branch may promote a rebound var's dtype (fp16 grad * fp32
        clip scale -> fp32) — the cond's pytrees must agree, and the
        optimizer already consumed the full-precision value INSIDE the
        branch, so the exit cast costs no update precision."""
        ref = env.get(n)
        if isinstance(v, _su.ShardVal):
            if shard_plan is not None and \
                    not isinstance(ref, _su.ShardVal):
                v = _su.gather_full(v, shard_plan, name=n)
            elif isinstance(ref, _su.ShardVal):
                return v.astype(ref.dtype) \
                    if v.dtype != ref.dtype else v
        if ref is not None and hasattr(ref, "dtype") \
                and hasattr(v, "astype") and v.dtype != ref.dtype:
            v = v.astype(ref.dtype)
        return v

    def apply_branch(_):
        e = dict(env)
        if shard_plan is not None:
            _su.run_sharded_post_ops(post_ops, e, key0, bwd_idx + 1,
                                     amp_lists, shard_plan, block)
        else:
            _run_ops(post_ops, e, key0, base_idx=bwd_idx + 1,
                     amp_lists=amp_lists)
        return tuple(_norm(n, e[n]) for n in out_names)

    shapes = jax.eval_shape(apply_branch, None) if created else None

    def skip_branch(_):
        return tuple(
            env[n] if n in env
            else jnp.zeros(shapes[i].shape, shapes[i].dtype)
            for i, n in enumerate(out_names))

    outs = lax.cond(found_inf, skip_branch, apply_branch, None)
    env.update(zip(out_names, outs))

    scale_n, good_n, bad_n = dls["scale"], dls["good"], dls["bad"]
    scale = jnp.reshape(env[scale_n], ()).astype(jnp.float32)
    good = jnp.reshape(env[good_n], ()).astype(jnp.int32)
    bad = jnp.reshape(env[bad_n], ()).astype(jnp.int32)
    new_good = jnp.where(found_inf, 0, good + 1)
    new_bad = jnp.where(found_inf, bad + 1, 0)
    grow = jnp.logical_and(
        jnp.logical_not(found_inf),
        new_good >= int(dls.get("incr_every_n_steps", 1000)))
    shrink = jnp.logical_and(
        found_inf,
        new_bad >= int(dls.get("decr_every_n_nan_or_inf", 2)))
    new_scale = jnp.where(
        shrink, scale * jnp.float32(dls.get("decr_ratio", 0.8)),
        jnp.where(grow, scale * jnp.float32(dls.get("incr_ratio", 2.0)),
                  scale))
    new_good = jnp.where(grow, 0, new_good)
    new_bad = jnp.where(shrink, 0, new_bad)
    for name, val in ((scale_n, new_scale), (good_n, new_good),
                      (bad_n, new_bad)):
        env[name] = jnp.reshape(val, env[name].shape).astype(
            env[name].dtype)


def _split_at_checkpoints(ops, ckpt_names):
    """Segment boundaries for activation recompute: a segment ends right
    after the (last) op that writes each checkpoint variable. Returns a
    list of (start, stop) index pairs covering `ops`."""
    cuts = set()
    for cn in ckpt_names:
        last = None
        for i, op in enumerate(ops):
            if cn in op.output_arg_names:
                last = i
        if last is not None and last + 1 < len(ops):
            cuts.add(last + 1)
    bounds, prev = [], 0
    for c in sorted(cuts):
        bounds.append((prev, c))
        prev = c
    bounds.append((prev, len(ops)))
    return bounds


def _remat_segments(fwd_ops, ckpt_names, live_out):
    """Plan jax.checkpoint segments (reference: backward.py:629 recompute
    segments + optimizer.py:4485 RecomputeOptimizer). Each entry is
    (start, stop, needed_after): `needed_after` is the set of names still
    read by later forward segments or by anything downstream (loss, post-
    backward ops, fetches, state outputs) — the only values a checkpointed
    segment must emit, so XLA stores just the boundary residuals and
    rematerializes segment interiors during the backward pass."""
    bounds = _split_at_checkpoints(fwd_ops, ckpt_names)
    if len(bounds) <= 1:
        return None
    out = []
    needed = set(live_out)
    for start, stop in reversed(bounds):
        out.append((start, stop, frozenset(needed)))
        for op in fwd_ops[start:stop]:
            needed.update(_op_reads_writes(op)[0])
    out.reverse()
    return out


def _diffable(block, name, env):
    v = block._find_var_recursive(name)
    if v is None or v.stop_gradient:
        return False
    import jax.numpy as jnp

    val = env.get(name)
    return val is not None and jnp.issubdtype(
        np.asarray(val).dtype if not hasattr(val, "dtype") else val.dtype,
        jnp.floating)


def build_block_fn(program, block, feed_names, fetch_names,
                   state_in, state_out, shard_plan=None,
                   sparse_plan=None, tp_plan=None):
    """Build the pure python fn to be jitted. With `shard_plan` (a
    parallel.sharded_update.ShardedUpdatePlan; only under _compile_dp),
    optimizer-bound gradients are reduce-scattered instead of pmean'd,
    the post-backward section runs on flat 1/N shards, and updated
    params are all-gathered back — ZeRO-1 weight-update sharding.

    With `sparse_plan` (an embedding.SparseTablePlan), vocab-sharded
    tables arrive as row shards, lookups lower through the sparse
    engine, and each table's gradient is collected via a zero "tap"
    diff var (the table itself never enters jax.vjp — no dense
    vocab-sized cotangent exists) and applied as a row-sparse update
    on the owning shard.

    With `tp_plan` (a parallel.tensor_parallel.TensorParallelPlan),
    model-sharded weights arrive as local blocks, their consuming ops
    lower through the TP engine's collectives on the `model` axis, and
    grad sync stays on the (dcn, replica) data axes — model members
    hold DISTINCT weight shards whose grads must never be averaged
    over `model`, while devices agreeing on the model coordinate hold
    the SAME shard, which is exactly the group the (dcn, ici)
    pmean/reduce-scatter already syncs."""
    import jax
    import jax.numpy as jnp

    if shard_plan is not None:
        from ..parallel import sharded_update as _su
    else:
        _su = None
    if sparse_plan is not None:
        from ..embedding import engine as _emb
    else:
        _emb = None
    if tp_plan is not None:
        from ..parallel import tensor_parallel as _tp
    else:
        _tp = None

    ops = list(block.ops)
    bwd_indices = [i for i, op in enumerate(ops) if op.type == "backward"]
    if len(bwd_indices) > 1:
        raise NotImplementedError("multiple backward sections in one block")
    bwd_idx = bwd_indices[0] if bwd_indices else None
    amp_lists = _amp_trace_policy(program)
    # Implicit DP grad sync (reference: multi_devices_graph_pass.cc:464
    # inserts an AllReduceOpHandle per gradient for ParallelExecutor).
    # The fleet transpiler emits explicit c_allreduce ops ON THE GRAD
    # VARS after backward instead — when those are present the program
    # owns its own sync and pmean-ing here would double-reduce. Only
    # grad-consuming allreduces count: a forward collective (e.g. a
    # globally averaged metric) must not disable the sync.
    _post_ops = ops[bwd_idx + 1:] if bwd_idx is not None else []
    _has_explicit_sync = any(
        (op.type.startswith("c_allreduce") or op.type == "allreduce")
        and any(n.endswith("@GRAD") for n in op.input_arg_names)
        for op in _post_ops)
    _implicit_dp = getattr(program, "_data_parallel", False) \
        and not _has_explicit_sync
    _dp_axis_name = getattr(program, "_dp_axis", "dp")
    # hybrid (dcn, ici) mesh: _dp_axis is the intra-pod ici axis and
    # _dcn_axis the cross-pod one; a full-tensor sync lowers
    # hierarchically (psum over ici, then the pod partials over dcn)
    # so its association matches the scatter path's — the pairing that
    # keeps the sharded update bit-identical to this reference
    _dcn_axis_name = getattr(program, "_dcn_axis", None)
    # tensor parallelism: the model axis never joins the grad sync, but
    # the AMP found_inf predicate must still psum over it — model
    # members hold DIFFERENT grad shards, and a lax.cond predicate that
    # differs across mesh members would deadlock the collectives inside
    _model_axis_name = tp_plan.model_axis if tp_plan is not None else None

    def _dp_sync_axes():
        from ..parallel import env as penv

        axes = penv.active_axes() or {}
        return tuple(a for a in (_dp_axis_name, _dcn_axis_name)
                     if a is not None and axes.get(a, 1) > 1)

    def _dp_pmean(g, name=None):
        """pmean over the dp axis when implicit sync is on and the axis
        is live (inside shard_map); identity otherwise. On a hybrid
        mesh: hierarchical psum (ici, then dcn) / world. `name` stamps
        the emitted collective with a grad-sync provenance marker so
        the census maps it back to its gradient."""
        if not _implicit_dp:
            return g
        live = _dp_sync_axes()
        if not live:
            return g
        import jax as _jax

        from ..observability import attribution as _attr

        with _attr.marker_scope(_attr.grad_sync_marker(name)) \
                if name else contextlib.nullcontext():
            if _dcn_axis_name is None:
                # flat dp: keep the exact pre-hybrid lowering
                return _jax.lax.pmean(g, _dp_axis_name)
            from ..parallel import env as penv

            axes = penv.active_axes() or {}
            total = g
            world = 1
            for a in live:
                total = _jax.lax.psum(total, a)
                world *= axes[a]
            return total / world


    def fn(feeds: Dict, states_mut: Dict, states_ro: Dict, seed):
        if sparse_plan is None and tp_plan is None:
            return _fn_body(feeds, states_mut, states_ro, seed)
        # install the sparse/TP plans for this trace (contextvars — the
        # engines' per-op routing in _exec_op_stamped reads them; safe
        # under concurrent background-warmup traces)
        with contextlib.ExitStack() as stack:
            if sparse_plan is not None:
                stack.enter_context(_emb.active_plan(sparse_plan))
            if tp_plan is not None:
                stack.enter_context(_tp.active_plan(tp_plan))
            return _fn_body(feeds, states_mut, states_ro, seed)

    def _fn_body(feeds: Dict, states_mut: Dict, states_ro: Dict, seed):
        env = {}
        env.update(states_ro)
        env.update(states_mut)
        env.update(feeds)
        key0 = make_key(seed)
        if shard_plan is not None:
            # sharded optimizer state arrives as raw (padded/N,) vecs
            # from shard_map; wrap with the logical shapes
            _su.wrap_sharded_state(env, shard_plan)
        if sparse_plan is not None:
            # vocab-sharded tables + per-row moments arrive as raw
            # local (rows/N, dim) blocks from shard_map; wrap them
            _emb.wrap_tables(env, sparse_plan)

        if bwd_idx is None:
            _run_ops(ops, env, key0, amp_lists=amp_lists)
        else:
            fwd_ops = ops[:bwd_idx]
            bop = ops[bwd_idx]
            loss_name = bop.attrs["loss_name"]
            requested = bop.attrs.get("diff_names", [])
            loss_scale = bop.attrs.get("loss_scale", 1.0)
            gm = bop.attrs.get("gradient_merge")
            # fp16 loss scaling: dynamic (scale state machine under
            # lax.cond) or static (constant factor, no skip). The
            # merged-grad cond owns the cadence under gradient merge,
            # so dls never combines with it (decorator warns).
            dls = bop.attrs.get("dynamic_loss_scaling") \
                if gm is None else None
            static_ls = bop.attrs.get("static_loss_scaling") \
                if gm is None else None
            if (dls is not None or static_ls) and _has_explicit_sync:
                # explicit-sync (fleet-transpiled) programs sum grads
                # via c_allreduce_sum ops INSIDE the post section: the
                # finite check here would see pre-sum local values
                # (overflow introduced by the N-way fp16 sum escapes
                # the skip-cond) and the unscale — dynamic OR static —
                # would flush small grads back to zero before the sum,
                # the protection inverted. Disable rather than
                # mis-protect; say so loudly once (the dynamic scale
                # state then passes through each step unchanged).
                import warnings

                warnings.warn(
                    "fp16 loss scaling is not wired for explicit-sync "
                    "(fleet-transpiled) gradient programs; training "
                    "proceeds UNSCALED — expect fp16 gradient "
                    "underflow. Use bfloat16 (no scaling needed) or "
                    "implicit DP sync.")
                dls = None
                static_ls = None
            tap_names = frozenset()
            if sparse_plan is not None:
                # vocab-sharded tables never enter vjp: their grads
                # arrive through the lookup-output taps instead (no
                # dense vocab-sized cotangent is ever built)
                requested = [n for n in requested
                             if n not in sparse_plan.tables]
            diff_names = [n for n in requested
                          if n in env and _diffable(block, n, env)]
            if sparse_plan is not None:
                taps = _emb.tap_specs(sparse_plan, env)
                env.update(taps)
                tap_names = frozenset(taps)
                diff_names = diff_names + sorted(taps)

            ckpt_names = list(bop.attrs.get("checkpoints", []) or [])
            segments = None
            if ckpt_names:
                live_out = set(fetch_names) | set(state_out) | {loss_name}
                for post_op in ops[bwd_idx + 1:]:
                    live_out.update(_op_reads_writes(post_op)[0])
                segments = _remat_segments(fwd_ops, ckpt_names, live_out)

            def fseg(dvars):
                e = dict(env)
                e.update(dvars)
                if segments is None:
                    _run_ops(fwd_ops, e, key0, amp_lists=amp_lists)
                else:
                    # the scan's policy on an unrolled stack: a segment
                    # hands on its outputs and the few values its ops
                    # named as cheaper to keep (ops/remat_names.py:
                    # masks, narrow products, what the flash kernels'
                    # forward call wrote)
                    from ..observability import attribution as _attr
                    from ..ops import remat_names

                    for i, (start, stop, needed) in enumerate(segments):
                        kept = []

                        def seg_fn(carry, _ops=fwd_ops[start:stop],
                                   _start=start, _needed=needed,
                                   _kept=kept):
                            _kept.clear()
                            ee = dict(carry)
                            _run_ops(_ops, ee, key0, base_idx=_start,
                                     amp_lists=amp_lists)
                            return {n: ee[n] for n in _needed if n in ee}

                        with remat_names.collecting(kept):
                            e.update(jax.checkpoint(
                                seg_fn,
                                policy=jax.checkpoint_policies
                                .save_only_these_names(
                                    *remat_names.KEPT))(e))
                        _record_remat_saved(
                            program, "%s/seg%d" % (
                                _attr.op_marker(bop, bwd_idx), i), 1, kept)
                loss_sum = jnp.sum(e[loss_name].astype(jnp.float32))
                return loss_sum, e

            diff_in = {n: env[n] for n in diff_names}
            _, vjp_fn, env_after = jax.vjp(fseg, diff_in, has_aux=True)
            ct = jnp.asarray(loss_scale, jnp.float32)
            amp_scale = None
            if dls is not None:
                # scale the cotangent by the LIVE scale state so
                # fp16 backward intermediates stay representable
                amp_scale = jnp.reshape(env[dls["scale"]],
                                        ()).astype(jnp.float32)
                ct = ct * amp_scale
            elif static_ls:
                amp_scale = jnp.asarray(static_ls, jnp.float32)
                ct = ct * amp_scale
            grads = vjp_fn(ct)[0]
            env = dict(env_after)
            tap_grads = {}
            if sparse_plan is not None:
                # tap cotangents stay LOCAL (per-replica batch slice):
                # the cross-replica combine happens inside the sparse
                # engine's gathered scatter-add, never via pmean
                tap_grads = {n: grads.pop(n) for n in list(grads)
                             if n in tap_names}
            if gm is None:
                if shard_plan is not None and _implicit_dp:
                    if shard_plan.buckets:
                        # bucketed, backward-ordered collectives
                        # (FLAGS_tpu_comm_bucket_mb): one psum_scatter
                        # per bucket, each depending only on its own
                        # grads — XLA's latency-hiding scheduler can
                        # start early buckets' ring transfers while the
                        # rest of the backward still computes
                        gnames = {n: framework.grad_var_name(n)
                                  for n in grads}
                        gdict = {gn: grads[n]
                                 for n, gn in gnames.items()
                                 if gn in shard_plan.grad_names}
                        scattered = _su.bucketed_reduce_scatter(
                            gdict, shard_plan, mean=True)
                        grads = {
                            n: (scattered[gn] if gn in scattered
                                else _dp_pmean(grads[n], gn))
                            for n, gn in gnames.items()}
                    else:
                        # ZeRO-1 per-variable collectives (the exact
                        # FLAGS_tpu_comm_bucket_mb=0 lowering):
                        # optimizer-bound grads reduce-scattered (pmean
                        # semantics -> /N); everything else keeps the
                        # replicated pmean (e.g. a fetched grad)
                        grads = {
                            n: (_su.reduce_scatter_mean(
                                g, shard_plan,
                                name=framework.grad_var_name(n))
                                if framework.grad_var_name(n)
                                in shard_plan.grad_names
                                else _dp_pmean(
                                    g, framework.grad_var_name(n)))
                            for n, g in grads.items()}
                else:
                    grads = {n: _dp_pmean(g, framework.grad_var_name(n))
                             for n, g in grads.items()}
            # dynamic loss scaling: the finite check runs on the SYNCED
            # (scattered) values each replica will actually consume,
            # psum'd over the dp axis so the update-skip predicate is
            # replica-uniform (a collective inside a divergent cond
            # would deadlock the mesh)
            found_inf = None
            if dls is not None:
                found_inf = _amp_found_inf(
                    {n: grads[n] for n in diff_names if n in grads},
                    (_dp_axis_name, _dcn_axis_name, _model_axis_name))
            # under gradient merge, sync once on the MERGED grads at the
            # k-step boundary instead of k per-micro-step allreduces
            from ..observability import attribution as _attr

            for n in diff_names:
                if n in tap_names:
                    continue  # tap cotangents feed the engine
                gn = framework.grad_var_name(n)
                # stamp the grad post-processing (unscale + dtype cast)
                # with the gradient's provenance so its converts blame
                # the right var in the attribution report
                with _attr.marker_scope(_attr.grad_sync_marker(gn)):
                    g = grads[n]
                    if amp_scale is not None:
                        g = _amp_unscale(g, amp_scale)
                    env[gn] = g.astype(env[n].dtype)
            if sparse_plan is not None:
                # one SelectedRows-form gradient per table: site
                # (ids, dOut) pairs gathered over the data axes —
                # collective bytes proportional to touched rows
                _emb.install_sparse_grads(env, tap_grads, sparse_plan)
            loss_val = env[loss_name]
            env[framework.grad_var_name(loss_name)] = jnp.full(
                loss_val.shape, loss_scale, loss_val.dtype)
            if gm is None:
                if dls is not None:
                    _run_loss_scaled_post(ops, bwd_idx, dls, env, key0,
                                          amp_lists, shard_plan, block,
                                          found_inf,
                                          fetch_names=fetch_names)
                elif shard_plan is not None:
                    _su.run_sharded_post_ops(
                        ops[bwd_idx + 1:], env, key0, bwd_idx + 1,
                        amp_lists, shard_plan, block)
                else:
                    _run_ops(ops[bwd_idx + 1:], env, key0,
                             base_idx=bwd_idx + 1, amp_lists=amp_lists)
            else:
                _run_gradient_merge(ops, bwd_idx, gm, env, key0,
                                    amp_lists, sync_fn=_dp_pmean,
                                    shard_plan=shard_plan, block=block)

        fetches = []
        for n in fetch_names:
            if n not in env:
                raise RuntimeError("fetch var %r was never computed" % n)
            v = env[n]
            if shard_plan is not None and isinstance(v, _su.ShardVal):
                # fetched as full
                v = _su.gather_full(v, shard_plan, name=n)
            if sparse_plan is not None:
                if isinstance(v, _emb.TableShard):
                    # fetched tables gather back to the logical shape
                    v = _emb.gather_full(v, sparse_plan)
                elif isinstance(v, _emb.SparseRowGrad):
                    # debug fetch: the dense logical mean gradient
                    v = _emb.densify(v, sparse_plan)
            fetches.append(v)

        def _out_val(n):
            v = env[n]
            if sparse_plan is not None:
                v = _emb.unwrap_state(n, v, sparse_plan)
            if shard_plan is not None:
                v = _su.unwrap_out(n, v, shard_plan)
            return v

        new_states = {n: _out_val(n) for n in state_out if n in env}
        return fetches, new_states

    return fn


def compile_block(program, block, feed_specs, fetch_names, state_specs,
                  donate=None):
    """feed_specs/state_specs: name -> concrete arrays or ShapeDtypeStructs
    (only shapes/dtypes are read). Returns a LoweredFunction."""
    import jax

    if getattr(program, "_pipeline_cfg", None):
        from ..parallel.pipeline import compile_pipeline
        from ..parallel.sharded_update import _record_fallback

        # structured decline, not silence: the pipeline engine owns the
        # program partition, so the unified planner (sparse/TP/ZeRO-1)
        # never runs — perf_analysis --sharded-diff surfaces this entry
        # (one per program; recompiles must not duplicate it)
        trail = getattr(program, "_sharded_update_fallback", None) or []
        if not any(e.get("kind") == "pipeline_bypassed" for e in trail):
            _record_fallback(
                program, "pipeline schedule owns the program "
                "partition; plan_parallel (sparse/TP/ZeRO-1 axis "
                "assignment) is bypassed for _pipeline_cfg programs",
                kind="pipeline_bypassed")
        return compile_pipeline(program, block, feed_specs, fetch_names,
                                state_specs)

    feed_names = list(feed_specs)
    state_in, state_out = analyze_block(block, feed_names, fetch_names)
    missing = [n for n in state_in if n not in state_specs]
    if missing:
        raise RuntimeError(
            "variables %s are read by the program but absent from the scope "
            "— run the startup program (or feed them)" % (missing,))

    from ..parallel import env as penv

    mesh = getattr(program, "_mesh", None)
    if getattr(program, "_data_parallel", False) and mesh is None:
        # FLAGS_tpu_dcn_replicas / PADDLE_NUM_PODS > 1 factors the dp
        # world into a hybrid (dcn, ici) mesh; otherwise the flat
        # single-axis mesh, byte-for-byte the pre-hybrid lowering
        mesh = penv.create_hybrid_mesh() or \
            _default_mesh(getattr(program, "_dp_axis", "dp"))
        program._mesh = mesh
    # derive the axis roles from the mesh itself, so a hand-built
    # hybrid mesh (tests: program._mesh = Mesh(devs.reshape(2, 2),
    # ("dcn", "ici"))) lowers hierarchically without extra marking
    hier = penv.mesh_hierarchy(mesh)
    if hier is not None:
        program._dp_axis = hier[1]   # shard axis = intra-pod ici
        program._dcn_axis = hier[0]
    else:
        program._dcn_axis = None
    dp_axis = getattr(program, "_dp_axis", "dp")

    # ONE planner owns axis assignment (parallel/planner.py): sparse
    # tables → replica rows, tensor parallel → the model axis (via the
    # logical-axis rules), ZeRO-1 flat buffers → the replica axis with
    # TP-local shapes. Planned together so the engines compose instead
    # of colliding, and so the structured-decline trail
    # (program._sharded_update_fallback) covers all three.
    sparse_plan = tp_plan = shard_plan = None
    if mesh is not None and getattr(program, "_data_parallel", False) \
            and getattr(program, "_auto_parallel", None) is None \
            and not getattr(program, "_pipeline_cfg", None):
        from ..parallel import planner as _planner

        pplan = _planner.plan_parallel(program, block, mesh, dp_axis,
                                       feed_names=feed_names,
                                       fetch_names=fetch_names)
        sparse_plan = pplan.sparse_plan
        tp_plan = pplan.tp_plan
        shard_plan = pplan.shard_plan
    program._sparse_plan = sparse_plan
    program._tp_plan = tp_plan
    program._model_axis = tp_plan.model_axis if tp_plan is not None \
        else None
    program._shard_plan = shard_plan

    state_out_set = set(state_out)
    state_mut = [n for n in state_in if n in state_out_set]
    state_ro = [n for n in state_in if n not in state_out_set]
    if sparse_plan is not None:
        # every row-sharded var must flow through the step as scope
        # state (tables of a forward-only program ride state_ro)
        sparse_plan = sparse_plan.prune(state_mut, state_ro)
        program._sparse_plan = sparse_plan

    fn = build_block_fn(program, block, feed_names, fetch_names,
                        state_in, state_out, shard_plan=shard_plan,
                        sparse_plan=sparse_plan, tp_plan=tp_plan)

    if shard_plan is not None:
        # a would-be-sharded state var must flow in AND out of the step;
        # anything else degrades to the replicated layout
        for n in list(shard_plan.sharded_state):
            if n not in state_mut:
                del shard_plan.sharded_state[n]

    if donate is None:  # None = follow the global flag
        from ..utils.flags import get_flag

        donate = bool(get_flag("FLAGS_tpu_donate_buffers", True))
    from ..utils.flags import get_flag as _gf

    # feed-buffer donation: the executor device_puts a FRESH buffer per
    # step (or consumes a single-use prefetched one), so XLA may reuse
    # feed HBM for scratch/outputs instead of holding both live.
    # Programs whose feeds are ALWAYS caller-owned device arrays
    # (dygraph-to-static subgraphs, jit.load) set _feed_donate=False:
    # donation would buy nothing there (the caller's buffer stays live)
    # while the executor's defensive copy would cost one device copy
    # per feed per step
    feed_donate = donate and \
        bool(_gf("FLAGS_tpu_donate_feed_buffers", True)) and \
        getattr(program, "_feed_donate", True)

    ap_cfg = getattr(program, "_auto_parallel", None)
    if ap_cfg is not None:
        host, dynamic = _block_host_op_kinds(block)
        if host or dynamic:
            import warnings

            warnings.warn(
                "auto-parallel declined: the program contains host/"
                "dynamic-shape ops that cannot run under a GSPMD-"
                "partitioned jit; running single-device instead.")
        else:
            from ..parallel import auto_parallel as ap

            persistable = set()
            for n in state_in:
                v = block._find_var_recursive(n)
                if v is not None and getattr(v, "persistable", False):
                    persistable.add(n)
            # the unified planner owns axis assignment for the GSPMD
            # search too: candidate specs shard each param at the dim
            # the axis rules assign, not a blanket "last axis"
            from ..parallel import planner as _planner

            tp_dims = _planner.param_tp_dims(
                program, block, feed_names=feed_names,
                fetch_names=fetch_names)
            plan = ap.search_plan(fn, feed_specs, state_mut, state_ro,
                                  state_specs, persistable,
                                  configs=ap_cfg, state_out=state_out,
                                  donate=donate, tp_dims=tp_dims)
            program._auto_plan = plan
            jitted = ap.compile_with_plan(fn, plan, feed_names,
                                          state_mut, state_ro, state_out,
                                          donate=donate)
            return LoweredFunction(jitted, feed_names, state_in,
                                   state_out, state_mut, state_ro,
                                   fetch_names, mesh=plan.mesh,
                                   dp_axis="dp", auto_plan=plan)

    if mesh is not None and getattr(program, "_data_parallel", False):
        jitted = _compile_dp(fn, mesh, dp_axis, program, block,
                             feed_names, fetch_names, state_mut, state_ro,
                             donate, feed_donate, shard_plan=shard_plan,
                             tp_plan=tp_plan, state_out=state_out)
    else:
        _, dynamic = _block_host_op_kinds(block)
        if dynamic:
            # NMS-style host ops produce value-dependent output shapes —
            # impossible under XLA (the trace-time shape probe would lie
            # at runtime). The whole block runs unjitted, matching the
            # reference's CPU placement of these kernels.
            jitted = fn
            feed_donate = False
        else:
            # no_jit ops lower to pure_callback under jit; a host-op
            # block that fails there raises
            jitted = jax.jit(
                fn, donate_argnums=_donate_argnums(donate, feed_donate))

    return LoweredFunction(jitted, feed_names, state_in, state_out,
                           state_mut, state_ro, fetch_names, mesh=mesh,
                           dp_axis=dp_axis, feed_donate=feed_donate,
                           sharded_state=(dict(shard_plan.sharded_state)
                                          if shard_plan is not None
                                          else None),
                           sparse_tables=(dict(sparse_plan.state_vars)
                                          if sparse_plan is not None
                                          else None))


def _block_host_op_kinds(block):
    """(has_host_ops, has_dynamic_shape_ops) over the block incl.
    sub-blocks."""
    prog = block.program
    host = dynamic = False

    def scan(blk):
        nonlocal host, dynamic
        for op in blk.ops:
            if ops_lib.has_op(op.type):
                od = ops_lib.get_op(op.type)
                host = host or od.no_jit
                dynamic = dynamic or od.dynamic_shape
            for bi in _sub_block_idxs(op):
                scan(prog.block(bi))

    scan(block)
    return host, dynamic


# Donated feed buffers that cannot alias an output are simply freed
# after use by XLA — expected, not a bug — but jax warns "Some donated
# buffers were not usable" for them. Filter at MODULE IMPORT, exactly
# once per process: installing lazily at first compile put the filter
# inside whatever warnings.catch_warnings scope happened to be active
# (pytest wraps every test in one), where it silently evaporated. The
# filter also mutes that warning for state donation; the repo does not
# rely on it to catch aliasing regressions — `Executor.donation_report`
# and tests/test_donation.py assert the aliased byte count directly.
import warnings as _warnings

_warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


def _donate_argnums(state_donate, feed_donate):
    """jit donate_argnums for (feeds, states_mut, states_ro, seed)."""
    if feed_donate and state_donate:
        return (0, 1)
    if state_donate:
        return (1,)
    return ()


def _default_mesh(dp_axis):
    import jax
    from jax.sharding import Mesh

    devs = np.array(jax.devices())
    return Mesh(devs, (dp_axis,))


def data_partition_spec(mesh, dp_axis="dp"):
    """PartitionSpec of a data (batch-sharded) tensor on `mesh`: dim 0
    over the whole dp world — both axes of a hybrid (dcn, ici) mesh,
    the single axis otherwise. The one spec feeds/prefetched batches
    and non-persistable fetches share."""
    from jax.sharding import PartitionSpec as P

    from ..parallel import env as penv

    hier = penv.mesh_hierarchy(mesh)
    if hier is not None:
        return P((hier[0], hier[1]))
    return P(dp_axis)


# -- per-collective byte accounting (offline ICI evidence) -------------------

_COLLECTIVE_OPS = ("all_reduce", "reduce_scatter", "all_gather",
                   "all_to_all", "collective_permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8": 1,
                "i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i16": 2,
                "ui16": 2, "i8": 1, "ui8": 1, "i1": 1}


def _tensor_bytes(type_str):
    """bytes of one `tensor<AxBx...xDT>` type string (0 if unparsable)."""
    inner = type_str.strip()
    parts = inner.split("x")
    dt = parts[-1]
    size = _DTYPE_BYTES.get(dt)
    if size is None:
        return 0
    n = 1
    for d in parts[:-1]:
        try:
            n *= int(d)
        except ValueError:
            return 0
    return n * size


def _hlo_collective_hits(stablehlo_text, op_names=_COLLECTIVE_OPS):
    """Ordered `(kind, result_type, open_line, result_line)` hits of
    the collective ops in one StableHLO module text — textual order IS
    program order. Region-bearing ops (all_reduce/reduce_scatter) carry
    their `-> tensor<...>` result type (and the rest of their attrs) on
    the region's CLOSING line, several lines below the op itself.
    Shared by `collective_byte_census` and the divergence checker's
    `analysis.hlo_collective_schedule` so the two never drift."""
    import re

    open_pat = re.compile(
        r"\"?(?:stablehlo|mhlo)\.(%s)\"?" % "|".join(op_names))
    ret_pat = re.compile(r"->\s*(?:tuple<)?tensor<([^>]+)>")
    hits = []
    pending = None
    for line in stablehlo_text.splitlines():
        m = open_pat.search(line)
        r = ret_pat.search(line)
        if m and r:
            hits.append((m.group(1), r.group(1), line, line))
        elif m:
            pending = (m.group(1), line)
        elif pending and r and line.lstrip().startswith("})"):
            hits.append((pending[0], r.group(1), pending[1], line))
            pending = None
    return hits


_HLO_GROUPS_RE = None


def replica_groups_raw(open_line, close_line=""):
    """The raw text of one collective's `replica_groups = dense<...>`
    attribute, or None when absent. Region-bearing ops carry their
    attrs on the region's CLOSING line, so both lines are scanned.
    THE one replica_groups grammar — `parse_replica_groups` and the
    divergence checker's schedule records both read through here, so
    the two can never drift."""
    global _HLO_GROUPS_RE
    import re

    if _HLO_GROUPS_RE is None:
        _HLO_GROUPS_RE = re.compile(
            r"replica_groups\s*=\s*dense<([^>]*)>")
    m = _HLO_GROUPS_RE.search(open_line) or \
        (_HLO_GROUPS_RE.search(close_line) if close_line else None)
    return m.group(1).strip() if m is not None else None


def parse_replica_groups(open_line, close_line=""):
    """`replica_groups` of one StableHLO collective as a tuple of
    member tuples, or None when absent / unparsable."""
    import re

    body = replica_groups_raw(open_line, close_line)
    if not body:
        return None
    try:
        if "[" not in body:  # dense<0> scalar form
            return ((int(body),),)
        groups = []
        for grp in re.findall(r"\[([^\[\]]*)\]", body):
            grp = grp.strip()
            groups.append(tuple(int(t) for t in grp.split(",")) if grp
                          else ())
        return tuple(g for g in groups if g) or None
    except ValueError:
        return None


def classify_replica_groups(groups, ici_size, mp_size=1):
    """"ici" | "dcn" | "mp" lane of one collective's replica_groups on
    a hybrid mesh whose pods are contiguous device blocks (the
    create_hybrid_mesh CPU/emulation layout): a collective whose every
    group stays inside one pod rides the fast intra-pod ICI; any group
    spanning two pods crosses the slow DCN link. With a model axis
    (`mp_size` > 1, the (dcn, replica, model) factorization where
    model is INNERMOST — flat device d has model coord d % mp), a pod
    is `ici_size * mp_size` devices, and a group confined to one
    aligned mp-block (all members share d // mp — same pod, same
    replica) is a tensor-parallel exchange: lane "mp". None when the
    groups are unknown (caller treats the collective as ici — the
    flat-mesh reading)."""
    mp = max(int(mp_size or 1), 1)
    if not groups or ((not ici_size or ici_size <= 1) and mp <= 1):
        return None
    pod = max(int(ici_size or 1), 1) * mp
    for g in groups:
        pods = {d // pod for d in g}
        if len(pods) > 1:
            return "dcn"
    if mp > 1 and any(len(g) > 1 for g in groups) and \
            all(len({d // mp for d in g}) == 1 for g in groups):
        return "mp"
    return "ici"


def _ring_wire_bytes(op, b, n):
    """Ring-algorithm wire bytes of one collective over `n`
    participants: all_reduce 2(N-1)/N of the full tensor,
    reduce_scatter (N-1)x its 1/N result, all_gather (N-1)/N of its
    full result; data-movement ops move their payload once."""
    n = max(int(n), 1)
    if op == "all_reduce":
        return int(2 * (n - 1) / n * b)
    if op == "reduce_scatter":
        return (n - 1) * b
    if op == "all_gather":
        return int((n - 1) / n * b)
    return b


def collective_byte_census(stablehlo_text, ndev=1, ici_size=None,
                           mp_size=None):
    """Per-collective accounting from a lowered StableHLO module:
    {op: {count, tensor_bytes, ici_bytes}} + totals. `tensor_bytes`
    sums the RESULT tensor sizes; `ici_bytes` models ring-algorithm
    wire bytes over each collective's replica_groups participants
    (falling back to the `ndev`-device ring when groups are absent) —
    the quantity the sharded weight update halves on the grad+param
    exchange.

    `ici_size` (hybrid multi-pod mesh): additionally split the census
    into `lanes` — "ici" (intra-pod) vs "dcn" (cross-pod, the slow
    link that bounds grad-sync time at multi-pod scale) — with a
    per-collective byte list per lane, so the hierarchical lowering's
    claim (cross-pod bytes = flat-allreduce bytes / ici_size per
    bucket) is checkable from the census alone.

    `mp_size` (tensor parallelism): a third lane, "mp", for
    model-axis collectives — groups confined to one aligned mp-block
    — reported beside ici/dcn as `mp_bytes_total`, so the TP
    contract (grad-sync bytes confined to the (dcn, replica) axes,
    per-chip param bytes ∝ 1/mp) is checkable from the census too."""
    ndev = max(int(ndev), 1)
    mp = max(int(mp_size or 1), 1)
    out = {op: {"count": 0, "tensor_bytes": 0, "ici_bytes": 0}
           for op in _COLLECTIVE_OPS}
    lane_names = ("ici", "dcn", "mp") if mp > 1 else ("ici", "dcn")
    lanes = {ln: {"count": 0, "tensor_bytes": 0, "wire_bytes": 0,
                  "per_collective": []}
             for ln in lane_names}
    for op, ttype, open_line, close_line in \
            _hlo_collective_hits(stablehlo_text):
        b = _tensor_bytes(ttype)
        groups = parse_replica_groups(open_line, close_line)
        n = max((len(g) for g in groups), default=ndev) if groups \
            else ndev
        rec = out[op]
        rec["count"] += 1
        rec["tensor_bytes"] += b
        rec["ici_bytes"] += _ring_wire_bytes(op, b, n)
        if ici_size or mp > 1:
            lane = classify_replica_groups(groups, ici_size, mp) \
                or "ici"
            lrec = lanes[lane]
            lrec["count"] += 1
            lrec["tensor_bytes"] += b
            lrec["wire_bytes"] += _ring_wire_bytes(op, b, n)
            lrec["per_collective"].append(
                {"kind": op, "tensor_bytes": b, "participants": n})
    out = {k: v for k, v in out.items() if v["count"]}
    out["total_ici_bytes"] = sum(v["ici_bytes"] for v in out.values())
    out["total_tensor_bytes"] = sum(
        v["tensor_bytes"] for v in out.values() if isinstance(v, dict))
    out["ndev"] = ndev
    if ici_size or mp > 1:
        out["lanes"] = lanes
        out["ici_size"] = int(ici_size or 1)
        out["dcn_size"] = ndev // (int(ici_size or 1) * mp)
        out["dcn_bytes_total"] = lanes["dcn"]["wire_bytes"]
        if mp > 1:
            out["mp_size"] = mp
            out["mp_bytes_total"] = lanes["mp"]["wire_bytes"]
    return out


# -- collective/compute overlap audit (offline scheduling evidence) ---------

# opcodes that are pure data movement / bookkeeping: never "backward
# compute" even when they carry vjp metadata
_NONCOMPUTE_OPCODES = frozenset({
    "parameter", "constant", "iota", "tuple", "get-tuple-element",
    "bitcast", "copy", "copy-start", "copy-done", "reshape", "transpose",
    "broadcast", "slice", "dynamic-slice", "dynamic-update-slice",
    "concatenate", "pad", "convert", "partition-id", "replica-id",
    "after-all", "opt-barrier", "all-reduce", "all-reduce-start",
    "all-reduce-done", "reduce-scatter", "reduce-scatter-start",
    "reduce-scatter-done", "all-gather", "all-gather-start",
    "all-gather-done", "all-to-all", "collective-permute",
    "collective-permute-start", "collective-permute-done",
})

_AUDIT_COLLECTIVES = ("reduce-scatter", "all-reduce", "all-gather")

_HLO_SHAPE_RE = None

# optimized-HLO dtype spellings (s32/u32/pred — NOT the StableHLO
# i32/ui32/i1 of _DTYPE_BYTES, which parses lowered-but-unoptimized
# module text)
_HLO_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2,
                    "f8e4m3fn": 1, "f8e5m2": 1,
                    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2,
                    "u16": 2, "s8": 1, "u8": 1, "pred": 1}


def _hlo_result_bytes(result_type):
    """bytes of an HLO instruction's result-type text — SUMS every
    `dt[d1,d2,...]` shape so tuple results (async `-start` ops,
    combiner-merged multi-operand collectives) count whole, not just
    their first element (0 if unparsable)."""
    global _HLO_SHAPE_RE
    import re

    if _HLO_SHAPE_RE is None:
        _HLO_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
    total = 0
    for m in _HLO_SHAPE_RE.finditer(result_type):
        size = _HLO_DTYPE_BYTES.get(m.group(1))
        if size is None:
            continue
        n = 1
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        total += n * size
    return total


import re as _re

#: the optimized-HLO instruction grammar, shared with
#: observability/attribution.py's activation-provenance walker so the
#: two parsers can never drift on the dump format
_HLO_INSTR_RE = _re.compile(r"^\s+(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$")
_HLO_OPCODE_RE = _re.compile(r"([a-z][a-z0-9\-]*)\(")
_HLO_OPNAME_RE = _re.compile(r'op_name="([^"]*)"')


def _parse_hlo_module(optimized_hlo):
    """One pass over an optimized HLO dump. Returns (entry, regions):
    `entry` is the ENTRY computation as an ordered list of (name,
    opcode, operand_names, metadata_op_name, result_bytes) — with
    `is_scheduled=true` (every compiled module) the textual order IS
    the schedule; `regions` lists collectives living in NON-entry
    computations (lax.cond / while bodies — gradient merge traces its
    bucketed merged-grad scatters inside the HLO conditional's branch
    computation), fenced by construction: a conditional executes as
    one unit in the entry schedule, so nothing inside it can overlap
    entry backward compute — but the audit must still SEE them rather
    than report 'no collectives' for the gm-sharded path."""
    import re

    name_re = _HLO_INSTR_RE
    opcode_re = _HLO_OPCODE_RE
    opname_re = _HLO_OPNAME_RE
    entry, regions = [], []
    comp = None  # None = between computations; "" = ENTRY
    for line in optimized_hlo.splitlines():
        if line.startswith("ENTRY "):
            comp = ""
            continue
        if line.startswith("%"):  # non-entry computation header
            comp = line.split(" ", 1)[0].lstrip("%")
            continue
        if line.startswith("}"):
            comp = None
            continue
        if comp is None:
            continue
        m = name_re.match(line)
        if m is None:
            continue
        name, rhs = m.group(1), m.group(2)
        om = opcode_re.search(rhs)
        if om is None:
            continue
        opcode = om.group(1)
        # the result type is everything before the opcode name; operand
        # references appear after its open paren (computation refs like
        # to_apply=%region also match but never resolve to entry names)
        nbytes = _hlo_result_bytes(rhs[:om.start()])
        if comp == "":
            operands = re.findall(r"%([\w.\-]+)", rhs[om.end():])
            nm = opname_re.search(rhs)
            entry.append((name, opcode, operands,
                          nm.group(1) if nm else "", nbytes))
        else:
            kind = opcode[:-6] if opcode.endswith("-start") else opcode
            if kind in _AUDIT_COLLECTIVES:
                regions.append({"kind": kind, "name": name,
                                "computation": comp, "bytes": nbytes})
    return entry, regions


def _is_backward_opname(op_name):
    """vjp-generated ops: jax scopes the transpose of the forward trace
    as ".../transpose(jvp(f))/..." (sub-jits) or a bare ".../transpose"
    path component (inline primitives like the dot_general grads)."""
    if "transpose(" in op_name:
        return True
    return any(part == "transpose" for part in op_name.split("/"))


def collective_overlap_audit(optimized_hlo):
    """Scheduling audit over an optimized (scheduled) HLO dump: can the
    grad collectives overlap backward compute, or are they fenced at
    the end of the backward pass?

    For every reduce-scatter / all-reduce / all-gather in the entry
    schedule, `ready` is the dataflow-ready position (max schedule
    position of its operands) — the earliest point the transfer could
    start — and `backward_after` counts backward-compute instructions
    (vjp-metadata ops that are not pure data movement) scheduled after
    it: the compute a latency-hiding scheduler can run DURING the
    transfer. `combined` models XLA's collective combiner merging all
    same-kind collectives into one (what the per-variable lowering
    degenerates to on real ICI without
    --xla_*_combine_threshold_bytes): its ready position is the max
    over members, so the single-buffer exchange shows backward_after=0
    — nothing left to hide behind. The bucketed lowering
    (FLAGS_tpu_comm_bucket_mb > 0) is the point of this audit: early
    buckets' reduce-scatters must show backward_after > 0."""
    instrs, region_collectives = _parse_hlo_module(optimized_hlo)
    pos = {name: i for i, (name, _, _, _, _) in enumerate(instrs)}
    backward = [i for i, (_, opc, _, op_name, _) in enumerate(instrs)
                if op_name and _is_backward_opname(op_name)
                and opc not in _NONCOMPUTE_OPCODES]
    final_backward = max(backward) if backward else -1
    collectives = []
    for i, (name, opc, operands, _, nbytes) in enumerate(instrs):
        kind = opc[:-6] if opc.endswith("-start") else opc
        if kind not in _AUDIT_COLLECTIVES:
            continue
        ready = max([pos[o] for o in operands if o in pos] or [-1])
        after = sum(1 for b in backward if b > ready)
        collectives.append({
            "kind": kind, "name": name, "pos": i, "ready": ready,
            "backward_after": after, "bytes": nbytes,
            "starts_before_final_backward": ready < final_backward,
        })
    combined = {}
    for kind in _AUDIT_COLLECTIVES:
        members = [c for c in collectives if c["kind"] == kind]
        if not members:
            continue
        ready = max(c["ready"] for c in members)
        combined[kind] = {
            "count": len(members),
            "ready": ready,
            "backward_after": sum(1 for b in backward if b > ready),
            "bytes": sum(c["bytes"] for c in members),
        }
    return {
        "is_scheduled": "is_scheduled=true" in
                        optimized_hlo[:optimized_hlo.find("\n")],
        "n_instructions": len(instrs),
        "n_backward_compute": len(backward),
        "final_backward_pos": final_backward,
        "collectives": collectives,
        "overlappable_reduce_scatters": sum(
            1 for c in collectives
            if c["kind"] == "reduce-scatter" and c["backward_after"] > 0),
        "combined": combined,
        # collectives inside cond/while region computations (gradient
        # merge): fenced by construction — a conditional executes as
        # one unit, nothing inside can overlap the entry schedule
        "region_collectives": region_collectives,
    }


def _compile_dp(fn, mesh, dp_axis, program, block, feed_names, fetch_names,
                state_mut, state_ro, donate, feed_donate=False,
                shard_plan=None, tp_plan=None, state_out=None):
    """Data-parallel lowering: shard_map over the mesh; feeds sharded on
    axis 0, state replicated. Collective ops inside see the live axis and
    emit psum over ICI (reference flow: transpiler/collective.py:178-268 +
    c_allreduce kernels -> here SURVEY.md §3C TPU mapping). With a
    shard_plan, optimizer-state vars get P(dp_axis) in/out specs — their
    scope arrays are flat buffers sharded over the mesh, so per-replica
    optimizer HBM is ~1/N across steps (ZeRO-1).

    With a tp_plan, state splits into FOUR layouts: replicated P();
    ZeRO flat buffers P(dp); ZeRO flat buffers of model-sharded vars
    P((model, dp)) — the model-major concat of per-member local flats;
    and model-sharded params P(model @ their tp_dim) — the scope keeps
    LOGICAL shapes, shard_map hands each device its local block
    (save-logical / restore-sharded falls out of the specs, no
    checkpoint special-casing)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..parallel import env as penv

    ndev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    axes = {a: mesh.shape[a] for a in mesh.axis_names}
    # vocab-sharded embedding tables + per-row moments share the
    # dp-axis in/out spec with the ZeRO flat buffers: P(dp_axis) on a
    # (padded_rows, dim) buffer shards dim 0 over the (intra-pod)
    # axis and replicates across dcn pods — the same layout rule
    sparse_plan = getattr(program, "_sparse_plan", None)
    row_sharded = frozenset(sparse_plan.state_vars) \
        if sparse_plan is not None else frozenset()
    sharded_names = (frozenset(shard_plan.sharded_state)
                     if shard_plan is not None else frozenset()) \
        | row_sharded
    # hybrid (dcn, ici) mesh: data (batch) shards over BOTH data axes —
    # row-major, so device (pod p, chip j) holds the same batch slice
    # as flat device p*ici+j — while sharded opt-state stays P(ici)
    # only (each pod holds a full copy of the 1/ici shards). The model
    # axis NEVER carries data: its mp members duplicate the batch slice
    # and hold distinct weight shards instead.
    hier = penv.mesh_hierarchy(mesh)
    data_axes = (hier[0], hier[1]) if hier is not None else dp_axis
    mp_axis = tp_plan.model_axis if tp_plan is not None else None
    # ZeRO'd vars that are ALSO model-sharded ride P((model, dp)) flat
    # buffers; model-sharded vars NOT in ZeRO state (live params, or
    # moments when the ZeRO planner declined) keep logical shapes in
    # scope with P(model @ tp_dim)
    zero_tp = frozenset(
        n for n, info in shard_plan.sharded_state.items()
        if info.tp_dim is not None) if shard_plan is not None \
        else frozenset()
    tp_only = frozenset(tp_plan.var_dims) - sharded_names \
        if tp_plan is not None else frozenset()

    def tp_spec(n):
        return tp_plan.spec_for(n)

    def wrapped(feeds, states_mut, states_ro, seed):
        with penv.collective_scope(axes):
            fetches, new_states = fn(feeds, states_mut, states_ro, seed)
        # split state outs by layout: shard_map needs distinct out
        # specs for replicated vs dp-sharded vs model-sharded state
        rep, sh, sh_ztp, sh_tp = {}, {}, {}, {}
        for n, v in new_states.items():
            if n in zero_tp:
                sh_ztp[n] = v
            elif n in sharded_names:
                sh[n] = v
            elif n in tp_only:
                sh_tp[n] = v
            else:
                rep[n] = v
        return fetches, rep, sh, sh_ztp, sh_tp

    feed_specs = {n: P(data_axes) for n in feed_names}

    def state_spec(n):
        if n in zero_tp:
            return P((mp_axis, dp_axis))
        if n in sharded_names:
            return P(dp_axis)
        if n in tp_only:
            return tp_spec(n)
        return P()

    state_specs_mut = {n: state_spec(n) for n in state_mut}
    # forward-only programs hold their sparse tables (and model-sharded
    # params) as read-only state — still sharded
    state_specs_ro = {n: state_spec(n) if n in tp_only
                      else (P(dp_axis) if n in row_sharded else P())
                      for n in state_ro}
    # out specs for the model-sharded group need the per-name tp_dim, so
    # the names must be static: state_out is the traced fn's exact
    # new_states key set
    out_names = state_out if state_out is not None else state_mut
    tp_out_specs = {n: tp_spec(n) for n in out_names if n in tp_only}

    def out_spec_for_fetch(n):
        if sparse_plan is not None and (
                n in row_sharded or n in sparse_plan.grad_of):
            # gathered table / densified SelectedRows grad: replicated
            return P()
        v = block._find_var_recursive(n)
        if v is not None and v.persistable:
            return P()
        return P(data_axes)

    # state_out names are discovered inside fn; replicated except the
    # plan's sharded optimizer state
    fetch_specs = [out_spec_for_fetch(n) for n in fetch_names]

    from ..parallel.env import shard_map_compat

    smapped = shard_map_compat(
        wrapped, mesh=mesh,
        in_specs=(feed_specs, state_specs_mut, state_specs_ro, P()),
        out_specs=(fetch_specs, P(), P(dp_axis),
                   P((mp_axis, dp_axis)) if mp_axis is not None
                   else P(dp_axis), tp_out_specs),
        check_vma=False)

    def merged(feeds, states_mut, states_ro, seed):
        fetches, rep, sh, sh_ztp, sh_tp = smapped(
            feeds, states_mut, states_ro, seed)
        rep.update(sh)
        rep.update(sh_ztp)
        rep.update(sh_tp)
        return fetches, rep

    return jax.jit(merged,
                   donate_argnums=_donate_argnums(donate, feed_donate))
