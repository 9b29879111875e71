"""Coalesced optimizer updates — the reference's
fuse_optimizer_ops_pass family (`framework/ir/fuse_optimizer_ops_pass/`:
fuse_sgd/momentum/adam over coalesced gradient buffers), re-done as a
program rewrite: N same-configured sgd/momentum/adam ops collapse into
ONE fused_* Program op whose compute applies the single-tensor kernel to
each member (ops/optimizer_ops.py fused_*), so the math is bit-identical
to the unfused ops.

What it buys: one op instead of N in the Program (fewer ops to trace and
to verify). It does NOT concatenate the members into a flat vector: that
form handed the TPU compiler a hundred-million-element 1-D array, which
it padded 64-fold and refused (BERT-base: 34 GB). At run time XLA's
horizontal fusion merges the per-member loops either way, so the bench
builders no longer call the pass; `BuildStrategy.fuse_all_optimizer_ops`
still does.

Entry points: `fuse_optimizer_ops(program)` (idempotent), honored by
`BuildStrategy.fuse_all_optimizer_ops` through Executor.run on a
CompiledProgram.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from . import lowering

# op type -> (input slots to coalesce, output slots produced per member)
_FUSABLE: Dict[str, Tuple[Tuple[str, ...], Tuple[str, ...]]] = {
    "sgd": (("Param", "Grad"), ("ParamOut",)),
    "momentum": (("Param", "Grad", "Velocity"),
                 ("ParamOut", "VelocityOut")),
    "adam": (("Param", "Grad", "Moment1", "Moment2", "Beta1Pow",
              "Beta2Pow"),
             ("ParamOut", "Moment1Out", "Moment2Out", "Beta1PowOut",
              "Beta2PowOut")),
}


def _attr_sig(op):
    return tuple(sorted(
        (k, repr(v)) for k, v in op.attrs.items()
        if not k.startswith("_") and k != "op_callstack"))


def fuse_optimizer_ops(program) -> int:
    """Fuse groups of same-configured optimizer ops in the global
    block. Returns the number of ops fused away. Idempotent (marks the
    program)."""
    if getattr(program, "_opt_fused", False):
        return 0
    block = program.global_block()
    ops = list(block.ops)
    # one recursive (reads, writes) walk per op, shared by every group's
    # interference scan below (groups typically span the whole tail)
    rw = [lowering._op_reads_writes(op) for op in ops]
    rw = [(set(r), set(w)) for r, w in rw]

    groups: Dict[tuple, List[int]] = {}
    for i, op in enumerate(ops):
        if op.type not in _FUSABLE:
            continue
        in_slots, _ = _FUSABLE[op.type]
        if any(len(op.input_names.get(s, [])) != 1 for s in in_slots):
            continue
        lr = op.input_names.get("LearningRate", [""])
        pvar = block._find_var_recursive(op.input_names["Param"][0])
        dtype = str(getattr(pvar, "dtype", "float32"))
        key = (op.type, _attr_sig(op), lr[0], dtype)
        groups.setdefault(key, []).append(i)

    fused_away = 0
    to_remove = set()
    inserts = []  # (position, new op ctor args)
    for key, idxs in groups.items():
        if len(idxs) < 2:
            continue
        op_type, _, lr_name, _ = key
        in_slots, out_slots = _FUSABLE[op_type]
        members = [ops[i] for i in idxs]
        written = set()
        member_reads = set()
        for m in members:
            for names in m.output_names.values():
                written.update(names)
            for names in m.input_names.values():
                member_reads.update(names)
        # safety: ops interleaved with the group must not (a) touch the
        # group's outputs — a reader between two member updates would
        # observe a different schedule after fusion — nor (b) WRITE any
        # member input (a grad rescaled between members would be read
        # post-mutation by the fused op planted at the last position)
        member_ids = {id(m) for m in members}
        safe = True
        for j in range(min(idxs), max(idxs) + 1):
            op = ops[j]
            if id(op) in member_ids:
                continue
            # recursive touch sets: a control-flow op whose sub-block
            # reads/writes group vars is interference too (ADVICE r4 —
            # input/output_arg_names don't surface sub-block accesses)
            reads_j, writes_j = rw[j]
            if (reads_j | writes_j) & written:
                safe = False
                break
            if writes_j & member_reads:
                safe = False
                break
        if not safe:
            continue

        inputs = {slot: [block._find_var_recursive(
            m.input_names[slot][0]) for m in members]
            for slot in in_slots}
        if lr_name:
            inputs["LearningRate"] = [
                block._find_var_recursive(lr_name)]
        outputs = {slot: [block._find_var_recursive(
            m.output_names[slot][0]) for m in members]
            for slot in out_slots}
        attrs = {k: v for k, v in members[0].attrs.items()
                 if not k.startswith("_")}
        inserts.append((max(idxs), "fused_" + op_type, inputs, outputs,
                        attrs))
        to_remove.update(idxs)
        fused_away += len(members) - 1

    if not inserts:
        program._opt_fused = True
        return 0

    # splice: walk ops in order, dropping members and planting each
    # fused op at its group's LAST member position (every grad/decay
    # producer has run by then; the safety check above guarantees no
    # interleaved consumer)
    insert_at = {pos: args for pos, *args in inserts}
    new_ops = []
    for i, op in enumerate(ops):
        if i in insert_at:
            t, ins_, outs_, attrs_ = insert_at[i]
            fused = block.append_op(type=t, inputs=ins_, outputs=outs_,
                                    attrs=attrs_)
            block.ops.pop()  # append_op put it at the tail
            new_ops.append(fused)
            continue
        if i in to_remove:
            continue
        new_ops.append(op)
    block.ops = new_ops
    program._version += 1
    program._opt_fused = True
    return fused_away
