"""Checker 5 — per-op dtype/shape contract check.

The block's declared var dtypes/shapes are the program's CONTRACT: the
executor sizes feed buffers, the checkpoint layer sizes restores, and
the sharded-update planner sizes shard layouts from them. The actual
values come from each op's registered compute (`ops/registry.py`) at
trace time. This checker replays compile-time inference
(`ops_lib.infer_outputs` — the same jax.eval_shape path
`Block._infer_op_shapes` uses at build time) for every registered op
and diffs the inferred output dtype/shape against the declaration, so
drift introduced AFTER append_op (a transpiler rewriting input slots, a
pass mutating attrs, a hand-edited var) surfaces before it becomes a
runtime shape error — or worse, doesn't.

Special attention to **silent fp64 promotion**: an op whose inferred
output is float64 while no input is float64 doubles the payload bytes
of everything downstream (and fp64 runs on TPU's slow path); it almost
always means a python float leaked into a jnp op under x64. Flagged
even when the declaration agrees.

All findings are warnings: a drifted declaration is usually a latent
bug, but the traced value (not the declaration) is what actually runs,
so nothing here is a proven wrong answer.

Skipped by design: `no_jit` host ops (their shape probe EXECUTES the
compute — printing, saving files...), `dynamic_shape` ops (the contract
is value-dependent), framework pseudo-ops (feed/fetch/backward/control
flow — not registered), and ops whose inference raises (same contract
as Block._infer_op_shapes: leave declared shapes alone).

AMP awareness (programs marked by `mixed_precision.decorate`): the AMP
pass inserts its casts at TRACE time, invisible to declarations — so a
float32<->compute-dtype disagreement is the policy working, not drift,
and is suppressed; likewise the fp64-promotion check never fires on
white-listed ops (they run in the 16-bit dtype at runtime, where an
inferred f64 cannot occur). New ``redundant-cast`` warnings flag cast
round-trips the AMP pass should have elided: an explicit
``cast(cast(x, f32), bf16)`` chain whose intermediate has no other
reader, and an up-cast to fp32 feeding ONLY white-list ops (the policy
re-casts those inputs straight back down).

Quantized programs (`check_quantization_contracts`, run as part of the
same checker): a slim/PTQ fake-quant op that consumes a scale and is
missing its calibrated scale input is an **error**, and so is a PTQ
inference quantizer under ``is_test`` without its ``static_scale``.
"""
from __future__ import annotations

from typing import List

from .findings import Finding

#: slim/PTQ quantizer ops -> the input slot(s) carrying their
#: calibrated scale; empty slot = uncalibrated quantization.
_QUANT_SCALE_SLOTS = {
    "fake_quantize_moving_average_abs_max": ("InScale",),
    "fake_quantize_dequantize_moving_average_abs_max": ("InScale",),
    "fake_quantize_range_abs_max": ("InScale",),
    "fake_dequantize_max_abs": ("Scale",),
    "dequantize_abs_max": ("Scale",),
    "fake_channel_wise_dequantize_max_abs": ("Scales",),
}


def _shapes_conflict(declared, inferred):
    """True when two shape tuples disagree on a STATIC dim (-1 on
    either side is a wildcard)."""
    if len(declared) != len(inferred):
        # rank drift, except the common scalar () vs (1,) looseness the
        # builder layer tolerates everywhere; -1 stays a wildcard here
        # too (a declared (-1,) against an inferred (8, 1) is not drift)
        flat_d = [d for d in declared if d != 1]
        flat_i = [d for d in inferred if d != 1]
        if len(flat_d) != len(flat_i):
            return True
        return any(a != b for a, b in zip(flat_d, flat_i)
                   if int(a) >= 0 and int(b) >= 0)
    return any(a != b for a, b in zip(declared, inferred)
               if int(a) >= 0 and int(b) >= 0)


def _is_f64_request(attr_value):
    """True for attr values that name the float64 dtype (strings and
    numpy dtypes only — float VALUES like a 2.0 scale are not dtype
    requests)."""
    import numpy as np

    if isinstance(attr_value, str):
        return attr_value in ("float64", "double", "fp64")
    return isinstance(attr_value, np.dtype) and \
        attr_value == np.dtype("float64")


def _amp_policy_of(program):
    """(amp_lists, low_dtype_name) for AMP programs, else (None, None)."""
    if not getattr(program, "_amp", False):
        return None, None
    lists = getattr(program, "_amp_lists", None)
    if lists is None:
        return None, None
    return lists, str(getattr(program, "_amp_dtype", "bfloat16"))


def check_dtype_shape_contracts(program) -> List[Finding]:
    from .. import ops as ops_lib

    amp_lists, amp_low = _amp_policy_of(program)

    def amp_mixed_ok(a, b):
        # under AMP the trace-time casts make EITHER side of the
        # f32<->compute-dtype pair a legitimate declaration
        return amp_lists is not None and {str(a), str(b)} == \
            {"float32", amp_low}

    findings: List[Finding] = []
    findings += _check_redundant_casts(program, amp_lists, amp_low)
    for block in program.blocks:
        for op_idx, op in enumerate(block.ops):
            if not ops_lib.has_op(op.type):
                continue  # framework pseudo-op (feed/fetch/backward/...)
            opdef = ops_lib.get_op(op.type)
            if opdef.no_jit or opdef.dynamic_shape:
                continue
            in_specs = {}
            missing = False
            any_f64_in = False
            for slot, names in op.input_names.items():
                if not names:
                    continue
                specs = []
                for n in names:
                    v = block._find_var_recursive(n)
                    if v is None:
                        missing = True
                        break
                    dt = str(v.dtype)
                    any_f64_in = any_f64_in or dt == "float64"
                    specs.append((tuple(v.shape), dt))
                if missing:
                    break
                in_specs[slot] = specs
            if missing:
                continue
            amp_white = amp_lists is not None and \
                op.type in amp_lists.white_list
            if not any_f64_in and not amp_white:
                # white-listed ops under AMP run in the 16-bit compute
                # dtype at runtime — a promotion to f64 cannot occur
                # there, so the check would only mis-flag them
                f64_attrs = [k for k, v in op.attrs.items()
                             if _is_f64_request(v)]
                if f64_attrs:
                    # the request itself is the leak: under the default
                    # x64-off config jax truncates it to f32 at trace
                    # time (so declaration AND compute agree on f32 and
                    # no drift would ever fire) — the op still asked
                    # for a dtype the program doesn't get
                    findings.append(Finding(
                        "dtype-contract", "warning",
                        "silent fp64 promotion: op requests float64 "
                        "via attr(s) %s from non-float64 inputs — 2x "
                        "payload bytes downstream and TPU's slow path "
                        "when x64 is on, a silent truncation to f32 "
                        "when off; a python-side float64 likely "
                        "leaked into the op." % (f64_attrs,),
                        block_idx=block.idx, op_idx=op_idx,
                        op_type=op.type,
                        var=(op.output_arg_names or [None])[0]))
            try:
                out_specs = ops_lib.infer_outputs(op.type, in_specs,
                                                  dict(op.attrs))
            except Exception:  # noqa: BLE001 - same contract as append_op
                continue
            for slot, names in op.output_names.items():
                specs = out_specs.get(slot, [])
                for n, spec in zip(names, specs):
                    v = block._find_var_recursive(n)
                    if v is None:
                        continue
                    inf_shape = tuple(spec[0])
                    inf_dtype = str(spec[1])
                    decl_dtype = str(v.dtype)
                    loc = dict(block_idx=block.idx, op_idx=op_idx,
                               op_type=op.type, var=n)
                    if not any_f64_in and not amp_white and \
                            "float64" in (inf_dtype, decl_dtype):
                        # inferred f64 only appears with x64 enabled;
                        # a DECLARED f64 out from non-f64 inputs is the
                        # same leak seen from the contract side (under
                        # the default x64-off config it silently
                        # truncates to f32 at trace time)
                        findings.append(Finding(
                            "dtype-contract", "warning",
                            "silent fp64 promotion: output %r is "
                            "float64 (declared %s, computed %s) from "
                            "non-float64 inputs — 2x the payload "
                            "bytes downstream and TPU's slow path "
                            "when x64 is on, a silent truncation to "
                            "f32 when off; a python float likely "
                            "leaked into the op." % (
                                n, decl_dtype, inf_dtype),
                            **loc))
                    elif inf_dtype != decl_dtype and \
                            not amp_mixed_ok(inf_dtype, decl_dtype):
                        findings.append(Finding(
                            "dtype-contract", "warning",
                            "out var %r declares dtype %s but the "
                            "registered compute produces %s — the "
                            "declaration (what feeds/checkpoints/"
                            "shard planning size against) has "
                            "drifted from the traced value." % (
                                n, decl_dtype, inf_dtype),
                            **loc))
                    decl_shape = tuple(v.shape)
                    if _shapes_conflict(decl_shape, inf_shape):
                        findings.append(Finding(
                            "dtype-contract", "warning",
                            "out var %r declares shape %s but the "
                            "registered compute produces %s." % (
                                n, decl_shape, inf_shape),
                            **loc))
    return findings


def check_quantization_contracts(program) -> List[Finding]:
    """Quantization-tier contracts (part of the dtype-contract
    checker): calibrated-scale presence on the slim/PTQ fake-quant ops.
    See the module docstring; these are ERRORS, not warnings — each one
    is a proven wrong-math path, not a drifted declaration."""
    findings: List[Finding] = []
    for block in program.blocks:
        for op_idx, op in enumerate(block.ops):
            slots = _QUANT_SCALE_SLOTS.get(op.type)
            if slots is not None:
                for slot in slots:
                    names = op.input_names.get(slot) or []
                    if not names or any(
                            block._find_var_recursive(n) is None
                            for n in names):
                        findings.append(Finding(
                            "dtype-contract", "error",
                            "quantizer op %r is missing its calibrated "
                            "scale input %r — it would (de)quantize "
                            "with no scale at all." % (op.type, slot),
                            block_idx=block.idx, op_idx=op_idx,
                            op_type=op.type,
                            var=(op.output_arg_names or [None])[0]))
            if op.type in ("fake_quantize_abs_max",
                           "fake_quantize_dequantize_abs_max") and \
                    op.attrs.get("is_test") and \
                    op.attrs.get("static_scale") is None:
                findings.append(Finding(
                    "dtype-contract", "error",
                    "PTQ inference quantizer %r runs with is_test but "
                    "no calibrated static_scale — inference would "
                    "re-derive scales per batch, losing the "
                    "calibration." % (op.type,),
                    block_idx=block.idx, op_idx=op_idx,
                    op_type=op.type,
                    var=(op.output_arg_names or [None])[0]))
    return findings


def _itemsize(dtype_name):
    try:
        from ..core.types import to_numpy_dtype
        import numpy as np

        return np.dtype(to_numpy_dtype(dtype_name)).itemsize
    except Exception:  # noqa: BLE001 - unknown dtype name: no opinion
        return 0


def _check_redundant_casts(program, amp_lists, amp_low) -> List[Finding]:
    """redundant-cast: cast round-trips the AMP pass should have elided.

    (a) ``z = cast(y, D)`` where ``y = cast(x, _)`` with x's dtype == D,
        y at least as wide as D (the LOSSLESS direction — bf16 -> fp32
        -> bf16 is an identity; fp32 -> bf16 -> fp32 is an intended
        truncation) and y has no other reader: the pair burns two
        converts and an HBM round-trip of the full tensor for nothing.
    (b) AMP programs only: ``y = cast(x, float32)`` where x is the
        16-bit compute dtype and EVERY reader of y is a white-list op —
        the trace-time policy casts white-list inputs straight back
        down, so the explicit up-cast round-trips by construction.
    """
    from ..fluid import lowering

    findings: List[Finding] = []
    for block in program.blocks:
        readers: dict = {}  # var -> [ops reading it]
        for op in block.ops:
            # _op_reads_writes descends into while/scan/cond bodies: a
            # sub-block read of the cast intermediate must count, or
            # both warnings below fire on casts a loop body depends on
            # (the reader recorded is the ENCLOSING control-flow op,
            # which is never white-listed — conservative for rule (b))
            for n in set(lowering._op_reads_writes(op)[0]):
                readers.setdefault(n, []).append(op)
        cast_src: dict = {}  # var -> source dtype of the cast chain
        producer: dict = {}  # var -> last writer op type
        for op_idx, op in enumerate(block.ops):
            if op.type != "cast":
                for n in op.output_arg_names:
                    cast_src.pop(n, None)
                    producer[n] = op.type
                continue
            x = (op.input_names.get("X") or [None])[0]
            out = (op.output_names.get("Out") or [None])[0]
            if x is None or out is None:
                continue
            xv = block._find_var_recursive(x)
            out_dt = str(op.attrs.get("out_dtype", ""))
            # the dtype BEFORE the producer cast (its input's dtype) —
            # a round trip closes when this cast restores it
            src_dt = cast_src.get(x)
            loc = dict(block_idx=block.idx, op_idx=op_idx,
                       op_type=op.type, var=out)
            x_dt = str(getattr(xv, "dtype", "")) if xv is not None \
                else ""
            if producer.get(x) == "cast" and src_dt and \
                    src_dt == out_dt and \
                    _itemsize(x_dt) >= _itemsize(out_dt) and \
                    len(readers.get(x, [])) == 1:
                findings.append(Finding(
                    "dtype-contract", "warning",
                    "redundant-cast: %r round-trips %s -> %s -> %s "
                    "through single-use intermediate %r — the pair is "
                    "an identity the AMP pass should have elided." % (
                        out, src_dt,
                        str(getattr(xv, "dtype", "?")) if xv is not None
                        else "?", out_dt, x),
                    **loc))
            elif amp_lists is not None and out_dt == "float32" and \
                    str(getattr(xv, "dtype", "")) == amp_low and \
                    out not in (getattr(amp_lists, "black_varnames",
                                        None) or ()):
                # a black-named var is PINNED to fp32 — the policy
                # skips the down-cast for it, so this up-cast is
                # load-bearing, not redundant
                outs_readers = readers.get(out, [])
                if outs_readers and all(
                        r.type in amp_lists.white_list
                        for r in outs_readers):
                    findings.append(Finding(
                        "dtype-contract", "warning",
                        "redundant-cast: %r up-casts %s -> float32 but "
                        "every reader is a white-list op — the AMP "
                        "policy casts those inputs straight back to "
                        "%s; drop the explicit cast." % (
                            out, amp_low, amp_low),
                        **loc))
            producer[out] = "cast"
            cast_src[out] = str(op.attrs.get("in_dtype", "") or x_dt)
    return findings
