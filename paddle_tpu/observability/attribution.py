"""Per-op resource attribution: provenance from the fluid Program IR
through StableHLO/optimized HLO to HBM and device-time blame, with an
OOM pre-flight gate and crash forensics.

PR 7 made the runtime observable at the *step phase* level; this module
names the *framework op* (layer, bucket, buffer class) behind a byte or
a microsecond. Three pieces:

1. **Provenance stamping** — `fluid/lowering.py` wraps every traced op
   in a `jax.named_scope` carrying a compact marker
   (`pp[b<block>;o<op_idx>;<op_type>;<out_var>]`; collectives get
   `pp[bucket;<id>;scatter|gather]` / `pp[gsync;<grad>]` /
   `pp[gather;<var>]` / `pp[amp;found_inf]` stamps from
   `parallel/sharded_update.py`). The scope rides jax's name stack into
   BOTH HLO forms: the lowered StableHLO's `loc("...")` debug locations
   and the optimized HLO's `metadata={op_name="..."}` — and the vjp
   transpose re-emits forward scopes inside `transpose(...)` paths, so
   backward ops attribute to their forward op for free. `@` is the one
   character XLA truncates op_name metadata at, so markers encode it as
   `!` (`fc_0.w_0@GRAD` -> `fc_0.w_0!GRAD`).

2. **HBM attribution** — `build_report` decomposes the compiled
   executable's `memory_analysis()` peak into buffer classes (feed /
   param / master / opt_state / grad_bucket / state_other from the
   Program + ShardedUpdatePlan, activation from the optimized HLO's
   stamped instruction result bytes), per framework op / layer, with a
   `cross_check` block proving the class totals equal the
   already-trusted `Executor.donation_report` numbers. Surfaced as
   `Executor.attribution_report`, the bench `attribution` block
   (observability/publish.py) and `tools/perf_analysis.py
   --attribution`.

3. **OOM pre-flight + forensics** — `FLAGS_tpu_hbm_budget_mb` arms a
   pre-dispatch gate: the executor AOT-compiles a fresh entry, models
   peak HBM (memory_analysis + prefetch feed buffers) and raises
   `HbmBudgetExceeded` (a structured `ResourceExhaustedError` naming
   the top-k consumers) BEFORE the first dispatch. A real
   `RESOURCE_EXHAUSTED` in the dispatch path lands the attributed
   breakdown in the flight-recorder dump (`record_oom_forensics`), so
   the postmortem answers "what was resident" without a repro.

`time_attribution` folds a profile's device time (the `XLA Ops` thread
of each `/device:TPU:<n>` process in the `trace.json.gz` sidecar that
`jax.profiler.stop_trace` writes beside the `.xplane.pb`; self times, a
loop's body counted once) back through the markers and the name stack
to per-op / per-layer / per-bucket time and to the step's regions:
forward, recompute, backward, update, collective. The sidecar is the
file to read because `jax.profiler.ProfileData` hands out an event's
own stats and not its metadata's, where the scope path lives.
`perf_analysis.py --stragglers --xplane-dir D` blames a *layer*, not
just a phase; the benchmark's `device_*_ms` metrics read the regions.
An op whose code names its parts (`part_scope`: `pt[<part>]` scopes
under the op's own marker) is folded one level further, by op type,
part and region (`by_op_part`, `op_part_table`).
"""
from __future__ import annotations

import contextlib
import re
from typing import Dict, List, Optional

import numpy as np

from ..core.errors import ResourceExhaustedError

__all__ = [
    "enabled", "op_marker", "op_scope", "marker_scope", "bucket_marker",
    "grad_sync_marker", "gather_marker", "amp_marker", "part_scope",
    "parse_marker", "provenance_of", "part_of", "layer_of",
    "stablehlo_debug_asm",
    "collective_provenance", "hlo_activation_provenance",
    "optimizer_state_vars", "classify_state_var", "build_report",
    "cross_check_donation", "static_breakdown", "budget_bytes",
    "HbmBudgetExceeded", "is_resource_exhausted",
    "record_oom_forensics", "load_trace_events", "device_op_rows",
    "region_of", "time_attribution", "op_part_table", "REGIONS",
]

#: marker grammar: `pp[<field>;<field>;...]` — `;` and `]` never occur
#: in fluid var names, and every other marker character survives XLA's
#: op_name metadata verbatim (only `@` is truncated — see _sanitize)
_MARKER_RE = re.compile(r"pp\[([^\[\]]+)\]")
#: a part of one op, named where the op's code makes it: `pt[<part>]`
_PART_RE = re.compile(r"pt\[([a-z0-9_]+)\]")

_AT_ESCAPE = "!"  # '@' truncates HLO op_name metadata; '!' survives


def _sanitize(name) -> str:
    return str(name).replace("@", _AT_ESCAPE)


def _unsanitize(text) -> str:
    return text.replace(_AT_ESCAPE, "@")


def enabled() -> bool:
    """FLAGS_tpu_op_provenance (default on): stamping costs one python
    context manager per op at TRACE time only — nothing at runtime."""
    from ..utils.flags import get_flag

    return bool(get_flag("FLAGS_tpu_op_provenance", True))


# ---------------------------------------------------------------------------
# markers & trace-time stamping
# ---------------------------------------------------------------------------

def op_marker(op, op_idx) -> str:
    """Provenance marker of one fluid op: block idx / op idx / op type /
    first output var (the name HBM+time blame reports lead with)."""
    outs = op.output_arg_names
    out = _sanitize(outs[0]) if outs else ""
    blk = getattr(op.block, "idx", 0)
    return "pp[b%d;o%d;%s;%s]" % (blk, int(op_idx), op.type, out)


def bucket_marker(index, action="scatter") -> str:
    """PR-4 bucketed collectives: `pp[bucket;<id>;scatter|gather]`."""
    return "pp[bucket;%d;%s]" % (int(index), action)


def grad_sync_marker(var) -> str:
    """Per-variable gradient sync collective (pmean / reduce-scatter)."""
    return "pp[gsync;%s]" % _sanitize(var)


def gather_marker(var) -> str:
    """Param / fetched-value all-gather back to replicated form."""
    return "pp[gather;%s]" % _sanitize(var)


def amp_marker(what) -> str:
    """AMP machinery collectives (the found_inf psum)."""
    return "pp[amp;%s]" % _sanitize(what)


def marker_scope(marker):
    """`jax.named_scope(marker)` when provenance is on, else a no-op
    context. Safe inside and outside a trace."""
    if not enabled():
        return contextlib.nullcontext()
    import jax

    return jax.named_scope(marker)


def op_scope(op, op_idx):
    return marker_scope(op_marker(op, op_idx))


def part_scope(name):
    """A part of the op being traced, named by the op's own code: the
    scope `pt[<name>]` (`name` a short lower-case word, `[a-z0-9_]+`)
    under the op's `pp[...]` marker, a no-op context when provenance is
    off. `part_of` reads it back; `time_attribution` folds by it."""
    return marker_scope("pt[%s]" % name)


# ---------------------------------------------------------------------------
# marker recovery from HLO text
# ---------------------------------------------------------------------------

def parse_marker(body_or_marker) -> Optional[dict]:
    """Decode one marker (`pp[...]` or its bare body) into a dict:
    {"kind": "op", "block": int, "op_idx": int, "op_type": str,
    "var": str} | {"kind": "bucket", "bucket": int, "action": str} |
    {"kind": "grad_sync"|"gather", "var": str} |
    {"kind": "amp", "what": str}. None when unparsable."""
    text = body_or_marker
    m = _MARKER_RE.search(text)
    if m is not None:
        text = m.group(1)
    parts = text.split(";")
    try:
        if len(parts) == 4 and parts[0].startswith("b") \
                and parts[1].startswith("o"):
            return {"kind": "op", "block": int(parts[0][1:]),
                    "op_idx": int(parts[1][1:]), "op_type": parts[2],
                    "var": _unsanitize(parts[3])}
        if parts[0] == "bucket" and len(parts) >= 2:
            return {"kind": "bucket", "bucket": int(parts[1]),
                    "action": parts[2] if len(parts) > 2 else "scatter"}
        if parts[0] == "gsync" and len(parts) == 2:
            return {"kind": "grad_sync", "var": _unsanitize(parts[1])}
        if parts[0] == "gather" and len(parts) == 2:
            return {"kind": "gather", "var": _unsanitize(parts[1])}
        if parts[0] == "amp" and len(parts) == 2:
            return {"kind": "amp", "what": parts[1]}
    except ValueError:
        return None
    return None


def provenance_of(path) -> Optional[dict]:
    """Innermost marker in a scope path (an HLO `op_name` or a StableHLO
    loc string). Control-flow nesting stamps the parent op's scope
    OUTSIDE the sub-block op's, so the last marker is the true source;
    the vjp transpose path re-emits the forward scope the same way."""
    hits = _MARKER_RE.findall(path or "")
    if not hits:
        return None
    return parse_marker(hits[-1])


def part_of(path) -> Optional[str]:
    """The part (`part_scope`) a scope path lies in: the LAST `pt[...]`
    after the path's last `pp[...]` marker, else None. A part belongs
    to the innermost fluid op (a sub-block's ops carry their own marker
    inside their parent's, and a part before it is the parent's), and
    of two parts the inner one wins: jax keeps both through `jvp(`,
    `transpose(` and a `custom_vjp`'s rules
    (`.../pp[...]/jvp(pt[local])/pt[inverse]/dot_general`)."""
    path = path or ""
    marker = path.rfind("pp[")
    hits = _PART_RE.findall(path, marker) if marker >= 0 else None
    return hits[-1] if hits else None


def layer_of(var) -> str:
    """Layer key of a var name: the prefix before the first '.', with
    any '@...' role suffix stripped first ('encoder_layer_3.tmp_2' ->
    'encoder_layer_3', 'fc_0.w_0@GRAD' -> 'fc_0')."""
    name = str(var).split("@", 1)[0]
    return name.split(".", 1)[0] if name else str(var)


def stablehlo_debug_asm(lowered) -> Optional[str]:
    """The lowered StableHLO printed WITH debug locations (jax's default
    `as_text()` strips them): every op line ends in `loc(#locN)` and the
    `#locN = loc("<scope path>"(...))` definitions at the bottom carry
    the provenance markers. None when the IR is unavailable (eager
    fallback entries)."""
    try:
        ir = lowered.compiler_ir(dialect="stablehlo")
        return ir.operation.get_asm(enable_debug_info=True)
    except Exception:  # noqa: BLE001 - evidence, not gating
        return None


_LOC_DEF_RE = re.compile(r'^#loc(\d+)\s*=\s*loc\((.*)\)\s*$')
_LOC_REF_RE = re.compile(r"loc\(#loc(\d+)\)")
_LOC_INLINE_RE = re.compile(r'loc\("([^"]*)"')


def _loc_defs(asm) -> Dict[str, str]:
    defs = {}
    for line in asm.splitlines():
        m = _LOC_DEF_RE.match(line.strip())
        if m:
            defs[m.group(1)] = m.group(2)
    return defs


def _resolve_loc(body, defs, depth=0) -> Optional[str]:
    """A loc def body -> the first scope string containing a marker,
    following `#locN` references (fused locs) up to a small depth."""
    m = _MARKER_RE.search(body)
    if m is not None:
        return body
    if depth >= 4:
        return None
    for ref in re.findall(r"#loc(\d+)", body):
        sub = defs.get(ref)
        if sub:
            got = _resolve_loc(sub, defs, depth + 1)
            if got is not None:
                return got
    return None


def line_provenance(line, defs) -> Optional[dict]:
    """Marker of one StableHLO debug-asm line via its trailing loc."""
    m = _LOC_INLINE_RE.search(line)
    if m is not None:
        got = provenance_of(m.group(1))
        if got is not None:
            return got
    for ref in _LOC_REF_RE.findall(line):
        body = defs.get(ref)
        if body:
            resolved = _resolve_loc(body, defs)
            if resolved:
                return provenance_of(resolved)
    return None


def collective_provenance(stablehlo_asm) -> List[dict]:
    """Every collective in the lowered module (the census's own line
    scan — `lowering._hlo_collective_hits`, so the two can never count
    differently) mapped back to its provenance marker. Entries:
    {"kind": <hlo op>, "tensor_bytes": int, "provenance": dict|None}.
    The acceptance contract: provenance is non-None for every hit — a
    collective nobody stamped is a lowering path the map does not
    survive."""
    from ..fluid import lowering

    defs = _loc_defs(stablehlo_asm)
    out = []
    for kind, ttype, open_line, close_line in \
            lowering._hlo_collective_hits(stablehlo_asm):
        prov = line_provenance(close_line, defs) or \
            line_provenance(open_line, defs)
        out.append({"kind": kind,
                    "tensor_bytes": lowering._tensor_bytes(ttype),
                    "provenance": prov})
    return out


_HLO_CALLEE_RE = re.compile(r"(?:to_apply|calls|body)=%([\w.\-]+)")


_HLO_PARAM_IDX_RE = re.compile(r"\s*(\d+)\s*\)")
_HLO_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def hlo_activation_provenance(optimized_hlo, arg_names=None) -> dict:
    """Per-marker activation/temp byte attribution over the optimized
    HLO's ENTRY instructions: each non-parameter instruction's result
    bytes are charged to the marker in its `op_name` metadata. Two
    resolution fallbacks for instructions XLA strips metadata from:

    - wrapper instructions (the CPU backend outlines fusions into
      `call(...) to_apply=%parallel_*` whose call carries none, and
      layout-assignment fusions drop theirs) resolve through the
      CALLED computation's dominant (largest-result) marker-bearing
      instruction;
    - anything still unmarked inherits from its largest already-
      attributed operand — with `arg_names` (the flat jit argument
      order: sorted feeds, sorted mut state, sorted ro state, seed)
      entry parameters seed that chain as {"kind": "state"} records,
      so an XLA-inserted weight upcast blames its weight.

    Returns {"by_op": {key: {...}}, "by_layer": {layer: bytes},
    "matched_bytes", "unmatched_bytes", "backward_bytes"} — the
    instruction-result sum OVERSTATES live bytes (XLA reuses buffers),
    so callers use the matched FRACTION, not the absolute sum."""
    from ..fluid import lowering

    # pass 1: one walk over every computation — entry instructions
    # kept whole, non-entry computations reduced to their dominant
    # marker (max result bytes among marker-bearing instructions)
    instr_re = lowering._HLO_INSTR_RE
    opcode_re = lowering._HLO_OPCODE_RE
    opname_re = lowering._HLO_OPNAME_RE
    comp = None  # None = between computations; "" = ENTRY
    comp_best: Dict[str, tuple] = {}  # comp -> (bytes, prov, op_name)
    entries = []  # (name, opcode, nbytes, op_name, callee, rhs_tail)
    for line in optimized_hlo.splitlines():
        if line.startswith("ENTRY "):
            comp = ""
            continue
        if line.startswith("%"):
            comp = line.split(" ", 1)[0].lstrip("%")
            continue
        if line.startswith("}"):
            comp = None
            continue
        if comp is None:
            continue
        m = instr_re.match(line)
        if m is None:
            continue
        rhs = m.group(2)
        om = opcode_re.search(rhs)
        if om is None:
            continue
        opcode = om.group(1)
        nbytes = lowering._hlo_result_bytes(rhs[:om.start()])
        nm = opname_re.search(rhs)
        op_name = nm.group(1) if nm else ""
        if comp == "":
            cm = _HLO_CALLEE_RE.search(rhs)
            entries.append((m.group(1), opcode, nbytes, op_name,
                            cm.group(1) if cm else None,
                            rhs[om.end():]))
        elif op_name:
            prov = provenance_of(op_name)
            if prov is not None and \
                    nbytes >= comp_best.get(comp, (-1,))[0]:
                comp_best[comp] = (nbytes, prov, op_name)

    by_op: Dict[str, dict] = {}
    by_layer: Dict[str, int] = {}
    provs: Dict[str, dict] = {}   # entry instr name -> prov
    sizes: Dict[str, int] = {}    # entry instr name -> result bytes
    matched = unmatched = backward = 0
    for name, opcode, nbytes, op_name, callee, tail in entries:
        sizes[name] = nbytes
        if opcode == "parameter":
            # tail is the text after "parameter(" — the index leads it
            if arg_names:
                pm = _HLO_PARAM_IDX_RE.match(tail or "")
                idx = int(pm.group(1)) if pm else None
                if idx is not None and idx < len(arg_names):
                    provs[name] = {"kind": "state",
                                   "var": arg_names[idx]}
            continue
        if opcode in ("constant", "get-tuple-element", "tuple",
                      "bitcast"):
            # pass-through bookkeeping: carry the operand's provenance
            # without charging bytes
            for o in _HLO_OPERAND_RE.findall(tail):
                if o in provs:
                    provs[name] = provs[o]
                    break
            continue
        prov = provenance_of(op_name)
        if prov is None and callee and callee in comp_best:
            _b, prov, op_name = comp_best[callee]
        if prov is None:
            # operand inheritance: blame the largest attributed input
            best = -1
            for o in _HLO_OPERAND_RE.findall(tail):
                p = provs.get(o)
                if p is not None and sizes.get(o, 0) > best:
                    best = sizes.get(o, 0)
                    prov = p
        if prov is not None:
            provs[name] = prov
        if not nbytes:
            continue
        if prov is None:
            unmatched += nbytes
            continue
        matched += nbytes
        if op_name and lowering._is_backward_opname(op_name):
            backward += nbytes
        key = _prov_key(prov)
        rec = by_op.setdefault(key, {
            "provenance": prov, "bytes": 0, "instructions": 0})
        rec["bytes"] += nbytes
        rec["instructions"] += 1
        var = prov.get("var")
        if var:
            lk = layer_of(var)
            by_layer[lk] = by_layer.get(lk, 0) + nbytes
    return {"by_op": by_op, "by_layer": by_layer,
            "matched_bytes": matched, "unmatched_bytes": unmatched,
            "backward_bytes": backward}


def _prov_key(prov) -> str:
    """Stable display key of one provenance record."""
    k = prov.get("kind")
    if k == "op":
        return "b%d/o%d %s -> %s" % (prov["block"], prov["op_idx"],
                                     prov["op_type"], prov["var"])
    if k == "bucket":
        return "bucket %d (%s)" % (prov["bucket"], prov["action"])
    if k in ("grad_sync", "gather", "state"):
        return "%s %s" % (k, prov["var"])
    if k == "amp":
        return "amp %s" % prov["what"]
    return str(prov)


# ---------------------------------------------------------------------------
# buffer-class attribution
# ---------------------------------------------------------------------------

def optimizer_state_vars(block) -> set:
    """Optimizer accumulator vars of a block, found STRUCTURALLY: an op
    carrying Param+Grad slots that reads AND writes the same non-Param
    var (Moment1/Moment1Out, velocity, beta pow accumulators, ...) is an
    optimizer update; the in/out var is its state. Robust to the
    unique_name suffixes the name-based guesses would miss."""
    out = set()
    for op in block.ops:
        ins = op.input_names
        if "Param" not in ins or "Grad" not in ins:
            continue
        params = set(ins.get("Param", []))
        reads = {n for names in ins.values() for n in names}
        for slot, names in op.output_names.items():
            if slot == "ParamOut":
                continue
            for n in names:
                if n in reads and n not in params:
                    out.add(n)
    return out


def classify_state_var(name, block, masters, opt_state, plan=None):
    """Buffer class of one scope state var: "master" (AMP fp32 master
    weights), "opt_state" (moments / pow accumulators — sharded or
    not), "param" (framework Parameters and their 16-bit live copies),
    "state_other" (lr, counters, loss-scale state, BN stats...)."""
    from ..fluid import framework

    if name in masters:
        return "master"
    if name in opt_state or \
            (plan is not None and name in plan.sharded_state
             and name not in masters):
        return "opt_state"
    v = block._find_var_recursive(name)
    if isinstance(v, framework.Parameter):
        return "param"
    return "state_other"


def _aval_bytes(aval) -> int:
    shape = tuple(getattr(aval, "shape", ()) or ())
    return int(np.prod(shape or (1,))) * np.dtype(aval.dtype).itemsize


def _sharded_replica_bytes(info, ndev) -> int:
    return (info.padded // max(int(ndev), 1)) * info.dtype.itemsize


def state_attribution(program, block, plan, ndev, state_avals) -> dict:
    """Classify every state argument of the compiled step and size it
    PER REPLICA (a ZeRO-sharded flat buffer costs padded/N bytes on
    each device — the same accounting donation_report uses). Returns
    {"classes": {cls: bytes}, "vars": [{name, class, bytes, layer,
    sharded}...]} sorted by bytes descending."""
    masters = set((getattr(program, "_amp_master_of", None) or {})
                  .values())
    opt_state = optimizer_state_vars(block)
    sharded = dict(getattr(plan, "sharded_state", None) or {}) \
        if plan is not None else {}
    classes: Dict[str, int] = {}
    rows = []
    for name, aval in state_avals.items():
        cls = classify_state_var(name, block, masters, opt_state,
                                 plan=plan)
        info = sharded.get(name)
        nbytes = (_sharded_replica_bytes(info, ndev)
                  if info is not None else _aval_bytes(aval))
        classes[cls] = classes.get(cls, 0) + nbytes
        rows.append({"name": name, "class": cls, "bytes": nbytes,
                     "layer": layer_of(name),
                     "sharded": info is not None})
    rows.sort(key=lambda r: (-r["bytes"], r["name"]))
    return {"classes": classes, "vars": rows}


def build_report(program, block, plan, ndev, feed_avals, state_avals,
                 ma=None, optimized_hlo=None, stablehlo_asm=None,
                 topk=10, arg_names=None) -> dict:
    """The HBM attribution report (see module docstring). `ma` is a
    jax CompiledMemoryStats; `optimized_hlo` / `stablehlo_asm` are the
    compiled and lowered module texts (either may be None — the
    corresponding section is omitted); `arg_names` is the flat jit
    argument order for parameter-seeded operand inheritance."""
    st = state_attribution(program, block, plan, ndev, state_avals)
    classes = dict(st["classes"])
    feed_bytes = sum(_aval_bytes(a) for a in feed_avals.values())
    classes["feed"] = feed_bytes
    # per-class totals over the SHARDED state vars only (the numbers
    # donation_report's opt_state_per_replica_bytes covers) — computed
    # over the FULL var list, not the truncated display rows
    sharded_classes: Dict[str, int] = {}
    for r in st["vars"]:
        if r["sharded"]:
            sharded_classes[r["class"]] = \
                sharded_classes.get(r["class"], 0) + r["bytes"]

    # transient grad-bucket shard buffers (ZeRO-2 lifetimes): one shard
    # buffer per bucket coexists across the post section
    buckets = getattr(plan, "buckets", ()) if plan is not None else ()
    if buckets:
        classes["grad_bucket"] = sum(
            b.shard_numel(ndev) * b.dtype.itemsize for b in buckets)

    report = {
        "ndev": int(ndev),
        "classes": classes,
        "sharded_class_bytes": sharded_classes,
        "state_vars": st["vars"][:max(topk, 10)],
        "n_state_vars": len(st["vars"]),
        "feed_bytes": feed_bytes,
    }

    act = None
    if optimized_hlo:
        act = hlo_activation_provenance(optimized_hlo,
                                        arg_names=arg_names)
        top_ops = sorted(act["by_op"].items(),
                         key=lambda kv: -kv[1]["bytes"])[:topk]
        report["activation"] = {
            "by_op_top": [
                {"op": k, "bytes": v["bytes"],
                 "instructions": v["instructions"]}
                for k, v in top_ops],
            "by_layer": dict(sorted(act["by_layer"].items(),
                                    key=lambda kv: -kv[1])[:topk]),
            "matched_bytes": act["matched_bytes"],
            "unmatched_bytes": act["unmatched_bytes"],
            "backward_bytes": act["backward_bytes"],
        }

    if stablehlo_asm:
        colls = collective_provenance(stablehlo_asm)
        report["collectives"] = {
            "count": len(colls),
            "mapped": sum(1 for c in colls
                          if c["provenance"] is not None),
            "entries": colls,
        }

    if ma is not None:
        arg = int(getattr(ma, "argument_size_in_bytes", 0))
        out_b = int(getattr(ma, "output_size_in_bytes", 0))
        temp = int(getattr(ma, "temp_size_in_bytes", 0))
        alias = int(getattr(ma, "alias_size_in_bytes", 0))
        peak = max(arg + out_b + temp - alias, 1)
        # arguments are attributed by NAME (every class above); the
        # temp+output pool is attributed at the stamped fraction of the
        # instruction-result bytes (the sum itself overstates live
        # bytes — XLA reuses buffers — so the ratio is the honest
        # number, not the absolute sum)
        arg_attr = min(sum(classes.values()), arg)
        scratch = max(arg + out_b + temp - alias - arg_attr, 0)
        if act is not None and (act["matched_bytes"]
                                + act["unmatched_bytes"]) > 0:
            frac = act["matched_bytes"] / float(
                act["matched_bytes"] + act["unmatched_bytes"])
        else:
            frac = 0.0
        attributed = arg_attr + int(scratch * frac)
        report["memory"] = {
            "argument_bytes": arg, "output_bytes": out_b,
            "temp_bytes": temp, "alias_bytes": alias,
            "peak_model_bytes": peak,
            "attributed_bytes": attributed,
            "coverage": round(min(attributed / float(peak), 1.0), 4),
        }
    report["top_consumers"] = top_consumers(report, k=topk)
    return report


def top_consumers(report, k=5) -> List[dict]:
    """The k largest attributed buffers across classes: named state
    vars + the grad-bucket pool + the feed pool + top activation ops."""
    rows = [{"name": r["name"], "class": r["class"],
             "bytes": r["bytes"]} for r in report.get("state_vars", [])]
    if report.get("classes", {}).get("grad_bucket"):
        rows.append({"name": "<grad buckets>", "class": "grad_bucket",
                     "bytes": report["classes"]["grad_bucket"]})
    if report.get("feed_bytes"):
        rows.append({"name": "<feeds>", "class": "feed",
                     "bytes": report["feed_bytes"]})
    for ent in report.get("activation", {}).get("by_op_top", [])[:k]:
        rows.append({"name": ent["op"], "class": "activation",
                     "bytes": ent["bytes"]})
    rows.sort(key=lambda r: -r["bytes"])
    return rows[:k]


def cross_check_donation(report, donation) -> dict:
    """Prove the attribution class totals against the already-trusted
    donation_report numbers — EXACT equality, both sides computed from
    the same plan/program sources. Keys checked only when the donation
    report carries them (AMP / buckets absent on plain programs)."""
    classes = report.get("classes", {})
    checks = {}
    if donation is None:
        return {"ok": False, "reason": "no donation report", "keys": {}}

    def add(key, ours):
        theirs = donation.get(key)
        if theirs is None:
            return
        checks[key] = {"donation": int(theirs), "attribution": int(ours),
                       "ok": int(theirs) == int(ours)}

    add("param_bf16_bytes", classes.get("param", 0))
    add("param_master_bytes", classes.get("master", 0))
    add("grad_bucket_per_replica_bytes", classes.get("grad_bucket", 0))
    if "opt_state_per_replica_bytes" in donation:
        # donation sums EVERY sharded var (masters included); our
        # master/opt_state split re-partitions the same bytes
        sc = report.get("sharded_class_bytes", {})
        add("opt_state_per_replica_bytes",
            sc.get("master", 0) + sc.get("opt_state", 0))
    return {"ok": all(c["ok"] for c in checks.values()),
            "keys": checks}


# ---------------------------------------------------------------------------
# OOM pre-flight + forensics
# ---------------------------------------------------------------------------

class HbmBudgetExceeded(ResourceExhaustedError):
    """Pre-dispatch HBM budget violation (FLAGS_tpu_hbm_budget_mb):
    the compiled step's modeled peak exceeds the budget. Structured:
    `.predicted_bytes`, `.budget_bytes`, `.top_consumers` (list of
    {name, class, bytes} dicts, largest first)."""

    def __init__(self, predicted_bytes, budget_bytes, top):
        self.predicted_bytes = int(predicted_bytes)
        self.budget_bytes = int(budget_bytes)
        self.top_consumers = list(top)
        lines = "".join(
            "\n  %-12s %8.2f MB  %s" % (c["class"], c["bytes"] / 1e6,
                                        c["name"])
            for c in self.top_consumers)
        super().__init__(
            "predicted HBM peak %.2f MB exceeds FLAGS_tpu_hbm_budget_mb"
            " (%.2f MB); the program was NOT dispatched. Top consumers:"
            "%s\nShrink the batch, raise the budget, or shard more "
            "state (see Executor.attribution_report)."
            % (self.predicted_bytes / 1e6, self.budget_bytes / 1e6,
               lines))


def budget_bytes() -> Optional[int]:
    """The armed HBM budget in bytes, or None when pre-flight is off.
    FLAGS_tpu_hbm_budget_mb: 0/unset = off; > 0 = explicit MB budget;
    < 0 (or "auto") = the device's own HBM limit from
    `core.memory.memory_stats()["bytes_limit"]` (off when the backend
    does not report one — CPU meshes usually don't)."""
    from ..utils.flags import get_flag

    raw = get_flag("FLAGS_tpu_hbm_budget_mb", 0)
    if raw in (None, "", 0, 0.0, False):
        return None
    if isinstance(raw, str):
        if raw.strip().lower() == "auto":
            raw = -1
        else:
            try:
                raw = float(raw)
            except ValueError:
                return None
    mb = float(raw)
    if mb > 0:
        return int(mb * 1e6)
    from ..core import memory

    limit = memory.memory_stats().get("bytes_limit")
    return int(limit) if limit else None


def predicted_peak_bytes(ma, feed_bytes) -> int:
    """Pre-flight peak model: the compiled module's args + temps +
    outputs minus donated aliases, PLUS the input pipeline's prefetched
    feed buffers (FLAGS_tpu_prefetch_depth batches live in HBM ahead of
    the consuming step — the step's own feed args are already in the
    argument bytes)."""
    from ..utils.flags import get_flag

    depth = int(get_flag("FLAGS_tpu_prefetch_depth", 2) or 0)
    return (int(getattr(ma, "argument_size_in_bytes", 0))
            + int(getattr(ma, "output_size_in_bytes", 0))
            + int(getattr(ma, "temp_size_in_bytes", 0))
            - int(getattr(ma, "alias_size_in_bytes", 0))
            + int(feed_bytes) * max(depth, 0))


_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "RESOURCE EXHAUSTED",
                "Out of memory", "out of memory", "OOM")


def is_resource_exhausted(exc) -> bool:
    """Does this dispatch-path exception look like device OOM? Matches
    jax/XLA RESOURCE_EXHAUSTED runtime errors and the framework's own
    ResourceExhaustedError."""
    if isinstance(exc, ResourceExhaustedError):
        return True
    msg = str(exc)
    return any(m in msg for m in _OOM_MARKERS)


class _FakeAval:
    __slots__ = ("shape", "dtype")

    def __init__(self, shape, dtype):
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)


def static_breakdown(program, block, plan, ndev, feed_arrays=None,
                     state_names=None, scope=None, topk=5) -> dict:
    """Attribution classes WITHOUT touching XLA — safe to compute on a
    process that just hit RESOURCE_EXHAUSTED (no compile, no
    allocation): state classified from the Program/plan at scope (or
    declared) shapes, feeds at their array shapes. Used by the flight
    recorder's OOM forensics and as the pre-flight error detail."""
    avals = {}
    names = list(state_names or [])
    if not names:
        names = [n for n in block.vars]
    for n in names:
        v = None
        if scope is not None:
            v = scope.find_var(n)
        if v is None:
            bv = block._find_var_recursive(n)
            if bv is None or not getattr(bv, "persistable", False):
                continue
            from ..core.types import to_numpy_dtype

            shape = tuple(int(d) if d > 0 else 1
                          for d in (bv.shape or ()))
            avals[n] = _FakeAval(shape, to_numpy_dtype(bv.dtype))
        else:
            avals[n] = _FakeAval(tuple(getattr(v, "shape", ()) or ()),
                                 getattr(v, "dtype", np.float32))
    st = state_attribution(program, block, plan, ndev, avals)
    classes = dict(st["classes"])
    feed_bytes = 0
    for a in (feed_arrays or {}).values():
        shape = tuple(getattr(a, "shape", ()) or ())
        feed_bytes += int(np.prod(shape or (1,))) * \
            np.dtype(getattr(a, "dtype", np.float32)).itemsize
    classes["feed"] = feed_bytes
    buckets = getattr(plan, "buckets", ()) if plan is not None else ()
    if buckets:
        classes["grad_bucket"] = sum(
            b.shard_numel(ndev) * b.dtype.itemsize for b in buckets)
    rep = {"classes": classes, "state_vars": st["vars"][:topk * 2],
           "feed_bytes": feed_bytes}
    rep["top_consumers"] = top_consumers(rep, k=topk)
    rep["total_bytes"] = sum(classes.values())
    return rep


def record_oom_forensics(program, block, plan, ndev, feed_arrays,
                         state_names, scope, error) -> Optional[str]:
    """A real RESOURCE_EXHAUSTED left the dispatch path: land the
    attributed memory breakdown in the flight-recorder dump so the
    postmortem answers "what was resident" without a repro. Records an
    `oom` event (ring + JSONL) and dumps the flight recorder with the
    breakdown as the fatal event. Never raises — the original error is
    the one the caller re-raises."""
    try:
        breakdown = static_breakdown(program, block, plan, ndev,
                                     feed_arrays=feed_arrays,
                                     state_names=state_names,
                                     scope=scope)
        top = breakdown["top_consumers"]
        fatal = {
            "kind": "event", "event": "oom",
            "error": str(error)[:500],
            "memory_breakdown": {
                "classes": breakdown["classes"],
                "total_bytes": breakdown["total_bytes"],
                "top_consumers": top,
            },
            "top_consumer": top[0]["name"] if top else None,
        }
        from .registry import registry

        registry().event("oom", error=str(error)[:200],
                         top_consumer=fatal["top_consumer"],
                         total_bytes=breakdown["total_bytes"])
        from . import flight

        flight.on_fatal("resource-exhausted", fatal)
        from .flight import recorder

        return recorder()._default_path()
    except Exception:  # noqa: BLE001 - forensics must never mask the OOM
        return None


# ---------------------------------------------------------------------------
# device-time attribution (xplane / chrome-trace folding)
# ---------------------------------------------------------------------------

def load_trace_events(trace_dir) -> List[dict]:
    """Chrome-trace events out of a jax.profiler capture directory (the
    `**/*.trace.json.gz` TensorBoard sidecar a PR 7 `capture.py` window
    writes) or a single `.json`/`.json.gz` trace file."""
    import gzip
    import json
    import os

    paths = []
    if os.path.isfile(trace_dir):
        paths = [trace_dir]
    else:
        for root, _dirs, files in os.walk(trace_dir):
            for f in files:
                if f.endswith(".trace.json.gz") or \
                        f.endswith(".trace.json"):
                    paths.append(os.path.join(root, f))
    events = []
    for p in sorted(paths):
        opener = gzip.open if p.endswith(".gz") else open
        try:
            with opener(p, "rt") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        evs = doc.get("traceEvents") if isinstance(doc, dict) else doc
        events.extend(e for e in (evs or []) if isinstance(e, dict))
    return events


def _event_paths(ev):
    """Strings of one trace event that may carry a provenance marker:
    the name plus any string args (xplane exports put the HLO op_name
    metadata in args like "name"/"long_name"/"tf_op")."""
    yield str(ev.get("name", ""))
    args = ev.get("args")
    if isinstance(args, dict):
        for v in args.values():
            if isinstance(v, str):
                yield v


def _scope_path(ev) -> str:
    """An operation's scope path: `args.tf_op` (jax's name stack, where
    the sidecar has it), else the first of the event's strings that
    holds a marker, else nothing."""
    args = ev.get("args")
    tf_op = args.get("tf_op") if isinstance(args, dict) else None
    if isinstance(tf_op, str) and tf_op:
        return tf_op
    for path in _event_paths(ev):
        if _MARKER_RE.search(path):
            return path
    return ""


_DEVICE_PROCESS_RE = re.compile(r"^/device:TPU:(\d+)$")
#: the thread of a device process with one event per executed
#: operation, and the one with one event per executed module
_OPS_THREAD = "XLA Ops"
_MODULES_THREAD = "XLA Modules"

#: where in the program a device operation ran, by `region_of`
REGIONS = ("forward", "recompute", "backward", "update", "collective",
           "unattributed")
_COLLECTIVE_KINDS = ("bucket", "grad_sync", "gather")


def _interval(ev):
    """(start, duration) of a device event in picoseconds: the device's
    own integers where the sidecar carries them (they nest exactly),
    else the chrome microseconds."""
    args = ev.get("args")
    if isinstance(args, dict) and "device_offset_ps" in args \
            and "device_duration_ps" in args:
        return int(args["device_offset_ps"]), \
            int(args["device_duration_ps"])
    return float(ev.get("ts", 0.0)) * 1e6, \
        float(ev.get("dur", 0.0) or 0.0) * 1e6


def device_op_rows(events) -> dict:
    """The device operations of a profile with their self times:
    {"devices": n, "steps": executions of the step's module on one
    device, "rows": [(name, scope path, self microseconds)]}.

    Kept are the events of each `/device:TPU:<n>` process's `XLA Ops`
    thread that start inside an execution of the step's module (the
    module of the `XLA Modules` thread with most device time; without
    that thread every operation is kept and `steps` is 0). A self time
    is an operation's duration less that of the operations nested
    directly inside it: the thread nests, a `while` holds every
    operation of its body, and a plain sum counts a loop's work twice.
    Host threads and the `Steps`/`XLA Modules` threads are not
    operations and are never counted."""
    procs, threads = {}, {}
    for ev in events:
        if ev.get("ph") != "M":
            continue
        name = (ev.get("args") or {}).get("name")
        if ev.get("name") == "process_name":
            procs[ev.get("pid")] = name
        elif ev.get("name") == "thread_name":
            threads[(ev.get("pid"), ev.get("tid"))] = name
    devices = {pid for pid, name in procs.items()
               if _DEVICE_PROCESS_RE.match(str(name))}
    ops = {pid: [] for pid in devices}
    modules = {pid: {} for pid in devices}
    for ev in events:
        pid = ev.get("pid")
        if ev.get("ph") != "X" or pid not in devices:
            continue
        thread = threads.get((pid, ev.get("tid")))
        start, dur = _interval(ev)
        if dur <= 0:
            continue
        if thread == _OPS_THREAD:
            ops[pid].append((start, dur, ev))
        elif thread == _MODULES_THREAD:
            modules[pid].setdefault(str(ev.get("name", "")), []).append(
                (start, start + dur))
    rows, steps = [], 0
    for pid in sorted(devices):
        runs = None
        if modules[pid]:
            runs = sorted(max(modules[pid].values(),
                              key=lambda r: sum(e - s for s, e in r)))
            steps += len(runs)
        stack, r = [], 0   # [index into rows, end] of the open parents
        for start, dur, ev in sorted(ops[pid],
                                     key=lambda o: (o[0], -o[1])):
            if runs is not None:
                while r < len(runs) and runs[r][1] <= start:
                    r += 1
                if r == len(runs) or start < runs[r][0]:
                    continue
            while stack and stack[-1][1] <= start:
                stack.pop()
            if stack:
                rows[stack[-1][0]][2] -= dur / 1e6
            stack.append((len(rows), start + dur))
            rows.append([str(ev.get("name", "")), _scope_path(ev),
                         dur / 1e6])
    n = len(devices)
    return {"devices": n, "steps": steps // n if n else 0,
            "rows": [tuple(row) for row in rows]}


def region_of(path, prov, differentiated=True) -> str:
    """The region of the program a device operation ran in, from its
    scope path and innermost marker; the first rule that holds.
    `rematted_computation`, `transpose(` and `jvp(` are jax's own
    name-stack grammar (tests/test_attribution.py holds a compiled
    program to it). What is jitted outside the differentiated function
    (the optimizer, master-weight casts, loss scaling) is `update`;
    in a trace that differentiates nothing, an inference program,
    marked operations are `forward`."""
    if "rematted_computation" in path:
        return "recompute"
    if "transpose(" in path:
        return "backward"
    if "jvp(" in path:
        return "forward"
    if prov is not None and prov.get("kind") in _COLLECTIVE_KINDS:
        return "collective"
    if prov is not None and not differentiated:
        return "forward"
    return "update" if path else "unattributed"


def time_attribution(events) -> dict:
    """Fold a profile's device time back through the provenance markers
    and the name stack: {"steps", "devices", "by_region": {region: us},
    "by_op_type": {fluid op type: us}, "by_op_type_region": {op type:
    {region: us}}, "by_op_part": {op type: {part or "": {region: us}}},
    "by_op": {key: us}, "by_layer": {layer: us}, "by_bucket":
    {bucket_id: us}, "matched_us" (under a marker), "unmatched_us",
    "unattributed_us" (no scope path at all), "total_us"}, each of self
    time (`device_op_rows`) over the traced executions of the step's
    module, in microseconds of ONE device. The per-layer view is the
    straggler answer one level deeper than PR 7's phase blame (WHICH
    layer's ops ate the step); the regions say where in the step:
    forward, recompute, backward, update; the two crossed keys say
    where in the step an op type ran and in which of the parts its own
    code names (`part_of`; "" under the op's marker and under no
    part), and for every op type each sums to `by_op_type`'s entry. A
    fusion's self time goes to the one scope path the fusion carries,
    so a part reads what XLA left under its name."""
    got = device_op_rows(events)
    scale = 1.0 / max(got["devices"], 1)
    by_region = dict.fromkeys(REGIONS, 0.0)
    by_op_type: Dict[str, float] = {}
    by_op_type_region: Dict[str, Dict[str, float]] = {}
    by_op_part: Dict[str, Dict[str, Dict[str, float]]] = {}
    by_op: Dict[str, float] = {}
    by_layer: Dict[str, float] = {}
    by_bucket: Dict[int, float] = {}
    matched = unmatched = total = 0.0
    differentiated = any("jvp(" in path or "transpose(" in path
                         for _name, path, _us in got["rows"])
    for _name, path, us in got["rows"]:
        us *= scale
        total += us
        prov = provenance_of(path)
        region = region_of(path, prov, differentiated)
        by_region[region] += us
        if prov is None:
            unmatched += us
            continue
        matched += us
        key = _prov_key(prov)
        by_op[key] = by_op.get(key, 0.0) + us
        kind = prov["op_type"] if prov["kind"] == "op" else prov["kind"]
        by_op_type[kind] = by_op_type.get(kind, 0.0) + us
        for crossed in (by_op_type_region.setdefault(kind, {}),
                        by_op_part.setdefault(kind, {}).setdefault(
                            part_of(path) or "", {})):
            crossed[region] = crossed.get(region, 0.0) + us
        if prov["kind"] == "bucket":
            b = int(prov["bucket"])
            by_bucket[b] = by_bucket.get(b, 0.0) + us
        var = prov.get("var")
        if var:
            lk = layer_of(var)
            by_layer[lk] = by_layer.get(lk, 0.0) + us

    def by_time(d):
        return dict(sorted(d.items(), key=lambda kv: -kv[1]))

    def in_order(row):
        return {r: row[r] for r in REGIONS if r in row}

    by_op_type = by_time(by_op_type)
    return {
        "steps": got["steps"], "devices": got["devices"],
        "by_region": by_region, "by_op_type": by_op_type,
        "by_op_type_region": {kind: in_order(by_op_type_region[kind])
                              for kind in by_op_type},
        "by_op_part": {kind: {part: in_order(row) for part, row in sorted(
            by_op_part[kind].items(), key=lambda kv: -sum(kv[1].values()))}
            for kind in by_op_type},
        "by_op": by_time(by_op), "by_layer": by_time(by_layer),
        "by_bucket": dict(sorted(by_bucket.items())),
        "matched_us": matched, "unmatched_us": unmatched,
        "unattributed_us": by_region["unattributed"],
        "total_us": total,
    }


def op_part_table(t, top=12) -> List[str]:
    """The lines an operator reads of `time_attribution`'s crossed
    keys: the `top` op types by device time, a column a region that
    ran anything, in ms a step of one device; under an op type whose
    code names its parts one line a part (`(no part)` for what lies
    under the op's marker alone) and the share of its time a part
    carries. [] where the fold has no crossed keys or no time."""
    by_part = t.get("by_op_part")
    if not by_part or not t.get("total_us"):
        return []
    per = 1e3 * max(t["steps"], 1)
    regions = [r for r in REGIONS
               if any(r in row for row in t["by_op_type_region"].values())]
    fmt = "  %-32s" + " %10s" * (len(regions) + 1)

    def line(label, row):
        return fmt % ((label, "%.3f" % (sum(row.values()) / per)) + tuple(
            "%.3f" % (row[r] / per) if r in row else "-" for r in regions))

    out = ["device time by fluid op type and region (ms a step, self "
           "times):", fmt % (("op type / part", "all") + tuple(regions))]
    for kind, row in list(t["by_op_type_region"].items())[:top]:
        out.append(line(kind, row))
        parts = by_part[kind]
        if set(parts) == {""}:
            continue
        out.extend(line("  pt[%s]" % part if part else "  (no part)", prow)
                   for part, prow in parts.items())
        whole = sum(row.values())
        parted = whole - sum(parts.get("", {}).values())
        out.append("    %.2f %% of %s under a part"
                   % (100.0 * parted / whole if whole else 0.0, kind))
    return out
