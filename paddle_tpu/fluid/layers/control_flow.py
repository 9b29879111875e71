"""Control-flow layers: While / while_loop / cond / case / switch_case.

Reference: `python/paddle/fluid/layers/control_flow.py` (While:1020, cond,
case, switch_case) over the C++ control-flow ops
(`operators/controlflow/while_op.cc:42`,
`operators/controlflow/conditional_block_op.cc`).

TPU-native: sub-blocks lower to `lax.while_loop` / `lax.cond` /
`lax.switch` with an explicit functional carry (SURVEY.md §7 hard part
(b)): the reference's scope-mutation loop model becomes "carry = the
sub-block's writes that pre-exist in the enclosing env". Loop-carried
values must keep static shape/dtype across iterations — the XLA contract.
Loop bodies run under the same op registry, so everything composes
(collectives inside a while, AMP casts, etc.).
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence

from .. import framework
from ..framework import Variable, unique_name
from ..layer_helper import LayerHelper
from . import tensor as tensor_layers


def _flatten(x):
    if isinstance(x, (list, tuple)):
        out = []
        for e in x:
            out.extend(_flatten(e))
        return out
    return [x]


def _pack_like(template, flat):
    it = iter(flat)

    def rec(t):
        if isinstance(t, (list, tuple)):
            return type(t)(rec(e) for e in t)
        return next(it)

    return rec(template)


# ---------------------------------------------------------------------------
# Scan (fixed-trip lax.scan loop over stacked leading-axis inputs)
# ---------------------------------------------------------------------------

class Scan:
    """Fixed-trip loop lowered to `jax.lax.scan` — the TPU-native way to
    build deep stacks of identical layers: the body is traced and
    XLA-compiled ONCE regardless of trip count (a 12-layer encoder puts
    ONE body in the HLO instead of 12 clones; ~10x smaller program,
    proportionally faster compiles), and reverse-mode grads flow through
    jax.vjp over the scan.

    No direct reference counterpart: the reference's recurrent_op
    (`operators/recurrent_op.cc`) steps a sub-block per timestep via
    scope mutation and needs a dedicated recurrent_grad; here the loop
    is functional so autodiff is ordinary vjp. Carry contract is the
    While contract (`while_op.cc:42` analogue): loop-carried vars are
    created+initialized BEFORE the loop and rebound inside the body
    (e.g. ``layers.assign(new_x, output=x)``); per-layer parameters are
    stacked on a leading [n, ...] axis and sliced with
    ``scan.slice_input(stacked)`` inside the body.

    remat=True wraps the body in ``jax.checkpoint``: per-iteration
    activation recompute (the scan-over-layers equivalent of
    RecomputeOptimizer's checkpoint segments). Held across the n
    iterations are the boundary (the carry) and the few values of the
    body that cost less to keep than to make again — every dropout's
    boolean keep mask, the output of a matmul narrower than what it
    contracts, and the output and row statistics of an attention the
    flash kernels ran (``ops/remat_names.py``) — instead of O(n *
    body-internals); ``Executor.remat_saved`` says what a traced
    program keeps, in bytes.

    Usage::

        scan = layers.Scan(n=num_layers)
        with scan.block():
            w = scan.slice_input(stacked_w)   # [n, H, H] -> [H, H]
            new_x = layers.matmul(x, w)
            layers.assign(new_x, output=x)    # rebind the carry
    """

    def __init__(self, n: int, remat: bool = False, name: Optional[str] = None):
        if int(n) < 1:
            raise ValueError("Scan needs n >= 1, got %r" % (n,))
        self.n = int(n)
        self.remat = bool(remat)
        self.helper = LayerHelper("scan", name=name)
        self._main = framework.default_main_program()
        self._sub = None
        self._xs_stacked: List[Variable] = []
        self._xs_slice: List[Variable] = []
        self._iter_var: Optional[Variable] = None

    def iteration(self) -> Variable:
        """[1] int32 var holding the current iteration index inside the
        body — e.g. the scatter index for per-iteration slice updates of
        stacked state (BN running stats in a scanned residual stage).
        int32 is JAX's canonical index dtype (int64 would truncate
        under default config and warn on every trace)."""
        if self._sub is None:
            raise ValueError(
                "iteration() must be called inside `with scan.block():`")
        if self._iter_var is None:
            self._iter_var = self._sub.create_var(
                name=unique_name("scan_iter"), shape=(1,), dtype="int32")
        return self._iter_var

    def slice_input(self, stacked: Variable) -> Variable:
        """Declare `stacked` [n, ...] as a per-iteration input; returns
        its [...] slice for use inside the body."""
        if self._sub is None:
            raise ValueError(
                "slice_input must be called inside `with scan.block():`")
        if not isinstance(stacked, Variable):
            raise TypeError("slice_input expects a Variable")
        if int(stacked.shape[0]) != self.n:
            raise ValueError(
                "stacked input %r leading dim %s != scan n %d"
                % (stacked.name, stacked.shape[0], self.n))
        sl = self._sub.create_var(
            name=unique_name("scan_slice"),
            shape=tuple(int(d) for d in stacked.shape[1:]),
            dtype=stacked.dtype)
        self._xs_stacked.append(stacked)
        self._xs_slice.append(sl)
        return sl

    def block(self):
        import contextlib

        @contextlib.contextmanager
        def ctx():
            prog = self._main
            self._sub = prog._create_block()
            self._xs_stacked, self._xs_slice = [], []
            self._iter_var = None
            try:
                yield self
            except BaseException:
                # body raised: leave no half-built scan op behind (the
                # While guard's contract)
                prog._rollback()
                self._sub = None
                raise
            prog._rollback()
            sub = self._sub
            self._sub = None
            parent = prog.current_block()
            parent.append_op(
                type="scan",
                inputs={"X": list(self._xs_stacked)},
                outputs={},
                attrs={"sub_block": sub.idx, "n": self.n,
                       "remat": self.remat,
                       "xs_stacked": [v.name for v in self._xs_stacked],
                       "xs_slice": [v.name for v in self._xs_slice],
                       "iter_var": self._iter_var.name
                       if self._iter_var is not None else ""})

        return ctx()


# ---------------------------------------------------------------------------
# While (1.x context-manager form)
# ---------------------------------------------------------------------------

class While:
    """``while cond_var:`` over a sub-block (reference:
    control_flow.py While / while_op.cc:42).

    All loop-carried vars must be created AND initialized before the loop;
    writes inside the block to pre-existing vars are carried functionally.
    """

    def __init__(self, cond, is_test=False, name=None):
        if not isinstance(cond, Variable):
            raise TypeError("While cond must be a Variable")
        self.cond_var = cond
        self.helper = LayerHelper("while", name=name)
        self._main = framework.default_main_program()

    def block(self):
        return _WhileGuard(self)


class _WhileGuard:
    def __init__(self, while_op: While):
        self._w = while_op

    def __enter__(self):
        prog = self._w._main
        self._sub = prog._create_block()
        return self

    def __exit__(self, exc_type, exc_val, tb):
        prog = self._w._main
        prog._rollback()
        if exc_type is not None:
            return False
        parent = prog.current_block()
        parent.append_op(
            type="while",
            inputs={"Condition": [self._w.cond_var]},
            outputs={},
            attrs={"sub_block": self._sub.idx,
                   "cond_name": self._w.cond_var.name})
        return True


def while_loop(cond: Callable, body: Callable, loop_vars: Sequence,
               is_test: bool = False, name: Optional[str] = None):
    """Functional while (reference: control_flow.py while_loop): runs
    ``body`` while ``cond(*loop_vars)`` holds; returns the final vars."""
    loop_list = list(loop_vars)
    pre_cond = cond(*loop_list)
    w = While(pre_cond, is_test=is_test, name=name)
    with w.block():
        out = body(*loop_list)
        out_list = out if isinstance(out, (list, tuple)) else [out]
        flat_in = _flatten(loop_list)
        flat_out = _flatten(list(out_list))
        if len(flat_in) != len(flat_out):
            raise ValueError(
                "body returned %d vars, expected %d (the loop_vars "
                "structure)" % (len(flat_out), len(flat_in)))
        for lv, ov in zip(flat_in, flat_out):
            if ov is not lv:
                tensor_layers.assign(ov, output=lv)
        new_cond = cond(*loop_list)
        tensor_layers.assign(new_cond, output=pre_cond)
    return loop_vars


# ---------------------------------------------------------------------------
# cond / case / switch_case
# ---------------------------------------------------------------------------

def _trace_branch(prog, fn, out_vars=None):
    """Runs fn inside a fresh sub-block; assigns its returns onto out_vars
    (created in the parent on the first branch). Returns (block_idx,
    out_vars, template)."""
    sub = prog._create_block()
    try:
        ret = fn() if fn is not None else None
    except BaseException:
        prog._rollback()
        raise
    flat = _flatten(ret) if ret is not None else []
    if out_vars is None:
        parent = prog.block(sub.parent_idx)
        out_vars = []
        for i, r in enumerate(flat):
            if not isinstance(r, Variable):
                r = tensor_layers.fill_constant([1], "float32", float(r))
                flat[i] = r
            out_vars.append(parent.create_var(
                name=framework.unique_name("cond_out"),
                shape=r.shape, dtype=r.dtype))
    if len(flat) != len(out_vars):
        prog._rollback()
        raise ValueError("branches must return the same structure "
                         "(%d vs %d leaves)" % (len(flat), len(out_vars)))
    for r, ov in zip(flat, out_vars):
        if not isinstance(r, Variable):
            r = tensor_layers.fill_constant(ov.shape, ov.dtype, float(r))
        tensor_layers.assign(r, output=ov)
    prog._rollback()
    return sub.idx, out_vars, ret


def cond(pred, true_fn: Optional[Callable] = None,
         false_fn: Optional[Callable] = None, name: Optional[str] = None):
    """Two-way branch (reference: control_flow.py cond /
    conditional_block_op.cc). Both branches must return the same
    structure of vars with matching shapes/dtypes."""
    prog = framework.default_main_program()
    t_idx, out_vars, template = _trace_branch(prog, true_fn)
    f_idx, _, _ = _trace_branch(prog, false_fn, out_vars)
    parent = prog.current_block()
    parent.append_op(
        type="cond",
        inputs={"Cond": [pred]},
        outputs={"Out": list(out_vars)},
        attrs={"sub_block_t": t_idx, "sub_block_f": f_idx,
               "out_names": [v.name for v in out_vars],
               "cond_name": pred.name})
    if template is None:
        return None
    if isinstance(template, (list, tuple)):
        return _pack_like(template, out_vars)
    return out_vars[0]


def switch_case(branch_index, branch_fns, default=None,
                name: Optional[str] = None):
    """N-way branch on an integer index (reference: control_flow.py
    switch_case) -> lax.switch."""
    prog = framework.default_main_program()
    if isinstance(branch_fns, dict):
        items = sorted(branch_fns.items())
    elif branch_fns and all(isinstance(f, (list, tuple)) and len(f) == 2
                            for f in branch_fns):
        # reference API also accepts a list of (index, callable) pairs
        items = sorted((int(k), f) for k, f in branch_fns)
    else:
        items = list(enumerate(branch_fns))
    keys = [int(k) for k, _ in items]
    fns = [f for _, f in items]
    if default is None:
        # promote the last branch to default (and drop it from the match
        # list so it isn't traced twice)
        default = fns.pop()
        keys.pop()

    out_vars = None
    blocks = []
    template = None
    for f in fns:
        idx, out_vars, tmpl = _trace_branch(prog, f, out_vars)
        template = template if template is not None else tmpl
        blocks.append(idx)
    d_idx, out_vars, _ = _trace_branch(prog, default, out_vars)
    blocks.append(d_idx)

    parent = prog.current_block()
    parent.append_op(
        type="switch_case",
        inputs={"Index": [branch_index]},
        outputs={"Out": list(out_vars)},
        attrs={"sub_blocks": blocks, "keys": keys,
               "out_names": [v.name for v in out_vars],
               "index_name": branch_index.name})
    if isinstance(template, (list, tuple)):
        return _pack_like(template, out_vars)
    return out_vars[0]


def case(pred_fn_pairs, default=None, name: Optional[str] = None):
    """First-match-wins chain of (pred, fn) (reference: control_flow.py
    case), built from nested cond."""
    pairs = list(pred_fn_pairs)
    if not pairs:
        raise ValueError("pred_fn_pairs must be non-empty")
    if default is None:
        default = pairs[-1][1]
        pairs = pairs[:-1]
        if not pairs:
            return default()

    def build(i):
        if i == len(pairs):
            return default
        pred, fn = pairs[i]
        return lambda: cond(pred, fn, build(i + 1))

    return build(0)()


# ---------------------------------------------------------------------------
# misc control-flow helpers the reference exposes alongside While
# ---------------------------------------------------------------------------

def increment(x, value=1.0, in_place=True):
    """Reference: control_flow.py increment — x += value, in place by
    rebinding the same var name."""
    helper = LayerHelper("increment")
    out = x if in_place else helper.create_variable_for_type_inference(
        dtype=x.dtype)
    helper.append_op(type="increment", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"step": float(value)})
    return out


def less_than(x, y, force_cpu=None, cond=None):
    return _compare("less_than", x, y, cond)


def less_equal(x, y, cond=None):
    return _compare("less_equal", x, y, cond)


def greater_than(x, y, cond=None):
    return _compare("greater_than", x, y, cond)


def greater_equal(x, y, cond=None):
    return _compare("greater_equal", x, y, cond)


def equal(x, y, cond=None):
    return _compare("equal", x, y, cond)


def not_equal(x, y, cond=None):
    return _compare("not_equal", x, y, cond)


def _compare(op_type, x, y, out):
    helper = LayerHelper(op_type)
    if out is None:
        out = helper.create_variable_for_type_inference(dtype="bool")
    helper.append_op(type=op_type, inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]})
    return out


def Print(input, first_n=-1, message=None, summarize=20,
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_lod=True,
          print_phase="both"):
    """Host-side tensor print passthrough (reference: control_flow.py
    Print -> print_op). Returns its input so it can be chained."""
    helper = LayerHelper("print")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="print", inputs={"In": [input]}, outputs={"Out": [out]},
        attrs={"message": message or "", "summarize": summarize,
               "first_n": first_n, "print_phase": print_phase})
    return out


def Assert(cond, data=None, summarize=20, name=None):
    """Runtime assertion op (reference: control_flow.py Assert ->
    assert_op): raises AssertionError when `cond` is not all-true."""
    helper = LayerHelper("assert")
    inputs = {"Cond": [cond]}
    if data:
        inputs["Data"] = list(data)
    helper.append_op(type="assert", inputs=inputs, outputs={},
                     attrs={"summarize": summarize,
                            "message": name or ""})


class StaticRNN:
    """Static-length RNN builder (reference: layers/control_flow.py
    StaticRNN + operators/recurrent_op.cc). The user writes the step
    body ONCE inside `with rnn.step():` over time-major [T, B, ...]
    sequence inputs; the reference executes it via recurrent_op's
    sub-block loop. TPU-native: the step body is captured as an op
    template and UNROLLED at build time by cloning it per timestep with
    name substitution — T is static here by definition (the reference
    requires it too), unrolling gives XLA the whole computation to
    fuse/pipeline, and the backward falls out of the ordinary
    jax.vjp over the flattened program (no recurrent_grad op needed).
    For data-dependent lengths use layers.while_loop / layers.rnn."""

    BEFORE_RNN_BLOCK = 0
    IN_RNN_BLOCK = 1
    AFTER_RNN_BLOCK = 2

    def __init__(self, name=None):
        self.helper = LayerHelper("static_rnn", name=name)
        self.status = StaticRNN.BEFORE_RNN_BLOCK
        self.seq_len = None
        self._step_inputs = []   # (seq var, t0 var)
        self._mems = []          # {"pre": var, "update": name|None}
        self._step_outputs = []  # t0 output vars
        self._results = None
        self._start_idx = None
        # ops that SEED iteration 0 (t0 slices, memory init fills):
        # they must not be re-cloned per timestep — a clone would remap
        # their output names over the prev-iteration substitutions
        self._seed_op_ids = set()

    # -- step context ------------------------------------------------------
    def step(self):
        import contextlib

        @contextlib.contextmanager
        def ctx():
            self.status = StaticRNN.IN_RNN_BLOCK
            self._start_idx = len(self.helper.main_block.ops)
            try:
                yield
            finally:
                self.status = StaticRNN.AFTER_RNN_BLOCK
                self._complete()

        return ctx()

    def _assert_in_step(self, what):
        if self.status != StaticRNN.IN_RNN_BLOCK:
            raise ValueError("%s can only be invoked inside rnn.step()"
                             % what)

    def _slice_time(self, seq, t):
        """seq [T, B, ...] -> [B, ...] at time t."""
        block = self.helper.main_block
        sl = block.create_var(
            name=unique_name("srnn_slice"),
            shape=(1,) + tuple(seq.shape[1:]), dtype=seq.dtype)
        block.append_op(type="slice", inputs={"Input": [seq]},
                        outputs={"Out": [sl]},
                        attrs={"axes": [0], "starts": [t],
                               "ends": [t + 1]})
        out = block.create_var(name=unique_name("srnn_x"),
                               shape=tuple(seq.shape[1:]),
                               dtype=seq.dtype)
        block.append_op(type="reshape2", inputs={"X": [sl]},
                        outputs={"Out": [out], "XShape": [block.create_var(
                            name=unique_name("srnn_xs"), shape=(),
                            dtype=seq.dtype)]},
                        attrs={"shape": [int(d) for d in seq.shape[1:]]})
        return out

    def step_input(self, x):
        """Mark x [seq_len, batch, ...] as a sequence input; returns the
        per-step [batch, ...] slice."""
        self._assert_in_step("step_input")
        if self.seq_len is None:
            self.seq_len = int(x.shape[0])
        elif self.seq_len != int(x.shape[0]):
            raise ValueError("Static RNN only takes fixed seq_len: %d vs "
                             "%d" % (self.seq_len, int(x.shape[0])))
        n_before = len(self.helper.main_block.ops)
        t0 = self._slice_time(x, 0)
        for op in self.helper.main_block.ops[n_before:]:
            self._seed_op_ids.add(id(op))
        self._step_inputs.append((x, t0))
        return t0

    def memory(self, init=None, shape=None, batch_ref=None,
               init_value=0.0, init_batch_dim_idx=0, ref_batch_dim_idx=1):
        """Loop-carried state: init var, or zeros shaped like `shape`
        with the batch dim taken from batch_ref (reference:
        StaticRNN.memory)."""
        self._assert_in_step("memory")
        if init is None:
            if shape is None or batch_ref is None:
                raise ValueError(
                    "memory needs an init var OR shape + batch_ref")
            from . import tensor as t_layers

            n_before = len(self.helper.main_block.ops)
            feat = [int(d) for d in shape if int(d) != -1]
            init = t_layers.fill_constant_batch_size_like(
                batch_ref, shape=[-1] + feat, dtype=batch_ref.dtype,
                value=init_value, input_dim_idx=0, output_dim_idx=0)
            for op in self.helper.main_block.ops[n_before:]:
                self._seed_op_ids.add(id(op))
        self._mems.append({"pre": init, "update": None})
        return init

    def update_memory(self, mem, x):
        self._assert_in_step("update_memory")
        for m in self._mems:
            if m["pre"].name == mem.name:
                m["update"] = x.name
                return
        raise ValueError("update_memory: %r is not a memory of this RNN"
                         % mem.name)

    def step_output(self, o):
        self._assert_in_step("step_output")
        self._step_outputs.append(o)

    def output(self, *outputs):
        for o in outputs:
            self.step_output(o)

    # -- unrolling ---------------------------------------------------------
    def _complete(self):
        if self.seq_len is None:
            raise ValueError("StaticRNN needs at least one step_input")
        for m in self._mems:
            if m["update"] is None:
                raise ValueError("memory %r has no update_memory"
                                 % m["pre"].name)
        block = self.helper.main_block
        template = [op for op in block.ops[self._start_idx:]
                    if id(op) not in self._seed_op_ids]
        prev = {m["pre"].name: m["update"] for m in self._mems}
        outs_per_t = {o.name: [o.name] for o in self._step_outputs}

        for t in range(1, self.seq_len):
            mapping = {}
            for seq, t0 in self._step_inputs:
                mapping[t0.name] = self._slice_time(seq, t).name
            for m in self._mems:
                mapping[m["pre"].name] = prev[m["pre"].name]
            for op in template:
                if any(k in op.attrs for k in ("sub_block", "blocks")):
                    raise NotImplementedError(
                        "StaticRNN step body must not contain nested "
                        "control-flow blocks")
                ins = {}
                for slot, names in op.input_names.items():
                    ins[slot] = [mapping.get(n, n) for n in names]
                outs = {}
                for slot, names in op.output_names.items():
                    mapped = []
                    for n in names:
                        v = block._find_var_recursive(n)
                        if v is not None and v.persistable:
                            mapped.append(n)  # params update in place
                            continue
                        fresh = unique_name("%s_t%d" % (n, t))
                        nv = block.create_var(
                            name=fresh,
                            shape=v.shape if v is not None else (),
                            dtype=v.dtype if v is not None
                            else "float32")
                        mapping[n] = fresh
                        mapped.append(fresh)
                    outs[slot] = mapped
                block.append_op(type=op.type, inputs=ins, outputs=outs,
                                attrs=dict(op.attrs))
            for m in self._mems:
                prev[m["pre"].name] = mapping.get(m["update"],
                                                  m["update"])
            for o in self._step_outputs:
                outs_per_t[o.name].append(mapping.get(o.name, o.name))

        # stack each step output over time: [T, B, ...]
        results = []
        for o in self._step_outputs:
            out = block.create_var(
                name=unique_name("srnn_out"),
                shape=(self.seq_len,) + tuple(o.shape), dtype=o.dtype)
            block.append_op(type="stack",
                            inputs={"X": outs_per_t[o.name]},
                            outputs={"Y": [out]}, attrs={"axis": 0})
            results.append(out)
        self._results = results

    def __call__(self, *args, **kwargs):
        if self.status != StaticRNN.AFTER_RNN_BLOCK:
            raise ValueError("rnn() is only valid after the step block")
        if not self._results:
            raise ValueError("StaticRNN produced no step_output")
        return (self._results[0] if len(self._results) == 1
                else self._results)


def is_empty(x, cond=None):
    """True iff x has zero elements (reference: control_flow.py:3779 /
    is_empty_op.h — always computed host-side there too; here shapes
    are static so it is a trace-time constant)."""
    helper = LayerHelper("is_empty")
    out = cond if cond is not None else \
        helper.create_variable_for_type_inference(dtype="bool")
    helper.append_op(type="is_empty", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={})
    return out


def reorder_lod_tensor_by_rank(x, rank_table):
    """Permute batch rows into the rank table's order (reference:
    control_flow.py:3738 / reorder_lod_tensor_by_rank_op.cc)."""
    helper = LayerHelper("reorder_lod_tensor_by_rank")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type="reorder_lod_tensor_by_rank",
                     inputs={"X": [x], "RankTable": [rank_table]},
                     outputs={"Out": [out]}, attrs={})
    return out
