"""AMP program rewrites (reference:
`python/paddle/fluid/contrib/mixed_precision/fp16_utils.py`: cast
insertion + master-weight creation for OptimizerWithMixedPrecision).

TPU-native split of responsibilities:

- the white/black-list CASTS are applied at trace time by
  `fluid/lowering._apply_amp_casts` (XLA fuses them; no cast ops clutter
  the IR) — see `paddle_tpu/parallel/README.md` "Mixed precision &
  ZeRO-2";
- THIS module performs the two rewrites that must be visible in the IR
  because they change the program's state contract:

  1. ``rewrite_master_weights``: the live parameters become the compute
     dtype (bf16/fp16) while an fp32 MASTER copy (``<param>@MASTER``)
     becomes the value the optimizer op updates; a trailing ``cast`` op
     re-derives the live param from the updated master. Under the
     ZeRO-1 plan (`parallel/sharded_update.plan_sharded_update`) the
     masters live as P(dp)-sharded flat buffers across steps exactly
     like the moments, so per-replica param state is
     ``numel*2 (live bf16) + numel*4/N (master shard)`` instead of
     ``numel*4`` — and the param all-gather moves half the ICI bytes
     (it carries the bf16 cast of the updated shard).
  2. ``wire_dynamic_loss_scaling`` (fp16 only — bf16 shares fp32's
     exponent range and needs none by design): persistable scale /
     good-step / bad-step state vars plus a ``dynamic_loss_scaling``
     attr on the backward op; `fluid/lowering._run_loss_scaled_post`
     runs the whole post-backward section under ``lax.cond`` on the
     psum'd finite check and steps the scale state machine.
"""
from __future__ import annotations

from ... import framework
from ...framework import grad_var_name, unique_name
from ....core.types import normalize_dtype

MASTER_SUFFIX = "@MASTER"


def master_name(param_name: str) -> str:
    return param_name + MASTER_SUFFIX


def _fp32_pinned_params(block, amp_lists):
    """Names read through a slot of `amp_lists.fp32_param_slots`, in
    this block or a sub-block: parameters that stay fp32."""
    slots = getattr(amp_lists, "fp32_param_slots", None) or {}
    pinned = set()
    for blk in block.program.blocks:
        for op in blk.ops:
            for slot in slots.get(op.type, ()):
                pinned.update(op.input_names.get(slot, []))
    return pinned


def rewrite_master_weights(program, startup_program, compute_dtype,
                           amp_lists=None):
    """Rewire every optimizer op's Param/ParamOut to an fp32 master var,
    flip the live params (and their grads) to `compute_dtype`, and
    append one ``cast`` op per param re-deriving the live value from the
    updated master. Returns {param_name: master_name}. A parameter read
    through one of `amp_lists.fp32_param_slots` keeps its fp32 value as
    the live one and gets no master.

    Startup contract: the initializer op still fills the EXACT fp32
    init value; the master is assigned from it BEFORE the live param is
    down-cast — so the fp32 master starts bit-identical to a non-AMP
    run's param, and the live param is its 16-bit cast.
    """
    compute_dtype = normalize_dtype(compute_dtype)
    block = program.global_block()
    bwd_idx = next((i for i, op in enumerate(block.ops)
                    if op.type == "backward"), None)
    post = block.ops[bwd_idx + 1:] if bwd_idx is not None else block.ops

    pinned = _fp32_pinned_params(block, amp_lists)
    master_of = {}
    for op in post:
        params = op.input_names.get("Param", [])
        pouts = op.output_names.get("ParamOut", [])
        if not params or not pouts:
            continue
        for i, p in enumerate(params):
            if p.endswith(MASTER_SUFFIX) or p in pinned:
                continue
            v = block._find_var_recursive(p)
            if v is None or str(v.dtype) != "float32" \
                    or not getattr(v, "persistable", False):
                continue
            m = master_of.get(p)
            if m is None:
                m = _create_master(program, startup_program, v,
                                   compute_dtype)
                master_of[p] = m
            op.input_names["Param"][i] = m
            for j, po in enumerate(op.output_names["ParamOut"]):
                if po == p:
                    op.output_names["ParamOut"][j] = m

    # one trailing cast per param: the live 16-bit value is re-derived
    # from the updated fp32 master. Marked so the ZeRO planner can prove
    # this is the master's ONLY reader outside its optimizer op (it
    # becomes a shard-space cast whose output all-gathers in 16 bits).
    for p, m in master_of.items():
        block.append_op(
            type="cast", inputs={"X": [m]}, outputs={"Out": [p]},
            attrs={"in_dtype": "float32", "out_dtype": str(compute_dtype),
                   "__amp_param_cast__": True})
    if master_of:
        program._version += 1
    return master_of


def _create_master(program, startup_program, v, compute_dtype):
    block = program.global_block()
    m = master_name(v.name)
    mv = block.create_var(name=m, shape=list(v.shape), dtype="float32",
                          persistable=True)
    mv.stop_gradient = True
    if startup_program is not None:
        sb = startup_program.global_block()
        if sb.has_var(v.name):
            sb.create_var(name=m, shape=list(v.shape), dtype="float32",
                          persistable=True)
            # master = the exact fp32 init; then the live param becomes
            # its 16-bit cast (order matters: assign reads fp32)
            sb.append_op(type="assign", inputs={"X": [v.name]},
                         outputs={"Out": [m]})
            sb.append_op(
                type="cast", inputs={"X": [v.name]},
                outputs={"Out": [v.name]},
                attrs={"in_dtype": "float32",
                       "out_dtype": str(compute_dtype),
                       "__amp_param_cast__": True})
    # flip the live param and its grad to the compute dtype — the vjp
    # binds gradients at the param's dtype (lowering), so grads are
    # 16-bit too and the grad reduce-scatter bytes halve with the params
    v.dtype = compute_dtype
    g = block._find_var_recursive(grad_var_name(v.name))
    if g is not None:
        g.dtype = compute_dtype
    return m


def wire_dynamic_loss_scaling(program, startup_program, cfg):
    """Create the persistable loss-scale state (scale fp32, good/bad
    step counters int32) and attach the ``dynamic_loss_scaling`` attr to
    the backward op. The state rides the backward op's input/output
    slots so `lowering.analyze_block` threads it as mutable scope state
    — it persists across steps and through checkpoint save/restore like
    any other optimizer state. Returns the attr dict (or None when the
    program has no backward section)."""
    block = program.global_block()
    bop = next((op for op in block.ops if op.type == "backward"), None)
    if bop is None:
        return None
    sb = startup_program.global_block() if startup_program is not None \
        else None

    def state(stem, dtype, value):
        v = block.create_var(name=unique_name(stem), shape=[1],
                             dtype=dtype, persistable=True)
        v.stop_gradient = True
        if sb is not None:
            sb.create_var(name=v.name, shape=[1], dtype=dtype,
                          persistable=True)
            sb.append_op(type="fill_constant", outputs={"Out": [v.name]},
                         attrs={"shape": [1], "dtype": dtype,
                                "value": float(value)})
        return v.name

    dls = {
        "scale": state("loss_scaling", "float32",
                       cfg["init_loss_scaling"]),
        "good": state("num_good_steps", "int32", 0),
        "bad": state("num_bad_steps", "int32", 0),
        "incr_every_n_steps": int(cfg["incr_every_n_steps"]),
        "decr_every_n_nan_or_inf": int(cfg["decr_every_n_nan_or_inf"]),
        "incr_ratio": float(cfg["incr_ratio"]),
        "decr_ratio": float(cfg["decr_ratio"]),
    }
    bop.attrs["dynamic_loss_scaling"] = dls
    extra = [dls["scale"], dls["good"], dls["bad"]]
    bop.input_names["LossScaleState"] = list(extra)
    bop.output_names["LossScaleState"] = list(extra)
    program._version += 1
    return dls


class EagerMasterWeightOptimizer:
    """Dygraph fp32-master shim (`hapi.Model.prepare(amp_level='O2')`):
    the live parameters stay in the 16-bit compute dtype; each step the
    inner optimizer updates an fp32 master copy (kept here, keyed by
    param name) and the live param is rebound to the updated master's
    16-bit cast — so update precision never degrades to bf16/fp16
    round-off while forward/backward run on 16-bit params."""

    def __init__(self, optimizer):
        self._opt = optimizer
        self._masters = {}
        # the exact live array object this wrapper last assigned per
        # param: any external reassignment (Model.load, set_state_dict,
        # a user _assign_raw) replaces it with a DIFFERENT object, which
        # invalidates the cached master — otherwise the next step would
        # swap the stale pre-load master back over the loaded weights
        self._last_live = {}

    def __getattr__(self, item):
        return getattr(self._opt, item)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        import jax.numpy as jnp

        params = parameter_list if parameter_list is not None \
            else getattr(self._opt, "_parameter_list", None) or []
        # grads must be taken against the LIVE 16-bit values; they are
        # stored on the param object and survive the value swap below
        if not getattr(loss, "_backward_ran", False):
            loss.backward()
        swapped = []
        for p in params:
            val = p._value()
            if not jnp.issubdtype(val.dtype, jnp.floating) \
                    or val.dtype == jnp.float32:
                continue
            m = self._masters.get(p.name)
            if m is None or tuple(m.shape) != tuple(val.shape) \
                    or self._last_live.get(p.name) is not val:
                m = val.astype(jnp.float32)
                # masters shard over the mesh like the eager optimizer
                # accumulators (P(ici) dim-0, divisibility-gated):
                # FLAGS_tpu_sharded_update + an active global mesh move
                # the fp32 copy's memory off every replica, and XLA
                # partitions the master update against the layout
                from ....parallel.sharded_update import \
                    eager_accumulator_sharding

                sh = eager_accumulator_sharding(tuple(m.shape))
                if sh is not None:
                    import jax

                    m = jax.device_put(m, sh)
            swapped.append((p, val.dtype))
            p._assign_raw(m)
        try:
            result = self._opt.minimize(
                loss, parameter_list=parameter_list,
                no_grad_set=no_grad_set)
        finally:
            for p, low in swapped:
                new_master = p._value()
                self._masters[p.name] = new_master
                live = new_master.astype(low)
                self._last_live[p.name] = live
                p._assign_raw(live)
        return result
