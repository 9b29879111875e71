"""AMP decorator (reference:
`python/paddle/fluid/contrib/mixed_precision/decorator.py:27-218`:
OptimizerWithMixedPrecision rewrites the program inserting casts + dynamic
loss scaling via amp_check_finite_and_scale).

TPU-native: `decorate()` marks the program with a white/black-list
compute policy that the lowering applies per-op at trace time (white
list ops run on the MXU in the 16-bit compute dtype; black list ops
compute in fp32) AND — at amp_level "O2", the default — rewrites the
program for **fp32 master weights**: live params (and their grads)
become the compute dtype, every optimizer op updates an fp32
``<param>@MASTER`` var, and a trailing cast re-derives the live param
(fp16_utils.rewrite_master_weights). Under the ZeRO-1 plan
(`parallel/sharded_update`), the masters live SHARDED as P(dp) flat
buffers across steps like the moments, the optimizer consumes the
reduce-scattered 16-bit grad shard, and the per-bucket all-gather
carries the 16-bit cast of the updated shard — so param HBM and
all-gather ICI bytes both halve relative to fp32 data parallelism.
Full catalog + knobs: `paddle_tpu/parallel/README.md`
("Mixed precision & ZeRO-2").

Loss scaling: bfloat16 shares fp32's exponent range, so bf16 (the
default `amp_dtype`) needs none by design. With `amp_dtype="float16"`,
dynamic loss scaling is wired for real: the loss cotangent is scaled by
a persistable scale var, gradients are finite-checked (psum'd across
the dp axis so the predicate is replica-uniform) and unscaled, the
whole weight update runs under a ``lax.cond`` that SKIPS it on
overflow, and the scale grows every `incr_every_n_steps` clean steps /
decays after `decr_every_n_nan_or_inf` overflows
(fluid/lowering._run_loss_scaled_post). The scale state persists in the
Scope and through checkpoint save/restore like any optimizer state.

`FLAGS_tpu_amp_level` overrides the decorate-time level ("O0" is the
kill switch: decorated programs lower exactly like undecorated ones).
"""
from __future__ import annotations

from ... import framework
from .fp16_lists import AutoMixedPrecisionLists


def _normalize_amp_dtype(amp_dtype):
    from ....core.types import normalize_dtype

    try:
        d = normalize_dtype(amp_dtype)
    except ValueError:
        d = None
    if d not in ("bfloat16", "float16"):
        raise ValueError(
            "amp_dtype must be 'bfloat16' or 'float16', got %r"
            % (amp_dtype,))
    return d


class OptimizerWithMixedPrecision:
    def __init__(self, optimizer, amp_lists=None, init_loss_scaling=2.**15,
                 use_dynamic_loss_scaling=True, incr_every_n_steps=1000,
                 decr_every_n_nan_or_inf=2, incr_ratio=2.0,
                 decr_ratio=0.8, amp_dtype="bfloat16", amp_level="O2"):
        self._optimizer = optimizer
        self._amp_lists = amp_lists or AutoMixedPrecisionLists()
        self._loss_scaling = float(init_loss_scaling)
        self._use_dynamic_loss_scaling = bool(use_dynamic_loss_scaling)
        self._incr_every_n_steps = int(incr_every_n_steps)
        self._decr_every_n_nan_or_inf = int(decr_every_n_nan_or_inf)
        self._incr_ratio = float(incr_ratio)
        self._decr_ratio = float(decr_ratio)
        self._amp_dtype = _normalize_amp_dtype(amp_dtype)
        if amp_level not in ("O0", "O1", "O2"):
            raise ValueError("amp_level must be one of O0/O1/O2, got %r"
                             % (amp_level,))
        self._amp_level = amp_level
        self._master_of = {}
        self._scale_state = None

    def __getattr__(self, item):
        return getattr(self._optimizer, item)

    def get_loss_scaling(self):
        """Current loss scale: the live scope value under dynamic
        scaling, the static init value otherwise."""
        if self._scale_state is not None:
            from ....core.scope import global_scope
            import numpy as np

            v = global_scope().find_var(self._scale_state["scale"])
            if v is not None:
                return float(np.asarray(v).reshape(-1)[0])
        return self._loss_scaling

    def get_master_weights(self):
        """{param_name: master_var_name} after minimize() at level O2."""
        return dict(self._master_of)

    def backward(self, loss, **kwargs):
        return self._optimizer.backward(loss, **kwargs)

    def apply_gradients(self, params_grads):
        return self._optimizer.apply_gradients(params_grads)

    def _effective_level(self):
        from ....utils.flags import get_flag

        flag = str(get_flag("FLAGS_tpu_amp_level", "") or "").upper()
        if flag in ("O0", "O1", "O2"):
            return flag
        return self._amp_level

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        program = loss.block.program
        level = self._effective_level()
        if level == "O0":  # kill switch: lower exactly like undecorated
            return self._optimizer.minimize(loss, startup_program,
                                            parameter_list, no_grad_set)
        program._amp = True
        program._amp_lists = self._amp_lists
        program._amp_dtype = self._amp_dtype
        result = self._optimizer.minimize(loss, startup_program,
                                          parameter_list, no_grad_set)
        startup = startup_program or framework.default_startup_program()
        from .fp16_utils import (rewrite_master_weights,
                                 wire_dynamic_loss_scaling)

        if level == "O2":
            self._master_of = rewrite_master_weights(
                program, startup, self._amp_dtype, self._amp_lists)
            program._amp_master_of = dict(self._master_of)
        if self._amp_dtype == "float16":
            bop = next((op for op in program.global_block().ops
                        if op.type == "backward"), None)
            if bop is not None and \
                    bop.attrs.get("gradient_merge") is not None:
                import warnings

                warnings.warn(
                    "fp16 loss scaling is not wired under gradient "
                    "merge (the merged-grad cond owns the update "
                    "cadence); training proceeds UNSCALED — expect "
                    "fp16 gradient underflow. Use bfloat16 instead.")
            elif self._use_dynamic_loss_scaling:
                self._scale_state = wire_dynamic_loss_scaling(
                    program, startup, {
                        "init_loss_scaling": self._loss_scaling,
                        "incr_every_n_steps": self._incr_every_n_steps,
                        "decr_every_n_nan_or_inf":
                            self._decr_every_n_nan_or_inf,
                        "incr_ratio": self._incr_ratio,
                        "decr_ratio": self._decr_ratio,
                    })
            elif bop is not None:
                # static scaling: the lowering scales the cotangent and
                # unscales the synced grads — identity math, but fp16
                # backward intermediates stay representable
                bop.attrs["static_loss_scaling"] = self._loss_scaling
        program._version += 1
        return result


def decorate(optimizer, amp_lists=None, init_loss_scaling=2.**15,
             incr_every_n_steps=1000, decr_every_n_nan_or_inf=2,
             incr_ratio=2.0, decr_ratio=0.8,
             use_dynamic_loss_scaling=True, amp_dtype="bfloat16",
             amp_level="O2"):
    """Reference: decorator.py:218. `amp_dtype` selects the low-precision
    compute tier: "bfloat16" (the default; no loss scaling needed) or
    "float16" (dynamic loss scaling); anything else raises ValueError.
    `amp_level` "O1" = cast policy only, "O2" (default) = policy +
    16-bit live params with ZeRO-sharded fp32 master weights."""
    return OptimizerWithMixedPrecision(
        optimizer, amp_lists, init_loss_scaling, use_dynamic_loss_scaling,
        incr_every_n_steps, decr_every_n_nan_or_inf, incr_ratio,
        decr_ratio, amp_dtype=amp_dtype, amp_level=amp_level)
