"""What the five decoder test files share: a decoder's train step (SGD,
bf16 AMP where asked, every checkpoint recomputed), its batch, the
laying of seeded weights into a scope, the one SGD step at rate 1 that
reads the gradients, the check that an op's part names move no number,
and the traced step's jaxpr with its walk. A decoder's own `*_loss` and
sequence length come in as a `Case`; the five cases are below, each at
the length its file's tests read."""
import re

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.core.scope import Scope, global_scope
from paddle_tpu.fluid import framework
from paddle_tpu.fluid.contrib import mixed_precision
from paddle_tpu.models import (kimi_linear, kimi_vl, laguna, nemotron_h,
                               qwen3_next)


class Case:
    """A decoder under test: its `loss_fn(cfg, seq_len,
    checkpoints_out)` and the batch of `b` sequences of `s` positions
    its tests feed."""

    def __init__(self, loss_fn, s, b=2):
        self.loss_fn, self.s, self.b = loss_fn, s, b

    def batch(self, cfg, seed):
        r = np.random.default_rng(seed)
        ids = r.integers(0, cfg.vocab_size, (self.b, self.s + 1))
        return {"ids": ids[:, :-1].astype(np.int64),
                "labels": ids[:, 1:].astype(np.int64)}

    def build(self, cfg, amp, remat=True, lr=1.0):
        main, startup = framework.Program(), framework.Program()
        main.random_seed = startup.random_seed = 3
        with framework.program_guard(main, startup):
            with framework.unique_name_guard():
                ckpts = []
                loss, counters, _ = self.loss_fn(cfg, self.s,
                                                 checkpoints_out=ckpts)
                opt = fluid.optimizer.SGDOptimizer(learning_rate=lr)
                if amp:
                    opt = mixed_precision.decorate(
                        opt, use_dynamic_loss_scaling=False,
                        amp_dtype="bfloat16")
                if remat:
                    opt = fluid.optimizer.RecomputeOptimizer(opt)
                    opt._set_checkpoints(ckpts)
                opt.minimize(loss)
        return main, startup, loss, counters

    def loss_and_grads(self, cfg, amp, weights, batch, remat=True):
        """One SGD step at rate 1: the parameters' change is the
        gradient."""
        main, startup, loss, _ = self.build(cfg, amp, remat)
        scope = Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        masters = lay(main, scope, weights)
        value = exe.run(main, feed=batch, fetch_list=[loss], scope=scope)[0]
        grads = {k: w - np.asarray(scope.find_var(masters.get(k, k)),
                                   np.float32)
                 for k, w in weights.items()}
        return float(np.asarray(value).reshape(())), grads, main, exe


NEMOTRON_H = Case(nemotron_h.nemotron_h_loss, 24)      # 3 chunks of 8
QWEN3_NEXT = Case(qwen3_next.qwen3_next_loss, 80)      # 2 of 64, padded
KIMI_VL = Case(kimi_vl.kimi_vl_loss, 40)
LAGUNA = Case(laguna.laguna_loss, 40)
KIMI_LINEAR = Case(kimi_linear.kimi_linear_loss, 80)   # 2 of 64, padded


def lay(main, scope, weights):
    """`weights` into `scope` in each variable's dtype, and into its
    float32 master where AMP keeps one; returns {param: master}."""
    masters = dict(getattr(main, "_amp_master_of", {}))
    for name, w in weights.items():
        live = scope.find_var(name)
        assert tuple(live.shape) == w.shape, (name, live.shape, w.shape)
        if name in masters:
            scope.set_var(masters[name], jnp.asarray(w))
        scope.set_var(name, jnp.asarray(w).astype(live.dtype))
    return masters


def _parts_by_region(op_type, fn, *args):
    """{region: the parts named there} of the compiled gradient of
    `fn` under the op's marker and `jax.checkpoint` (its output read
    inside the checkpoint, as a layer's next op reads it), and the
    gradient itself: what a trace's fold would read of the op."""
    from paddle_tpu.observability import attribution as attr

    marker = "pp[b0;o3;%s;mix.tmp_0]" % op_type

    def layer(*a):
        with attr.marker_scope(marker):
            y = fn(*a)
        return jnp.sin(y)

    grad = jax.jit(jax.grad(lambda *a: jnp.sum(jax.checkpoint(layer)(*a)),
                            argnums=tuple(range(len(args)))))
    got = {}
    for name in set(re.findall(r'op_name="([^"]*)"',
                               grad.lower(*args).compile().as_text())):
        if marker in name:
            got.setdefault(attr.region_of(name, attr.provenance_of(name)),
                           set()).add(attr.part_of(name))
    return got, grad(*args)


def stamps_change_no_number(op_type, fn, args, parts):
    """With `FLAGS_tpu_op_provenance` on, every one of `parts`
    ({region: names}) stands in an `op_name` of that region; off, none
    does; the gradients are equal to the bit."""
    from paddle_tpu.utils.flags import get_flag, set_flags

    was = get_flag("FLAGS_tpu_op_provenance")
    try:
        set_flags({"FLAGS_tpu_op_provenance": True})
        (on, g_on), y_on = _parts_by_region(op_type, fn, *args), fn(*args)
        set_flags({"FLAGS_tpu_op_provenance": False})
        (off, g_off), y_off = _parts_by_region(op_type, fn, *args), fn(*args)
    finally:
        set_flags({"FLAGS_tpu_op_provenance": was})
    assert off == {}
    assert set(on) == {"recompute", "backward"}
    for region, names in parts.items():
        assert set(names) <= on[region], (region, on[region])
    for a, b in zip(g_on + (y_on,), g_off + (y_off,)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def step_jaxpr(main, st, feed, fetch):
    """The jaxpr of the whole train step, traced and not compiled."""
    from paddle_tpu.fluid import lowering

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(st)
    block = main.global_block()
    feeds = exe._prepare_feed(block, feed)
    state_in, _ = lowering.analyze_block(block, list(feeds), [fetch.name])
    specs = {n: global_scope().find_var(n) for n in state_in}
    entry = lowering.compile_block(main, block, feeds, [fetch.name], specs)
    return entry.jitted.trace(
        {n: exe._aval_of(a) for n, a in feeds.items()},
        {n: exe._aval_of(specs[n]) for n in entry.state_mut_names},
        {n: exe._aval_of(specs[n]) for n in entry.state_ro_names},
        jax.ShapeDtypeStruct((), np.uint32)).jaxpr.jaxpr


def walk(jaxpr, path=()):
    """(names of the enclosing equations, equation), depth first."""
    for eqn in jaxpr.eqns:
        yield path, eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from walk(sub, path + (eqn.primitive.name,))


FLASH_S = 128


def flash_from_its_length(monkeypatch):
    """Attention of `FLASH_S` keys goes to the flash kernels, as on
    the chip (tests/test_sdpa_dispatch.py does the same)."""
    import importlib

    from paddle_tpu.utils import flags

    # the package's attribute of that name is the function
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_interpret_default", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setitem(flags._FLAGS, "FLAGS_flash_attention_min_seq",
                        FLASH_S)
