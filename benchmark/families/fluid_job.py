"""What the training families share: one compiled training step of a
Fluid program with its state, as the window drives it and as the
comparison reads it. A family gives the program (built through the
library's public API), the weights' spec and where the optimizer keeps
the first gradient; the parallel layout comes from the cell's file."""
from __future__ import annotations

import gc

import numpy as np

from benchmark import harness
from benchmark.reference import common


class FluidTrainJob:
    """One compiled training step with its state: what set-up drives
    through the checked steps and then hands to the window.

    A subclass sets `ref` (its reference module, for `leaves`) and
    implements `build_program() -> (main, startup, loss)`, `weight_spec()`
    and `first_gradient_state(main) -> ({param: state var}, scale)`."""

    ref = None

    def __init__(self, config, traffic, cell, seed, devices):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.core.scope import Scope

        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.recipe = config["recipe"]
        main_p, startup_p, loss = self.build_program()
        self.main, self.loss = main_p, loss
        self.program = self._parallel(main_p, loss, cell, devices)
        self.scope = Scope()
        self.exe = fluid.Executor(fluid.TPUPlace())
        self.exe.run(startup_p, scope=self.scope)
        self.spec = self.weight_spec()
        self.shapes = {n: tuple(sh) for n, sh, _, _ in self.spec}
        self.sizes = {n: int(np.prod(sh)) for n, sh in self.shapes.items()}
        self._check_spec(main_p)
        self.masters = dict(getattr(main_p, "_amp_master_of", {}))
        self.grad_state, self.grad_scale = self.first_gradient_state(main_p)
        self._lay_weights()
        self.lay_state(main_p)
        self._last_feed = None

    def fresh_programs(self):
        from paddle_tpu.fluid import framework

        main_p, startup_p = framework.Program(), framework.Program()
        main_p.random_seed = startup_p.random_seed = self.seed % (2 ** 31)
        return main_p, startup_p

    def _parallel(self, main_p, loss, cell, devices):
        layout = cell.get("parallel") or {}
        if not layout:
            return main_p
        import paddle_tpu.fluid as fluid

        if set(layout) != {"dp"} or int(layout["dp"]) != len(devices):
            raise ValueError("this family lays out %r only as {'dp': n} "
                             "over the cell's %d chips"
                             % (layout, len(devices)))
        return fluid.CompiledProgram(main_p).with_data_parallel(
            loss_name=loss.name, places=[fluid.TPUPlace(d.id)
                                         for d in devices])

    def _check_spec(self, main_p):
        have = {p.name: tuple(p.shape) for p in main_p.all_parameters()
                if p.trainable}
        want = {n: tuple(s) for n, s, _, _ in self.spec}
        if have != want:
            odd = sorted(set(have.items()) ^ set(want.items()))
            raise AssertionError(
                "the program's parameters are not the configuration's: %r"
                % (odd,))

    def _state_name(self, leaf):
        return self.masters.get(leaf, leaf)

    def _lay_weights(self):
        """The benchmark's weights into the program's state: float32
        into the masters, their cast into the live parameters."""
        weights = harness.make_weights(self.spec, self.seed)
        for name, w in weights.items():
            live = self.scope.find_var(name)
            if name in self.masters:
                self.scope.set_var(self.masters[name], w)
                self.scope.set_var(name, w.astype(live.dtype))
            else:
                self.scope.set_var(name, w)

    def lay_state(self, main_p):
        """Optimizer state a family starts elsewhere than the library's
        startup program does; none by default."""

    # -- the window's own call --
    def step(self, feed):
        self._last_feed = feed
        return self.exe.run(self.program, feed=feed, fetch_list=[self.loss],
                            scope=self.scope, return_numpy=False)[0]

    @staticmethod
    def loss_value(handle):
        # a data-parallel program fetches one loss per replica, each over
        # its share of the rows: the batch's loss is their mean
        return float(np.mean(np.asarray(handle, dtype=np.float64)))

    # -- what the comparison reads --
    def _shaped(self, names):
        """{leaf: the state array `names[leaf]`, in the leaf's shape}"""
        out = {}
        for leaf, name in names.items():
            v = self.scope.find_var(name)
            if v.ndim == 1 and v.size != self.sizes[leaf]:
                # the sharded update keeps its state flat and padded to a
                # multiple of the replica count
                v = v[:self.sizes[leaf]]
            out[leaf] = v.reshape(self.shapes[leaf])
        return out

    def first_gradient(self):
        """The first gradient as the optimizer got it, from its state
        after one step: ({leaf: float32 host array}, {leaf: norm})."""
        grads = common.scaled(self.ref.leaves(self._shaped(
            {leaf: self.grad_state[self._state_name(leaf)]
             for leaf, _, _, _ in self.spec})), self.grad_scale)
        norms = common.leaf_norms(grads)
        return ({k: np.asarray(v) for k, v in grads.items()},
                {k: float(v) for k, v in norms.items()})

    def change_norms(self):
        start = harness.make_weights(self.spec, self.seed)
        now = self._shaped({leaf: self._state_name(leaf)
                            for leaf, _, _, _ in self.spec})
        return {k: float(v) for k, v in common.diff_norms(
            self.ref.leaves(now), self.ref.leaves(start)).items()}

    def step_memory(self):
        """The compiler's account of the step that ran. There is no
        public accessor yet: this goes through the executor's private
        methods, as `chip_smoke._step_memory_gb` does."""
        entry, lowered, smut = self.exe._cached_lowerable(
            self.program, self._last_feed, [self.loss], self.scope)[:3]
        ma = self.exe._aot_compile(entry, lowered, smut).memory_analysis()
        out = {k: int(getattr(ma, k + "_size_in_bytes"))
               for k in ("argument", "output", "alias", "temp")}
        out["generated_code"] = int(
            getattr(ma, "generated_code_size_in_bytes", 0))
        return out

    def free(self):
        for name in list(self.scope.local_var_names()):
            self.scope.erase(name)
        self.exe.close()
        self.exe = self.program = self.main = self._last_feed = None
        gc.collect()
