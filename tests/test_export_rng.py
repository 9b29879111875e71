"""A traced train step serialized with jax.export and re-jitted from the
deserialized module. With FLAGS_prng_impl=rbg (what `auto` resolves to
on TPU — core/rng.py) the lowered program contains stablehlo
rng_bit_generator custom ops; this guards that the export round-trip
still works with them, on the CPU, before a chip run discovers it
doesn't."""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, lowering
from paddle_tpu.core.scope import global_scope
from paddle_tpu.utils.flags import get_flag, set_flags


@pytest.fixture
def _impl_flag():
    old = get_flag("FLAGS_prng_impl")
    yield
    set_flags({"FLAGS_prng_impl": old})


@pytest.mark.parametrize("impl", ["threefry2x32", "rbg"])
def test_export_roundtrip_with_dropout(_impl_flag, impl):
    import jax
    import jax.export  # noqa: F401 - 0.4.x needs the explicit submodule import

    set_flags({"FLAGS_prng_impl": impl})
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 3
    with framework.program_guard(main, startup):
        with framework.unique_name_guard():
            x = fluid.layers.data("x", shape=[16], dtype="float32")
            h = fluid.layers.fc(x, size=16)
            h = fluid.layers.dropout(h, dropout_prob=0.2)
            loss = fluid.layers.mean(h)
            fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {"x": np.ones((4, 16), np.float32)}

    block = main.global_block()
    state_in, _ = lowering.analyze_block(block, list(feed), [loss.name])
    state_specs = {n: global_scope().find_var(n) for n in state_in}
    entry = lowering.compile_block(main, block, feed, [loss.name],
                                   state_specs)

    def aval(v):
        a = np.asarray(v)
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    favals = {k: aval(v) for k, v in feed.items()}
    smut = {n: aval(global_scope().find_var(n))
            for n in entry.state_mut_names}
    sro = {n: aval(global_scope().find_var(n))
           for n in entry.state_ro_names}
    exp = jax.export.export(entry.jitted)(
        favals, smut, sro, jax.ShapeDtypeStruct((), np.uint32))
    blob = exp.serialize()
    assert len(blob) > 0

    re_exp = jax.export.deserialize(bytearray(blob))
    rejit = jax.jit(re_exp.call, donate_argnums=(1,))
    smut_vals = {n: np.asarray(global_scope().find_var(n))
                 for n in entry.state_mut_names}
    sro_vals = {n: np.asarray(global_scope().find_var(n))
                for n in entry.state_ro_names}
    out = rejit(feed, smut_vals, sro_vals, np.uint32(11))
    fetched, new_state = out
    flat = np.asarray(jax.tree_util.tree_leaves(fetched)[0])
    assert np.isfinite(flat).all()

    # direct call of the original entry with the same seed must agree
    out2 = entry.jitted(feed, smut_vals, sro_vals, np.uint32(11))
    flat2 = np.asarray(jax.tree_util.tree_leaves(out2[0])[0])
    np.testing.assert_allclose(flat, flat2, rtol=1e-6)
