"""ShardedCheckpointManager (orbax-backed, SURVEY §5 TPU mapping for
checkpoint/resume): mesh-sharded SPMD trainer state round-trips with
shardings preserved, retention prunes old steps, and resumed training
continues bit-identically."""
import tempfile

import numpy as np
import pytest


def _mesh_and_params():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = np.array(jax.devices()).reshape(4, 2)
    mesh = Mesh(devs, ("dp", "tp"))
    r = np.random.RandomState(0)
    w = jnp.asarray(r.randn(8, 16).astype("float32"))
    b = jnp.asarray(r.randn(16).astype("float32"))
    w = jax.device_put(w, NamedSharding(mesh, P(None, "tp")))
    b = jax.device_put(b, NamedSharding(mesh, P("tp")))
    step = jax.device_put(jnp.int32(3), NamedSharding(mesh, P()))
    return mesh, {"w": w, "b": b, "step": step}


def test_sharded_roundtrip_preserves_sharding():
    import jax

    from paddle_tpu.distributed import ShardedCheckpointManager

    mesh, tree = _mesh_and_params()
    d = tempfile.mkdtemp()
    mgr = ShardedCheckpointManager(d, max_to_keep=2)
    mgr.save(0, tree)
    assert mgr.latest_step() == 0

    restored = mgr.restore(template=tree)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
    np.testing.assert_array_equal(np.asarray(restored["b"]),
                                  np.asarray(tree["b"]))
    assert int(restored["step"]) == 3
    # layout landed back on the live mesh, not gathered to one device
    assert restored["w"].sharding == tree["w"].sharding
    assert restored["b"].sharding == tree["b"].sharding
    mgr.close()


def test_restore_lays_out_again_on_a_different_world_size():
    """Elastic restart (N' != N): a checkpoint written by a 4-device dp
    mesh restores DIRECTLY into a template laid out on a 2-device mesh
    (and vice versa back to 4) — orbax re-lays shards out against the
    template's shardings, values exactly preserved. This is the
    SPMD-trainer half of the world-size-change story (the ZeRO flat
    buffers re-shard via the executor's scope conversion; see
    tests/test_elastic.py)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed import ShardedCheckpointManager

    def tree_on(ndev):
        mesh = Mesh(np.array(jax.devices()[:ndev]), ("dp",))
        r = np.random.RandomState(7)
        w = jnp.asarray(r.randn(8, 16).astype("float32"))
        b = jnp.asarray(r.randn(16).astype("float32"))
        return {
            "w": jax.device_put(w, NamedSharding(mesh, P("dp"))),
            "b": jax.device_put(b, NamedSharding(mesh, P())),
        }

    d = tempfile.mkdtemp()
    mgr = ShardedCheckpointManager(d, max_to_keep=2)
    four = tree_on(4)
    mgr.save(0, four)

    two = tree_on(2)
    restored = mgr.restore(template=two)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(four["w"]))
    assert restored["w"].sharding == two["w"].sharding
    assert len(restored["w"].sharding.device_set) == 2

    # shrink persists: a checkpoint SAVED at 2 grows back to 4
    mgr.save(1, restored)
    regrown = mgr.restore(template=four)
    np.testing.assert_array_equal(np.asarray(regrown["w"]),
                                  np.asarray(four["w"]))
    assert len(regrown["w"].sharding.device_set) == 4
    mgr.close()


def test_scalar_leaves_roundtrip():
    """Plain python scalars in the state tree (lr, epoch) must survive
    the save -> restore(template) round trip."""
    from paddle_tpu.distributed import ShardedCheckpointManager

    _, tree = _mesh_and_params()
    tree = dict(tree, lr=0.05, epoch=2)
    d = tempfile.mkdtemp()
    mgr = ShardedCheckpointManager(d)
    mgr.save(0, tree)
    restored = mgr.restore(template=tree)
    assert float(restored["lr"]) == 0.05
    assert int(restored["epoch"]) == 2
    mgr.close()


def test_retention_prunes_old_steps():
    from paddle_tpu.distributed import ShardedCheckpointManager

    _, tree = _mesh_and_params()
    d = tempfile.mkdtemp()
    mgr = ShardedCheckpointManager(d, max_to_keep=2)
    for s in (1, 2, 3):
        mgr.save(s, tree)
    assert mgr.latest_step() == 3
    assert set(mgr.all_steps()) == {2, 3}
    mgr.close()


def test_restore_falls_back_past_corrupt_latest_step(tmp_path):
    """A mid-save kill can leave a partial/truncated latest step dir:
    default restore must validate it and fall back to the newest INTACT
    step instead of dying (or training from scratch). An explicitly
    requested step still raises."""
    import glob
    import os

    import jax.numpy as jnp

    from paddle_tpu.distributed import ShardedCheckpointManager

    _, tree = _mesh_and_params()
    d = str(tmp_path)
    mgr = ShardedCheckpointManager(d, max_to_keep=3)
    mgr.save(1, dict(tree, step=jnp.int32(1)))
    mgr.save(2, dict(tree, step=jnp.int32(2)))
    # simulate the truncation a kill mid-save leaves behind
    step_dir = os.path.join(d, "2")
    files = [p for p in glob.glob(os.path.join(step_dir, "**"),
                                  recursive=True) if os.path.isfile(p)]
    assert files, "expected orbax files under %s" % step_dir
    for p in files:
        open(p, "w").close()

    restored = mgr.restore(template=tree)
    assert int(restored["step"]) == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
    with pytest.raises(Exception):
        mgr.restore(step=2, template=tree)  # explicit step: no fallback
    mgr.close()


def test_restore_raises_when_no_step_is_intact(tmp_path):
    import glob
    import os

    from paddle_tpu.distributed import ShardedCheckpointManager

    _, tree = _mesh_and_params()
    d = str(tmp_path)
    mgr = ShardedCheckpointManager(d)
    mgr.save(1, tree)
    for p in glob.glob(os.path.join(d, "1", "**"), recursive=True):
        if os.path.isfile(p):
            open(p, "w").close()
    with pytest.raises(RuntimeError, match="no intact checkpoint"):
        mgr.restore(template=tree)
    mgr.close()


def test_resume_training_continues_identically():
    """Save mid-run, keep training; reload and retrain from the
    checkpoint: the loss tails must match exactly."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed import ShardedCheckpointManager

    mesh, tree = _mesh_and_params()
    x = jnp.asarray(np.random.RandomState(1).randn(4, 8)
                    .astype("float32"))

    @jax.jit
    def step(params):
        def loss_fn(p):
            return jnp.mean((x @ p["w"] + p["b"]) ** 2)

        l, g = jax.value_and_grad(loss_fn)(
            {"w": params["w"], "b": params["b"]})
        return l, {"w": params["w"] - 0.05 * g["w"],
                   "b": params["b"] - 0.05 * g["b"],
                   "step": params["step"] + 1}

    d = tempfile.mkdtemp()
    mgr = ShardedCheckpointManager(d)
    p = tree
    for _ in range(3):
        _, p = step(p)
    mgr.save(int(p["step"]), p)
    tail_a = []
    q = p
    for _ in range(3):
        l, q = step(q)
        tail_a.append(float(l))

    restored = mgr.restore(template=tree)
    tail_b = []
    q2 = restored
    for _ in range(3):
        l, q2 = step(q2)
        tail_b.append(float(l))
    np.testing.assert_array_equal(tail_a, tail_b)
    mgr.close()
