"""The measurement entry points (`bench.py`, `chip_smoke.py`) and what
they stand on: the command-line gates that refuse a backend that is not
`tpu`, the device-peaks table, `TPUPlace` resolution, where the compile
cache is placed, and a CPU rehearsal of every `chip_smoke.py` phase at
tiny size with the device check steered here, in the test."""
import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import bench  # noqa: E402
import chip_smoke  # noqa: E402
from childenv import cpu_child_env  # noqa: E402


def _run(argv, cwd=_REPO, extra=None):
    return subprocess.run([sys.executable] + argv, cwd=cwd,
                          env=cpu_child_env(extra), text=True,
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          timeout=300)


# -- gates: no chip, no number ------------------------------------------------

@pytest.mark.parametrize("leg", [[], ["--bert", "8"], ["--longctx"],
                                 ["--resnet"], ["--serving", "2"],
                                 ["--embedding", "1"]],
                         ids=lambda a: "_".join(a) or "default")
def test_bench_cli_refuses_cpu_backend(leg):
    proc = _run([os.path.join(_REPO, "bench.py")] + leg)
    assert proc.returncode != 0
    assert proc.stdout == ""          # no metric, no result line
    assert "not tpu" in proc.stderr


def test_chip_smoke_refuses_cpu_backend():
    proc = _run([os.path.join(_REPO, "chip_smoke.py")])
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "tpu" in last["error"]
    assert '"phase"' not in proc.stdout      # no phase ran on the CPU


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """Without the program beside it the script must fail, not print a
    result: steer the device check past the CPU so the import is what
    fails."""
    shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; chip_smoke.EXPECT_PLATFORM = 'cpu'; "
         "sys.exit(chip_smoke.main([]))"],
        cwd=str(tmp_path), env=cpu_child_env({"PYTHONPATH": ""}),
        text=True, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "paddle_tpu" in proc.stderr


# -- peaks table and places ---------------------------------------------------

def test_device_peaks_knows_the_v5e():
    row = bench.device_peaks("TPU v5 lite")
    assert row["bf16_flops"] == 197e12 and row["int8_ops"] == 393e12
    assert row["hbm_bytes_per_s"] == 819e9 and row["hbm_bytes"] == 16e9


def test_device_peaks_rejects_unknown_kind():
    with pytest.raises(ValueError, match="no published peaks"):
        bench.device_peaks("TPU v9000")
    # and so no utilization is ever computed against a guessed peak
    result = {"device": {"platform": "tpu", "kind": "cpu", "count": 1}}
    with pytest.raises(ValueError):
        bench._mfu_pct(result, 1e12)


def test_tpu_place_is_the_virtual_device_of_that_index():
    import jax

    import paddle_tpu.fluid as fluid

    assert fluid.TPUPlace(3).jax_device() == jax.devices()[3]
    assert fluid.TPUPlace().jax_device() == jax.devices()[0]


@pytest.mark.parametrize("index", [8, 64, -1])
def test_tpu_place_out_of_range_raises(index):
    import paddle_tpu.fluid as fluid

    with pytest.raises(ValueError, match="names device"):
        fluid.TPUPlace(index).jax_device()


# -- compile-cache placement ----------------------------------------------------

@pytest.fixture
def cc():
    from paddle_tpu.fluid import compile_cache

    compile_cache._reset_for_tests()
    yield compile_cache
    compile_cache.disable()
    compile_cache._reset_for_tests()


def test_cache_dir_is_the_env_var_and_nothing_else(cc, tmp_path,
                                                   monkeypatch):
    import jax

    want = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    monkeypatch.setattr(cc, "default_dir",
                        lambda: str(tmp_path / "default"))
    assert cc.cache_dir() == want
    assert cc.use_default_dir() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.path.isdir(os.path.join(want, "index"))   # the index too
    assert not os.path.exists(str(tmp_path / "default"))


def test_cache_dir_unset_is_checkout_jax_cache(cc, tmp_path, monkeypatch):
    """Unset: the library stays inert, an entry point gets the fixed
    `<checkout>/.jax_cache`, and the environment is left as it was (a
    value put there would outlive the call and re-place every later
    cache of the process)."""
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cc.default_dir() == os.path.join(_REPO, ".jax_cache")
    assert cc.cache_dir() is None
    # the rule, not the checkout: this test writes nothing into it
    stand_in = str(tmp_path / ".jax_cache")
    monkeypatch.setattr(cc, "default_dir", lambda: stand_in)
    assert cc.use_default_dir() == stand_in
    assert cc.cache_dir() == stand_in
    assert jax.config.jax_compilation_cache_dir == stand_in
    assert os.path.isdir(os.path.join(stand_in, "index"))
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ


def test_no_test_child_caches_in_the_checkout(tmp_path):
    """Every child a test starts gets a cache directory of its own (the
    launcher would otherwise export `<checkout>/.jax_cache`, and cold
    compiles would depend on what an earlier run left there)."""
    a, b = (cpu_child_env()["JAX_COMPILATION_CACHE_DIR"] for _ in "ab")
    assert a != b and os.path.isdir(a) and os.path.isdir(b)
    assert not a.startswith(os.path.join(_REPO, ".jax_cache"))
    placed = cpu_child_env({"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert placed["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)


def test_launcher_passes_the_env_var_through(monkeypatch, tmp_path):
    from paddle_tpu.distributed import launch

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    d = launch._compile_cache_dir()
    assert d == str(tmp_path)
    env = launch._worker_env(["127.0.0.1:1"], 0, 0, compile_cache_dir=d)
    assert env["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    d = launch._compile_cache_dir()
    assert d == os.path.join(_REPO, ".jax_cache")
    env = launch._worker_env(["127.0.0.1:1"], 0, 0, base_env={},
                             compile_cache_dir=d)
    assert env["JAX_COMPILATION_CACHE_DIR"] == d


def test_native_library_staleness_is_a_hash_not_a_file_time():
    from paddle_tpu.core.native import build

    so = build.build()
    want = build._sources_hash()
    assert open(so + ".sha256").read().strip() == want
    os.utime(so, (1, 1))                   # a copy changes file times
    assert not build._stale(want)
    assert build._stale("0" * 64)          # a changed source is stale


# -- chip_smoke phases, rehearsed on the CPU at tiny size -------------------------

@pytest.fixture
def smoke(monkeypatch):
    """The device check steered to the CPU; the kernel-in-HLO checks
    apply on the chip only."""
    monkeypatch.setattr(chip_smoke, "EXPECT_PLATFORM", "cpu")
    return chip_smoke


def _tiny_bert():
    from paddle_tpu.models import bert

    return bert.BertConfig(vocab_size=512, hidden_size=64,
                           num_hidden_layers=2, num_attention_heads=4,
                           intermediate_size=128,
                           max_position_embeddings=64)


def test_smoke_device_phase(smoke, monkeypatch):
    got = smoke.phase_device(count=8)
    assert got == {"platform": "cpu", "kind": "cpu", "count": 8}
    with pytest.raises(AssertionError, match="need 1 device"):
        smoke.phase_device(count=1)
    monkeypatch.setattr(chip_smoke, "EXPECT_PLATFORM", "tpu")
    with pytest.raises(AssertionError, match="not 'tpu'"):
        smoke.phase_device(count=8)


def test_smoke_bert_train_phase(smoke, capsys):
    got = smoke._run_phase("bert_train", smoke.phase_bert_train, batch=8,
                           seq_len=32, cfg=_tiny_bert(), steps=5)
    assert got["losses"][-1] < got["losses"][0]
    assert got["state_arrays_on_device"] > 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "bert_train" and line["checked"] == got
    assert {"seconds", "compile_seconds", "cache"} <= set(line)


def test_smoke_resnet_train_phase(smoke):
    got = smoke.phase_resnet_train(batch=8, depth=18, img=32,
                                   class_dim=10, steps=4)
    assert got["model"] == "resnet18"
    assert got["losses"][-1] < got["losses"][0]


def test_smoke_flash_attention_phase(smoke, monkeypatch):
    """Steered to the op's TPU dispatch (the flash kernel), under the
    Pallas interpreter since nothing here compiles for Mosaic."""
    import jax

    from paddle_tpu.utils.flags import get_flag, set_flags

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    calls = []
    real_fwd = fa._fwd_call

    def spy(*a, **kw):
        calls.append(1)
        return real_fwd(*a, **kw)

    monkeypatch.setattr(fa, "_fwd_call", spy)
    monkeypatch.setattr(fa, "_interpret_default", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    old = get_flag("FLAGS_flash_attention_min_seq")
    set_flags({"FLAGS_flash_attention_min_seq": 128})
    try:
        got = smoke.phase_flash_attention(B=1, H=2, S=256, D=64)
    finally:
        set_flags({"FLAGS_flash_attention_min_seq": old})
    assert calls, "the SDPA op did not dispatch to the flash kernel"
    assert max(got["max_rel_err_vs_reference"].values()) < got["tolerance"]


def test_smoke_serving_phase(smoke):
    got = smoke.phase_serving(n_requests=3, max_new=4)
    for kv_dtype in ("bfloat16", "int8"):
        assert got[kv_dtype]["streams_equal"]
        assert got[kv_dtype]["tokens"] == 12


def test_smoke_data_parallel_phase(smoke):
    got = smoke.phase_bert_data_parallel(batch=16, seq_len=32,
                                         cfg=_tiny_bert, steps=4, ndev=8)
    assert got["mesh"] == {"dp": 8} and got["per_device_batch"] == 2
    tol = got["tolerances"]
    assert max(got["loss_rel_diff"]) <= tol["loss_rel"]
    for run in ("after_first_update", "control_rows_permuted_one_chip"):
        assert got[run]["moment1_rel_l2"] <= tol["moment1_rel_l2"]
        assert got[run]["master_max_abs_diff_in_lr"] <= \
            tol["master_abs_in_lr"]
    assert got["sharded_state_arrays"] > 0
    hlo = got["collectives_in_hlo"]
    assert hlo["all-reduce"] + hlo["reduce-scatter"] > 0


@pytest.mark.parametrize("fault, match", [
    (None, None),
    ("sum_for_mean", "averaged gradients"),
    ("replica_lost_from_the_sum", "averaged gradients"),
    ("wrong_step_size", "two learning rates"),
])
def test_smoke_update_agreement_catches(smoke, fault, match):
    """The data-parallel phase's check of the first update refuses what
    a loss comparison lets through: Adam's step does not depend on the
    gradient's scale."""
    import numpy as np

    r = np.random.RandomState(0)
    grads = [r.randn(4, 64).astype("float32") for _ in range(3)]
    lr = 1e-4
    want = {"moment1": {"m%d" % i: 0.1 * g.mean(0)
                        for i, g in enumerate(grads)},
            "master": {"w%d" % i: -lr * np.sign(g.mean(0))
                       for i, g in enumerate(grads)}}
    scale = 4.0 if fault == "sum_for_mean" else 1.0
    kept = 3 if fault == "replica_lost_from_the_sum" else 4
    step = 3.2 * lr if fault == "wrong_step_size" else lr

    class Scope:
        def find_var(self, name):
            g = grads[int(name[1:])]
            if name[0] == "m":
                return 0.1 * scale * g[:kept].sum(0) / 4   # share lost
            return -step * np.sign(g.mean(0))

    if fault is None:
        got = smoke._update_agreement(want, Scope(), lr, smoke.DP_TOL)
        assert got["moment1_rel_l2"] == 0.0
        return
    with pytest.raises(AssertionError, match=match):
        smoke._update_agreement(want, Scope(), lr, smoke.DP_TOL)


def test_smoke_fails_whole_when_a_phase_raises(smoke, monkeypatch, capsys):
    """No phase's failure is caught and carried past: the script's last
    line says ok false and the exit code is 1."""
    monkeypatch.setattr(chip_smoke, "phase_device",
                        lambda count, device=None: device)

    def boom(**kw):
        raise RuntimeError("bert blew up")

    ran = []
    monkeypatch.setattr(chip_smoke, "phase_bert_train", boom)
    monkeypatch.setattr(chip_smoke, "phase_resnet_train",
                        lambda **kw: ran.append("resnet"))
    from paddle_tpu.fluid import compile_cache

    monkeypatch.setattr(compile_cache, "use_default_dir", lambda: "unused")
    assert chip_smoke.main([]) == 1
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["error"] == "RuntimeError: bert blew up"
    assert last["device"]["platform"] == "cpu"   # named even on failure
    assert ran == []


@pytest.mark.slow
def test_bench_resnet_path_runs_on_cpu():
    """The ResNet bench body end to end at toy scale."""
    import numpy as np

    res = bench._bench_resnet(batch=2, steps=1, warmup=0, depth=18,
                              img=32, class_dim=10)
    assert res["metric"] == "resnet50_train_throughput"
    assert res["value"] > 0 and "mfu_pct" not in res
    assert res["device"]["platform"] == "cpu"
    assert np.isfinite(res["loss"])
