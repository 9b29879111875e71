"""Serving runtime tests (paddle_tpu/serving): paged KV cache
accounting, continuous-batching correctness — token streams
BIT-IDENTICAL to sequential per-request decoding and exact against the
dense no-paging reference — block-table edge cases (page-boundary
crossing, chunked prefill), full-pool admission backpressure, cancel
eviction, AOT warmup all-hit through the persistent compile cache,
the registry-assembled bench ``serving`` block, telemetry schema
validity of serving_request/serving_step, and the tpu-lint
serving_decode exemplar's deliberate-defect twin (a fetch seeded into
the decode scan must fire the host-sync checker)."""
import json
import os
import sys

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import serving

_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_DIR)

MODEL_CFG = serving.TinyLMConfig(vocab=48, embed=24, layers=2, heads=2,
                                 kv_heads=2, head_dim=8, ffn=48,
                                 max_seq=48)
#: ONE model instance per run: engines over it share the jitted step,
#: so the many-engine tests don't recompile per engine
_MODEL = serving.TinyDecoderLM(MODEL_CFG)
_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = _MODEL.init_params(seed=3)
    return _PARAMS


def _engine(**over):
    cfg = dict(num_pages=96, page_size=4, max_seqs=6)
    cfg.update(over)
    return serving.Engine(_MODEL, params=_params(),
                          config=serving.EngineConfig(**cfg))


@pytest.fixture(autouse=True)
def _fresh_registry():
    obs.reset_registry()
    yield
    obs.reset_registry()


# -- paged KV cache ---------------------------------------------------------

def test_kv_cache_alloc_free_occupancy():
    cfg = serving.KVCacheConfig(num_pages=10, page_size=4,
                                pages_per_seq=5, num_layers=1,
                                num_kv_heads=1, head_dim=8)
    kv = serving.PagedKVCache(cfg)
    assert kv.pages_free == 10 and kv.occupancy == 0.0
    p0 = kv.alloc(0, 9)             # ceil(9/4) = 3 pages
    assert len(p0) == 3 and kv.pages_in_use == 3
    p1 = kv.alloc(1, 4)             # exactly one page boundary
    assert len(p1) == 1
    assert set(p0).isdisjoint(p1)
    assert kv.block_table(0) == p0
    assert kv.peak_pages_in_use == 4
    assert kv.free(0) == 3
    assert kv.pages_in_use == 1 and kv.free(0) == 0  # idempotent
    with pytest.raises(ValueError, match="already"):
        kv.alloc(1, 2)
    with pytest.raises(ValueError, match="max_context"):
        kv.alloc(2, 21)             # > pages_per_seq * page_size


def test_kv_cache_admission_backpressure():
    cfg = serving.KVCacheConfig(num_pages=4, page_size=4,
                                pages_per_seq=4, num_layers=1,
                                num_kv_heads=1, head_dim=8)
    kv = serving.PagedKVCache(cfg)
    assert kv.alloc(0, 12) is not None      # 3 of 4 pages
    assert not kv.can_admit(8)
    assert kv.alloc(1, 8) is None           # pool can't cover 2 pages
    assert kv.alloc(2, 4) is not None       # but 1 page still fits
    kv.free(0)
    assert kv.can_admit(8)


# -- engine correctness -----------------------------------------------------

def test_single_request_matches_dense_reference():
    """Engine greedy stream == dense (no paging, no engine) decode,
    including EOS stop."""
    eng = _engine()
    r = np.random.RandomState(0)
    prompt = r.randint(0, 48, size=7).astype(np.int32)
    req = eng.submit(prompt, max_new_tokens=10)
    eng.run_until_idle()
    ref = serving.dense_decode_reference(_MODEL, _params(), prompt, 10)
    assert req.output_tokens == ref
    # EOS: pick the first generated token as eos -> stream stops at 1
    eos = ref[0]
    eng2 = _engine()
    req2 = eng2.submit(prompt, max_new_tokens=10, eos_id=eos)
    eng2.run_until_idle()
    assert req2.output_tokens == [eos]
    assert req2.state == serving.RequestState.FINISHED


def test_continuous_batching_bit_identical_to_sequential():
    """THE acceptance property: staggered concurrent requests through
    the continuous-batching engine produce token streams bit-identical
    to decoding each request alone (fresh engine, same weights)."""
    r = np.random.RandomState(1)
    prompts = [r.randint(0, 48, size=n).astype(np.int32)
               for n in (5, 17, 3, 9, 21, 2, 7)]
    maxnew = [6, 9, 4, 12, 5, 8, 7]
    arrive = [0, 0, 1, 2, 2, 5, 7]

    eng = _engine()
    reqs, i, step = [], 0, 0
    while i < len(prompts) or not eng.scheduler.idle:
        while i < len(prompts) and arrive[i] <= step:
            reqs.append(eng.submit(prompts[i], max_new_tokens=maxnew[i]))
            i += 1
        eng.step()
        step += 1
    batched = [list(q.output_tokens) for q in reqs]
    assert all(len(b) == m for b, m in zip(batched, maxnew))

    sequential = []
    for p, m in zip(prompts, maxnew):
        e = _engine()
        q = e.submit(p, max_new_tokens=m)
        e.run_until_idle()
        sequential.append(list(q.output_tokens))
    assert batched == sequential


def test_page_boundary_crossing_and_chunked_prefill():
    """A prompt longer than the largest prefill bucket (16 here, after
    the max-context clamp) prefills in chunks, and decode repeatedly
    crosses page boundaries (page_size=4) — stream still exact vs the
    dense reference."""
    eng = _engine()
    assert eng.plan.max_prefill_chunk == 16
    r = np.random.RandomState(2)
    prompt = r.randint(0, 48, size=21).astype(np.int32)  # 2 chunks
    req = eng.submit(prompt, max_new_tokens=13)          # crosses pages
    eng.run_until_idle()
    ref = serving.dense_decode_reference(_MODEL, _params(), prompt, 13)
    assert req.output_tokens == ref
    assert eng.kv.pages_in_use == 0  # retired -> freed


def test_full_pool_admission_backpressure():
    """Pool sized for ~1 request: later submissions queue (depth gauge
    rises) and admit only as earlier requests retire; all finish with
    the same streams they'd produce alone."""
    eng = _engine(num_pages=6, max_seqs=6)  # 6*4 = 24 tokens of pool
    r = np.random.RandomState(3)
    prompts = [r.randint(0, 48, size=8).astype(np.int32)
               for _ in range(3)]
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]  # 4 pages
    depth_seen = 0
    steps = 0
    while not eng.scheduler.idle and steps < 200:
        stats = eng.step()
        depth_seen = max(depth_seen, stats["queue_depth"])
        assert eng.kv.pages_in_use <= 6
        steps += 1
    assert depth_seen >= 1  # backpressure actually engaged
    assert all(q.state == serving.RequestState.FINISHED for q in reqs)
    solo = []
    for p in prompts:
        e = _engine()
        q = e.submit(p, max_new_tokens=8)
        e.run_until_idle()
        solo.append(list(q.output_tokens))
    assert [list(q.output_tokens) for q in reqs] == solo


def test_cancel_evicts_pages_mid_decode():
    eng = _engine()
    r = np.random.RandomState(4)
    keep = eng.submit(r.randint(0, 48, size=6).astype(np.int32),
                      max_new_tokens=20)
    kill = eng.submit(r.randint(0, 48, size=6).astype(np.int32),
                      max_new_tokens=20)
    for _ in range(3):
        eng.step()
    assert kill.output_tokens  # decoding underway
    in_use_before = eng.kv.pages_in_use
    eng.cancel(kill)
    eng.step()  # retire happens at the step boundary
    assert kill.state == serving.RequestState.CANCELLED
    assert eng.kv.pages_in_use < in_use_before
    got = list(kill.stream())  # stream closed, yields the partial set
    assert got == kill.output_tokens
    eng.run_until_idle()
    assert keep.state == serving.RequestState.FINISHED
    assert len(keep.output_tokens) == 20
    assert eng.kv.pages_in_use == 0
    # the cancelled request's telemetry says cancelled
    snap = obs.registry().snapshot()
    assert snap["counters"]["serving.requests_cancelled"] == 1


def test_submit_validation_and_queue_bound():
    eng = _engine(max_queue=1)
    with pytest.raises(ValueError, match="empty"):
        eng.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="max context"):
        eng.submit(np.zeros((40,), np.int32), max_new_tokens=40)
    eng.submit(np.zeros((4,), np.int32), max_new_tokens=2)
    with pytest.raises(RuntimeError, match="queue full"):
        eng.submit(np.zeros((4,), np.int32), max_new_tokens=2)
    eng.close()
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(np.zeros((4,), np.int32))


def test_over_length_request_rejected_at_model_max_seq():
    """Page rounding makes the pool bound looser than the model's
    max_seq (ceil(20/8)*8 = 24): admission must reject against the
    MODEL bound, or positions would clip and KV slots collide."""
    model = serving.TinyDecoderLM(serving.TinyLMConfig(
        vocab=32, embed=16, layers=1, heads=2, kv_heads=2, head_dim=8,
        ffn=32, max_seq=20))
    eng = serving.Engine(model, config=serving.EngineConfig(
        num_pages=16, page_size=8, max_seqs=2))
    assert eng.kv.config.max_context == 24  # pool bound, rounded up
    with pytest.raises(ValueError, match="max context"):
        eng.submit(np.zeros((15,), np.int32), max_new_tokens=7)  # 22>20
    eng.submit(np.zeros((15,), np.int32), max_new_tokens=5)      # ==20


def test_cancel_while_queued_publishes_event():
    """A request cancelled BEFORE admission still produces its
    serving_request event and the cancelled counter — submitted ==
    finished + cancelled must reconcile for the bench block."""
    eng = _engine(max_seqs=2)
    a = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=6)
    b = eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=6)
    c = eng.submit(np.arange(6, dtype=np.int32), max_new_tokens=6)
    eng.step()  # a, b admitted; c queued behind max_seqs
    assert c.state == serving.RequestState.QUEUED
    eng.cancel(c)
    eng.step()
    assert c.state == serving.RequestState.CANCELLED
    eng.run_until_idle()
    reg = obs.registry()
    snap = reg.snapshot()["counters"]
    assert snap["serving.requests_submitted"] == 3
    assert snap["serving.requests_finished"] == 2
    assert snap["serving.requests_cancelled"] == 1
    assert snap["event.serving_request"] == 3
    assert a.state == b.state == serving.RequestState.FINISHED


def test_attention_impl_conflict_raises():
    model = serving.TinyDecoderLM(serving.TinyLMConfig(
        vocab=32, embed=16, layers=1, heads=2, kv_heads=2, head_dim=8,
        ffn=32, max_seq=16), attention_impl="reference")
    with pytest.raises(ValueError, match="conflicts"):
        serving.Engine(model, config=serving.EngineConfig(
            num_pages=8, page_size=4, max_seqs=2,
            attention_impl="kernel"))


def test_close_cancels_everything():
    eng = _engine()
    a = eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=30)
    b = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=30)
    eng.step()
    eng.close()
    assert a.state == serving.RequestState.CANCELLED
    assert b.state == serving.RequestState.CANCELLED
    assert eng.kv.pages_in_use == 0
    assert a.result() == a.output_tokens  # streams closed, no hang


# -- AOT warmup through the persistent compile cache ------------------------

def test_warmup_all_hit_on_restart(tmp_path, monkeypatch):
    """Cold engine warmup: every bucket a classified MISS; a second
    engine (the restarted serving process) warms ALL-HIT from the
    fingerprint index — with serving_decode/serving_prefill sources."""
    from paddle_tpu.fluid import compile_cache as cc

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    cc._reset_for_tests()
    try:
        model = serving.TinyDecoderLM(serving.TinyLMConfig(
            vocab=32, embed=16, layers=1, heads=2, kv_heads=2,
            head_dim=8, ffn=32, max_seq=16))
        cfg = serving.EngineConfig(num_pages=16, page_size=4,
                                   max_seqs=2)
        cold = serving.Engine(model, config=cfg, seed=0).warmup()
        assert cold["misses"] == len(cold["buckets"])
        assert cold["hits"] == 0 and cold["unclassified"] == 0
        warm = serving.Engine(serving.TinyDecoderLM(model.config),
                              config=cfg, seed=0).warmup()
        assert warm["hits"] == len(warm["buckets"])
        assert warm["misses"] == 0
        reg = obs.registry()
        assert reg.counter("event.compile_cache").value >= \
            2 * len(cold["buckets"])
    finally:
        cc.disable()
        cc._reset_for_tests()


# -- bench block + telemetry ------------------------------------------------

def test_serving_bench_block_assembled_from_registry(tmp_path):
    """Tier-1 CI leg: the synthetic multi-tenant trace runs, the
    ``serving`` block is ASSEMBLED FROM THE REGISTRY (block dict ==
    registry().blocks()["serving"]), and it carries tokens/sec +
    p50/p99 + queue depth."""
    from paddle_tpu.observability import publish

    reg = obs.configure(telemetry_dir=str(tmp_path), rank=0)
    eng = _engine(max_seqs=4)
    trace = serving.synthetic_trace(n_requests=10, n_tenants=3, seed=7,
                                    vocab=48, prompt_range=(3, 14),
                                    output_range=(3, 8))
    summary = serving.run_trace(eng, trace, warmup=False)
    assert summary["finished"] == 10
    block = publish.serving_block()
    assert block is not None
    assert reg.blocks()["serving"] == block
    assert block["tokens_per_sec"] == summary["tokens_per_sec"] > 0
    assert block["requests_finished"] == 10
    assert block["latency_ms"]["p50"] is not None
    assert block["latency_ms"]["p99"] >= block["latency_ms"]["p50"]
    assert block["queue_depth"]["max"] is not None
    assert block["tokens_generated"] == summary["tokens_generated"]


def test_serving_block_none_without_engine():
    from paddle_tpu.observability import publish

    assert publish.serving_block() is None


def test_serving_events_schema_valid(tmp_path):
    """Every record the engine writes — serving_request /
    serving_step / steps — validates against the locked telemetry
    schema, and the per-event required fields are present."""
    obs.configure(telemetry_dir=str(tmp_path), rank=0)
    eng = _engine(max_seqs=4)
    reqs = [eng.submit(np.arange(1 + i, dtype=np.int32) % 48,
                       max_new_tokens=3, tenant="t%d" % (i % 2))
            for i in range(3)]
    eng.run_until_idle()
    eng.cancel(reqs[0])  # already finished: no-op event-wise
    recs = []
    for name in os.listdir(tmp_path):
        if name.endswith(".jsonl"):
            with open(os.path.join(tmp_path, name)) as f:
                recs.extend(json.loads(ln) for ln in f if ln.strip())
    assert recs
    problems = obs.validate_records(recs, obs.load_schema(
        os.path.join(_REPO, "tools", "telemetry_schema.json")))
    assert problems == []
    kinds = {}
    for r in recs:
        if r.get("kind") == "event":
            kinds.setdefault(r["event"], []).append(r)
    assert len(kinds.get("serving_request", [])) == 3
    assert kinds["serving_step"]
    req_ev = kinds["serving_request"][0]
    assert req_ev["status"] == "finished"
    assert req_ev["output_tokens"] == 3
    st_ev = kinds["serving_step"][0]
    assert {"running", "queue_depth", "kv_blocks_in_use",
            "kv_page_dtype", "kv_page_bytes",
            "resident_batch"} <= set(st_ev)
    assert st_ev["kv_page_dtype"] == "float32"


def test_bench_serving_leg_inprocess():
    """bench.py's --serving leg returns the registry-assembled block
    and a tokens/sec headline (run in-process, tiny trace; the command
    line itself refuses a backend that is not tpu)."""
    sys.path.insert(0, _REPO)
    try:
        import bench
    finally:
        sys.path.pop(0)
    out = bench._bench_serving(n_requests=4, seed=1)
    assert out["metric"] == "serving_tokens_per_sec"
    assert out["value"] > 0
    assert out["serving"]["requests_submitted"] == 4
    assert out["serving"] == obs.registry().blocks()["serving"]


# -- quantization tier: int8 KV pages + PTQ weights -------------------------

def test_int8_attention_bounded_error_and_kernel_parity():
    """int8 pages with per-slot scales: the reference attention stays
    within bounded error of the float pages, and the Pallas kernel
    (interpret mode on CPU) matches the quantized reference."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention, ragged_paged_attention_reference)

    r = np.random.RandomState(0)
    S, Q, Hq, Hkv, D = 3, 4, 4, 2, 16
    P, page, npp = 8, 8, 4
    q = r.standard_normal((S, Q, Hq, D)).astype(np.float32)
    kf = r.standard_normal((P, page, Hkv, D)).astype(np.float32)
    vf = r.standard_normal((P, page, Hkv, D)).astype(np.float32)
    tbl = r.randint(0, P, (S, npp)).astype(np.int32)
    ctx = np.array([page * 2, 5, page * 4], np.int32)
    ql = np.array([2, 4, 1], np.int32)
    s_k = np.maximum(np.abs(kf).max(axis=(2, 3)), 1e-9) / 127.0
    s_v = np.maximum(np.abs(vf).max(axis=(2, 3)), 1e-9) / 127.0
    kq = np.clip(np.round(kf / s_k[:, :, None, None]), -127,
                 127).astype(np.int8)
    vq = np.clip(np.round(vf / s_v[:, :, None, None]), -127,
                 127).astype(np.int8)

    o_f = ragged_paged_attention_reference(q, kf, vf, tbl, ctx, ql)
    o_q = ragged_paged_attention_reference(q, kq, vq, tbl, ctx, ql,
                                           k_scale=s_k, v_scale=s_v)
    err = float(np.max(np.abs(np.asarray(o_f) - np.asarray(o_q))))
    assert err < 0.05, err
    o_ker = ragged_paged_attention(q, kq, vq, tbl, ctx, ql,
                                   impl="kernel", k_scale=s_k,
                                   v_scale=s_v)
    d = float(np.max(np.abs(np.asarray(o_ker) - np.asarray(o_q))))
    assert d < 1e-5, d
    # scale arrays are both-or-neither
    with pytest.raises(ValueError, match="k_scale"):
        ragged_paged_attention_reference(q, kq, vq, tbl, ctx, ql,
                                         k_scale=s_k)


def test_int8_page_roundtrip_bit_exact():
    """Values of the form n * stored_scale (n integer in [-127, 127])
    survive the quantize -> dequantize page round-trip bit-exactly."""
    r = np.random.RandomState(1)
    P, page, Hkv, D = 8, 8, 2, 16
    sex = np.full((P, page), 2.0 / 127.0, np.float32)
    n = r.randint(-127, 128, (P, page, Hkv, D))
    kex = n.astype(np.float32) * sex[:, :, None, None]
    kq = np.clip(np.round(kex / sex[:, :, None, None]), -127,
                 127).astype(np.int8)
    rt = kq.astype(np.float32) * sex[:, :, None, None]
    assert np.array_equal(rt, kex)


def test_int8_page_byte_census_and_admission():
    """page_bytes: int8 pages cost elem bytes + per-slot fp32 scales —
    under a FIXED pool byte budget that admits ~2x the bf16 resident
    batch (~4x fp32). Device state: int8 layers are 4-tuples
    (k, v, k_scale, v_scale); float layers stay 2-tuples (the
    byte-identity of the unquantized path is structural)."""
    import jax.numpy as jnp

    kw = dict(num_pages=16, page_size=8, pages_per_seq=4, num_layers=2,
              num_kv_heads=2, head_dim=16)
    c32 = serving.KVCacheConfig(dtype="float32", **kw)
    c16 = serving.KVCacheConfig(dtype="bfloat16", **kw)
    c8 = serving.KVCacheConfig(dtype="int8", **kw)
    # per slot: 2 (k+v) * Hkv * D * elem_bytes (+ 2*4 scale when int8)
    assert c32.page_bytes == 2 * 8 * (2 * 2 * 16 * 4)
    assert c16.page_bytes == 2 * 8 * (2 * 2 * 16 * 2)
    assert c8.page_bytes == 2 * 8 * (2 * 2 * 16 * 1 + 2 * 4)
    budget = c32.pool_bytes
    p32, p16, p8 = (c.pages_for_budget(budget) for c in (c32, c16, c8))
    assert p16 == 2 * p32
    assert p8 >= 1.75 * p16          # ~2x minus the scale overhead
    assert c8.resident_batch == kw["num_pages"] // kw["pages_per_seq"]
    st8 = serving.PagedKVCache(c8).init_device_state()
    assert len(st8[0]) == 4
    assert st8[0][0].dtype == jnp.int8
    assert st8[0][2].shape == (16, 8)
    assert st8[0][2].dtype == jnp.float32
    st32 = serving.PagedKVCache(c32).init_device_state()
    assert len(st32[0]) == 2
    with pytest.raises(ValueError, match="dtype"):
        serving.KVCacheConfig(dtype="int4", **kw)


def test_int8_engine_batched_bit_identical_and_stats():
    """Continuous batching over int8 KV pages is bit-identical to
    sequential decoding at the same page dtype, and the engine stats /
    serving_step telemetry carry the quantization-tier fields."""
    r = np.random.RandomState(2)
    prompts = [r.randint(0, 48, size=n).astype(np.int32)
               for n in (5, 9, 3, 12)]

    def run(batched):
        eng = _engine(kv_dtype="int8")
        outs = []
        if batched:
            reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
            eng.run_until_idle()
            outs = [list(q.output_tokens) for q in reqs]
        else:
            for p in prompts:
                q = eng.submit(p, max_new_tokens=6)
                eng.run_until_idle()
                outs.append(list(q.output_tokens))
        stats = eng.stats()
        eng.close()
        return outs, stats

    batched, stats = run(True)
    sequential, _ = run(False)
    assert batched == sequential
    assert stats["kv_page_dtype"] == "int8"
    kvc = serving.KVCacheConfig(num_pages=96, page_size=4,
                                pages_per_seq=12, num_layers=2,
                                num_kv_heads=2, head_dim=8,
                                dtype="int8")
    assert stats["kv_page_bytes"] == kvc.page_bytes
    # pages_per_seq = ceil(max_seq 48 / page_size 4) = 12
    assert stats["kv_resident_batch"] == 96 // 12
    snap = obs.registry().snapshot()
    assert snap["gauges"].get("serving.kv_page_dtype") == "int8"


def test_int8_engine_step_events_schema_valid(tmp_path):
    """serving_step records from an int8 engine validate against the
    locked schema and carry kv_page_dtype='int8'."""
    obs.configure(telemetry_dir=str(tmp_path), rank=0)
    eng = _engine(kv_dtype="int8", max_seqs=4)
    eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
    eng.run_until_idle()
    eng.close()
    recs = []
    for name in os.listdir(tmp_path):
        if name.endswith(".jsonl"):
            with open(os.path.join(tmp_path, name)) as f:
                recs.extend(json.loads(ln) for ln in f if ln.strip())
    problems = obs.validate_records(recs, obs.load_schema(
        os.path.join(_REPO, "tools", "telemetry_schema.json")))
    assert problems == []
    steps = [r for r in recs if r.get("kind") == "event"
             and r.get("event") == "serving_step"]
    assert steps and steps[0]["kv_page_dtype"] == "int8"
    assert steps[0]["kv_page_bytes"] >= 0
    assert steps[0]["resident_batch"] > 0


def test_ptq_weights_roundtrip_and_engine_golden():
    """Post-training int8 weight quantization: ~4x byte reduction over
    the quantized subset, identity on unquantized leaves, and the
    quantized-weight engine decodes bit-identically to the dense
    reference run on the SAME quantized params (batched == sequential
    included)."""
    from paddle_tpu.serving.quantize import (is_quantized,
                                             maybe_dequantize,
                                             quantize_tensor,
                                             quantize_weights_int8)

    params = _params()
    qparams = quantize_weights_int8(params)

    def census(dense, quant):
        if is_quantized(quant):
            return (int(np.asarray(dense).nbytes),
                    int(np.asarray(quant["q"]).nbytes)
                    + int(np.asarray(quant["qscale"]).nbytes))
        if isinstance(dense, dict):
            pairs = [census(dense[k], quant[k]) for k in dense]
        elif isinstance(dense, (list, tuple)):
            pairs = [census(d, q) for d, q in zip(dense, quant)]
        else:
            return (0, 0)
        return (sum(a for a, _ in pairs), sum(b for _, b in pairs))

    dense_b, quant_b = census(params, qparams)
    assert dense_b > 0
    assert quant_b * 3.5 <= dense_b
    # per-tensor: abs-max per output channel, bounded dequant error
    w = np.asarray(params["layers"][0]["wq"])
    qt = quantize_tensor(w)
    assert np.asarray(qt["q"]).dtype == np.int8
    err = np.max(np.abs(np.asarray(maybe_dequantize(qt)) - w))
    assert err <= np.abs(w).max() / 127.0 * 0.5 + 1e-7
    # identity on plain arrays: unquantized traces are unchanged
    assert maybe_dequantize(w) is w

    r = np.random.RandomState(3)
    prompts = [r.randint(0, 48, size=n).astype(np.int32)
               for n in (4, 8, 3)]

    def run(batched):
        eng = serving.Engine(_MODEL, params=_params(),
                             config=serving.EngineConfig(
                                 num_pages=96, page_size=4, max_seqs=6,
                                 kv_dtype="int8",
                                 quantize_weights=True))
        outs = []
        if batched:
            reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
            eng.run_until_idle()
            outs = [list(q.output_tokens) for q in reqs]
        else:
            for p in prompts:
                q = eng.submit(p, max_new_tokens=5)
                eng.run_until_idle()
                outs.append(list(q.output_tokens))
        eng.close()
        return outs

    batched = run(True)
    assert batched == run(False)
    golden = serving.dense_decode_reference(_MODEL, qparams,
                                            prompts[0], 5)
    assert batched[0] == golden


def test_float_kv_state_structurally_unchanged():
    """Kill-switch guarantee: at the default float page dtype the
    device state, engine stats and step records are EXACTLY the
    pre-quantization shapes — 2-tuple layers, no scale arrays."""
    eng = _engine()
    cfg = eng.kv.config
    assert cfg.dtype == "float32" and not cfg.quantized
    layers = serving.PagedKVCache(cfg).init_device_state()
    assert all(len(entry) == 2 for entry in layers)
    eng.close()


# -- lint: the decode loop has no per-token host sync -----------------------

def _tpu_lint():
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        import tpu_lint
    finally:
        sys.path.pop(0)
    return tpu_lint


def test_serving_decode_exemplar_lints_clean():
    from paddle_tpu import analysis

    tpu_lint = _tpu_lint()
    prog, _ = tpu_lint.build_serving_decode()
    findings = analysis.run_static_checks(prog)
    s = analysis.summarize(findings)
    assert s["errors"] == 0, s["findings"]
    assert s["warnings"] == 0, s["findings"]


def test_fetch_in_decode_scan_fires_host_sync_error():
    """The deliberate-defect twin: seed a fetch INTO the decode scan
    body — the PR 5 host-sync checker must fire an ERROR anchored at
    the sub-block op (a per-token host sync would serialize the whole
    decode loop)."""
    from paddle_tpu import analysis

    tpu_lint = _tpu_lint()
    prog, _ = tpu_lint.build_serving_decode()
    scan_op = next(op for op in prog.global_block().ops
                   if op.type == "scan")
    sub = prog.block(scan_op.attrs["sub_block"])
    victim = sub.ops[0].output_arg_names[0]
    sub.append_op(type="fetch", inputs={"X": [victim]}, outputs={},
                  attrs={})
    findings = analysis.run_static_checks(prog)
    errs = [f for f in findings
            if f.checker == "host-sync" and f.severity == "error"]
    assert errs, findings
    assert errs[0].op_type == "fetch"
    assert errs[0].block_idx == sub.idx  # anchored inside the loop body
    assert "every iteration" in errs[0].message
