"""serving.Engine — the persistent inference runtime front end.

One Engine owns: a model (the `ServingModel` duck type — see
serving/model.py), the paged KV cache, the continuous-batching
scheduler, and the per-bucket AOT executables. Callers interact
through three thread-safe verbs:

    req = engine.submit(prompt_ids, max_new_tokens=32)   # enqueue
    for tok in req.stream(): ...                         # consume
    req.cancel()                                         # evict

and the engine advances by `step()` (or `run_until_idle()`); each step
retires/admits between decode steps and issues at most one prefill and
one decode dispatch, both at fixed bucket shapes.

Hot-loop contract: the per-token loop is host-side around fully
compiled fixed-shape steps — no data-dependent shapes, no fetch inside
a device loop (the tpu-lint `serving_decode` exemplar pins the
IR-level claim); the only per-STEP host sync is the sampled-token
harvest (a `LazyFetch` materialization, accounted to the profiler's
sync phase), which EOS detection and streaming need.

Telemetry (PR 7 registry): request-level p50/p99 latency and TTFT
histograms, queue-depth and KV-occupancy gauges, tokens/sec counters,
plus `serving_request` / `serving_step` events (schema-locked in
tools/telemetry_schema.json). The bench `serving` block
(observability/publish.serving_block) assembles from these.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from .aot import BucketCompiler
from .kv_cache import PagedKVCache
from .scheduler import BucketPlan, Request, RequestState, Scheduler

__all__ = ["EngineConfig", "Engine", "drain_manifest_entry",
           "adopt_submit_kwargs"]


@dataclass(frozen=True)
class EngineConfig:
    """Serving knobs; defaults read the FLAGS_tpu_serving_* surface
    (see serving/README.md for the full table)."""

    num_pages: int = 512
    page_size: int = 16
    max_seqs: int = 8
    max_queue: int = 0
    max_context: Optional[int] = None  # None = the model's max_seq
    attention_impl: str = "auto"
    step_event_every: int = 1
    kv_dtype: str = "float32"          # "float32" | "bfloat16" | "int8"
    quantize_weights: bool = False     # PTQ int8 params at init
    prefix_cache: bool = True          # share/COW prompt-prefix pages
    aging_steps: int = 32              # priority aging (0 disables)
    cached_pages: object = None        # prefix-cache budget: pages, or
    #                                    "64mb"-style byte strings; None
    #                                    reads the flag, 0 = unbounded

    @staticmethod
    def from_flags(**overrides) -> "EngineConfig":
        from ..utils.flags import get_flag

        kw = dict(
            num_pages=int(get_flag("FLAGS_tpu_serving_num_pages", 512)),
            page_size=int(get_flag("FLAGS_tpu_serving_page_size", 16)),
            max_seqs=int(get_flag("FLAGS_tpu_serving_max_seqs", 8)),
            max_queue=int(get_flag("FLAGS_tpu_serving_max_queue", 0)),
            attention_impl=str(get_flag(
                "FLAGS_tpu_serving_attention_impl", "auto") or "auto"),
            kv_dtype=str(get_flag(
                "FLAGS_tpu_serving_kv_dtype", "float32") or "float32"),
            quantize_weights=bool(get_flag(
                "FLAGS_tpu_serving_quantize_weights", False)),
            prefix_cache=bool(get_flag(
                "FLAGS_tpu_serving_prefix_cache", True)),
            aging_steps=int(get_flag(
                "FLAGS_tpu_serving_aging_steps", 32)),
            cached_pages=get_flag("FLAGS_tpu_serving_cached_pages", 0),
        )
        kw.update(overrides)
        return EngineConfig(**kw)


def drain_manifest_entry(req) -> dict:
    """One drain() manifest entry for an unfinished request: the
    continuation prompt is the original prompt PLUS the tokens already
    generated, with the remaining budget — the survivor's re-prefill
    reproduces the stream bit-identically (see Engine.drain). Shared by
    Engine.drain and the analysis/proto_models serving_drain model so
    the checker explores the EXACT entry shape production exports."""
    return {
        "prompt": [int(t) for t in req.prompt]
        + [int(t) for t in req.output_tokens],
        "max_new_tokens": int(req.max_new_tokens)
        - len(req.output_tokens),
        "eos_id": req.eos_id,
        "tenant": req.tenant,
        "already_emitted": len(req.output_tokens),
        "priority": req.priority,
        "temperature": req.temperature,
        "top_k": req.top_k,
        "top_p": req.top_p,
        "seed": req.seed,
        # the adopter's streams keep drawing per-index sampling keys
        # where this engine stopped
        "sample_step_offset": req.sample_step_offset
        + len(req.output_tokens),
    }


def adopt_submit_kwargs(entry) -> dict:
    """submit() kwargs for one manifest entry — the adopt() half of the
    same shared contract (prompt arrives as the positional arg)."""
    return dict(
        max_new_tokens=int(entry["max_new_tokens"]),
        eos_id=entry.get("eos_id"),
        tenant=entry.get("tenant", ""),
        priority=int(entry.get("priority", 0)),
        temperature=float(entry.get("temperature", 0.0)),
        top_k=int(entry.get("top_k", 0)),
        top_p=float(entry.get("top_p", 1.0)),
        seed=int(entry.get("seed", 0)),
        sample_step_offset=int(entry.get(
            "sample_step_offset", entry.get("already_emitted", 0))))


class Engine:
    """Continuous-batching serving engine over a paged KV cache."""

    def __init__(self, model, params=None, config: Optional[
            EngineConfig] = None, seed: int = 0):
        import jax

        self.config = config or EngineConfig.from_flags()
        self.model = model
        model_impl = getattr(model, "attention_impl", None) or "auto"
        if self.config.attention_impl != "auto":
            if model_impl not in ("auto", self.config.attention_impl):
                raise ValueError(
                    "EngineConfig.attention_impl=%r conflicts with "
                    "model.attention_impl=%r (the jitted step is "
                    "shared per model — use one impl per model "
                    "instance)" % (self.config.attention_impl,
                                   model_impl))
            model.attention_impl = self.config.attention_impl
        self.params = params if params is not None else \
            model.init_params(seed)
        if self.config.quantize_weights:
            from .quantize import quantize_weights_int8, weight_bytes

            dense_bytes = weight_bytes(self.params)
            self.params = quantize_weights_int8(self.params)
            try:
                from ..observability import registry

                reg = registry()
                reg.set_gauge("serving.weight_bytes_dense", dense_bytes)
                reg.set_gauge("serving.weight_bytes",
                              weight_bytes(self.params))
                reg.set_gauge("serving.weights_quantized", 1)
            except Exception:  # noqa: BLE001 - telemetry never gates
                pass
        # the TRUE per-request bound is the model's max_seq; pages
        # round UP to whole pages, so the pool bound can be looser
        max_ctx = min(self.config.max_context or model.config.max_seq,
                      model.config.max_seq)
        pages_per_seq = -(-int(max_ctx) // self.config.page_size)
        self.kv = PagedKVCache(model.kv_cache_spec(
            self.config.num_pages, self.config.page_size,
            pages_per_seq, dtype=self.config.kv_dtype),
            prefix_cache=self.config.prefix_cache,
            cached_pages=self.config.cached_pages)
        self.plan = BucketPlan.from_flags(
            self.config.max_seqs, self.kv.config.max_context)
        self.scheduler = Scheduler(self.kv, self.plan,
                                   self.config.max_seqs,
                                   self.config.max_queue,
                                   max_context=max_ctx,
                                   aging_steps=self.config.aging_steps)
        self.pages = self.kv.init_device_state()
        self._lock = threading.RLock()
        self._steps = 0
        self._tokens_generated = 0
        self._t_started = time.time()
        self._closed = False
        self._draining = False

        # the page state is donated into the step, like the executor's
        from ..utils.flags import get_flag

        donate = bool(get_flag("FLAGS_tpu_donate_buffers", True))
        self._donate = donate
        self._copy_fn = None  # lazy-jitted COW page copier

        # memoized on the model object: two engines over the SAME model
        # (a restart, the sequential-reference twin in tests) share
        # jax's in-process executable cache instead of re-tracing.
        # Keyed on (donate, attention_impl, kv_dtype): forward() closes
        # over the impl at trace time, so a stale memo would silently
        # serve the wrong attention path; the page dtype changes the
        # carried pytree structure (int8 pools carry scale arrays)
        memo_key = (donate, getattr(model, "attention_impl", "auto"),
                    self.config.kv_dtype)
        self._jitted = getattr(model, "_serving_jitted", None)
        if self._jitted is None or \
                getattr(model, "_serving_jitted_key", None) != memo_key:
            def _step(params, pages, tokens, block_tables,
                      context_lens, q_lens, temps, top_ks, top_ps,
                      seeds, steps, _model=model):
                return _model.forward(
                    params, tokens, pages, block_tables, context_lens,
                    q_lens,
                    sampling=(temps, top_ks, top_ps, seeds, steps))

            self._jitted = jax.jit(
                _step, donate_argnums=(1,) if donate else ())
            model._serving_jitted = self._jitted
            model._serving_jitted_key = memo_key
        self._compiler = BucketCompiler(self._jitted,
                                        self.kv.config.pages_per_seq)

    # -- public verbs ------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 16,
               eos_id: Optional[int] = None, tenant: str = "",
               priority: int = 0, temperature: float = 0.0,
               top_k: int = 0, top_p: float = 1.0, seed: int = 0,
               sample_step_offset: int = 0) -> Request:
        """Enqueue one generation request (thread-safe). Raises when
        the prompt exceeds max context or the bounded queue is full
        (FLAGS_tpu_serving_max_queue).

        `priority` is the scheduling class (higher preempts strictly
        lower — see scheduler.Scheduler.admit). `temperature` > 0
        samples via a per-request `seed` folded with the token index
        (temperature 0 = greedy argmax, the default); `top_k` /
        `top_p` filter the distribution first. `sample_step_offset`
        is the drain/adopt continuation hook: tokens the stream
        already emitted elsewhere, so a migrated sampled stream keeps
        drawing the same per-index keys."""
        with self._lock:
            # inside the lock: a submit racing close() must not land a
            # request no step() will ever retire (its stream would
            # never close)
            if self._closed:
                raise RuntimeError("engine is closed")
            if self._draining:
                raise RuntimeError(
                    "engine is draining (preemption notice) — "
                    "resubmit on the survivor")
            req = self.scheduler.new_request(
                prompt, max_new_tokens, eos_id=eos_id, tenant=tenant,
                priority=priority, temperature=temperature,
                top_k=top_k, top_p=top_p, seed=seed,
                sample_step_offset=sample_step_offset)
        self._reg_safe(lambda r: r.inc("serving.requests_submitted"))
        return req

    def cancel(self, request: Request) -> None:
        """Cancel a request: its stream closes and its KV pages free at
        the next step boundary (immediate when it is still queued)."""
        request.cancel()

    def warmup(self) -> dict:
        """AOT-compile every scheduler bucket through the persistent
        compile cache (PR 13) before first traffic — a restarted
        serving process reports all-hit here. Returns the
        BucketCompiler report plus the bucket list."""
        with self._lock:
            report = self._compiler.warmup(self.plan.all_buckets(),
                                           self.params, self.pages)
        report["buckets"] = [list(b)
                             for b in self.plan.all_buckets()]
        self._reg_safe(lambda r: r.set_gauge(
            "serving.buckets_compiled",
            len(self._compiler.compiled_buckets)))
        return report

    def step(self) -> dict:
        """One engine iteration: retire -> admit -> prefill dispatch ->
        decode dispatch -> telemetry. Returns step stats."""
        if self._closed:
            raise RuntimeError("engine is closed")
        t0 = time.perf_counter()
        with self._lock:
            for req in self.scheduler.retire():
                self._publish_request(req)
            admitted, preempted = self.scheduler.admit()
            # copy-on-write boundary pages queued at admission MUST be
            # materialized before any dispatch of this step can write
            self._apply_cow_copies()
            prefill_stats = self._run_prefill()
            decode_stats = self._run_decode()
            for req in self.scheduler.retire():
                self._publish_request(req)
            self._steps += 1
            hit = sum(self.kv.seq_cached_tokens(r.request_id)
                      for r in admitted)
            stats = {
                "step": self._steps,
                "queue_depth": self.scheduler.queue_depth,
                "running": len(self.scheduler.running),
                "kv_pages_in_use": self.kv.pages_in_use,
                "kv_pages_cached": self.kv.pages_cached,
                "prefix_hit_tokens": hit,
                "n_preempted": len(preempted),
                **prefill_stats, **decode_stats,
                "step_ms": round((time.perf_counter() - t0) * 1e3, 3),
            }
        for req in preempted:
            self._publish_preemption(req)
        if hit:
            self._reg_safe(lambda r: r.inc(
                "serving.prefix_hit_tokens", hit))
        self._publish_step(stats)
        return stats

    def _apply_cow_copies(self) -> None:
        """Materialize pending copy-on-write pages: one jitted
        row-copy per (src, dst) pair over every per-layer array — int8
        pools copy the per-slot scale arrays alongside the values
        because the copier walks the whole page tuple. Admission-time,
        outside the decode hot loop."""
        copies = self.kv.take_pending_copies()
        if not copies:
            return
        import jax
        import jax.numpy as jnp

        if self._copy_fn is None:
            def _copy(pages, src, dst):
                return [tuple(a.at[dst].set(a[src]) for a in entry)
                        for entry in pages]

            self._copy_fn = jax.jit(
                _copy, donate_argnums=(0,) if self._donate else ())
        for src, dst in copies:
            self.pages = self._copy_fn(self.pages, jnp.int32(src),
                                       jnp.int32(dst))

    def run_until_idle(self, max_steps: int = 100000) -> int:
        """Step until every submitted request finished (trace runner /
        tests). Returns the number of steps taken."""
        n = 0
        while not self.scheduler.idle and n < max_steps:
            self.step()
            n += 1
        return n

    def drain(self, grace_s: Optional[float] = None) -> dict:
        """Preemption-notice drain: stop admission, keep stepping so
        in-flight requests COMPLETE within the grace window, and export
        a migration manifest for whatever could not finish in time.

        Each manifest entry re-prefills on the survivor engine via
        `adopt()`: the new prompt is the original prompt PLUS the
        tokens already generated here, with the remaining token budget
        — under greedy decoding the chunked-prefill path's final-chunk
        logits reproduce the continuation bit-identically (the tpu-lint
        serving_decode exemplar's batched-vs-sequential contract), so a
        migrated stream is the uninterrupted stream, split in two.
        Requests that could not finish retire as `cancelled` HERE (one
        serving_request event each, as always); `already_emitted` tells
        the caller how many tokens the consumer already saw.

        Returns {"completed", "migrated": [entries...], "drain_s"} and
        publishes a `serving_drain` event. Idempotent admission stop:
        submit() raises while draining or after close()."""
        from ..distributed.preemption import default_grace_s

        grace = default_grace_s() if grace_s is None else float(grace_s)
        t0 = time.perf_counter()
        with self._lock:
            self._draining = True
            inflight = list(self.scheduler.queued) + \
                list(self.scheduler.running.values())
        deadline = t0 + grace
        while not self.scheduler.idle \
                and time.perf_counter() < deadline:
            self.step()
        manifest = []
        with self._lock:
            for req in inflight:
                if req.state == RequestState.FINISHED:
                    continue
                remaining = int(req.max_new_tokens) - \
                    len(req.output_tokens)
                if req.state == RequestState.CANCELLED \
                        or remaining <= 0:
                    continue
                manifest.append(drain_manifest_entry(req))
                req.cancel()
            for req in self.scheduler.retire():
                self._publish_request(req)
        completed = sum(1 for r in inflight
                        if r.state == RequestState.FINISHED)
        drain_s = round(time.perf_counter() - t0, 6)
        self._reg_safe(lambda reg: reg.event(
            "serving_drain", completed=completed,
            migrated=len(manifest), grace_s=grace, dur_ms=round(
                drain_s * 1e3, 3)))
        return {"completed": completed, "migrated": manifest,
                "drain_s": drain_s}

    def adopt(self, manifest) -> list:
        """Survivor half of a drained migration: resubmit every
        manifest entry (continuation prompts re-prefill through the
        chunked path). Returns the new Request list, aligned with the
        manifest order; entry `already_emitted` tokens of each stream
        were already delivered by the drained engine."""
        out = []
        for entry in manifest:
            out.append(self.submit(
                np.asarray(entry["prompt"], np.int32),
                **adopt_submit_kwargs(entry)))
        return out

    def close(self) -> None:
        """Cancel everything in flight and release the pool."""
        with self._lock:
            for req in list(self.scheduler.queued) + \
                    list(self.scheduler.running.values()):
                req.cancel()
            # retire() drains cancelled queued requests too, so the
            # queue is empty here and every request got its one
            # serving_request event
            for req in self.scheduler.retire():
                self._publish_request(req)
            self._closed = True

    # -- dispatch ----------------------------------------------------------
    def _dispatch(self, bucket: Tuple[int, int], group, tokens, ctx,
                  qlens) -> np.ndarray:
        """Pack one bucket, upload it through the PR 2 device-put path,
        run the AOT executable, and harvest the sampled tokens via
        LazyFetch (ONE per-step host sync, profiler-accounted)."""
        from ..fluid.executor import LazyFetch
        from ..reader.prefetcher import device_put_batch

        B, T = bucket
        npages = self.kv.config.pages_per_seq
        tables = np.zeros((B, npages), np.int32)
        temps = np.zeros((B,), np.float32)
        top_ks = np.zeros((B,), np.int32)
        top_ps = np.ones((B,), np.float32)
        seeds = np.zeros((B,), np.int32)
        steps = np.zeros((B,), np.int32)
        for b, req in enumerate(group):
            row = self.kv.block_table(req.request_id)
            tables[b, :len(row)] = row
            temps[b] = req.temperature
            top_ks[b] = req.top_k
            top_ps[b] = req.top_p
            seeds[b] = req.seed
            # the token this dispatch emits is stream index
            # len(output_tokens); offset carries indices a previous
            # engine already emitted (drain/adopt)
            steps[b] = req.sample_step_offset + len(req.output_tokens)
        feed = device_put_batch({
            "tokens": tokens.astype(np.int32),
            "tables": tables,
            "ctx": ctx.astype(np.int32),
            "qlens": qlens.astype(np.int32),
            "temps": temps, "top_ks": top_ks, "top_ps": top_ps,
            "seeds": seeds, "steps": steps,
        })
        next_tok, _logits, self.pages = self._compiler(
            bucket, self.params, self.pages, feed["tokens"],
            feed["tables"], feed["ctx"], feed["qlens"],
            feed["temps"], feed["top_ks"], feed["top_ps"],
            feed["seeds"], feed["steps"])
        return LazyFetch(next_tok).numpy()

    def _run_prefill(self) -> dict:
        group, B, T = self.scheduler.prefill_group()
        if not group:
            return {"n_prefill": 0, "prefill_tokens": 0}
        tokens = np.zeros((B, T), np.int32)
        ctx = np.zeros((B,), np.int32)
        qlens = np.zeros((B,), np.int32)
        chunks = []
        for b, req in enumerate(group):
            # full_prompt: the original prompt, or prompt + generated
            # tokens when re-prefilling after a preemption; prefilled
            # starts at the prefix-cache hit, so fully cached chunks
            # are never dispatched
            prompt = req.full_prompt
            chunk = min(T, req.prefill_len - req.prefilled)
            tokens[b, :chunk] = prompt[req.prefilled:
                                       req.prefilled + chunk]
            qlens[b] = chunk
            ctx[b] = req.prefilled + chunk
            chunks.append(chunk)
        toks = self._dispatch((B, T), group, tokens, ctx, qlens)
        for b, req in enumerate(group):
            req.prefilled += chunks[b]
            req.context_len = req.prefilled
            if req.prefilled >= req.prefill_len:
                # final chunk: its last-row logits ARE the first
                # generated token. Index the now-complete prompt's
                # pages for future prefix sharing.
                self.kv.register_prefix(req.request_id,
                                        req.full_prompt)
                req.state = RequestState.RUNNING
                req.last_token = int(toks[b])
                req._emit(req.last_token)
                self._tokens_generated += 1
                self.scheduler.finish_if_done(req)
        n_tok = int(sum(chunks))
        self._reg_safe(lambda r: r.inc("serving.prefill_tokens", n_tok))
        return {"n_prefill": len(group), "prefill_tokens": n_tok}

    def _run_decode(self) -> dict:
        group, B = self.scheduler.decode_group()
        if not group:
            return {"n_decode": 0}
        tokens = np.zeros((B, 1), np.int32)
        ctx = np.zeros((B,), np.int32)
        qlens = np.zeros((B,), np.int32)
        for b, req in enumerate(group):
            tokens[b, 0] = req.last_token
            ctx[b] = req.context_len + 1  # incl. the token written now
            qlens[b] = 1
        toks = self._dispatch((B, 1), group, tokens, ctx, qlens)
        for b, req in enumerate(group):
            req.context_len += 1
            req.last_token = int(toks[b])
            req._emit(req.last_token)
            self._tokens_generated += 1
            self.scheduler.finish_if_done(req)
        return {"n_decode": len(group)}

    # -- telemetry ---------------------------------------------------------
    def _reg_safe(self, fn) -> None:
        try:
            from ..observability import registry

            fn(registry())
        except Exception:  # noqa: BLE001 - telemetry must never gate
            pass

    def _publish_request(self, req: Request) -> None:
        def pub(reg):
            now = req.t_finish or time.time()
            latency_ms = (now - req.t_submit) * 1e3
            ttft_ms = ((req.t_first_token - req.t_submit) * 1e3
                       if req.t_first_token else None)
            status = req.state
            reg.inc("serving.requests_" + status)
            reg.inc("serving.tokens_generated",
                    len(req.output_tokens))
            reg.observe("serving.request_latency_ms", latency_ms)
            if ttft_ms is not None:
                reg.observe("serving.ttft_ms", ttft_ms)
            fields = dict(status=status,
                          latency_ms=round(latency_ms, 3),
                          output_tokens=len(req.output_tokens),
                          prompt_tokens=req.prompt_len,
                          request=int(req.request_id))
            if ttft_ms is not None:
                fields["ttft_ms"] = round(ttft_ms, 3)
            if req.tenant:
                fields["tenant"] = req.tenant
            if req.priority:
                fields["priority"] = req.priority
            if req.prefix_hit_tokens:
                fields["prefix_hit_tokens"] = req.prefix_hit_tokens
            if req.preemptions:
                fields["preemptions"] = req.preemptions
            reg.event("serving_request", **fields)

        self._reg_safe(pub)

    def _publish_preemption(self, req: Request) -> None:
        self._reg_safe(lambda reg: (
            reg.inc("serving.preemptions"),
            reg.event("serving_preempt",
                      request=int(req.request_id),
                      priority=int(req.priority),
                      output_tokens=len(req.output_tokens),
                      preemptions=int(req.preemptions))))

    def _publish_step(self, stats: dict) -> None:
        def pub(reg):
            reg.inc("serving.steps")
            reg.set_gauge("serving.queue_depth", stats["queue_depth"])
            reg.set_gauge("serving.running", stats["running"])
            reg.observe("serving.queue_depth", stats["queue_depth"])
            reg.observe("serving.step_ms", stats["step_ms"])
            if stats.get("n_decode"):
                reg.observe("serving.decode_batch", stats["n_decode"])
            every = max(1, int(self.config.step_event_every))
            if self._steps % every == 0:
                kvc = self.kv.config
                reg.event("serving_step",
                          running=stats["running"],
                          queue_depth=stats["queue_depth"],
                          kv_blocks_in_use=stats["kv_pages_in_use"],
                          n_prefill=stats.get("n_prefill", 0),
                          n_decode=stats.get("n_decode", 0),
                          kv_page_dtype=kvc.dtype,
                          kv_page_bytes=stats["kv_pages_in_use"]
                          * kvc.page_bytes,
                          resident_batch=kvc.resident_batch,
                          kv_pages_cached=stats.get(
                              "kv_pages_cached", 0),
                          prefix_hit_tokens=stats.get(
                              "prefix_hit_tokens", 0),
                          n_preempted=stats.get("n_preempted", 0))

        self._reg_safe(pub)

    # -- introspection -----------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            up = max(1e-9, time.time() - self._t_started)
            return {
                "steps": self._steps,
                "queue_depth": self.scheduler.queue_depth,
                "running": len(self.scheduler.running),
                "tokens_generated": self._tokens_generated,
                "tokens_per_sec": self._tokens_generated / up,
                "kv_pages_in_use": self.kv.pages_in_use,
                "kv_pages_cached": self.kv.pages_cached,
                "kv_occupancy": round(self.kv.occupancy, 4),
                "kv_peak_pages": self.kv.peak_pages_in_use,
                "prefix_cache": self.kv.prefix_cache,
                "prefix_hit_tokens": self.kv.prefix_hit_tokens,
                "cow_copies": self.kv.cow_copies,
                "prefix_evictions": self.kv.evictions,
                "preemptions": self.scheduler.preemption_count,
                "kv_page_dtype": self.kv.config.dtype,
                "kv_page_bytes": self.kv.config.page_bytes,
                "kv_pool_bytes": self.kv.config.pool_bytes,
                "kv_resident_batch": self.kv.config.resident_batch,
                "buckets_compiled": [
                    list(b) for b in self._compiler.compiled_buckets],
            }
