"""Executor: feed -> compiled block -> fetch.

Reference parity: `python/paddle/fluid/executor.py` (`Executor.run`
`executor.py:896`, `_run_impl:1087`) driving the C++ op-loop executor
(`framework/executor.cc:184-471`). TPU-native: `run` lowers the block to a
single jitted XLA computation (cached by program version + feed shapes;
reference analogue: the prepared-ctx program cache `executor.cc:184`),
device_puts the feeds, executes, and device_gets the fetches. Persistable
state lives in the Scope as device-resident jax Arrays between runs —
feed/fetch are the only host<->HBM transfers per step.
"""
from __future__ import annotations

import time as _time
from typing import Dict, List, Optional

import numpy as np

from . import framework, lowering
from ..core.scope import Scope, global_scope
from ..core.types import to_numpy_dtype
from ..reader.prefetcher import is_donatable, is_on_device, \
    mark_donatable


class LazyFetch:
    """Device-resident fetch handle (`Executor.run(...,
    return_numpy=False)`): the host does NOT block on the step that
    produced it. Materialize explicitly with `.numpy()` (or implicitly
    via `np.asarray` / `float`); `.value` is the raw device array;
    `.block_until_ready()` waits without copying. Every host
    materialization is accounted to the profiler's `sync` step phase,
    so deferred-fetch loops show exactly when they blocked."""

    __slots__ = ("_v",)

    def __init__(self, v):
        self._v = v

    @property
    def value(self):
        return self._v

    @property
    def shape(self):
        return tuple(self._v.shape)

    @property
    def dtype(self):
        return self._v.dtype

    def block_until_ready(self):
        import jax

        jax.block_until_ready(self._v)
        return self

    def numpy(self):
        from . import profiler as _prof

        with _prof.span("exe.sync"):
            return Executor._fetch_to_numpy(self._v)

    def __array__(self, dtype=None, copy=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def __float__(self):
        return float(self.numpy().reshape(-1)[0])

    def __repr__(self):
        return "LazyFetch(shape=%s, dtype=%s)" % (self.shape, self.dtype)


class Executor:
    def __init__(self, place=None):
        from collections import OrderedDict

        self.place = place if place is not None else \
            framework._current_expected_place()
        # LRU of compiled executables, bounded by
        # FLAGS_tpu_compile_cache_size (dead programs no longer pin
        # compiled artifacts forever)
        self._cache = OrderedDict()

    # -- public API --------------------------------------------------------
    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            feed_var_name="feed", fetch_var_name="fetch",
            return_numpy=True, use_program_cache=True):
        """One step. Per-step wall time is split into the profiler's
        step phases (feed / dispatch / sync / host, plus compile on a
        cache miss) so infeed/compute overlap is measurable — see
        fluid/profiler.py step_phase_summary. Each phase is a
        `profiler.span` (`exe.feed`, `exe.bind`, `exe.compile`,
        `exe.dispatch`, `exe.writeback`, `exe.sync`) nested under one
        `exe.step`, so a jax profile shows them beside the device's
        line."""
        from . import profiler as _prof
        from .. import observability as _obs

        # hang forensics: stamp "inside a step" on the armed watchdog
        # (FLAGS_tpu_hang_timeout_s); a bare global check when off
        _obs.on_step_begin()
        t_step = _time.perf_counter()
        # this step's own seconds in the phases `host` is the residual
        # of; the spans add to it and to the process-wide counters
        ph = {"feed": 0.0, "dispatch": 0.0, "sync": 0.0, "compile": 0.0}
        comm0 = _prof.step_phase_total("comm")
        lanes0 = {ln: _prof.step_phase_total(ln)
                  for ln in ("comm_ici", "comm_dcn", "comm_mp")}
        try:
            with _prof.step_span("exe.step"):
                return self._run_impl(program, feed, fetch_list, scope,
                                      return_numpy, use_program_cache, ph)
        finally:
            total = _time.perf_counter() - t_step
            if ph["dispatch"] > 0.0:
                # a run that failed before dispatching is not a step:
                # it gets no `host` residual and no telemetry record.
                # host-collective time recorded DURING this step (PS
                # barriers, cross-rank agreement) already counted
                # itself into the comm phase — keep host disjoint
                comm_dt = _prof.step_phase_total("comm") - comm0
                host_dt = max(0.0, total - sum(ph.values()) - comm_dt)
                _prof.record_step_phase("host", host_dt)
                # one per-step telemetry record (observability registry:
                # JSONL sink + flight-recorder ring + capture poll);
                # a few dict ops when telemetry is idle
                from .. import observability as _obs

                rec = {
                    "feed_ms": ph["feed"] * 1e3,
                    "dispatch_ms": ph["dispatch"] * 1e3,
                    "comm_ms": comm_dt * 1e3,
                    "sync_ms": ph["sync"] * 1e3,
                    "host_ms": host_dt * 1e3,
                    "compile_ms": ph["compile"] * 1e3,
                    "total_ms": total * 1e3,
                }
                # multi-pod comm lanes: the slice of comm_ms spent on
                # cross-pod (dcn) vs intra-pod (ici) host coordination
                # — present only when a pod topology recorded any
                for ln, t0v in lanes0.items():
                    lane_dt = _prof.step_phase_total(ln) - t0v
                    if lane_dt > 0.0:
                        rec[ln + "_ms"] = lane_dt * 1e3
                # epoch-domain step START (t_step is perf_counter
                # time — unusable next to the event records' epoch
                # ts in the same JSONL stream)
                _obs.on_executor_step(rec, ts=_time.time() - total)

    def _run_impl(self, program, feed, fetch_list, scope, return_numpy,
                  use_program_cache, ph):
        from .profiler import span

        program = program or framework.default_main_program()
        # CompiledProgram front (compiler.py) wraps a Program
        from . import compiler

        _compiled = program if isinstance(
            program, compiler.CompiledProgram) else None
        if _compiled is not None:
            program = _compiled._unwrap()
        scope = scope or global_scope()
        feed = feed or {}
        fetch_list = fetch_list or []

        fetch_names = [
            f.name if isinstance(f, framework.Variable) else str(f)
            for f in fetch_list]

        if _compiled is not None:
            # BuildStrategy-driven fusion rewrites (first run decides:
            # idempotent markers make later runs no-ops). Fetch names
            # guard the passes from fusing away an observed var.
            bsty = _compiled._build_strategy
            if getattr(bsty, "fuse_elewise_add_act_ops", None):
                from .fusion_passes import fuse_elewise_add_act

                fuse_elewise_add_act(program, keep_names=fetch_names)
            if getattr(bsty, "fuse_bn_act_ops", None):
                from .fusion_passes import fuse_bn_act

                fuse_bn_act(program, keep_names=fetch_names)

        # the fusion passes run once, keyed to the FIRST run's fetch
        # list; a later run fetching a since-fused-away intermediate
        # must get an error naming the responsible knob, not lowering's
        # generic "never computed"
        fused_away = getattr(program, "_fused_away_vars", {})
        for n in fetch_names:
            if n in fused_away:
                raise RuntimeError(
                    "fetch var %r was removed from this program by the "
                    "BuildStrategy.%s fusion pass (applied on the "
                    "program's first run, which did not fetch it). "
                    "Fetch it on the first run, disable the knob, or "
                    "rebuild the program." % (n, fused_away[n]))

        # PS mode: the communicator needs this step's grads — extend the
        # fetch list internally (reference: send ops read the grad vars)
        ps_cfg = getattr(program, "_ps_cfg", None)
        n_user_fetches = len(fetch_names)
        if ps_cfg is not None and ps_cfg["mode"] in ("sync", "async", "half_async"):
            fetch_names = fetch_names + [
                g for g in sorted(ps_cfg["grad_of"])
                if g not in fetch_names]
            fetch_names = fetch_names + [
                m["grad"] for m in ps_cfg.get("sparse_tables",
                                              {}).values()
                if m["grad"] not in fetch_names]

        # elastic (strategy.elastic): auto-resume from the latest
        # checkpoint before the first step of this program
        ecfg = getattr(program, "_elastic_cfg", None)
        if ecfg is not None and not ecfg.get("_resumed"):
            self._elastic_resume(program, ecfg, scope)

        block = program.global_block()
        with span("exe.feed", ph):
            feed_arrays = self._prepare_feed(block, feed)
        if ps_cfg is not None and ps_cfg.get("sparse_tables"):
            # distributed_lookup_table: fetch this batch's unique rows
            # into the @PREFETCH/@REMAP feeds before compiling/running
            comm = self._ps_communicator(program, ps_cfg, scope)
            comm.prefetch(feed_arrays, scope)

        with span("exe.bind"):
            key = self._cache_key(program, feed_arrays, fetch_names, scope)
            entry, tail_n, feed_arrays = self._lookup_entry(
                program, feed_arrays, fetch_names, scope, key,
                use_program_cache)
        fresh_compile = entry is None
        if fresh_compile:
            with span("exe.compile", ph):
                entry = self._compile_and_cache(
                    program, block, feed_arrays, fetch_names, scope, key,
                    use_program_cache)
        with span("exe.bind"):
            states_mut, states_ro = self._bind_state(entry, scope)
        self._check_sparse_ids(program, feed_arrays)
        if fresh_compile:
            # OOM pre-flight (FLAGS_tpu_hbm_budget_mb, off by default):
            # reject a program whose modeled HBM peak exceeds the
            # budget BEFORE the first dispatch, naming the consumers.
            # A failed gate EVICTS the just-cached entry — same
            # invariant as the post-compile static checks: a caught-
            # and-retried run must re-enter the gate, not cache-hit
            # past it and dispatch the known-over-budget program
            try:
                self._hbm_preflight(program, entry, feed_arrays,
                                    states_mut, states_ro, scope)
            except Exception:
                self._cache.pop(key, None)
                raise
        if fresh_compile:
            # persistent compile-cache tier
            # (JAX_COMPILATION_CACHE_DIR): fingerprint the lowered
            # StableHLO at the exact avals the dispatch below will use
            # and look up the cross-process index — the lowering also
            # warms jax's trace cache, so the first dispatch re-pays
            # (at most) the backend compile the persistent tier
            # eliminates. No-op when the tier is off.
            with span("exe.compile", ph):
                self._cc_classify(entry, feed_arrays, states_mut,
                                  states_ro)
        seed = framework._global_seed_and_bump(program)
        with span("exe.feed", ph):
            feeds_dev = self._shard_feeds(entry, feed_arrays)
        cc_snap = None
        if fresh_compile and entry.cc_fingerprint is not None:
            from . import compile_cache as _cc

            cc_snap = (_cc.jax_stats(), _time.time())
        try:
            with span("exe.dispatch", ph, fresh=int(fresh_compile)):
                fetches, new_states = entry.jitted(
                    feeds_dev, states_mut, states_ro,
                    np.uint32(seed % (2**31)))
        except Exception as e:
            from ..observability import attribution as _attr

            if _attr.is_resource_exhausted(e):
                # OOM forensics: land the attributed memory breakdown
                # in the flight-recorder dump so the postmortem answers
                # "what was resident" without a repro; the original
                # error still propagates
                _attr.record_oom_forensics(
                    program, block, self._shard_plan_of(program),
                    self._shard_count(entry), feed_arrays,
                    list(entry.state_mut_names)
                    + list(entry.state_ro_names), scope, e)
            raise
        if cc_snap is not None:
            # hit/miss verdict + compile_cache event; the measured
            # backend-compile seconds move from the dispatch phase into
            # compile_ms, so a warm restart's first step shows
            # compile_ms ~ 0 where a cold one shows the full XLA cost
            self._cc_finish(entry, ph, cc_snap)
        if fresh_compile:
            self._maybe_elastic_warmup(program, entry, feed_arrays,
                                       fetch_names, scope)
        with span("exe.writeback"):
            for n, v in new_states.items():
                scope.set_var(n, v)
            if tail_n is not None:
                fetches = self._unreplicate_tail(block, fetch_names,
                                                 fetches, tail_n)
        if ecfg is not None:
            self._elastic_tick(program, ecfg, scope)

        from ..utils.flags import get_flag

        if get_flag("FLAGS_check_nan_inf"):
            with span("exe.sync", ph):
                self._check_nan_inf(fetch_names, fetches, new_states)
        if get_flag("FLAGS_benchmark"):
            # per-step device sync (reference: operator.cc:997)
            import jax

            with span("exe.sync", ph):
                jax.block_until_ready(fetches)

        if ps_cfg is not None:
            comm = self._ps_communicator(program, ps_cfg, scope)
            if ps_cfg["mode"] in ("sync", "async", "half_async"):
                # the communicator pushes THIS step's grads over RPC —
                # a required host sync, kept on every step
                with span("exe.sync", ph):
                    sparse_gvals = {
                        w: np.asarray(
                            fetches[fetch_names.index(m["grad"])])
                        for w, m in ps_cfg.get("sparse_tables",
                                               {}).items()}
                    gvals = {}
                    for g, p in ps_cfg["grad_of"].items():
                        gvals[p] = np.asarray(
                            fetches[fetch_names.index(g)])
                if sparse_gvals:
                    comm.push_sparse(sparse_gvals)
                comm.step(gvals, scope)
            else:
                comm.step({}, scope)
            fetches = fetches[:n_user_fetches]
        if return_numpy:
            with span("exe.sync", ph):
                return [self._fetch_to_numpy(v) for v in fetches]
        return [LazyFetch(v) for v in fetches]

    @staticmethod
    def _unreplicate_tail(block, fetch_names, fetches, tail_n):
        """Batch-majored fetches of a bucketed tail cut back to its
        rows (leading program dim -1 marks the batch axis; fixed-shape
        fetches pass through)."""
        sliced = []
        for fname, v in zip(fetch_names, fetches):
            fv = block._find_var_recursive(fname)
            shp = tuple(getattr(fv, "shape", ()) or ()) if fv is not None \
                else ()
            if shp[:1] == (-1,) and getattr(v, "ndim", 0) >= 1:
                v = v[:tail_n]
            sliced.append(v)
        return sliced

    def _lookup_entry(self, program, feed_arrays, fetch_names, scope, key,
                      use_program_cache):
        """(cached entry or None, rows of a bucketed batch tail or None,
        the feeds as that entry takes them)."""
        entry = self._cache.get(key) if use_program_cache else None
        if entry is not None:
            self._cache.move_to_end(key)
        tail_n = None
        if entry is None and use_program_cache:
            # batch-tail bucketing (SURVEY §7 hard part (d); reference
            # contract executor.cc:184 — any batch size runs without
            # recompiling): if a cached bucket's batch is an integer
            # multiple of this batch, replicate rows m times and run the
            # CACHED executable. Row replication is exact for mean-type
            # losses, their grads, and biased batch statistics (each row
            # appears exactly m times), so the step matches the
            # unbucketed one bit-for-bit up to fp reduction order; RNG
            # ops sample per padded row (documented divergence).
            # Non-divisible tails fall through to a one-time compile
            # that the cache then amortizes across epochs.
            hit = self._find_tail_bucket(program, feed_arrays,
                                         fetch_names, scope)
            if hit is not None:
                bkey, m, tail_n, rep_names = hit
                entry = self._cache[bkey]
                self._cache.move_to_end(bkey)
                feed_arrays = {
                    n: (self._replicate_rows(a, m)
                        if n in rep_names else a)
                    for n, a in feed_arrays.items()}
        return entry, tail_n, feed_arrays

    @staticmethod
    def _bind_state(entry, scope):
        """(states_mut, states_ro) of one step: the entry's state read
        from the scope, in the layout the executable was compiled for."""
        states_mut = {n: scope.find_var(n) for n in entry.state_mut_names}
        states_ro = {n: scope.find_var(n) for n in entry.state_ro_names}
        if entry.sharded_state:
            # ZeRO-1 layout: sharded optimizer state lives in the scope
            # as flat (padded,) buffers NamedSharding'd over the dp axis
            # — convert once (startup-initialized / checkpoint-restored
            # values arrive at their logical shapes)
            from ..parallel import sharded_update as _su

            for n, info in entry.sharded_state.items():
                v = states_mut.get(n)
                # model-sharded ZeRO vars: the device layout is the
                # model-major concat of mp per-member padded flats
                expect = (info.padded * info.mp,) \
                    if info.tp_dim is not None else (info.padded,)
                if v is not None and \
                        tuple(getattr(v, "shape", ())) != expect:
                    v = _su.to_sharded_global(v, info, entry.mesh,
                                              entry.dp_axis)
                    states_mut[n] = v
                    scope.set_var(n, v)
        if entry.sparse_tables:
            # vocab-sharded embedding layout: tables + per-row moments
            # live in the scope as (padded_rows, dim) buffers
            # NamedSharding'd P(axis) on the vocab axis — convert once
            # (logical-shape values from startup/checkpoint restore, or
            # a stale world's padding after an elastic N' restart)
            from ..embedding import engine as _emb

            for n, info in entry.sparse_tables.items():
                for d in (states_mut, states_ro):
                    v = d.get(n)
                    if v is not None and tuple(getattr(v, "shape", ())) \
                            != info.device_shape:
                        v = _emb.to_row_sharded_global(
                            v, info, entry.mesh, entry.dp_axis)
                        d[n] = v
                        scope.set_var(n, v)
        return states_mut, states_ro

    @staticmethod
    def _check_sparse_ids(program, feed_arrays):
        """Host-side OOV pre-check for vocab-sharded embedding feeds:
        an out-of-range id raises (FLAGS_tpu_static_checks=error) or
        warns (=warn) with the table/feed named BEFORE the dispatch —
        the same fatal/non-fatal split as every other checker behind
        the flag — instead of the dense path's silent clipped gather.
        O(batch) numpy per step, only for programs that actually
        carry a sparse plan."""
        plan = getattr(program, "_sparse_plan", None)
        if plan is None:
            return
        from ..utils.flags import get_flag

        mode = str(get_flag("FLAGS_tpu_static_checks", "off")
                   or "off").lower()
        if mode not in ("warn", "error"):
            return
        from ..embedding import engine as _emb

        try:
            _emb.check_oov_feeds(plan, feed_arrays)
        except ValueError as e:
            if mode == "error":
                raise
            import warnings

            warnings.warn("tpu-lint: " + str(e))

    #: checkers that need nothing from compile_block (no shard plan),
    #: run before the XLA compile so error mode fails fast
    _PRE_COMPILE_CHECKERS = ("collective-divergence", "donation-safety",
                             "host-sync", "dtype-contract")

    @staticmethod
    def _static_checks(program, feed_arrays, fetch_names, checkers=None):
        """Opt-in compile-time tpu-lint (paddle_tpu/analysis):
        FLAGS_tpu_static_checks="warn" surfaces every finding as a
        python warning; "error" raises on error-severity findings
        (collective divergence, read-after-donate, fetch-in-loop,
        shard-plan violations) BEFORE the first dispatch — the IR-only
        checkers even before the XLA compile. Runs only on
        compile-cache misses — steady-state steps never pay."""
        from ..utils.flags import get_flag

        mode = str(get_flag("FLAGS_tpu_static_checks", "off")
                   or "off").lower()
        if mode not in ("warn", "error"):
            return
        from .. import analysis

        findings = analysis.run_static_checks(
            program, feed_names=list(feed_arrays),
            fetch_names=list(fetch_names), checkers=checkers)
        if not findings:
            return
        import warnings

        for f in findings:
            warnings.warn("tpu-lint: " + analysis.format_finding(f))
        errors = [f for f in findings if f.severity == "error"]
        if mode == "error" and errors:
            raise RuntimeError(
                "FLAGS_tpu_static_checks=error: %d static-check "
                "error(s) in this program:\n%s" % (
                    len(errors), "\n".join(
                        "  " + analysis.format_finding(f)
                        for f in errors)))

    def _compile_and_cache(self, program, block, feed_arrays,
                           fetch_names, scope, key, use_program_cache):
        """The fresh-compile path shared by run() and warmup():
        pre-compile static checks -> compile_block -> post-compile
        checks -> LRU insert. Evicted entries drop their AOT-compiled
        artifacts EAGERLY (a dead in-memory entry must not pin
        compiled XLA executables in host RAM); the persistent tier
        (JAX_COMPILATION_CACHE_DIR) survives eviction, so a
        re-admitted program is a persistent-cache hit, not a fresh
        compile."""
        from . import compile_cache as _cc

        _cc.ensure()
        # tpu-lint, pre-compile leg (FLAGS_tpu_static_checks): the
        # IR-only checkers need nothing from XLA, so in error mode
        # a known-bad program is rejected BEFORE paying the
        # (potentially tens of seconds) compile below
        self._static_checks(program, feed_arrays, fetch_names,
                            checkers=self._PRE_COMPILE_CHECKERS)
        state_in, _ = lowering.analyze_block(
            block, list(feed_arrays), fetch_names)
        state_specs = {}
        for n in state_in:
            v = scope.find_var(n)
            if v is not None:
                state_specs[n] = v
        entry = lowering.compile_block(
            program, block, feed_arrays, fetch_names, state_specs)
        from ..utils.flags import get_flag

        if get_flag("FLAGS_enable_unused_var_check"):
            # reference: framework/unused_var_check.cc (op inputs
            # declared but never read); block-level equivalent here
            import warnings

            used = set()
            for op in block.ops:
                used.update(lowering._op_reads_writes(op)[0])
            unused = [n for n in feed_arrays if n not in used]
            if unused:
                warnings.warn(
                    "feed variables never read by the program: %s"
                    % unused)
        # tpu-lint, post-compile leg: zero1-invariants and
        # zero2-lifetimes verify the ShardedUpdatePlan that
        # compile_block just attached (program._shard_plan), so
        # they cannot run in the fail-fast leg above. MUST run
        # before the entry is cached: in error mode a caught-and-
        # retried run would otherwise cache-hit past the check and
        # dispatch the known-bad program
        self._static_checks(program, feed_arrays, fetch_names,
                            checkers=("zero1-invariants",
                                      "zero2-lifetimes",
                                      "sparse-update"))
        if use_program_cache:
            self._cache[key] = entry
            limit = int(get_flag("FLAGS_tpu_compile_cache_size", 128)
                        or 128)
            while len(self._cache) > limit:
                _, evicted = self._cache.popitem(last=False)
                evicted.aot_compiled = None
        return entry

    # -- persistent compile cache (fluid/compile_cache) -----------------
    def _cc_classify(self, entry, feed_arrays, states_mut, states_ro):
        """Persistent-tier classification of a fresh compile: lower
        the entry at the avals the dispatch will use, fingerprint the
        canonicalized StableHLO + mesh topology + lowering-relevant
        flags + jax version, and look up the cross-process index.
        Leaves cc_fingerprint None (classification off) when the tier
        is disabled or the entry is not jit-lowered."""
        from . import compile_cache as _cc

        if not _cc.enabled() or not hasattr(entry.jitted, "lower"):
            return
        try:
            favals = {n: self._aval_of(a)
                      for n, a in feed_arrays.items()}
            smut = {n: self._aval_of(v)
                    for n, v in states_mut.items()}
            sro = {n: self._aval_of(v)
                   for n, v in states_ro.items()}
            lowered = self._lower_entry(entry, favals, smut, sro)
            fp = _cc.fingerprint(lowered.as_text(), entry.mesh)
            entry.cc_fingerprint = fp
            entry.cc_prev = _cc.index_lookup(fp)
        except Exception:  # noqa: BLE001 - classification is telemetry
            entry.cc_fingerprint = None

    def _cc_finish(self, entry, ph, cc_snap, source="step"):
        """Close out a classified fresh compile after its first
        dispatch: re-attribute the measured backend-compile seconds
        from the dispatch phase into compile_ms, decide hit/miss, emit
        the `compile_cache` telemetry event, and write the index
        sentinel the next process's classification reads."""
        from . import compile_cache as _cc
        from . import profiler as _prof

        before, t0 = cc_snap
        d = _cc.stats_delta(before)
        comp_s = max(0.0, d["backend_compile_s"])
        if ph is not None and comp_s > 0.0 and ph["dispatch"] > 0.0:
            # keep dispatch strictly positive: a zeroed dispatch would
            # drop the whole step from the telemetry stream
            moved = max(0.0, min(comp_s, ph["dispatch"] - 1e-9))
            ph["dispatch"] -= moved
            ph["compile"] += moved
            _prof.move_step_phase("dispatch", "compile", moved)
        prev = entry.cc_prev
        hit = prev is not None or d["persistent_hits"] > 0
        saved_ms = max(0.0, d["saved_s"] * 1e3)
        nbytes = 0
        if prev is not None:
            saved_ms = max(saved_ms,
                           float(prev.get("compile_ms", 0.0))
                           - comp_s * 1e3)
            nbytes = int(prev.get("bytes", 0))
        elif not hit:
            nbytes = _cc.new_entry_bytes(t0)
        _cc.record_event("hit" if hit else "miss",
                         entry.cc_fingerprint,
                         compile_ms=comp_s * 1e3, saved_ms=saved_ms,
                         nbytes=nbytes, source=source)
        if prev is None and entry.cc_fingerprint:
            _cc.index_store(entry.cc_fingerprint,
                            {"compile_ms": round(comp_s * 1e3, 3),
                             "bytes": nbytes,
                             "mesh": _cc.mesh_signature(entry.mesh)})

    # -- AOT warmup (pre-compile before traffic / before failure) --------
    def warmup(self, program=None, shapes=None, meshes=None,
               fetch_list=None, scope=None, background=False):
        """Pre-compile this program BEFORE traffic or a failure pays
        the cost (ROADMAP direction 4; see paddle_tpu/parallel/README
        "Compilation cache & warmup"). For every feed-shape bucket in
        `shapes` (a list of dicts: feed name -> concrete shape tuple,
        example array, or jax.ShapeDtypeStruct) the program is
        compiled and ONE discarded step executes on state COPIES — so
        both jax's in-process executable cache and the persistent tier
        (JAX_COMPILATION_CACHE_DIR) are warm, and the first real
        step of that shape dispatches with compile_ms ~ 0 — without
        mutating any scope state or the program's RNG stream.

        `meshes` additionally pre-populates the persistent tier for
        OTHER mesh topologies: "elastic" enumerates the likely N'
        shrink variants (parallel.env.elastic_mesh_variants), or pass
        explicit Mesh objects / device counts. Variant compiles run
        against a CLONE of the program and never touch the live
        program or the in-memory entry cache.

        background=True runs the whole warmup in a daemon thread (the
        elastic-variant recipe: schedule after the first step) and
        returns the Thread; its `.warmup_report` lands on completion.
        Foreground calls return the report dict: {"compiled": [...],
        "cached": [...], "skipped": [...]}."""
        from . import compiler

        program = program or framework.default_main_program()
        if isinstance(program, compiler.CompiledProgram):
            program = program._unwrap()
        scope = scope or global_scope()
        fetch_names = [
            f.name if isinstance(f, framework.Variable) else str(f)
            for f in (fetch_list or [])]
        if background:
            import threading

            def _bg():
                t.warmup_report = self._warmup_impl(
                    program, shapes, meshes, fetch_names, scope,
                    in_background=True)

            t = threading.Thread(target=_bg, daemon=True,
                                 name="paddle-tpu-warmup")
            t.warmup_report = None
            t.start()
            return t
        return self._warmup_impl(program, shapes, meshes, fetch_names,
                                 scope)

    def _warmup_impl(self, program, shapes, meshes, fetch_names, scope,
                     in_background=False, skip_base=False):
        from . import compile_cache as _cc

        _cc.ensure()
        report = {"compiled": [], "cached": [], "skipped": []}
        buckets = []
        for s in (shapes or []):
            try:
                buckets.append(self._warmup_feed_arrays(
                    program.global_block(), s))
            except Exception as e:  # noqa: BLE001 - best-effort API
                report["skipped"].append(
                    {"shapes": {k: repr(v) for k, v in s.items()},
                     "error": "%s: %s" % (type(e).__name__, e)})
        if not skip_base:
            for feed_arrays in buckets:
                # background warmup must not mutate the in-memory LRU
                # under the stepping main thread — persistent-tier
                # population only there
                self._warmup_one(program, feed_arrays, fetch_names,
                                 scope, report,
                                 use_cache=not in_background)
        if meshes is None:
            return report
        if not buckets:
            # no explicit shapes: reuse the feed buckets of this
            # program's already-compiled in-memory entries (the shapes
            # real traffic ran), so the runbook's post-first-step
            # `exe.warmup(meshes="elastic")` pre-populates the N'
            # variants without restating the batch geometry
            buckets = self._buckets_from_cache(program)
        if not buckets:
            report["skipped"].append(
                {"reason": "mesh variants need `shapes` (or a prior "
                           "run of this program to borrow them from)"})
            return report
        for ndev, mesh in self._warmup_meshes(program, meshes):
            if mesh is None:
                report["skipped"].append(
                    {"mesh_devices": ndev,
                     "reason": "exceeds the local device count"})
                continue
            clone = self._mesh_variant_program(program, mesh)
            if clone is None:
                report["skipped"].append(
                    {"mesh": _cc.mesh_signature(mesh),
                     "reason": "program not cloneable"})
                continue
            total = int(np.prod([mesh.shape[a]
                                 for a in mesh.axis_names]))
            for feed_arrays in buckets:
                bad = [n for n, a in feed_arrays.items()
                       if getattr(a, "ndim", 0) >= 1
                       and a.shape[0] % total]
                if bad:
                    report["skipped"].append({
                        "mesh_devices": ndev, "feeds": sorted(bad),
                        "reason": "batch not divisible by %d devices"
                                  % total})
                    continue
                self._warmup_one(clone, feed_arrays, fetch_names,
                                 scope, report, use_cache=False,
                                 variant=ndev)
        return report

    def _warmup_one(self, program, feed_arrays, fetch_names, scope,
                    report, use_cache=True, variant=None):
        import jax

        from . import compile_cache as _cc

        desc = {"feed_shapes": {n: tuple(a.shape)
                                for n, a in sorted(
                                    feed_arrays.items())}}
        if variant is not None:
            desc["mesh_devices"] = variant
        try:
            key = self._cache_key(program, feed_arrays, fetch_names,
                                  scope)
            if use_cache:
                entry = self._cache.get(key)
                if entry is not None:
                    self._cache.move_to_end(key)
                    report["cached"].append(desc)
                    return entry
            t0 = _time.perf_counter()
            entry = self._compile_and_cache(
                program, program.global_block(), feed_arrays,
                fetch_names, scope, key, use_cache)
            if not hasattr(entry.jitted, "lower"):
                desc["reason"] = "not jit-compiled (host/dynamic ops)"
                report["skipped"].append(desc)
                return entry
            # one DISCARDED step on state copies: lands the executable
            # in jax's in-process cache AND the persistent tier without
            # touching scope state or the program's RNG stream (the
            # jitted step donates its state args — hence the copies)
            # variant meshes get HOST copies: live state committed to
            # the full mesh cannot feed a jit over a different device
            # set ("incompatible devices"), while host arrays place
            # implicitly onto whatever mesh the variant uses
            host = variant is not None
            states_mut = {n: self._copy_state(scope.find_var(n),
                                              host=host)
                          for n in entry.state_mut_names}
            states_ro = ({n: self._copy_state(scope.find_var(n),
                                              host=True)
                          for n in entry.state_ro_names}
                         if host else
                         {n: scope.find_var(n)
                          for n in entry.state_ro_names})
            if entry.sharded_state:
                from ..parallel import sharded_update as _su

                for n, info in entry.sharded_state.items():
                    v = states_mut.get(n)
                    expect = (info.padded * info.mp,) \
                        if info.tp_dim is not None else (info.padded,)
                    if v is not None and tuple(
                            getattr(v, "shape", ())) != expect:
                        states_mut[n] = _su.to_sharded_global(
                            v, info, entry.mesh, entry.dp_axis)
            if entry.sparse_tables:
                from ..embedding import engine as _emb

                for n, info in entry.sparse_tables.items():
                    for d in (states_mut, states_ro):
                        v = d.get(n)
                        if v is not None and tuple(
                                getattr(v, "shape", ())) \
                                != info.device_shape:
                            d[n] = _emb.to_row_sharded_global(
                                v, info, entry.mesh, entry.dp_axis)
            # same gate invariant as run(): a warmup-cached entry must
            # not let the first real run cache-hit past the HBM
            # pre-flight (FLAGS_tpu_hbm_budget_mb; no-op when unset) —
            # an over-budget bucket is evicted and reported skipped
            try:
                self._hbm_preflight(program, entry, feed_arrays,
                                    states_mut, states_ro, scope)
            except Exception:
                if use_cache:
                    self._cache.pop(key, None)
                raise
            self._cc_classify(entry, feed_arrays, states_mut,
                              states_ro)
            cc_snap = (_cc.jax_stats(), _time.time())
            feeds_dev = self._shard_feeds(entry, feed_arrays)
            out = entry.jitted(feeds_dev, states_mut, states_ro,
                               np.uint32(0))
            jax.block_until_ready(out)
            del out, states_mut
            if entry.cc_fingerprint is not None:
                self._cc_finish(entry, None, cc_snap, source="warmup")
            desc["warmup_ms"] = round(
                (_time.perf_counter() - t0) * 1e3, 3)
            report["compiled"].append(desc)
            return entry
        except Exception as e:  # noqa: BLE001 - warmup is best-effort
            desc["error"] = "%s: %s" % (type(e).__name__, e)
            report["skipped"].append(desc)
            return None

    def _warmup_feed_arrays(self, block, spec):
        """A zero-filled feed dict from one warmup bucket spec: values
        are concrete shape tuples (dtype from the program var),
        example arrays, or ShapeDtypeStructs."""
        out = {}
        for name, v in spec.items():
            if hasattr(v, "shape") and hasattr(v, "dtype"):
                out[name] = np.zeros(tuple(v.shape), np.dtype(v.dtype))
                continue
            shape = tuple(int(d) for d in v)
            if any(d < 0 for d in shape):
                raise ValueError(
                    "warmup shapes must be concrete (got %r for %r) — "
                    "pass the real bucket batch, not -1"
                    % (shape, name))
            var = block._find_var_recursive(name)
            dtype = np.dtype(to_numpy_dtype(var.dtype)) \
                if var is not None else np.dtype("float32")
            out[name] = np.zeros(shape, dtype)
        return out

    def _buckets_from_cache(self, program):
        """Zero-filled feed dicts rebuilt from this program's cached
        in-memory entries' feed keys — the shapes real traffic already
        ran (mesh-variant warmup borrows them when the caller passes
        no explicit `shapes`)."""
        buckets = []
        seen = set()
        for k in self._cache:
            if k[0] != program._uid or k[2] in seen:
                continue
            seen.add(k[2])
            buckets.append({n: np.zeros(tuple(shape), np.dtype(dt))
                            for n, shape, dt in k[2]})
        return buckets

    @staticmethod
    def _warmup_meshes(program, meshes):
        """[(ndev, Mesh)] to pre-populate: "elastic" enumerates likely
        shrink variants from the program's current mesh; explicit Mesh
        objects and integer device counts pass through. An integer
        exceeding the local device count yields (n, None) so the
        caller reports it skipped instead of silently dropping it."""
        from ..parallel import env as penv

        if isinstance(meshes, str):
            if meshes != "elastic":
                raise ValueError("meshes: Mesh list, int list, or "
                                 "'elastic' (got %r)" % (meshes,))
            return penv.elastic_mesh_variants(
                getattr(program, "_mesh", None))
        out = []
        for m in meshes:
            if isinstance(m, int):
                out.append((m, penv.mesh_for_world(
                    m, dp_axis=getattr(program, "_dp_axis", "dp"))))
            else:
                out.append((int(np.prod([m.shape[a]
                                         for a in m.axis_names])), m))
        return out

    @staticmethod
    def _mesh_variant_program(program, mesh):
        """A clone of `program` pinned to `mesh`, for persistent-tier
        pre-population of another topology: the clone has its own _uid
        (separate in-memory key space) and grows its own shard plan;
        the live program's mesh/plan are never touched."""
        try:
            # clone() carries _data_parallel / _dp_axis / AMP marks;
            # only the mesh is overridden
            clone = program.clone()
        except Exception:  # noqa: BLE001 - exotic program front
            return None
        clone._mesh = mesh
        return clone

    @staticmethod
    def _copy_state(v, host=False):
        if v is None:
            return None
        if is_on_device(v):
            if host:
                return np.asarray(Executor._fetch_to_numpy(v))
            import jax.numpy as jnp

            return jnp.array(v, copy=True)
        return np.array(v, copy=True)

    def _maybe_elastic_warmup(self, program, entry, feed_arrays,
                              fetch_names, scope):
        """FLAGS_tpu_warmup_elastic_variants > 0: after the FIRST step
        of a data-parallel program, pre-compile the likely elastic N'
        mesh variants in a background daemon thread, so a future
        shrink's executables are already in the persistent tier before
        any rank dies. At most once per program."""
        from ..utils.flags import get_flag

        from . import compile_cache as _cc

        try:
            limit = int(get_flag("FLAGS_tpu_warmup_elastic_variants", 0)
                        or 0)
        except (TypeError, ValueError):
            limit = 0
        if limit <= 0 or not _cc.enabled() or entry.mesh is None \
                or not getattr(program, "_data_parallel", False) \
                or not hasattr(entry.jitted, "lower"):
            return
        started = getattr(self, "_elastic_warmed", None)
        if started is None:
            started = self._elastic_warmed = set()
        if program._uid in started:
            return
        started.add(program._uid)
        from ..parallel import env as penv

        import jax

        variants = penv.elastic_mesh_variants(entry.mesh, limit=limit)
        if not variants:
            return
        shapes = [{n: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)
                   for n, a in feed_arrays.items()}]
        import threading

        def _bg():
            t.warmup_report = self._warmup_impl(
                program, shapes, [m for _, m in variants],
                list(fetch_names), scope, in_background=True,
                skip_base=True)

        t = threading.Thread(target=_bg, daemon=True,
                             name="paddle-tpu-elastic-warmup")
        t.warmup_report = None
        t.start()
        self._elastic_warmup_thread = t

    def live_resize(self, program, mesh=None, ndev=None, scope=None):
        """In-place device-tier mesh resize — the survivor half of the
        zero-downtime elasticity seam (distributed/preemption.py): no
        process exit, no checkpoint round-trip.

        Rewrites every sharded state var of `program` back to its
        logical host shape (parallel.sharded_update.
        reshard_scope_to_logical: ZeRO-1 moments, ZeRO-2 masters,
        row-sharded embedding tables), materializes every OTHER
        device-resident scope var to host numpy (a jax array committed
        to the old mesh's devices would fail the new mesh's dispatch
        with incompatible-devices — replicated params included), evicts
        the program's in-memory cache entries, and swaps
        ``program._mesh`` to the new topology. The next run() re-plans
        and re-shards exactly like an elastic cold restart restoring
        from a checkpoint — same `to_sharded_global` stale-padding trim,
        same pre-warmed N' executables (warmup(meshes="elastic") /
        FLAGS_tpu_warmup_elastic_variants) — so post-seam losses are
        bit-identical to that restart.

        Pass the target as a `mesh` or a device count `ndev`
        (parallel.env.mesh_for_world builds the hybrid or flat mesh).
        Publishes `live_resize` + `elastic_transition(mode=live)`
        events; returns the seam report dict."""
        import time as _time

        import jax

        from . import compiler
        from ..core.scope import global_scope
        from ..parallel import env as penv
        from ..parallel import sharded_update as _su

        t0 = _time.perf_counter()
        if isinstance(program, compiler.CompiledProgram):
            program = program._unwrap()
        scope = scope or global_scope()
        old_mesh = getattr(program, "_mesh", None)
        old_ndev = (int(np.prod(list(old_mesh.shape.values())))
                    if old_mesh is not None else 1)
        if mesh is None:
            if ndev is None:
                raise ValueError("live_resize needs mesh= or ndev=")
            mesh = penv.mesh_for_world(
                int(ndev), dp_axis=getattr(program, "_dp_axis", "dp"))
            if mesh is None:
                raise ValueError(
                    "no mesh for ndev=%d (local devices: %d)"
                    % (int(ndev), len(jax.devices())))
        new_ndev = int(np.prod(list(mesh.shape.values())))
        # 1) sharded state -> logical host numpy (moments, masters,
        #    embedding tables drop the old world's padded layout)
        n_state = _su.reshard_scope_to_logical(program, scope)
        # 2) every remaining device-resident scope var -> host numpy:
        #    committed-to-old-devices arrays (replicated params, BN
        #    stats) must not reach the new mesh's dispatch
        n_moved = 0
        for name in scope.local_var_names():
            v = scope.find_var(name)
            if v is not None and is_on_device(v):
                scope.set_var(name, np.asarray(self._fetch_to_numpy(v)))
                n_moved += 1
        # 3) drop the old topology's in-memory executables (the
        #    persistent tier keeps the new world's warmed variants)
        n_evicted = 0
        for k in [k for k in self._cache if k[0] == program._uid]:
            self._cache.pop(k, None)
            n_evicted += 1
        # 4) swap the mesh; next run() re-plans against it
        program._mesh = mesh
        report = {
            "old_world": old_ndev, "new_world": new_ndev,
            "n_state": n_state, "n_host_moved": n_moved,
            "n_evicted": n_evicted,
            "coordination_s": round(_time.perf_counter() - t0, 6),
        }
        try:
            from ..observability.registry import registry

            reg = registry()
            reg.event("live_resize", old_world=old_ndev,
                      new_world=new_ndev, mode="live", status="ok",
                      coordination_s=report["coordination_s"],
                      rebuild_s=report["coordination_s"])
            reg.event("elastic_transition", old_world=old_ndev,
                      new_world=new_ndev, mode="live",
                      coordination_s=report["coordination_s"])
        except Exception:  # noqa: BLE001 - telemetry only
            pass
        return report

    @staticmethod
    def _fetch_to_numpy(v):
        """Multi-host: a fetch sharded over remote processes is not fully
        addressable; return the locally-addressable shards concatenated
        (reference analogue: each trainer fetches its own scope)."""
        try:
            return np.asarray(v)
        except Exception:
            shards = getattr(v, "addressable_shards", None)
            if not shards:
                raise
            datas = [np.asarray(s.data) for s in shards]
            return np.concatenate(datas, axis=0) if len(datas) > 1 \
                else datas[0]

    def _ps_communicator(self, program, ps_cfg, scope=None):
        if not hasattr(self, "_ps_comms"):
            self._ps_comms = {}
        key = program._uid
        # a user-started fluid.communicator.Communicator wins — even
        # over a previously cached instance, so start()/stop()/start()
        # cycles actually swap the communicator the steps use
        user_comm = getattr(program, "_ps_comm", None)
        cached = self._ps_comms.get(key)
        if user_comm is not None and cached is not None \
                and cached is not user_comm \
                and not getattr(cached, "_completed", False):
            # don't abandon the replaced instance mid-flight: its
            # half-async sender thread would keep pushing stale grads
            cached.complete()
        comm = user_comm or cached
        if comm is not None and getattr(comm, "_completed", False):
            # stop()'d/closed communicators are dead — never step them
            comm = None
        if comm is None:
            from ..distributed.ps import PSCommunicator

            comm = PSCommunicator(ps_cfg)
        if scope is not None and \
                not getattr(comm, "_params_inited", False):
            comm.init_params(scope)
            comm._params_inited = True
        self._ps_comms[key] = comm
        return comm

    def _check_nan_inf(self, fetch_names, fetches, new_states):
        """FLAGS_check_nan_inf (reference: operator.cc:1020
        CheckOpHasNanOrInf + details/nan_inf_utils_detail.cc): host-side
        scan of every fetch and updated state var, error names the var."""
        bad = []
        for n, v in list(zip(fetch_names, fetches)) + \
                list(new_states.items()):
            a = np.asarray(v)
            if np.issubdtype(a.dtype, np.floating) and \
                    not np.all(np.isfinite(a)):
                bad.append(n)
        if bad:
            raise RuntimeError(
                "Operator output contains Inf/Nan (FLAGS_check_nan_inf): "
                "%s" % bad)

    # -- helpers -----------------------------------------------------------
    def _prepare_feed(self, block, feed) -> Dict[str, np.ndarray]:
        """Feed normalization. Fast path: values already on device
        (jax Arrays, e.g. from reader.prefetch_to_device) pass through
        without a host round-trip — dtype casts happen device-side."""
        out = {}
        for name, value in feed.items():
            v = block._find_var_recursive(name)
            want = to_numpy_dtype(v.dtype) if v is not None else None
            if is_on_device(value):
                if want is not None:
                    import jax

                    # compare against the backend's canonical dtype:
                    # with x64 disabled an int64 var holds int32 on
                    # device, and casting back up would only warn
                    want_dev = jax.dtypes.canonicalize_dtype(want)
                    if value.dtype != want_dev:
                        # astype allocates a fresh executor-owned array
                        # — keep it donatable so the step can alias it
                        value = value.astype(want_dev)
                        mark_donatable(value)
                out[name] = value
                continue
            arr = np.asarray(value)
            if want is not None and arr.dtype != want:
                arr = arr.astype(want)
            out[name] = arr
        return out

    @staticmethod
    def _replicate_rows(a, m):
        """Batch-tail bucketing row replication; device arrays
        replicate on device (no host round-trip)."""
        if is_on_device(a):
            import jax.numpy as jnp

            out = jnp.concatenate([a] * m, axis=0)
            mark_donatable(out)  # fresh executor-owned buffer
            return out
        return np.concatenate([a] * m, axis=0)

    # -- elastic training (strategy.elastic; reference reserves the knob
    # at distributed_strategy.proto:301 — here it is the preemption
    # checkpoint/auto-resume loop from fluid/checkpoint.py, wired into
    # every step of the marked program) -------------------------------
    def _elastic_resume(self, program, ecfg, scope):
        import logging

        from . import checkpoint as ckpt

        root = ecfg.get("checkpoint_dir") or "elastic_checkpoints"
        # mark resumed only AFTER the load succeeds (or cleanly finds
        # nothing): a transient load failure must stay retryable, not
        # silently restart from init and rotate out the good checkpoints
        status = ckpt.load_checkpoint(self, root, main_program=program,
                                      scope=scope)
        ecfg["_resumed"] = True
        if status is not None:
            ecfg["_step"] = status.step_no + 1
            logging.getLogger("paddle_tpu.elastic").info(
                "elastic: resumed at step %d from %r", status.step_no,
                root)
        else:
            ecfg.setdefault("_step", 0)

    def _elastic_tick(self, program, ecfg, scope):
        from . import checkpoint as ckpt

        step = ecfg.get("_step", 0)
        ecfg["_step"] = step + 1
        every = int(ecfg.get("save_steps", 100) or 100)
        if (step + 1) % every:
            return
        cp = ecfg.get("_ckpt")
        if cp is None:
            import atexit

            root = ecfg.get("checkpoint_dir") or "elastic_checkpoints"
            cp = ckpt.AsyncCheckpointer(
                root, main_program=program,
                checkpoint_num=int(ecfg.get("max_checkpoints", 3) or 3),
                scope=scope)
            ecfg["_ckpt"] = cp
            # flush the last pending save on normal interpreter exit
            # (the writer is a daemon thread); a failed write raises
            # here or on the next tick via check() — never silently
            atexit.register(cp.close)
        # save_async() calls check() first: a broken checkpoint_dir
        # surfaces as an error on the next tick instead of training for
        # days without preemption safety
        cp.save_async(ckpt.TrainStatus(epoch_no=0, step_no=step))

    def _shard_feeds(self, entry, feed_arrays):
        """Issue (non-blocking) H2D transfers for host arrays; arrays
        already on device pass straight through — the prefetcher put
        them against the program's sharding, so the step consumes them
        without re-putting. When the compiled step donates its feed
        buffers (entry.feed_donate), on-device arrays NOT produced by
        the prefetcher are defensively copied device-side first:
        donation would otherwise invalidate a buffer the caller (e.g. a
        dygraph tensor feeding a static subgraph) still holds."""
        import jax

        def guard(a):
            if entry.feed_donate and not is_donatable(a):
                import jax.numpy as jnp

                return jnp.copy(a)
            return a

        if entry.mesh is None:
            return {n: (guard(a) if is_on_device(a)
                        else jax.numpy.asarray(a))
                    for n, a in feed_arrays.items()}
        from jax.sharding import NamedSharding, PartitionSpec as P

        plan = getattr(entry, "auto_plan", None)
        data_spec = lowering.data_partition_spec(entry.mesh,
                                                 entry.dp_axis)
        out = {}
        for n, a in feed_arrays.items():
            spec = plan.feed_specs.get(n, P()) if plan is not None \
                else data_spec
            target = NamedSharding(entry.mesh, spec)
            if is_on_device(a):
                if getattr(a, "sharding", None) == target:
                    out[n] = guard(a)
                    continue
                a = guard(a)  # reshard below may alias the input
            out[n] = jax.device_put(a, target)
        return out

    def _find_tail_bucket(self, program, feed_arrays, fetch_names, scope):
        """Most-recent cached entry whose batch is an integer multiple of
        this feed's batch: returns (key, multiple, tail_batch,
        names_to_replicate) or None. A feed participates either
        identically (same shape, e.g. a constant side input) or
        replicated (same trailing dims, bucket batch = m * tail batch,
        one shared m). `.lod` offset feeds never bucket — offsets would
        need rebuilding, and ragged data already buckets at the dataset
        tier (fluid/dataset.py)."""
        from ..utils.flags import get_flag

        if not get_flag("FLAGS_batch_tail_bucketing", True):
            return None
        if not self._tail_bucket_safe(program):
            return None
        want_prefix = (program._uid, program._version)
        want_suffix = (tuple(fetch_names), getattr(scope, "_uid", 0))
        names = sorted(feed_arrays)
        for key in reversed(self._cache):
            if key[:2] != want_prefix or key[3:] != want_suffix:
                continue
            cached = {n: (shape, dt) for n, shape, dt in key[2]}
            if sorted(cached) != names:
                continue
            m = None
            rep = set()
            ok = True
            for n in names:
                a = feed_arrays[n]
                cshape, cdt = cached[n]
                if cdt != str(a.dtype):
                    ok = False
                    break
                if cshape == a.shape:
                    continue  # constant side input
                if (n.endswith(".lod") or not a.ndim
                        or cshape[1:] != a.shape[1:] or not a.shape[0]
                        or cshape[0] % a.shape[0]):
                    ok = False
                    break
                this_m = cshape[0] // a.shape[0]
                max_m = int(get_flag("FLAGS_batch_tail_max_multiple", 8)
                            or 8)
                # cap the replication factor: beyond it, compiling the
                # tail's own executable is cheaper than permanently
                # paying m-times the FLOPs per step
                if this_m < 2 or this_m > max_m \
                        or (m is not None and this_m != m):
                    ok = False
                    break
                m = this_m
                rep.add(n)
            if ok and m is not None:
                tails = {feed_arrays[n].shape[0] for n in rep}
                if len(tails) == 1:  # one shared batch axis extent
                    return key, m, tails.pop(), rep
        return None

    def _tail_bucket_safe(self, program):
        """Row replication is exact only for replication-invariant
        programs: a FORWARD op that sum/prod-collapses the batch axis
        (reduce_sum over dim 0 / all dims on a batch-majored var) scales
        by the multiple m, so such programs never bucket. Mean/max/min
        collapses and the grad ops of a mean-type loss are invariant
        (each row appears exactly m times and the 1/B normalization uses
        the padded B)."""
        cached = getattr(program, "_tail_bucket_safe_cache", None)
        if cached is not None and cached[0] == program._version:
            return cached[1]
        unsafe_types = {"reduce_sum", "reduce_prod"}
        # streaming/counting metric ops: replicated rows inflate their
        # per-row counts (histograms, Correct/Total, pair counts) m-fold
        # — in fetches AND in scope-resident accumulator state
        metric_types = {
            "auc", "accuracy", "precision_recall", "mean_iou",
            "detection_map", "positive_negative_pair", "chunk_eval",
            "edit_distance",
        }
        safe = True
        blocks = getattr(program, "blocks", None) or \
            [program.global_block()]
        for block in blocks:
            for op in block.ops:
                if op.type in metric_types:
                    safe = False
                    break
                if op.type not in unsafe_types:
                    continue
                dims = op.attrs.get("dim", op.attrs.get("axis", None))
                if isinstance(dims, int):
                    dims = [dims]
                if dims and 0 not in dims:
                    continue  # reduces non-batch axes only
                for slot_vars in op.input_names.values():
                    for vn in slot_vars:
                        v = block._find_var_recursive(vn)
                        shp = tuple(getattr(v, "shape", ()) or ()) \
                            if v is not None else ()
                        if shp[:1] == (-1,):
                            safe = False
                            break
                    if not safe:
                        break
                if not safe:
                    break
            if not safe:
                break
        program._tail_bucket_safe_cache = (program._version, safe)
        return safe

    def _cache_key(self, program, feed_arrays, fetch_names, scope):
        feed_key = tuple(sorted(
            (n, a.shape, str(a.dtype)) for n, a in feed_arrays.items()))
        # never-reused uids (not id()) so GC'd programs/scopes cannot
        # alias a stale compiled executable
        return (program._uid, program._version, feed_key,
                tuple(fetch_names), getattr(scope, "_uid", 0))

    def feed_sharding(self, program=None):
        """The sharding this program's compiled step expects for its
        feeds — hand it to `reader.prefetch_to_device` so prefetched
        batches land pre-sharded on the right devices. Returns None for
        single-device programs, one NamedSharding for data-parallel
        programs (batch axis over the mesh), or a name->sharding dict
        when an auto-parallel plan exists."""
        from . import compiler

        program = program or framework.default_main_program()
        if isinstance(program, compiler.CompiledProgram):
            program = program._unwrap()
        plan = getattr(program, "_auto_plan", None)
        if plan is not None:
            from jax.sharding import NamedSharding

            return {n: NamedSharding(plan.mesh, s)
                    for n, s in plan.feed_specs.items()}
        mesh = getattr(program, "_mesh", None)
        dp_axis = getattr(program, "_dp_axis", "dp")
        if mesh is None and getattr(program, "_data_parallel", False):
            # same construction compile_block will use — a prefetcher
            # asking for the sharding BEFORE the first compile must not
            # pin a flat mesh on a program the dcn flag would factor
            from ..parallel import env as penv

            mesh = penv.create_hybrid_mesh() or \
                lowering._default_mesh(dp_axis)
            program._mesh = mesh
        if mesh is None:
            return None
        from jax.sharding import NamedSharding

        return NamedSharding(mesh,
                             lowering.data_partition_spec(mesh, dp_axis))

    def _cached_lowerable(self, program, feed, fetch_list, scope):
        """(entry, lowered, mut_avals, feed_avals, ro_avals) for the
        EXECUTOR path's cached executable of this (program, feed
        shapes, fetch list) — run the program once first so the entry
        exists. None when the entry isn't jit-lowered (eager fallback /
        unknown program)."""
        import jax

        program = program or framework.default_main_program()
        from . import compiler

        if isinstance(program, compiler.CompiledProgram):
            program = program._unwrap()
        scope = scope or global_scope()
        fetch_names = [
            f.name if isinstance(f, framework.Variable) else str(f)
            for f in (fetch_list or [])]
        feed_arrays = self._prepare_feed(program.global_block(),
                                         feed or {})
        key = self._cache_key(program, feed_arrays, fetch_names, scope)
        entry = self._cache.get(key)
        if entry is None:
            # dtype-canonicalization can make a host-numpy feed key
            # miss an entry compiled from prefetched device feeds
            # (int64 -> int32 with x64 off): fall back to any cached
            # entry of this program with the same feed names + shapes
            want_shapes = {n: tuple(a.shape)
                           for n, a in feed_arrays.items()}
            for k in reversed(self._cache):
                if k[:2] == key[:2] and k[3:] == key[3:] and \
                        {n: tuple(s) for n, s, _ in k[2]} == want_shapes:
                    key, entry = k, self._cache[k]
                    break
        if entry is None or not hasattr(entry.jitted, "lower"):
            return None
        # feed avals from the CACHED key (the dtypes that executable
        # was actually compiled for), not from this call's arrays
        favals = {n: jax.ShapeDtypeStruct(tuple(s), np.dtype(dt))
                  for n, s, dt in key[2]}
        smut = {n: self._aval_of(scope.find_var(n))
                for n in entry.state_mut_names}
        sro = {n: self._aval_of(scope.find_var(n))
               for n in entry.state_ro_names}
        return (entry, self._lower_entry(entry, favals, smut, sro),
                smut, favals, sro)

    @staticmethod
    def _aval_of(v):
        """value (device array / numpy / python scalar) -> its jit
        argument aval."""
        import jax

        if hasattr(v, "shape") and hasattr(v, "dtype"):
            return jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
        a = np.asarray(v)
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    @staticmethod
    def _lower_entry(entry, favals, smut, sro):
        """THE (feeds, states_mut, states_ro, seed) lowering call every
        report/pre-flight path shares — one place to change if the jit
        argument shape ever grows."""
        import jax

        return entry.jitted.lower(
            favals, smut, sro, jax.ShapeDtypeStruct((), np.uint32))

    def donation_report(self, program=None, feed=None, fetch_list=None,
                        scope=None):
        """Donation audit via compiled-memory analysis of the EXECUTOR
        path's cached executable (run the program once first so the
        entry exists): verifies FLAGS_tpu_donate_buffers actually
        aliases params/opt-state — and, with
        FLAGS_tpu_donate_feed_buffers, how many feed bytes alias too.
        With the sharded weight update active, also reports the ZeRO-1
        optimizer-state footprint: `opt_state_sharded_vars`,
        `opt_state_logical_bytes` (what the replicated path would hold
        PER replica) vs `opt_state_per_replica_bytes` (~1/N of it).
        Returns {mut_bytes, feed_bytes, alias_bytes, aliases_state,
        feed_donate, ...} or None when the entry isn't jit-lowered
        (eager fallback / unknown program)."""
        got = self._cached_lowerable(program, feed, fetch_list, scope)
        if got is None:
            return None
        return self._donation_report_from(program, *got[:4])

    def step_memory(self, program=None, feed=None, fetch_list=None,
                    scope=None):
        """What the compiled step needs on one device, in bytes, by the
        compiler's `memory_analysis()` of the EXECUTOR path's cached
        executable (run the program once first so the entry exists):
        {argument, output, alias, temp, generated_code}. Its peak is
        argument + output - alias + temp + generated_code; the
        allocator's `peak_bytes_in_use` counts live arrays and misses a
        running step's temporaries. None when the entry isn't
        jit-lowered (eager fallback / unknown program)."""
        got = self._cached_lowerable(program, feed, fetch_list, scope)
        if got is None:
            return None
        entry, lowered, smut = got[:3]
        ma = self._aot_compile(entry, lowered, smut).memory_analysis()
        return {k: int(getattr(ma, k + "_size_in_bytes", 0))
                for k in ("argument", "output", "alias", "temp",
                          "generated_code")}

    def remat_saved(self, program=None):
        """What each `layers.Scan(remat=True)` of the program keeps
        across its per-layer checkpoint besides the carry, and each
        segment of an unrolled stack under `RecomputeOptimizer` besides
        its outputs, as the lowering recorded it when the step was
        traced (run the program once first): {scan op's provenance
        marker, or `<backward op's marker>/seg<i>`: {n, kept: [{name,
        shape, dtype, bytes}], bytes_per_layer, bytes_over_scan}}.
        Empty for a program with neither. `step_memory` reads the
        effect on the compiled step; this says what was chosen."""
        from . import compiler as _compiler

        prog = program or framework.default_main_program()
        if isinstance(prog, _compiler.CompiledProgram):
            prog = prog._unwrap()
        return dict(getattr(prog, "_remat_saved", None) or {})

    def _donation_report_from(self, program, entry, lowered, smut,
                              favals):
        """donation_report's body for callers that already hold the
        (entry, lowered, avals) tuple — attribution_report reuses this
        instead of paying a second full trace/lower of the module."""
        ma = self._aot_compile(entry, lowered, smut).memory_analysis()

        def nbytes(avals):
            return sum(int(np.prod(v.shape or (1,))) *
                       np.dtype(v.dtype).itemsize for v in avals.values())

        mut_bytes = nbytes(smut)
        feed_bytes = nbytes(favals)
        alias_bytes = int(getattr(ma, "alias_size_in_bytes", 0))
        sharded = entry.sharded_state or {}
        # shard granularity: the dp axis size — on a hybrid (dcn, ici)
        # mesh that is the INTRA-POD ici size (each pod holds a full
        # copy of the 1/ici shards), not the whole world
        ndev = self._shard_count(entry)
        if sharded:
            # XLA's alias_size_in_bytes is PER DEVICE; a sharded state
            # var occupies only padded/N bytes there — shrink the
            # donation target accordingly so the audit compares like
            # with like
            for info in sharded.values():
                if info.name in smut:
                    mut_bytes -= (info.padded - info.padded // ndev) \
                        * info.dtype.itemsize
        out = {
            "mut_bytes": mut_bytes,
            "feed_bytes": feed_bytes,
            "alias_bytes": alias_bytes,
            "aliases_state": alias_bytes >= mut_bytes,
            "feed_donate": bool(entry.feed_donate),
        }
        out["opt_state_sharded_vars"] = len(sharded)
        if sharded:
            out["opt_state_logical_bytes"] = sum(
                info.numel * info.dtype.itemsize
                for info in sharded.values())
            out["opt_state_per_replica_bytes"] = sum(
                (info.padded // ndev) * info.dtype.itemsize
                for info in sharded.values())
        plan = self._shard_plan_of(program)
        if plan is not None and getattr(plan, "buckets", ()):
            # bucketed grad exchange: the transient per-replica shard
            # buffers are one per bucket — SUM over buckets (there is
            # no single flat shard buffer whose scope var could be
            # read), logical = the pre-scatter padded grads
            out["grad_bucket_count"] = len(plan.buckets)
            out["grad_bucket_logical_bytes"] = sum(
                b.nbytes for b in plan.buckets)
            out["grad_bucket_per_replica_bytes"] = sum(
                b.shard_numel(ndev) * b.dtype.itemsize
                for b in plan.buckets)
            # ZeRO-2 gradient-lifetime model: full-size grad buffers die
            # bucket-by-bucket (each bucket's only full-value consumer
            # is its own reduce-scatter, verified statically by the
            # zero2-lifetimes checker), so at most ONE bucket's full
            # grads coexist with the accumulated 1/N shards — vs the
            # replicated path where every full grad is live at once
            out["grad_peak_per_replica_bytes"] = (
                max(b.nbytes for b in plan.buckets)
                + out["grad_bucket_per_replica_bytes"])
            out["grad_replicated_peak_bytes"] = \
                out["grad_bucket_logical_bytes"]
        # mixed precision (AMP level O2): live params in the 16-bit
        # compute dtype + fp32 masters — ZeRO-sharded masters cost
        # padded/N fp32 bytes per replica, so per-replica param state is
        # ~(2 + 4/N) bytes/elem vs fp32 DP's 4 (halved for N >= 4)
        prog = program or framework.default_main_program()
        from . import compiler as _compiler

        if isinstance(prog, _compiler.CompiledProgram):
            prog = prog._unwrap()
        amp_masters = dict(getattr(prog, "_amp_master_of", None) or {})
        if amp_masters:
            block = prog.global_block()
            p_bytes = m_rep = m_logical = 0
            for p, m in amp_masters.items():
                pv = block._find_var_recursive(p)
                if pv is None:
                    continue
                numel = int(np.prod(tuple(pv.shape) or (1,)))
                p_bytes += numel * np.dtype(
                    to_numpy_dtype(pv.dtype)).itemsize
                m_logical += numel * 4
                info = sharded.get(m)
                m_rep += ((info.padded // ndev) * 4 if info is not None
                          else numel * 4)
            out["param_bf16_bytes"] = p_bytes
            out["param_master_bytes"] = m_rep
            out["param_fp32_replicated_bytes"] = m_logical
            out["param_masters_sharded"] = sum(
                1 for m in amp_masters.values() if m in sharded)
        return out

    @staticmethod
    def _shard_count(entry):
        """ZeRO shard granularity of a cached entry: the dp-axis size
        (= intra-pod ici size on a hybrid mesh), 1 off-mesh."""
        if entry.mesh is None:
            return 1
        if entry.dp_axis in entry.mesh.shape:
            return int(entry.mesh.shape[entry.dp_axis])
        return int(np.prod(
            [entry.mesh.shape[a] for a in entry.mesh.axis_names]))

    @staticmethod
    def _aot_compile(entry, lowered, smut):
        """AOT-compile once per cache entry: donation_report and
        overlap_report both need the compiled artifact, and XLA does
        not memoize Lowered.compile() — without this, every report
        call recompiles the whole module. Keyed on the live state
        avals: a checkpoint restore writes LOGICAL-shaped arrays back
        into scope (the next step reconverts), so `lowered` can differ
        from the memoized compile — recompile rather than hand back a
        stale artifact."""
        key = tuple(sorted((n, tuple(a.shape), str(a.dtype))
                           for n, a in smut.items()))
        if entry.aot_compiled is None or entry.aot_compiled[0] != key:
            entry.aot_compiled = (key, lowered.compile())
        return entry.aot_compiled[1]

    @staticmethod
    def _shard_plan_of(program):
        program = program or framework.default_main_program()
        from . import compiler

        if isinstance(program, compiler.CompiledProgram):
            program = program._unwrap()
        return getattr(program, "_shard_plan", None)

    def collective_report(self, program=None, feed=None, fetch_list=None,
                          scope=None):
        """Per-collective byte accounting for the cached executable
        (run the program once first): parses the lowered StableHLO for
        all_reduce / reduce_scatter / all_gather ops and models ring
        ICI bytes — offline evidence that the sharded weight update
        actually halves the grad+param exchange (see
        lowering.collective_byte_census). With bucketed collectives
        (FLAGS_tpu_comm_bucket_mb > 0) the census also carries the
        per-bucket byte breakdown — per-replica totals SUM the buckets
        (there is no single flat shard buffer to read). None when not
        jit-lowered."""
        got = self._cached_lowerable(program, feed, fetch_list, scope)
        if got is None:
            return None
        entry, lowered = got[0], got[1]
        ndev = 1
        if entry.mesh is not None:
            ndev = int(np.prod([entry.mesh.shape[a]
                                for a in entry.mesh.axis_names]))
        from ..parallel import env as penv

        hier = penv.mesh_hierarchy(entry.mesh)
        census = lowering.collective_byte_census(
            lowered.as_text(), ndev,
            ici_size=(hier[3] if hier is not None else None),
            mp_size=(hier.mp_size if hier is not None else None))
        plan = self._shard_plan_of(program)
        shards = self._shard_count(entry)
        if plan is not None and getattr(plan, "buckets", ()):
            # the cap the plan was built under, not the live flag (a
            # flag change after compile must not contradict `buckets`)
            census["bucket_cap_mb"] = getattr(
                plan, "bucket_cap", 0) / float(1 << 20)
            census["buckets"] = [{
                "index": b.index,
                "grads": len(b.entries),
                "dtype": str(b.dtype),
                "bytes": b.nbytes,
                "shard_bytes": b.shard_numel(shards) * b.dtype.itemsize,
            } for b in plan.buckets]
            census["bucket_bytes_total"] = sum(
                b.nbytes for b in plan.buckets)
        return census

    def attribution_report(self, program=None, feed=None,
                           fetch_list=None, scope=None, topk=10):
        """Per-op HBM attribution of the cached executable (run the
        program once first): decomposes the compiled step's
        memory_analysis() peak into buffer classes (feed / param /
        master / opt_state / grad_bucket / state_other / activation)
        per framework op and layer via the provenance markers the
        lowering stamped (FLAGS_tpu_op_provenance), maps every
        collective in the lowered module back to its fluid op / bucket
        / gradient, and cross-checks the class totals against
        donation_report EXACTLY. See
        paddle_tpu/observability/attribution.py; bench.py emits this as
        the "attribution" block and `tools/perf_analysis.py
        --attribution` writes artifacts/attribution.json. None when not
        jit-lowered."""
        got = self._cached_lowerable(program, feed, fetch_list, scope)
        if got is None:
            return None
        entry, lowered, smut, favals, sro = got
        from ..observability import attribution as _attr

        prog = program or framework.default_main_program()
        from . import compiler as _compiler

        if isinstance(prog, _compiler.CompiledProgram):
            prog = prog._unwrap()
        compiled = self._aot_compile(entry, lowered, smut)
        state_avals = dict(smut)
        state_avals.update(sro)
        # flat jit argument order (feeds, mut state, ro state, seed;
        # dict pytrees flatten sorted by key) — seeds the optimized
        # HLO pass's parameter->var inheritance
        arg_names = (sorted(favals) + sorted(smut) + sorted(sro)
                     + ["<seed>"])
        rep = _attr.build_report(
            prog, prog.global_block(), self._shard_plan_of(program),
            self._shard_count(entry), favals, state_avals,
            ma=compiled.memory_analysis(),
            optimized_hlo=compiled.as_text(),
            stablehlo_asm=_attr.stablehlo_debug_asm(lowered),
            topk=topk, arg_names=arg_names)
        rep["cross_check"] = _attr.cross_check_donation(
            rep, self._donation_report_from(program, entry, lowered,
                                            smut, favals))
        return rep

    def _hbm_preflight(self, program, entry, feed_arrays, states_mut,
                       states_ro, scope):
        """OOM pre-flight (FLAGS_tpu_hbm_budget_mb; runs once per fresh
        compile, BEFORE the first dispatch): AOT-compile the entry,
        model peak HBM (memory_analysis + the input pipeline's
        prefetched feed buffers) and raise a structured
        HbmBudgetExceeded naming the top consumers when it exceeds the
        budget — a pre-dispatch failure with a named culprit instead of
        an opaque RESOURCE_EXHAUSTED mid-run."""
        from ..observability import attribution as _attr

        budget = _attr.budget_bytes()
        if budget is None or not hasattr(entry.jitted, "lower"):
            return
        favals = {n: self._aval_of(a) for n, a in feed_arrays.items()}
        smut = {n: self._aval_of(v) for n, v in states_mut.items()}
        sro = {n: self._aval_of(v) for n, v in states_ro.items()}
        lowered = self._lower_entry(entry, favals, smut, sro)
        ma = self._aot_compile(entry, lowered, smut).memory_analysis()
        feed_bytes = sum(
            int(np.prod(a.shape or (1,))) * np.dtype(a.dtype).itemsize
            for a in favals.values())
        predicted = _attr.predicted_peak_bytes(ma, feed_bytes)
        if predicted <= budget:
            return
        prog = program
        from . import compiler as _compiler

        if isinstance(prog, _compiler.CompiledProgram):
            prog = prog._unwrap()
        breakdown = _attr.static_breakdown(
            prog, prog.global_block(), self._shard_plan_of(program),
            self._shard_count(entry), feed_arrays=feed_arrays,
            state_names=list(states_mut) + list(states_ro),
            scope=scope)
        top = breakdown["top_consumers"]
        from .. import observability as _obs

        try:
            _obs.registry().event(
                "hbm_preflight", verdict="exceeded",
                predicted_bytes=int(predicted),
                budget_bytes=int(budget),
                top_consumer=top[0]["name"] if top else None)
        except Exception:  # noqa: BLE001 - telemetry is best-effort
            pass
        raise _attr.HbmBudgetExceeded(predicted, budget, top)

    def overlap_report(self, program=None, feed=None, fetch_list=None,
                       scope=None):
        """Collective/compute overlap audit of the cached executable's
        OPTIMIZED (scheduled) HLO — can the grad reduce-scatters start
        while backward compute is still outstanding, or are they fenced
        at the end? See lowering.collective_overlap_audit for the
        model; `tools/perf_analysis.py --overlap-audit` drives this on
        the BERT-tiny program and bench.py emits it as "overlap". None
        when not jit-lowered."""
        got = self._cached_lowerable(program, feed, fetch_list, scope)
        if got is None:
            return None
        entry, lowered, smut = got[0], got[1], got[2]
        rep = lowering.collective_overlap_audit(
            self._aot_compile(entry, lowered, smut).as_text())
        plan = self._shard_plan_of(program)
        if plan is not None:
            rep["n_buckets"] = len(getattr(plan, "buckets", ()))
        return rep

    def close(self):
        for comm in getattr(self, "_ps_comms", {}).values():
            comm.complete()
        if hasattr(self, "_ps_comms"):
            self._ps_comms.clear()
        self._cache.clear()

    # dataset-training entry points (reference: executor.py:1454) are
    # provided by the trainer runtime in paddle_tpu.fluid.trainer
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           fetch_handler=None):
        from .trainer import train_from_dataset as _tfd

        return _tfd(self, program, dataset, scope, fetch_list, print_period)

    def infer_from_dataset(self, *args, **kwargs):
        return self.train_from_dataset(*args, **kwargs)
