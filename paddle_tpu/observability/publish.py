"""Publishers: the existing perf/precision/lint surfaces -> the metrics
registry -> bench.py's result blocks.

Before this module, bench.py assembled each evidence block by hand
(`_attach_collectives` / `_attach_precision` / `_attach_static_checks`
plus an inline phases read) — four ad-hoc code paths no other tool
could reuse. Now each surface publishes THROUGH the registry
(`registry().publish_block`) and `bench_blocks()` is the one assembly
point: bench.py, tests and any future tool read identical dicts from
`registry().blocks()`.

Block producers (each returns the block dict or None, prints the same
one-line BENCH summary bench.py always printed, and publishes):

    phases_block()                      "phases"
    collectives_blocks(exe, p, f, fl)   "collectives",
                                        "opt_state_sharding", "overlap"
    hierarchy_block(exe, p, f, fl)      "hierarchy" (hybrid multi-pod
                                        mesh: dcn/ici lane census)
    precision_block(exe, p, f, fl)      "precision"
    quant_block()                       "quant" (int8 serving page +
                                        PTQ weight byte census)
    attribution_block(exe, p, f, fl)    "attribution" (per-op HBM
                                        blame + provenance coverage)
    static_checks_block(p)              "static_checks"
    compile_cache_block()               "compile_cache" (persistent
                                        compile-cache hit/miss roll-up
                                        + on-disk tier inventory)
    serving_block()                     "serving" (inference engine:
                                        tokens/sec, request p50/p99,
                                        queue depth, KV occupancy —
                                        from the serving.* metrics an
                                        Engine/trace run published)
    telemetry_block(group=None)         "telemetry" (registry counters,
                                        straggler report when a
                                        host-collective group is given)
"""
from __future__ import annotations

from typing import Optional

from .registry import registry

__all__ = ["phases_block", "collectives_blocks", "hierarchy_block",
           "model_parallel_block", "precision_block", "quant_block",
           "embedding_block", "attribution_block",
           "static_checks_block", "compile_cache_block",
           "serving_block", "telemetry_block", "bench_blocks"]


def phases_block() -> dict:
    """Host step-phase breakdown (fluid/profiler.py) as the "phases"
    block; per-phase averages also land as registry gauges."""
    from ..fluid import profiler as _prof

    block = _prof.step_phase_summary()
    reg = registry()
    for k, v in block.items():
        if isinstance(v, (int, float)):
            reg.set_gauge("phases." + k, v)
    reg.publish_block("phases", block)
    print("BENCH " + _prof.step_phase_line(), flush=True)
    return block


def collectives_blocks(exe, program, feed, fetch_list) -> dict:
    """Per-collective byte census + (when ZeRO-1 is active) the
    opt-state sharding footprint and the bucketed-overlap audit of the
    optimized schedule. Single-chip programs provably have no
    collectives and pay nothing. Returns {} or up to three blocks."""
    out = {}
    if getattr(program, "_mesh", None) is None or \
            not getattr(program, "_data_parallel", False):
        return out
    reg = registry()
    try:
        col = exe.collective_report(program, feed=feed,
                                    fetch_list=fetch_list)
    except Exception as e:  # noqa: BLE001 - evidence, not gating
        print("BENCH collective census failed: %r" % (e,), flush=True)
        return out
    if col and col.get("total_ici_bytes", 0) > 0:
        out["collectives"] = col
        reg.publish_block("collectives", col)
        reg.set_gauge("collectives.total_ici_bytes",
                      col["total_ici_bytes"])
        print("BENCH collectives: " + ", ".join(
            "%s x%d %.1fMB" % (k, v["count"], v["ici_bytes"] / 1e6)
            for k, v in col.items()
            if isinstance(v, dict) and "ici_bytes" in v),
            flush=True)
    if col and col.get("reduce_scatter"):
        # ZeRO-1 active: also report the per-replica optimizer-state
        # footprint (donation_report compiles via AOT — only pay that
        # when there is sharding to prove)
        rep = exe.donation_report(program, feed=feed,
                                  fetch_list=fetch_list)
        if rep and rep.get("opt_state_sharded_vars"):
            oss = {
                "vars": rep["opt_state_sharded_vars"],
                "logical_bytes": rep["opt_state_logical_bytes"],
                "per_replica_bytes": rep["opt_state_per_replica_bytes"],
            }
            out["opt_state_sharding"] = oss
            reg.publish_block("opt_state_sharding", oss)
        # bucketed-collective overlap audit (FLAGS_tpu_comm_bucket_mb):
        # how many grad reduce-scatters are dataflow-ready before the
        # final backward compute op — the transfers a latency-hiding
        # scheduler can overlap
        try:
            ov = exe.overlap_report(program, feed=feed,
                                    fetch_list=fetch_list)
        except Exception as e:  # noqa: BLE001 - evidence, not gating
            print("BENCH overlap audit failed: %r" % (e,), flush=True)
            ov = None
        region = (ov or {}).get("region_collectives") or []
        if ov and (ov.get("collectives") or region):
            rs = [c for c in ov["collectives"]
                  if c["kind"] == "reduce-scatter"]
            ovb = {
                "n_buckets": ov.get("n_buckets", 0),
                "n_backward_compute": ov["n_backward_compute"],
                "overlappable_reduce_scatters":
                    ov["overlappable_reduce_scatters"],
                "reduce_scatters": [
                    {k: c[k] for k in ("pos", "ready", "backward_after",
                                       "bytes")} for c in rs],
                "combined": ov["combined"],
                # gradient merge traces its collectives inside the
                # lax.cond region — fenced, but visible
                "region_collectives": region,
            }
            out["overlap"] = ovb
            reg.publish_block("overlap", ovb)
            print("BENCH overlap: %d/%d reduce-scatters ready before "
                  "the final backward op (buckets=%d, backward left "
                  "behind each: %s)"
                  % (ov["overlappable_reduce_scatters"], len(rs),
                     ov.get("n_buckets", 0),
                     [c["backward_after"] for c in rs]), flush=True)
    return out


def hierarchy_block(exe, program, feed, fetch_list) -> Optional[dict]:
    """Hierarchical DCN+ICI collective evidence (hybrid multi-pod
    mesh): the census's ici/dcn lane split, the cross-pod bytes per
    grad-sync collective, and the modeled flat-allreduce baseline —
    cross-pod (dcn) bytes should be flat bytes / ici_size per bucket.
    None for flat (single-axis) meshes."""
    from ..parallel import env as penv

    hier = penv.mesh_hierarchy(getattr(program, "_mesh", None))
    if hier is None or not getattr(program, "_data_parallel", False):
        return None
    try:
        col = exe.collective_report(program, feed=feed,
                                    fetch_list=fetch_list)
    except Exception as e:  # noqa: BLE001 - evidence, not gating
        print("BENCH hierarchy census failed: %r" % (e,), flush=True)
        return None
    if not col or "lanes" not in col:
        return None
    lanes = col["lanes"]
    dcn_grad = [c for c in lanes["dcn"]["per_collective"]
                if c["kind"] == "all_reduce"]
    # what ONE flat allreduce of the same grads would move cross-pod:
    # each dcn collective carries a 1/ici shard, so flat = shard * ici
    flat_bytes = sum(c["tensor_bytes"] for c in dcn_grad) * hier[3]
    block = {
        "dcn_replicas": hier[2],
        "ici_size": hier[3],
        "ici": {k: lanes["ici"][k]
                for k in ("count", "tensor_bytes", "wire_bytes")},
        "dcn": {k: lanes["dcn"][k]
                for k in ("count", "tensor_bytes", "wire_bytes")},
        "dcn_grad_sync_bytes": sum(
            c["tensor_bytes"] for c in dcn_grad),
        "flat_allreduce_bytes": flat_bytes,
        "dcn_reduction_factor": hier[3],
        "per_collective_dcn": lanes["dcn"]["per_collective"],
    }
    reg = registry()
    reg.set_gauge("hierarchy.dcn_bytes", block["dcn_grad_sync_bytes"])
    reg.set_gauge("hierarchy.dcn_replicas", hier[2])
    reg.publish_block("hierarchy", block)
    print("BENCH hierarchy: %dx%d (dcn x ici) mesh, cross-pod grad "
          "sync %.1f KB vs %.1f KB flat (1/%d per bucket), dcn "
          "collectives x%d ici x%d"
          % (hier[2], hier[3],
             block["dcn_grad_sync_bytes"] / 1e3, flat_bytes / 1e3,
             hier[3], lanes["dcn"]["count"], lanes["ici"]["count"]),
          flush=True)
    return block


def model_parallel_block(exe, program, feed, fetch_list) \
        -> Optional[dict]:
    """Tensor-parallel (model-axis) evidence: the TP plan's axis
    assignment (which params shard, at which dim), the per-chip param
    element reduction (∝ 1/mp for the sharded set), the structured
    decline trail (kind="tp_declined" entries the planner recorded),
    and the census's `mp` collective lane. None when no TP plan is
    attached (mp=1 — the flat/hierarchical lowering, byte-for-byte)."""
    import numpy as np

    tpp = getattr(program, "_tp_plan", None)
    if tpp is None:
        return None
    logical_elems = int(sum(int(np.prod(s)) for s in
                            tpp.logical_shapes.values()))
    local_elems = int(sum(int(np.prod(s)) for s in
                          tpp.local_shapes.values()))
    trail = getattr(program, "_sharded_update_fallback", None) or []
    declined = [dict(e) for e in trail
                if e.get("kind") == "tp_declined"]
    block = {
        "mp_degree": tpp.mp,
        "model_axis": tpp.model_axis,
        "sharded_params": {
            n: {"tp_dim": p.tp_dim, "kind": p.kind,
                "logical_shape": list(p.logical_shape),
                "local_shape": list(p.local_shape)}
            for n, p in sorted(tpp.params.items())},
        "sharded_vars": len(tpp.var_dims),
        "logical_param_elems": logical_elems,
        "local_param_elems": local_elems,
        "tp_declined": declined,
    }
    try:
        col = exe.collective_report(program, feed=feed,
                                    fetch_list=fetch_list)
    except Exception as e:  # noqa: BLE001 - evidence, not gating
        print("BENCH model_parallel census failed: %r" % (e,),
              flush=True)
        col = None
    if col:
        block["mp_bytes_total"] = int(col.get("mp_bytes_total", 0))
        lanes = col.get("lanes") or {}
        if "mp" in lanes:
            block["mp_collectives"] = {
                k: lanes["mp"][k]
                for k in ("count", "tensor_bytes", "wire_bytes")}
    reg = registry()
    reg.set_gauge("model_parallel.mp_degree", tpp.mp)
    reg.set_gauge("model_parallel.sharded_params", len(tpp.params))
    reg.publish_block("model_parallel", block)
    print("BENCH model_parallel: mp=%d sharded=%d declined=%d "
          "param elems %d -> %d per chip, mp lane bytes=%s"
          % (tpp.mp, len(tpp.params), len(declined), logical_elems,
             local_elems, block.get("mp_bytes_total", "n/a")),
          flush=True)
    return block


def precision_block(exe, program, feed, fetch_list) -> Optional[dict]:
    """Mixed-precision evidence: the AMP policy the step lowered under,
    the live-param vs fp32-master HBM split, the ZeRO-2 peak-grad
    model, and the fp16 loss-scale state machine's live state (read
    from scope; also published as gauges so the telemetry timeseries
    tracks scale decay/growth across a run)."""
    if not getattr(program, "_amp", False):
        return None
    try:
        import numpy as np

        reg = registry()
        lists = getattr(program, "_amp_lists", None)
        masters = dict(getattr(program, "_amp_master_of", None) or {})
        block = {
            "amp_dtype": str(getattr(program, "_amp_dtype", "bfloat16")),
            "level": "O2" if masters else "O1",
            "master_weights": len(masters),
            "white_list_ops": len(lists.white_list) if lists else 0,
            "black_list_ops": len(lists.black_list) if lists else 0,
        }
        rep = exe.donation_report(program, feed=feed,
                                  fetch_list=fetch_list)
        for k in ("param_bf16_bytes", "param_master_bytes",
                  "param_fp32_replicated_bytes", "param_masters_sharded",
                  "grad_peak_per_replica_bytes",
                  "grad_replicated_peak_bytes"):
            if rep and k in rep:
                block[k] = rep[k]
        bop = next((op for op in program.global_block().ops
                    if op.type == "backward"), None)
        dls = bop.attrs.get("dynamic_loss_scaling") if bop is not None \
            else None
        if dls:
            from ..core.scope import global_scope

            def read(name):
                v = global_scope().find_var(name)
                return (float(np.asarray(v).reshape(-1)[0])
                        if v is not None else None)

            block["loss_scaling"] = {
                "current": read(dls["scale"]),
                "good_steps": read(dls["good"]),
                "bad_steps": read(dls["bad"]),
                "incr_every_n_steps": dls["incr_every_n_steps"],
                "decr_every_n_nan_or_inf": dls["decr_every_n_nan_or_inf"],
            }
            for k in ("current", "good_steps", "bad_steps"):
                if block["loss_scaling"][k] is not None:
                    reg.set_gauge("amp.loss_scale." + k,
                                  block["loss_scaling"][k])
        else:
            block["loss_scaling"] = None
        reg.set_gauge("amp.level", block["level"])
        reg.publish_block("precision", block)
        msg = ("BENCH precision: %s level=%s masters=%d"
               % (block["amp_dtype"], block["level"],
                  block["master_weights"]))
        if "param_bf16_bytes" in block:
            msg += (", param %s MB live + %s MB master/replica (fp32 "
                    "DP would be %s MB)"
                    % tuple(round(block[k] / 1e6, 2) for k in
                            ("param_bf16_bytes", "param_master_bytes",
                             "param_fp32_replicated_bytes")))
        if block["loss_scaling"]:
            msg += ", loss_scale=%s" % block["loss_scaling"]["current"]
        print(msg, flush=True)
        return block
    except Exception as e:  # noqa: BLE001 - evidence, not gating
        print("BENCH precision block failed: %r" % (e,), flush=True)
        return None


def attribution_block(exe, program, feed, fetch_list) -> Optional[dict]:
    """Per-op HBM attribution evidence (Executor.attribution_report /
    observability/attribution.py): the buffer-class totals, the
    provenance coverage of the modeled peak, the top consumers, and
    the collective->provenance round-trip tally. None when the entry
    is not jit-lowered."""
    try:
        rep = exe.attribution_report(program, feed=feed,
                                     fetch_list=fetch_list)
    except Exception as e:  # noqa: BLE001 - evidence, not gating
        print("BENCH attribution failed: %r" % (e,), flush=True)
        return None
    if not rep:
        return None
    mem = rep.get("memory", {})
    colls = rep.get("collectives", {})
    block = {
        "classes": rep.get("classes", {}),
        "coverage": mem.get("coverage"),
        "peak_model_bytes": mem.get("peak_model_bytes"),
        "attributed_bytes": mem.get("attributed_bytes"),
        "top_consumers": rep.get("top_consumers", []),
        "collectives_mapped": colls.get("mapped", 0),
        "collectives_total": colls.get("count", 0),
        "cross_check_ok": rep.get("cross_check", {}).get("ok"),
    }
    reg = registry()
    if mem.get("coverage") is not None:
        reg.set_gauge("attribution.coverage", mem["coverage"])
    if mem.get("peak_model_bytes"):
        reg.set_gauge("attribution.peak_model_bytes",
                      mem["peak_model_bytes"])
    reg.publish_block("attribution", block)
    top = block["top_consumers"][:1]
    print("BENCH attribution: %.0f%% of %.2f MB peak attributed "
          "(%d/%d collectives mapped, cross-check %s)%s"
          % (100.0 * (block["coverage"] or 0.0),
             (block["peak_model_bytes"] or 0) / 1e6,
             block["collectives_mapped"], block["collectives_total"],
             "ok" if block["cross_check_ok"] else "FAILED",
             ", top: %s %.2f MB" % (top[0]["name"],
                                    top[0]["bytes"] / 1e6)
             if top else ""), flush=True)
    return block


def static_checks_block(program) -> Optional[dict]:
    """tpu-lint summary of the program that just ran: zero errors is
    the standing claim. Evidence, not gating."""
    try:
        from .. import analysis

        findings = analysis.run_static_checks(program)
        s = analysis.summarize(findings)
        block = {
            "errors": s["errors"],
            "warnings": s["warnings"],
            "by_checker": s["by_checker"],
            # cap the embedded detail; the CLI writes the full report
            "findings": s["findings"][:20],
        }
        try:
            # the protocol tier (analysis/protocol.py): a reduced-
            # budget interleaving sweep over the host protocols; the
            # full-budget sweep is `tools/tpu_lint.py --protocol`
            pf, prep = analysis.run_protocol_checks(budget=200)
            block["protocol"] = {
                "budget": prep["budget"],
                "errors": prep["errors"],
                "ok": prep["ok"],
                "models": {n: {"schedules": m["schedules"],
                               "states": m["states"],
                               "errors": m["errors"],
                               "truncated": m["truncated"]}
                           for n, m in prep["models"].items()},
                "findings": [f.to_dict() for f in pf[:20]],
            }
        except Exception as e:  # noqa: BLE001 - evidence, not gating
            block["protocol"] = {"error": repr(e)}
        reg = registry()
        reg.set_gauge("static_checks.errors", s["errors"])
        reg.set_gauge("static_checks.warnings", s["warnings"])
        if "errors" in block["protocol"]:
            reg.set_gauge("static_checks.protocol_errors",
                          block["protocol"]["errors"])
        reg.publish_block("static_checks", block)
        print("BENCH static checks: %d error(s), %d warning(s); "
              "protocol tier: %s"
              % (s["errors"], s["warnings"],
                 "%d error(s) over %d model(s)"
                 % (block["protocol"].get("errors", -1),
                    len(block["protocol"].get("models", {})))
                 if "errors" in block["protocol"] else "unavailable"),
              flush=True)
        return block
    except Exception as e:  # noqa: BLE001 - evidence, not gating
        print("BENCH static checks failed: %r" % (e,), flush=True)
        return None


def compile_cache_block() -> Optional[dict]:
    """Persistent compile-cache evidence (fluid/compile_cache,
    JAX_COMPILATION_CACHE_DIR): the process's hit/miss tally at the
    framework fingerprint granularity, compile milliseconds paid vs
    saved, and the on-disk tier inventory. None when the tier is off
    AND no compile was ever classified — cold-start cost only shows up
    once there is something to show."""
    try:
        from ..fluid import compile_cache as cc

        st = cc.stats()
    except Exception as e:  # noqa: BLE001 - evidence, not gating
        print("BENCH compile_cache block failed: %r" % (e,), flush=True)
        return None
    if not st["enabled"] and not (st["hits"] or st["misses"]):
        return None
    block = {
        "enabled": st["enabled"],
        "dir": st["dir"],
        "hits": st["hits"],
        "misses": st["misses"],
        "hit_rate": st["hit_rate"],
        "warmups": st["warmups"],
        "compile_ms_total": round(st["compile_ms_total"], 3),
        "saved_ms_total": round(st["saved_ms_total"], 3),
        "persistent_entries": st["persistent_entries"],
        "persistent_bytes": st["persistent_bytes"],
        "index_entries": st["index_entries"],
        "jax_backend_compiles": st["jax"]["backend_compiles"],
        "jax_persistent_hits": st["jax"]["persistent_hits"],
    }
    reg = registry()
    if st["hit_rate"] is not None:
        reg.set_gauge("compile_cache.hit_rate", st["hit_rate"])
    reg.set_gauge("compile_cache.persistent_bytes",
                  st["persistent_bytes"])
    reg.publish_block("compile_cache", block)
    print("BENCH compile_cache: %d hit(s) / %d miss(es), %.1fs "
          "compiled, %.1fs saved, %d entries (%.1f MB) at %s"
          % (block["hits"], block["misses"],
             block["compile_ms_total"] / 1e3,
             block["saved_ms_total"] / 1e3,
             block["persistent_entries"],
             block["persistent_bytes"] / 1e6,
             block["dir"] or "<off>"), flush=True)
    return block


def serving_block() -> Optional[dict]:
    """Serving-engine evidence (paddle_tpu/serving): tokens/sec and
    request-level p50/p99 latency under the trace the registry just
    measured, queue-depth distribution, KV-page occupancy peak, bucket
    AOT coverage. Assembled ENTIRELY from the serving.* metrics the
    Engine and trace runner published — bench.py --serving, the tier-1
    leg and any future tool read the identical dict. None when no
    Engine ran in this process."""
    reg = registry()
    snap = reg.snapshot()
    counters = snap["counters"]
    hists = snap["histograms"]
    gauges = snap["gauges"]
    if not counters.get("serving.steps"):
        return None
    lat = hists.get("serving.request_latency_ms") or {}
    ttft = hists.get("serving.ttft_ms") or {}
    qd = hists.get("serving.queue_depth") or {}
    block = {
        "steps": counters.get("serving.steps", 0),
        "requests_submitted": counters.get(
            "serving.requests_submitted", 0),
        "requests_finished": counters.get(
            "serving.requests_finished", 0),
        "requests_cancelled": counters.get(
            "serving.requests_cancelled", 0),
        "tokens_generated": counters.get(
            "serving.tokens_generated", 0),
        "tokens_per_sec": gauges.get("serving.tokens_per_sec"),
        "latency_ms": {k: lat.get(k)
                       for k in ("p50", "p99", "mean", "max")},
        "ttft_ms": {k: ttft.get(k) for k in ("p50", "p99")},
        "queue_depth": {k: qd.get(k) for k in ("mean", "max")},
        "kv_pages_total": gauges.get("serving.kv_pages_total"),
        "kv_peak_pages_in_use": gauges.get(
            "serving.kv_peak_pages_in_use"),
        "kv_occupancy": gauges.get("serving.kv_occupancy"),
        "buckets_compiled": gauges.get("serving.buckets_compiled"),
        # quantization tier: the page dtype the pool holds, its
        # per-page byte cost (scales included for int8), the fixed
        # pool budget, and the resident batch that budget admits
        "kv_page_dtype": gauges.get("serving.kv_page_dtype"),
        "kv_page_bytes": gauges.get("serving.kv_page_bytes"),
        "kv_pool_bytes": gauges.get("serving.kv_pool_bytes"),
        "kv_resident_batch": gauges.get("serving.kv_resident_batch"),
        # prefix-cache / preemption lane: prompt tokens the cache
        # covered (never prefilled), the prefill tokens actually
        # dispatched, their reuse ratio, copy-on-write page copies,
        # cached-tier occupancy/evictions, and priority preemptions
        "prefix_cache": gauges.get("serving.kv_prefix_cache"),
        "prefix_hit_tokens": counters.get(
            "serving.prefix_hit_tokens", 0),
        "prefill_tokens": counters.get("serving.prefill_tokens", 0),
        "prefix_reuse_ratio": round(
            counters.get("serving.prefix_hit_tokens", 0)
            / max(1, counters.get("serving.prefix_hit_tokens", 0)
                  + counters.get("serving.prefill_tokens", 0)), 4),
        "kv_pages_cached": gauges.get("serving.kv_pages_cached"),
        "kv_cow_copies": gauges.get("serving.kv_cow_copies"),
        "kv_prefix_evictions": gauges.get("serving.kv_evictions"),
        "preemptions": counters.get("serving.preemptions", 0),
    }
    reg.publish_block("serving", block)
    print("BENCH serving: %.1f tok/s, %d req (%d finished / %d "
          "cancelled), latency p50=%.1fms p99=%.1fms, queue mean=%.1f "
          "max=%s, kv peak=%s (%s pages, %s B/page), prefix reuse=%s "
          "(%s hit tok, %s cow), preemptions=%s"
          % (block["tokens_per_sec"] or 0.0,
             block["requests_submitted"], block["requests_finished"],
             block["requests_cancelled"],
             block["latency_ms"]["p50"] or 0.0,
             block["latency_ms"]["p99"] or 0.0,
             qd.get("mean") or 0.0, qd.get("max"),
             "%s/%s" % (block["kv_peak_pages_in_use"],
                        block["kv_pages_total"]),
             block["kv_page_dtype"] or "float32",
             block["kv_page_bytes"],
             block["prefix_reuse_ratio"],
             block["prefix_hit_tokens"], block["kv_cow_copies"],
             block["preemptions"]), flush=True)
    return block


def quant_block() -> Optional[dict]:
    """Quantization-tier evidence: the int8 serving lane (page
    dtype/bytes, resident batch under the fixed pool budget, PTQ weight
    bytes pre/post), from the gauges the serving engine published. None
    when the tier is not active. `tools/perf_analysis.py --quant` writes
    the offline artifact for the same claims."""
    reg = registry()
    gauges = reg.snapshot()["gauges"]
    if gauges.get("serving.kv_page_dtype") != "int8" and \
            not gauges.get("serving.weights_quantized"):
        return None
    srv = {
        "kv_page_dtype": gauges.get("serving.kv_page_dtype"),
        "kv_page_bytes": gauges.get("serving.kv_page_bytes"),
        "kv_pool_bytes": gauges.get("serving.kv_pool_bytes"),
        "kv_resident_batch": gauges.get("serving.kv_resident_batch"),
    }
    if gauges.get("serving.weights_quantized"):
        srv["weight_bytes_dense"] = gauges.get(
            "serving.weight_bytes_dense")
        srv["weight_bytes"] = gauges.get("serving.weight_bytes")
    block = {"int8_serving": srv}
    reg.publish_block("quant", block)
    print("BENCH quant: int8 serving pages %s B/page, resident batch %s"
          % (srv["kv_page_bytes"], srv["kv_resident_batch"]), flush=True)
    return block


def embedding_block(exe, program, feed, fetch_list) -> Optional[dict]:
    """Vocab-sharded embedding evidence (paddle_tpu/embedding): the
    per-table shard layout and per-replica HBM (table + per-row
    moments at padded_rows/N vs the replicated logical bytes), the
    MODELED per-step collective bytes of the sparse schedule (ids
    all_gathers + the lookup psum_scatter + tap gathers — all
    proportional to TOUCHED ROWS) against the dense reference's
    vocab-sized grad allreduce, and — when a cold-tier RowCache
    published this process — its resident-rows / hit-rate / evicted
    gauges. None when the program carries no sparse plan."""
    if program is not None and hasattr(program, "_unwrap"):
        program = program._unwrap()
    plan = getattr(program, "_sparse_plan", None)
    if plan is None:
        return None
    reg = registry()
    batch_rows = 0
    for t in plan.tables.values():
        for s in t.sites:
            a = (feed or {}).get(s.ids)
            if a is not None:
                import numpy as _np

                batch_rows += int(_np.asarray(a).size)
    tables = {}
    logical_bytes = replica_bytes = dense_sync_bytes = 0
    sparse_sync_bytes = 0
    total_sites = max(sum(len(t.sites)
                          for t in plan.tables.values()), 1)
    for name, t in plan.tables.items():
        info = t.info
        itemsize = info.dtype.itemsize
        n_state = 1 + len(t.row_state)  # table + per-row moments
        t_logical = info.vocab * info.dim * itemsize * n_state
        t_replica = info.rows_local * info.dim * itemsize * n_state
        logical_bytes += t_logical
        replica_bytes += t_replica
        # dense reference: one vocab-sized fp32 grad allreduce/table
        dense_sync_bytes += 2 * info.vocab * info.dim * itemsize
        # sparse schedule per step: ids gather (int32) + (batch, dim)
        # psum_scatter forward + (batch, dim) tap gather backward
        site_rows = batch_rows // total_sites
        sparse_sync_bytes += len(t.sites) * site_rows * (
            4 + 2 * info.dim * itemsize)
        tables[name] = {
            "vocab": info.vocab, "dim": info.dim,
            "padded_rows": info.padded_rows,
            "rows_per_replica": info.rows_local,
            "sites": len(t.sites), "optimizer": t.opt_type,
            "row_state_vars": sorted(t.row_state.values()),
        }
    snap = reg.snapshot()
    gauges = snap["gauges"]
    block = {
        "tables": tables,
        "shards": plan.ndev,
        "dcn_replicas": plan.dcn_size,
        "state_logical_bytes": logical_bytes,
        "state_per_replica_bytes": replica_bytes,
        "modeled_sparse_sync_bytes_per_step": sparse_sync_bytes,
        "modeled_dense_sync_bytes_per_step": dense_sync_bytes,
        "touched_rows_per_step": batch_rows,
    }
    if gauges.get("embedding.resident_rows") is not None:
        block["row_cache"] = {
            "resident_rows": gauges.get("embedding.resident_rows"),
            "hit_rate": gauges.get("embedding.hit_rate"),
            "evicted_rows": gauges.get("embedding.evicted_rows"),
        }
    reg.publish_block("embedding", block)
    print("BENCH embedding: %d table(s) sharded %d-way, state "
          "%.2fMB -> %.2fMB/replica, sync bytes/step %.3fMB sparse "
          "vs %.3fMB dense (%d touched rows)%s"
          % (len(tables), plan.ndev, logical_bytes / 1e6,
             replica_bytes / 1e6, sparse_sync_bytes / 1e6,
             dense_sync_bytes / 1e6, batch_rows,
             (", cache hit %.1f%%" % (100 * (block["row_cache"]
                                             ["hit_rate"] or 0))
              if "row_cache" in block else "")), flush=True)
    return block


def telemetry_block(group=None) -> dict:
    """Registry roll-up: counters, step count, JSONL sink location —
    and, when a host-collective `group` spans the run's ranks, the
    end-of-window cross-rank aggregation + straggler verdict."""
    from . import aggregate

    reg = registry()
    snap = reg.snapshot()
    block = {
        "rank": snap["rank"],
        "steps": snap["steps"],
        "counters": snap["counters"],
        "telemetry_dir": snap["telemetry_dir"],
        "jsonl": reg.jsonl_path,
        "step_total_ms": snap["histograms"].get("step.total_ms"),
    }
    if group is not None:
        summaries = aggregate.allgather_window(
            group, aggregate.window_summary(reg))
        block["cross_rank"] = aggregate.aggregate_summaries(summaries)
        st = block["cross_rank"]["straggler"]
        if st is not None:
            print("BENCH straggler: rank %d (%.2fms/step mean, "
                  "+%.2fms vs rank %d; blame=%s)"
                  % (st["rank"], st["total_ms_mean"], st["slack_ms"],
                     st["fastest_rank"], st["blame_phase"]), flush=True)
    reg.publish_block("telemetry", block)
    return block


def bench_blocks(exe, program, feed, fetch_list, group=None) -> dict:
    """Everything bench.py attaches to a measured child's result, read
    back from the ONE registry: {"phases": ..., "collectives": ...,
    "opt_state_sharding": ..., "overlap": ..., "precision": ...,
    "static_checks": ..., "telemetry": ...} (absent blocks omitted)."""
    reg = registry()
    reg.clear_blocks()  # one program's evidence per assembly
    phases_block()
    collectives_blocks(exe, program, feed, fetch_list)
    hierarchy_block(exe, program, feed, fetch_list)
    model_parallel_block(exe, program, feed, fetch_list)
    precision_block(exe, program, feed, fetch_list)
    quant_block()
    embedding_block(exe, program, feed, fetch_list)
    attribution_block(exe, program, feed, fetch_list)
    static_checks_block(program)
    compile_cache_block()
    telemetry_block(group=group)
    return reg.blocks()
