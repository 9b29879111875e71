"""Persistent, cross-process compilation cache for the Executor.

A cold XLA compile of one BERT-base step takes tens of seconds, and the
elastic restart path (PR 9) made restarts *routine*: every transition
re-paid full compilation across the whole cohort. This module is the
persistent tier layered UNDER the Executor's in-memory LRU
(`Executor._cache`):

- the XLA executables themselves persist through
  `jax.experimental.compilation_cache` (`_configure_jax`), rooted at
  `cache_dir()`. One rule places it: `JAX_COMPILATION_CACHE_DIR` where
  the environment sets it (the cache can be placed from outside, and
  nothing in the tree points jax anywhere else then); where it does
  not, an entry point (`bench.py`, `chip_smoke.py`) that called
  `use_default_dir()` gets the fixed `<checkout>/.jax_cache` — the path
  is part of jax's cache key, so it is never made from a temporary
  name, a pid or the time — and a library user gets no persistent
  tier. The launch supervisor exports the same choice to every worker
  and across restarts, so a restarted N' cohort deserializes
  executables in seconds instead of recompiling;
- a *fingerprint index* (`index/<fp>.json` sentinels) keyed on
  (canonicalized lowered StableHLO, mesh topology, the
  lowering-relevant `FLAGS_tpu_*` set, jax/jaxlib version + backend)
  classifies every fresh-process compile as a persistent *hit* or
  *miss* at the framework's own key granularity — the telemetry the
  raw jax tier cannot provide — and remembers the original compile
  cost so `saved_ms` is bookkeeping, not a guess;
- jax's monitoring hooks (`install_listeners`) attribute the actual
  backend-compile seconds of the first dispatch into the step record's
  `compile_ms` phase and count XLA-level persistent hits, feeding the
  per-compile `compile_cache` telemetry events, the registry
  counters/gauges, the bench `compile_cache` block
  (observability/publish.py) and `tools/perf_analysis.py
  --compile-cache`.

Everything here is inert while no directory is named: `enabled()` is
False, no jax config is touched, no listeners install, and the
Executor's behavior is byte-identical to a cache-less build.
"""
from __future__ import annotations

import hashlib
import json
import os
import re
import threading
import time
from typing import Dict, Optional

__all__ = ["cache_dir", "default_dir", "use_default_dir", "enabled",
           "ensure", "disable",
           "lowering_flags", "fingerprint", "index_lookup",
           "index_store", "install_listeners", "jax_stats",
           "stats_delta", "record_event", "stats",
           "classified_compile"]

#: flags whose value shapes the lowered computation — part of the
#: fingerprint, so flipping any of them can never alias a stale
#: executable (the StableHLO usually changes too; this is the explicit
#: contract, and it also covers flags whose effect is
#: backend-option-only)
LOWERING_FLAGS = (
    "FLAGS_tpu_donate_buffers",
    "FLAGS_tpu_donate_feed_buffers",
    "FLAGS_tpu_sharded_weight_update",
    "FLAGS_tpu_comm_bucket_mb",
    "FLAGS_tpu_dcn_replicas",
    "FLAGS_tpu_amp_level",
    "FLAGS_tpu_op_provenance",
    "FLAGS_prng_impl",
    "FLAGS_flash_attention_min_seq",
)

_lock = threading.RLock()
_configured_dir: Optional[str] = None
#: the directory an entry point chose through use_default_dir() when
#: the environment named none; process state, never exported
_entry_dir: Optional[str] = None
_listeners_installed = False
#: cumulative jax-tier stats fed by the monitoring listeners; snapshot
#: with jax_stats() / delta with stats_delta() around a compile
_jax = {"backend_compiles": 0, "backend_compile_s": 0.0,
        "persistent_hits": 0, "saved_s": 0.0, "retrieval_s": 0.0}
#: process-level roll-up at the framework key granularity (one entry
#: per classified fresh compile; in-memory LRU hits never reach here)
_stats = {"hits": 0, "misses": 0, "compile_ms_total": 0.0,
          "saved_ms_total": 0.0, "warmups": 0}


_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def cache_dir() -> Optional[str]:
    """The persistent tier's root: `JAX_COMPILATION_CACHE_DIR` where
    the environment sets it, else the directory an entry point chose
    (`use_default_dir()`), else None (the tier is off)."""
    return os.environ.get(_ENV_VAR) or _entry_dir


def default_dir() -> str:
    """`<checkout>/.jax_cache`: where the entry points keep the cache
    when the environment names no directory."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def use_default_dir() -> str:
    """Entry points only (bench.py, chip_smoke.py): keep the directory
    the environment names, or choose `default_dir()` for this process
    (the environment is left as it was), and wire the tier. Raises if
    the directory cannot be used."""
    global _entry_dir
    _entry_dir = default_dir()
    d = ensure()
    if d is None:
        raise RuntimeError("compile cache directory %r is unusable"
                           % (cache_dir(),))
    return d


def enabled() -> bool:
    return cache_dir() is not None


def ensure() -> Optional[str]:
    """Idempotently wire the persistent tier: point
    jax.experimental.compilation_cache at `cache_dir()` (min
    compile time / entry size floors dropped so EVERY executor
    executable persists — a 40ms test program and a 90s BERT step both
    must round-trip) and install the monitoring listeners. Returns the
    active directory, or None when no directory is named. Never raises —
    an unwritable directory degrades to cache-off, it must not take
    down a training step."""
    global _configured_dir
    d = cache_dir()
    if d is None:
        return None
    with _lock:
        if _configured_dir == d:
            return d
        try:
            os.makedirs(os.path.join(d, "index"), exist_ok=True)
            _configure_jax(d)
            _configured_dir = d
        except Exception:  # noqa: BLE001 - cache is an optimization
            return None
    install_listeners()
    return d


def _configure_jax(d: str) -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _reset_jax_cache_instance()


def _reset_jax_cache_instance() -> None:
    """jax memoizes its cache object at first use — a dir change
    mid-process (tests re-pointing the directory) must drop the
    memo or writes keep landing in the OLD directory."""
    try:
        from jax.experimental.compilation_cache import (
            compilation_cache as _jcc)

        _jcc.reset_cache()
    except Exception:  # noqa: BLE001 - cache not yet initialized
        pass


def disable() -> None:
    """Detach the jax-level tier (tests; the listeners stay — they are
    cheap and delta-snapshotted)."""
    global _configured_dir
    with _lock:
        if _configured_dir is None:
            return
        _configured_dir = None
    try:
        import jax

        jax.config.update("jax_compilation_cache_dir", None)
    except Exception:  # noqa: BLE001
        pass
    _reset_jax_cache_instance()


# -- jax monitoring listeners ---------------------------------------------

def install_listeners() -> bool:
    """Register (once) for the jax monitoring events that carry the
    ground truth no wrapper can fake: `backend_compile_duration` (the
    actual XLA compile seconds the first dispatch pays — re-attributed
    from the step's dispatch phase into compile_ms),
    `compilation_cache/cache_hits` (the persistent tier served an
    executable) and `compile_time_saved_sec`."""
    global _listeners_installed
    with _lock:
        if _listeners_installed:
            return True
        try:
            import jax._src.monitoring as mon

            # the callbacks fire ON THE COMPILING THREAD: bump the
            # process totals (bench block) AND the caller thread's own
            # tally (jax_stats/stats_delta) — a background warmup
            # thread's compiles must never leak into the main thread's
            # hit/miss verdict or compile_ms re-attribution
            def _on_event(name, **kw):
                if name == "/jax/compilation_cache/cache_hits":
                    with _lock:
                        _jax["persistent_hits"] += 1
                    _thread_jax()["persistent_hits"] += 1

            def _on_duration(name, dur, **kw):
                if name == "/jax/core/compile/backend_compile_duration":
                    with _lock:
                        _jax["backend_compiles"] += 1
                        _jax["backend_compile_s"] += float(dur)
                    tl = _thread_jax()
                    tl["backend_compiles"] += 1
                    tl["backend_compile_s"] += float(dur)
                elif name == "/jax/compilation_cache/" \
                             "compile_time_saved_sec":
                    with _lock:
                        _jax["saved_s"] += max(0.0, float(dur))
                    _thread_jax()["saved_s"] += max(0.0, float(dur))
                elif name == "/jax/compilation_cache/" \
                             "cache_retrieval_time_sec":
                    with _lock:
                        _jax["retrieval_s"] += float(dur)
                    _thread_jax()["retrieval_s"] += float(dur)

            mon.register_event_listener(_on_event)
            mon.register_event_duration_secs_listener(_on_duration)
            _listeners_installed = True
            return True
        except Exception:  # noqa: BLE001 - exotic jax: stats stay 0
            return False


_tls = threading.local()


def _thread_jax() -> Dict[str, float]:
    d = getattr(_tls, "jax", None)
    if d is None:
        d = _tls.jax = {"backend_compiles": 0,
                        "backend_compile_s": 0.0,
                        "persistent_hits": 0, "saved_s": 0.0,
                        "retrieval_s": 0.0}
    return d


def jax_stats() -> Dict[str, float]:
    """THIS thread's cumulative jax-tier tally (snapshot before a
    compile, stats_delta after): thread-local so a concurrent
    background warmup's compiles never pollute the main step loop's
    classification. The process-wide totals live in stats()["jax"]."""
    return dict(_thread_jax())


def stats_delta(before: Dict[str, float]) -> Dict[str, float]:
    now = jax_stats()
    return {k: now[k] - before.get(k, 0) for k in now}


# -- fingerprinting --------------------------------------------------------

_LOC_RE = re.compile(r"\s*loc\([^)]*\)")
_LOCDEF_RE = re.compile(r"^#loc.*$", re.M)


def canonicalize_stablehlo(text: str) -> str:
    """Strip MLIR location metadata (file paths / line numbers of the
    framework source) so the fingerprint survives a repo relocation and
    interpreter-version drift in debug info, while every semantic
    change (an op, a shape, a sharding, a provenance-visible rewrite)
    still changes it."""
    return _LOCDEF_RE.sub("", _LOC_RE.sub("", text))


def mesh_signature(mesh) -> str:
    """Deterministic topology signature: axis names x sizes + the
    device kinds/ids — two processes agree iff they would compile for
    the same device assignment."""
    if mesh is None:
        return "mesh:none"
    try:
        axes = ",".join("%s=%d" % (a, int(mesh.shape[a]))
                        for a in mesh.axis_names)
        devs = ",".join(
            "%s:%s" % (getattr(d, "platform", "?"), getattr(d, "id", "?"))
            for d in mesh.devices.flat)
        return "mesh:(%s)[%s]" % (axes, devs)
    except Exception:  # noqa: BLE001 - exotic mesh object
        return "mesh:%r" % (mesh,)


def lowering_flags() -> Dict[str, object]:
    from ..utils.flags import get_flag

    return {name: get_flag(name) for name in LOWERING_FLAGS}


def fingerprint(stablehlo_text: str, mesh=None, extra=None) -> str:
    """The persistent cache key: sha256 over (canonical StableHLO,
    mesh topology, lowering-relevant flag values, jax/jaxlib version +
    backend platform)."""
    import jax
    import jaxlib

    h = hashlib.sha256()
    h.update(canonicalize_stablehlo(stablehlo_text).encode())
    h.update(mesh_signature(mesh).encode())
    h.update(json.dumps(lowering_flags(), sort_keys=True,
                        default=repr).encode())
    h.update(("jax=%s;jaxlib=%s;backend=%s"
              % (jax.__version__, jaxlib.__version__,
                 jax.default_backend())).encode())
    if extra:
        h.update(json.dumps(extra, sort_keys=True,
                            default=repr).encode())
    return h.hexdigest()


# -- fingerprint index (hit/miss classification + saved-seconds) ----------

def _index_path(fp: str) -> Optional[str]:
    d = cache_dir()
    if d is None:
        return None
    return os.path.join(d, "index", fp + ".json")


def index_lookup(fp: str) -> Optional[dict]:
    """The sentinel a previous process (or an evicted-and-readmitted
    entry in THIS process) left after compiling this fingerprint —
    presence means the XLA executables for it are already on disk."""
    path = _index_path(fp)
    if path is None or not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def index_store(fp: str, meta: dict) -> Optional[str]:
    """Atomically record a completed compile (tmp-then-replace: the
    whole cohort shares one index and a torn sentinel must never
    poison a reader)."""
    path = _index_path(fp)
    if path is None:
        return None
    doc = dict(meta)
    doc.setdefault("fingerprint", fp)
    doc.setdefault("created_ts", time.time())
    doc.setdefault("flags", lowering_flags())
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = "%s.tmp.%d" % (path, os.getpid())
        with open(tmp, "w") as f:
            json.dump(doc, f, sort_keys=True, default=repr)
        os.replace(tmp, path)
        return path
    except OSError:
        return None


def new_entry_bytes(since_ts: float) -> int:
    """Approximate bytes the jax tier wrote since `since_ts` — the
    on-disk cost of a miss (compiles are rare enough that one
    directory scan per miss is noise). APPROXIMATE by design: the
    cache dir is shared across a cohort, so ranks cold-starting
    simultaneously each count the window's overlapping writes; treat
    the per-event `bytes` field as disk-cost magnitude, not an exact
    per-module size (the miss sentinel pins whatever this rank
    observed)."""
    d = cache_dir()
    if d is None:
        return 0
    total = 0
    try:
        with os.scandir(d) as it:
            for e in it:
                try:
                    st = e.stat()
                except OSError:
                    continue
                if e.is_file() and st.st_mtime >= since_ts - 1.0:
                    total += int(st.st_size)
    except OSError:
        return 0
    return total


def classified_compile(lowered, mesh=None, extra=None, source="aot"):
    """Compile a `jax.stages.Lowered` while classifying it against the
    persistent tier — the generic twin of the Executor's per-entry
    classification, used by non-Program compile paths (the serving
    engine's decode/prefill step buckets, `source="serving_decode"` /
    `"serving_prefill"`; `tools/perf_analysis.py --compile-cache`
    breaks its report down by this source tag).

    Returns (compiled, info) where info is None when the tier is off,
    else {"status": "hit"|"miss", "fingerprint", "compile_ms",
    "saved_ms"}. The jax-stat delta is THREAD-LOCAL (jax_stats), so
    concurrent warmups classify independently. Classification errors
    degrade to an unclassified compile — never a failed one."""
    ensure()
    if not enabled():
        return lowered.compile(), None
    try:
        fp = fingerprint(lowered.as_text(), mesh, extra=extra)
        prev = index_lookup(fp)
    except Exception:  # noqa: BLE001 - classification is telemetry
        return lowered.compile(), None
    before, t0 = jax_stats(), time.time()
    compiled = lowered.compile()
    d = stats_delta(before)
    comp_ms = max(0.0, d["backend_compile_s"]) * 1e3
    hit = prev is not None or d["persistent_hits"] > 0
    saved_ms = max(0.0, d["saved_s"] * 1e3)
    nbytes = 0
    if prev is not None:
        saved_ms = max(saved_ms,
                       float(prev.get("compile_ms", 0.0)) - comp_ms)
        nbytes = int(prev.get("bytes", 0))
    elif not hit:
        nbytes = new_entry_bytes(t0)
    status = "hit" if hit else "miss"
    record_event(status, fp, compile_ms=comp_ms, saved_ms=saved_ms,
                 nbytes=nbytes, source=source)
    if prev is None:
        index_store(fp, {"compile_ms": round(comp_ms, 3),
                         "bytes": nbytes, "source": str(source),
                         "mesh": mesh_signature(mesh)})
    return compiled, {"status": status, "fingerprint": fp,
                      "compile_ms": round(comp_ms, 3),
                      "saved_ms": round(saved_ms, 3)}


# -- telemetry -------------------------------------------------------------

def record_event(status: str, fp: Optional[str], compile_ms: float,
                 saved_ms: float = 0.0, nbytes: int = 0,
                 source: str = "step") -> Optional[dict]:
    """One classified compile -> a `compile_cache` telemetry event
    (JSONL sink + flight ring), the registry counters/gauges the bench
    block assembles from, and the module roll-up. Never raises."""
    with _lock:
        if status == "hit":
            _stats["hits"] += 1
        elif status == "miss":
            _stats["misses"] += 1
        if source == "warmup":
            _stats["warmups"] += 1
        _stats["compile_ms_total"] += max(0.0, float(compile_ms))
        _stats["saved_ms_total"] += max(0.0, float(saved_ms))
    try:
        from ..observability import registry

        reg = registry()
        reg.inc("compile_cache." + status)
        reg.set_gauge("compile_cache.compile_ms_total",
                      round(_stats["compile_ms_total"], 3))
        reg.set_gauge("compile_cache.saved_ms_total",
                      round(_stats["saved_ms_total"], 3))
        return reg.event(
            "compile_cache", status=str(status),
            key=(fp or "")[:16], compile_ms=round(float(compile_ms), 3),
            saved_ms=round(float(saved_ms), 3), bytes=int(nbytes),
            source=str(source))
    except Exception:  # noqa: BLE001 - telemetry must never kill a step
        return None


def stats() -> dict:
    """Process roll-up + on-disk tier inventory — the bench
    `compile_cache` block's payload."""
    with _lock:
        out = dict(_stats)
        out["jax"] = dict(_jax)
    d = cache_dir()
    out["enabled"] = d is not None
    out["dir"] = d
    total = out["hits"] + out["misses"]
    out["hit_rate"] = (out["hits"] / total) if total else None
    out["persistent_entries"] = 0
    out["persistent_bytes"] = 0
    out["index_entries"] = 0
    if d and os.path.isdir(d):
        try:
            with os.scandir(d) as it:
                for e in it:
                    if e.is_file():
                        out["persistent_entries"] += 1
                        try:
                            out["persistent_bytes"] += int(
                                e.stat().st_size)
                        except OSError:
                            pass
            idx = os.path.join(d, "index")
            if os.path.isdir(idx):
                out["index_entries"] = len(
                    [f for f in os.listdir(idx)
                     if f.endswith(".json")])
        except OSError:
            pass
    return out


def _reset_for_tests() -> None:
    global _configured_dir, _entry_dir
    with _lock:
        _configured_dir = _entry_dir = None
        for k in _jax:
            _jax[k] = 0 if isinstance(_jax[k], int) else 0.0
        for k in _stats:
            _stats[k] = 0 if isinstance(_stats[k], int) else 0.0
    _tls.jax = None
