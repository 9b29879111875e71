"""On-demand `jax.profiler` capture from a LIVE run — no code changes,
no restart.

Chip time is budgeted, and the run that is already going is the one to
profile. Two triggers, both armed
by `install()` (which the executor arms automatically once a telemetry
dir is configured):

- **trigger file**: `touch <telemetry_dir>/capture.trigger` starts an
  xplane trace into `<telemetry_dir>/xplane/`; removing the file stops
  it. The step loop polls the file's existence at most every
  `poll_interval_s` (default 1s) — an os.stat per second, nothing on
  the hot path.
- **SIGUSR2**: each delivery toggles start/stop (for runs whose
  filesystem is awkward to reach).

Every start/stop lands a "capture" event in the telemetry stream, so
the trace window is locatable in the JSONL timeline afterwards.
"""
from __future__ import annotations

import os
import signal
import threading
import time
from typing import Optional

__all__ = ["CaptureController", "controller", "install"]


class CaptureController:
    def __init__(self, out_dir: Optional[str] = None,
                 poll_interval_s: float = 1.0):
        self._dir = out_dir
        self._interval = float(poll_interval_s)
        self._lock = threading.Lock()
        self._tracing = False
        # the first poll always looks: time.monotonic() counts from an
        # arbitrary origin (often boot), so 0.0 is not "long ago"
        self._last_poll = float("-inf")
        self._trace_no = 0

    # -- resolution --------------------------------------------------------
    def _base_dir(self) -> Optional[str]:
        if self._dir:
            return self._dir
        from .registry import registry

        return registry().telemetry_dir

    @property
    def trigger_path(self) -> Optional[str]:
        base = self._base_dir()
        return os.path.join(base, "capture.trigger") if base else None

    @property
    def tracing(self) -> bool:
        return self._tracing

    # -- the actual profiler calls (monkeypatchable in tests) --------------
    def _start_trace(self, out_dir: str) -> None:
        import jax.profiler

        jax.profiler.start_trace(out_dir)

    def _stop_trace(self) -> None:
        import jax.profiler

        jax.profiler.stop_trace()

    # -- toggling ----------------------------------------------------------
    def start(self) -> Optional[str]:
        with self._lock:
            if self._tracing:
                return None
            base = self._base_dir()
            if base is None:
                return None
            self._trace_no += 1
            out = os.path.join(base, "xplane",
                               "trace%03d" % self._trace_no)
            os.makedirs(out, exist_ok=True)
            try:
                self._start_trace(out)
            except Exception:  # noqa: BLE001 - capture is best-effort
                return None
            self._tracing = True
        from .registry import registry

        registry().event("capture", action="start", dir=out)
        return out

    def stop(self) -> bool:
        with self._lock:
            if not self._tracing:
                return False
            self._tracing = False
            try:
                self._stop_trace()
            except Exception:  # noqa: BLE001 - capture is best-effort:
                # a failed stop (profiler session already gone) must
                # never propagate into the interrupted training loop
                return False
        from .registry import registry

        registry().event("capture", action="stop")
        return True

    def toggle(self) -> None:
        if self._tracing:
            self.stop()
        else:
            self.start()

    def capture_for(self, duration_s: float) -> Optional[str]:
        """Bounded capture window: start a trace now and stop it after
        `duration_s` on a one-shot timer thread — the hang watchdog's
        "photograph the wedged window" hook (the wedged step loop can't
        reach the usual trigger-file poll). Returns the trace dir, or
        None when a trace is already running / no telemetry dir."""
        out = self.start()
        if out is None:
            return None
        t = threading.Timer(max(0.05, float(duration_s)), self.stop)
        t.daemon = True
        t.start()
        return out

    # -- step-loop poll ----------------------------------------------------
    def poll(self, now: Optional[float] = None) -> None:
        """Called from the executor's step epilogue: throttled
        trigger-file check; starts/stops to MATCH the file's
        existence."""
        now = time.monotonic() if now is None else now
        if now - self._last_poll < self._interval:
            return
        self._last_poll = now
        trig = self.trigger_path
        if trig is None:
            return
        want = os.path.exists(trig)
        if want and not self._tracing:
            self.start()
        elif not want and self._tracing:
            self.stop()


# -- process-global controller -------------------------------------------

_lock = threading.Lock()
_controller: Optional[CaptureController] = None
_signal_installed = False


def controller() -> CaptureController:
    global _controller
    if _controller is None:
        with _lock:
            if _controller is None:
                _controller = CaptureController()
    return _controller


def install(signum: int = signal.SIGUSR2) -> bool:
    """Arm the SIGUSR2 toggle (idempotent; main thread only — the
    trigger-file path needs no installation beyond a telemetry dir).
    Returns True when the handler landed."""
    global _signal_installed
    with _lock:
        if _signal_installed:
            return True
        if threading.current_thread() is not threading.main_thread():
            return False

        def _on_usr2(s, f):
            try:
                controller().toggle()
            except Exception:  # noqa: BLE001 - the handler interrupts
                pass  # arbitrary main-thread code; never raise into it

        try:
            signal.signal(signum, _on_usr2)
        except (ValueError, OSError):
            return False
        _signal_installed = True
        return True


def _reset_for_tests() -> None:
    global _controller, _signal_installed
    with _lock:
        _controller = None
        _signal_installed = False
