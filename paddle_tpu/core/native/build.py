"""Builds libpaddle_tpu_native.so from the C++ sources with g++.

No pybind11 in this image, so the library exposes a plain C ABI
(src/capi.h) consumed via ctypes. The .so is not tracked by git: it is
built on first use wherever it is absent, and rebuilt when the sha256 of
the tracked sources differs from the one recorded beside it
(`libpaddle_tpu_native.so.sha256`) — file times are not consulted, a
copy of the tree changes them. Importing paddle_tpu.core.native triggers
this lazily; the build is a single g++ invocation (< 10s) and a failed
build raises.
"""
from __future__ import annotations

import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "src")
_SO = os.path.join(_DIR, "libpaddle_tpu_native.so")
_STAMP = _SO + ".sha256"
_SOURCES = ["channel.cc", "allocator.cc", "data_feed.cc", "monitor.cc",
            "trace_events.cc", "ragged.cc", "crypto.cc"]
_lock = threading.Lock()


def _sources_hash() -> str:
    h = hashlib.sha256()
    for name in _SOURCES + ["capi.h"]:
        h.update(name.encode() + b"\0")
        with open(os.path.join(_SRC, name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _stale(want: str) -> bool:
    if not os.path.exists(_SO):
        return True
    try:
        with open(_STAMP) as f:
            return f.read().strip() != want
    except OSError:
        return True


def build(force: bool = False) -> str:
    """Returns the path to the built shared library."""
    with _lock:
        want = _sources_hash()
        if not force and not _stale(want):
            return _SO
        # build beside the target and rename into place: concurrent
        # builders (test workers) each install a whole file
        tmp = "%s.build%d" % (_SO, os.getpid())
        cmd = [
            "g++", "-O2", "-std=c++17", "-fPIC", "-shared", "-pthread",
            "-Wall", "-o", tmp,
        ] + [os.path.join(_SRC, s) for s in _SOURCES]
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                "native runtime build failed (%s):\n%s"
                % (" ".join(cmd), proc.stderr))
        os.replace(tmp, _SO)
        with open(tmp, "w") as f:
            f.write(want + "\n")
        os.replace(tmp, _STAMP)
        return _SO


if __name__ == "__main__":
    print(build(force=True))
