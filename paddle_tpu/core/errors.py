"""Typed error/enforce system.

Reference parity: `paddle/fluid/platform/enforce.h:302-355`
(PADDLE_ENFORCE/PADDLE_THROW with typed payloads), `platform/
error_codes.proto` (the error classes), and `framework/op_call_stack.cc`
(python creation-site tracebacks attached to op errors so users see
WHERE in their model code the failing op was built).
"""
from __future__ import annotations

import traceback


class EnforceNotMet(RuntimeError):
    """Base framework error (reference: enforce.h EnforceNotMet)."""

    code = "LEGACY"


class InvalidArgumentError(EnforceNotMet):
    code = "INVALID_ARGUMENT"


class NotFoundError(EnforceNotMet):
    code = "NOT_FOUND"


class OutOfRangeError(EnforceNotMet):
    code = "OUT_OF_RANGE"


class AlreadyExistsError(EnforceNotMet):
    code = "ALREADY_EXISTS"


class ResourceExhaustedError(EnforceNotMet):
    code = "RESOURCE_EXHAUSTED"


class PreconditionNotMetError(EnforceNotMet):
    code = "PRECONDITION_NOT_MET"


class PermissionDeniedError(EnforceNotMet):
    code = "PERMISSION_DENIED"


class ExecutionTimeoutError(EnforceNotMet):
    code = "EXECUTION_TIMEOUT"


class UnimplementedError(EnforceNotMet):
    code = "UNIMPLEMENTED"


class UnavailableError(EnforceNotMet):
    code = "UNAVAILABLE"


class FatalError(EnforceNotMet):
    code = "FATAL"


class ExternalError(EnforceNotMet):
    code = "EXTERNAL"


def enforce(condition, message="enforce failed",
            exc=InvalidArgumentError):
    """PADDLE_ENFORCE (reference: enforce.h:314)."""
    if not condition:
        raise exc(message)


def enforce_not_none(value, name="value", exc=NotFoundError):
    if value is None:
        raise exc("%s should not be null" % name)
    return value


# -- op creation-site attribution (reference: op_call_stack.cc) -----------

_FRAMEWORK_MARKERS = ("/paddle_tpu/", "<frozen")


def capture_user_callstack(limit=3):
    """Topmost non-framework frames of the current stack — recorded on
    each Operator at build time, attached to lowering/execution errors.
    Walks raw frames with early stop (no linecache source resolution),
    so BERT-scale program builds pay microseconds per op, not
    extract_stack's full-stack cost."""
    import sys

    frames = []
    f = sys._getframe(2)
    while f is not None and len(frames) < limit:
        fn = f.f_code.co_filename or ""
        if not any(m in fn for m in _FRAMEWORK_MARKERS):
            frames.append("%s:%d in %s" % (fn, f.f_lineno,
                                           f.f_code.co_name))
        f = f.f_back
    return frames


def attach_op_callstack(exc, op):
    """Wrap an exception with the op's creation site (reference:
    InsertCallStackInfo, op_call_stack.cc)."""
    stack = getattr(op, "_creation_stack", None)
    note = "\n  [operator %s error]" % op.type
    if stack:
        note += "\n  op created at:\n    " + "\n    ".join(stack)
    raise type(exc)(str(exc) + note) if isinstance(exc, EnforceNotMet) \
        else RuntimeError(str(exc) + note) from exc
