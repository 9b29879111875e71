"""proto-check: explicit-state interleaving checker for the host
protocol tier.

tpu-lint's six IR checkers prove SPMD properties of the *device*
program; this module proves safety properties of the *host* protocols
around it — retried RPC envelopes, exactly-once PS apply, the elastic
seam's doomed-set agreement, serving drain/adopt manifests, refcounted
copy-on-write KV pages. Those tiers are only exercised on the handful
of schedules the runner scripts happen to produce; here the checker
owns EVERY nondeterministic choice (delivery order, duplication,
delayed retries, crash points, notice timing) and explores the
schedule space exhaustively up to a bounded budget.

Design — replay-based explicit-state DFS:

- a **ProtocolModel** (see proto_models.py for the shipped adapters)
  wraps the real code behind a simulated transport. It exposes the
  currently *enabled* actions as compact hashable tuples
  ``(actor, label, *args)``, applies one action per ``step()``, and
  reports invariant violations after every state transition.
- the engine enumerates schedules depth-first. Models drive real,
  non-snapshottable objects (an RpcServer dedup table, a PagedKVCache),
  so instead of checkpointing state the engine REPLAYS the prefix from
  a fresh model at every backtrack — the standard stateless-search
  trade: O(depth) extra steps per schedule, zero assumptions about the
  code under test. Models must therefore be deterministic functions of
  their action sequence.
- **sleep-set style reduction**: after a subtree for action ``a`` is
  explored at a node, ``a`` moves into the sleep set of sibling
  subtrees whose first action is independent of it (the model's
  ``independent`` hook; default = nothing commutes, i.e. full
  exploration). Classic partial-order reduction, scoped conservatively.
- **state dedup**: a model may expose ``fingerprint()``; revisited
  fingerprints prune the subtree (invariants were already checked
  there). This is what makes retry/drop loops terminate: the state
  after drop+resend equals the state before the drop.
- **budget**: ``max_schedules`` bounds explored interleavings,
  ``max_depth`` bounds schedule length. Exhaustion truncates with
  coverage stats; it is never an error.
- **every finding is replayable**: the compact trace printed in the
  finding (``Finding.trace``) is the full schedule; ``replay()`` runs
  it alone on a fresh model and reproduces the violation
  deterministically — the debugging loop is one function call, not a
  multi-process repro.

Invariants asserted at every state (the shipped models split them):
exactly-once (no retried seq applied twice), quiescence/no-deadlock
(no state where all actors block while messages are deliverable —
surfaced as a state with no enabled action that is not ``done()``),
seam agreement (survivors agree on doomed set and generation),
drain/adopt conservation (every admitted request retired exactly once)
and KV page conservation (free + cached + referenced == total,
refcounts >= 0, COW never writes a shared page).

Surfaces: ``tools/tpu_lint.py --protocol`` (and the ``perf_analysis
--lint`` alias), ``artifacts/protocol_checks.json``, the bench
``static_checks.protocol`` section, and tests/test_proto_check.py's
seeded-defect mutants.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from .findings import Finding

__all__ = [
    "ProtocolModel", "ExploreResult", "explore", "replay",
    "format_trace", "parse_trace", "run_protocol_checks",
]

#: action tuples are (actor, label, *args) of str/int — keep them tiny,
#: they are hashed per state and printed verbatim in findings
Action = Tuple


class ProtocolModel:
    """Duck-typed base for protocol models. Subclasses drive the REAL
    code through a simulated transport; the checker owns every
    nondeterministic choice by picking which enabled action fires next.

    Contract: ``step`` must be a deterministic function of the action
    sequence since construction (the engine replays prefixes on fresh
    instances), and ``actions``/``invariants``/``done`` must be pure
    observations."""

    #: registry / report name
    name = "model"

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        """Build the initial state (fresh real objects + transport)."""

    def actions(self) -> List[Action]:
        """Currently enabled actions, deterministic order."""
        return []

    def step(self, action: Action) -> None:
        """Apply one action (deliver/dup/drop/crash/...)."""
        raise NotImplementedError

    def invariants(self) -> List[Tuple[str, str]]:
        """(invariant-name, message) violations visible in the current
        state; empty = healthy. Checked after EVERY transition."""
        return []

    def done(self) -> bool:
        """Terminal accepting state (quiescent with all work retired).
        A state with no enabled actions that is NOT done is a
        deadlock."""
        return False

    def fingerprint(self):
        """Hashable state digest for revisit pruning, or None to
        disable. Exclude wall-clock/ids that vary across replays."""
        return None

    def independent(self, a: Action, b: Action) -> bool:
        """True when actions commute (same state either order) — the
        sleep-set reduction hook. Default: nothing commutes."""
        return False

    def close(self) -> None:
        """Release per-schedule resources / restore globals the model
        swapped (env vars, module singletons). Called after every
        explored schedule and every replay."""


class ExploreResult:
    """Coverage + findings for one model's exploration."""

    __slots__ = ("model", "schedules", "states", "deepest", "truncated",
                 "findings")

    def __init__(self, model, schedules, states, deepest, truncated,
                 findings):
        self.model = model
        self.schedules = schedules
        self.states = states
        self.deepest = deepest
        self.truncated = truncated
        self.findings = findings

    @property
    def errors(self) -> int:
        return sum(1 for f in self.findings if f.severity == "error")

    def to_dict(self) -> dict:
        return {
            "model": self.model,
            "schedules": self.schedules,
            "states": self.states,
            "deepest": self.deepest,
            "truncated": self.truncated,
            "errors": self.errors,
            "findings": [f.to_dict() for f in self.findings],
        }


# -- trace encoding ------------------------------------------------------

_INT_RE = re.compile(r"^-?\d+$")


def format_trace(trace: Iterable[Action]) -> str:
    """Compact replayable encoding: steps joined by ';', fields by ':'.
    Round-trips through parse_trace for str/int action fields."""
    return ";".join(":".join(str(f) for f in a) for a in trace)


def parse_trace(text: str) -> List[Action]:
    out: List[Action] = []
    for step in (text or "").split(";"):
        if not step:
            continue
        out.append(tuple(int(f) if _INT_RE.match(f) else f
                         for f in step.split(":")))
    return out


def _mk_finding(model: str, invariant: str, message: str,
                trace: Tuple[Action, ...]) -> Finding:
    last = trace[-1] if trace else None
    return Finding(
        "protocol", "error",
        "%s: %s: %s" % (model, invariant, message),
        op_idx=len(trace) - 1 if trace else None,
        op_type=str(last[1]) if last is not None and len(last) > 1
        else None,
        var=str(last[0]) if last is not None else None,
        trace=format_trace(trace))


# -- exploration ---------------------------------------------------------

def explore(factory: Callable[[], ProtocolModel], *,
            max_schedules: int = 1000, max_depth: int = 96,
            max_findings: int = 8,
            dedupe_states: bool = True) -> ExploreResult:
    """Explicit-state DFS over the model's schedule space. `factory`
    must return a FRESH deterministic model per call (the engine
    replays prefixes on new instances at every backtrack)."""
    probe = factory()
    name = getattr(probe, "name", type(probe).__name__)
    _close(probe)

    findings: List[Finding] = []
    fkeys = set()
    seen = set()
    stats = {"schedules": 0, "states": 0, "deepest": 0,
             "truncated": False}

    def emit(invariant, message, trace):
        key = (invariant, str(message))
        if key in fkeys or len(findings) >= max_findings:
            stats["truncated"] = stats["truncated"] or key not in fkeys
            return
        fkeys.add(key)
        findings.append(_mk_finding(name, invariant, message,
                                    tuple(trace)))

    def observe(m, trace):
        """Check the state just reached; return the branchable action
        list, or None when this branch ends here (violation, terminal,
        deadlock, or an already-visited state)."""
        stats["states"] += 1
        stats["deepest"] = max(stats["deepest"], len(trace))
        try:
            viols = m.invariants()
        except Exception as e:  # noqa: BLE001 - invariant hook crashed
            emit("model-exception",
                 "invariants() raised %s: %s" % (type(e).__name__, e),
                 trace)
            return None
        if viols:
            for inv, msg in viols:
                emit(inv, msg, trace)
            return None
        acts = list(m.actions())
        if not acts:
            if not m.done():
                emit("deadlock",
                     "no enabled action in a non-terminal state "
                     "(all actors blocked)", trace)
            return None
        if dedupe_states:
            fp = m.fingerprint()
            if fp is not None:
                if fp in seen:
                    return None
                seen.add(fp)
        return acts

    # DFS frontier: (prefix, untried siblings, explored siblings,
    # node's sleep set). `untried`/`explored` are mutated in place.
    stack: List[Tuple[Tuple[Action, ...], List[Action], List[Action],
                      frozenset]] = []

    def descend(m, prefix, acts, sleep):
        """Greedily extend one schedule, pushing backtrack nodes."""
        while True:
            branch = [a for a in acts if a not in sleep]
            if not branch:
                return  # every enabled action is covered elsewhere
            a = branch[0]
            stack.append((prefix, branch[1:], [a], sleep))
            child_sleep = frozenset(
                x for x in sleep if m.independent(x, a))
            try:
                m.step(a)
            except Exception as e:  # noqa: BLE001 - model crashed
                emit("model-exception",
                     "step(%r) raised %s: %s"
                     % (a, type(e).__name__, e), prefix + (a,))
                return
            prefix = prefix + (a,)
            if len(prefix) >= max_depth:
                stats["truncated"] = True
                return
            acts = observe(m, prefix)
            if acts is None:
                return
            sleep = child_sleep

    # schedule 1: the root descent
    m = factory()
    try:
        stats["schedules"] += 1
        acts = observe(m, ())
        if acts is not None:
            descend(m, (), acts, frozenset())
    finally:
        _close(m)

    while stack and stats["schedules"] < max_schedules \
            and len(findings) < max_findings:
        prefix, untried, explored, sleep = stack[-1]
        if not untried:
            stack.pop()
            continue
        b = untried.pop(0)
        stats["schedules"] += 1
        m = factory()
        try:
            ok = True
            for a in prefix:
                try:
                    m.step(a)
                except Exception as e:  # noqa: BLE001
                    # the prefix succeeded once; a replay failure means
                    # the model is nondeterministic — itself a bug
                    emit("replay-divergence",
                         "prefix replay failed at %r (%s: %s)"
                         % (a, type(e).__name__, e), prefix)
                    ok = False
                    break
            if not ok:
                stack.pop()
                continue
            child_sleep = frozenset(
                x for x in list(sleep) + explored
                if x != b and m.independent(x, b))
            explored.append(b)
            try:
                m.step(b)
            except Exception as e:  # noqa: BLE001
                emit("model-exception",
                     "step(%r) raised %s: %s"
                     % (b, type(e).__name__, e), prefix + (b,))
                continue
            new_prefix = prefix + (b,)
            if len(new_prefix) >= max_depth:
                stats["truncated"] = True
                continue
            acts = observe(m, new_prefix)
            if acts is not None:
                descend(m, new_prefix, acts, child_sleep)
        finally:
            _close(m)
    if stack and stats["schedules"] >= max_schedules:
        stats["truncated"] = True

    return ExploreResult(name, stats["schedules"], stats["states"],
                         stats["deepest"], stats["truncated"],
                         findings)


def _close(m) -> None:
    try:
        m.close()
    except Exception:  # noqa: BLE001 - cleanup must never mask results
        pass


def replay(factory: Callable[[], ProtocolModel], trace) -> dict:
    """Run ONE schedule (a finding's compact trace or an action list)
    on a fresh model and report what it reproduces: every invariant
    violation observed along the way, plus the terminal deadlock
    verdict. Deterministic — the whole point of the compact trace."""
    actions = parse_trace(trace) if isinstance(trace, str) \
        else [tuple(a) for a in trace]
    m = factory()
    violations: List[Tuple[str, str]] = []
    steps = 0
    deadlock = False
    try:
        violations.extend(m.invariants())
        for a in actions:
            if violations:
                break  # the trace ends where the finding was emitted
            try:
                m.step(a)
            except Exception as e:  # noqa: BLE001
                violations.append((
                    "model-exception",
                    "step(%r) raised %s: %s"
                    % (a, type(e).__name__, e)))
                steps += 1
                break
            steps += 1
            violations.extend(m.invariants())
        if not violations and not m.actions() and not m.done():
            deadlock = True
    finally:
        _close(m)
    return {"steps": steps, "violations": violations,
            "deadlock": deadlock,
            "reproduced": bool(violations) or deadlock}


# -- the batch surface (CLI / artifact / bench block) --------------------

def run_protocol_checks(budget: Optional[int] = None,
                        models: Optional[Iterable[str]] = None,
                        max_depth: int = 96,
                        ) -> Tuple[List[Finding], dict]:
    """Explore every registered protocol model (proto_models.PROTOCOLS)
    at `budget` interleavings each. Returns (findings, report); the
    report is the artifacts/protocol_checks.json shape:

        {"budget", "errors", "ok", "models": {name: coverage+findings}}

    Emits one `protocol_check` telemetry event per model (schema-locked
    in tools/telemetry_schema.json)."""
    from . import proto_models  # heavy deps (serving/distributed): lazy
    from .findings import sort_findings

    budget = int(budget) if budget else 1000
    wanted = set(models) if models else None
    if wanted:
        unknown = wanted - set(proto_models.PROTOCOLS)
        if unknown:
            raise ValueError(
                "unknown protocol model(s) %s; have %s"
                % (sorted(unknown), sorted(proto_models.PROTOCOLS)))
    all_findings: List[Finding] = []
    per_model: Dict[str, dict] = {}
    for mname, factory in proto_models.PROTOCOLS.items():
        if wanted and mname not in wanted:
            continue
        res = explore(factory, max_schedules=budget,
                      max_depth=max_depth)
        all_findings.extend(res.findings)
        per_model[mname] = res.to_dict()
        try:
            from ..observability import registry

            registry().event("protocol_check", model=mname,
                             schedules=res.schedules,
                             states=res.states, errors=res.errors)
        except Exception:  # noqa: BLE001 - telemetry never gates
            pass
    errors = sum(d["errors"] for d in per_model.values())
    report = {
        "budget": budget,
        "errors": errors,
        "ok": errors == 0,
        "models": per_model,
    }
    return sort_findings(all_findings), report
