"""CPU tests of the readers of the program's own names (PR 25), on a
fixture cut from a chip trace of `bert-base-s128` by
`tools/cut_regions_fixture.py`: one step's `XLA Ops` with their scope
paths, its `XLA Modules` and `Steps` events, and the host's `exe.*` and
`bench.*` spans around it.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_tracing_readers.py -q

Tier-1 does not collect them. The numbers asserted are the recorded
step's, read on the chip; a CPU run gives none."""
import gzip
import json
import math
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import harness, sidecar  # noqa: E402
from benchmark.readers import (compile_total, device_region,  # noqa: E402
                               idle_by_span, phase_breakdown)

FIXTURE = os.path.join(harness.BENCH_DIR, "fixtures",
                       "regions-bert-base-s128.json.gz")
TRACED = {"trace": {"steps": 1}}
REGIONS = ("forward", "recompute", "backward", "update")
EXE_SPANS = ["exe.feed", "exe.bind", "exe.compile", "exe.dispatch",
             "exe.writeback"]
NEW = ["device_forward_ms.tok", "device_forward_ms.img",
       "device_recompute_ms.tok", "device_backward_ms.tok",
       "device_backward_ms.img", "device_update_ms.tok",
       "device_update_ms.img", "exe_state_ms.tok", "exe_state_ms.img",
       "idle_under_executor_pct.tok", "idle_under_executor_pct.img",
       "setup_compile_s"]


def fixture_events():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)["traceEvents"]


def write_sidecar(tmp_path, events, name="cut.trace.json.gz"):
    path = str(tmp_path / name)
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)
    return path


def metric(name):
    return harness.load_json("metrics", name + ".json")


# -- the entries ---------------------------------------------------------------

def test_the_twelve_entries_are_appended_and_each_is_a_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    names = [m["name"] for m in bm["per_layer"]]
    assert names[-len(NEW):] == NEW
    by_name = {m["name"]: m for m in bm["per_layer"]}
    for name in NEW:
        entry, spec = by_name[name], metric(name)
        assert entry["workloads"], name
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "readers", spec["reader"] + ".py"))
        tok = "bert-base-s128" in entry["workloads"]
        if name.endswith(".tok"):
            assert entry["workloads"] == ["bert-base-s128"]
            assert entry["moves"] == "train_tokens_per_s"
        elif name.endswith(".img"):
            assert entry["workloads"] == ["resnet50-b256"]
            assert entry["moves"] == "train_images_per_s"
        else:
            assert tok and entry["moves"] == "setup_s"
    # a trace-read entry says so; the two counters say `program_counter`
    assert {by_name[n]["source"] for n in NEW if n.startswith(
        ("device_", "idle_"))} == {"device_trace"}
    assert {by_name[n]["source"] for n in NEW if n.startswith(
        ("exe_", "setup_"))} == {"program_counter"}


# -- device_region -------------------------------------------------------------

def test_regions_sum_to_the_device_self_time_less_the_unattributed_part():
    t = sidecar.fold(FIXTURE)
    assert t["steps"] == 1 and t["devices"] == 1
    got = {r: device_region.read(TRACED, r, trace_dir=FIXTURE)
           for r in REGIONS}
    assert all(math.isfinite(v) and v > 0 for v in got.values())
    # nothing is counted twice and nothing is lost: with no collective
    # on one chip, the four regions are all the time that has a path
    assert sum(got.values()) * 1e3 == pytest.approx(
        t["total_us"] - t["unattributed_us"], rel=1e-9)
    # the recorded step of bert-base-s128: the four regions are nearly
    # all of the device's busy time, and the recompute is a forward again
    assert sum(got.values()) * 1e3 >= 0.95 * t["total_us"]
    assert 150.0 < got["recompute"] < 220.0
    assert 0.85 < got["recompute"] / got["forward"] < 1.0
    assert got["backward"] > got["forward"] > got["update"]
    # by the files the cell names
    for name in NEW[:7]:
        spec = metric(name)
        assert spec["reader"] == "device_region"
        assert device_region.read(TRACED, trace_dir=FIXTURE, **spec["args"]) \
            == got[spec["args"]["region"]]


def test_a_fold_that_lost_its_names_reports_nothing(tmp_path, capsys):
    events = fixture_events()
    ops = [e for e in events if e["ph"] == "X" and "tf_op" in e.get(
        "args", {})]
    # strip the scope path from operations until a quarter of the
    # device's time has none: under 80 % coverage the reader is silent
    total = sum(e["dur"] for e in ops)
    lost = 0.0
    for e in sorted(ops, key=lambda e: -e["dur"]):
        if "while" in e["name"]:
            continue            # a loop's duration is its body's
        del e["args"]["tf_op"]
        lost += e["dur"]
        if lost > 0.3 * total:
            break
    path = write_sidecar(tmp_path, events)
    assert device_region.read(TRACED, "forward", trace_dir=path) is None
    assert "carries a scope path" in capsys.readouterr().err
    # no profile, no sidecar, no trace: nothing, and no exception
    assert device_region.read(TRACED, "forward",
                              trace_dir=str(tmp_path / "none")) is None
    assert device_region.read({"trace": None}, "forward",
                              trace_dir=FIXTURE) is None


def test_a_program_whose_fold_knows_no_regions_reports_nothing(
        tmp_path, monkeypatch):
    """The parent commit's `time_attribution` returns no `by_region`:
    the reader returns nothing there and does not raise."""
    from paddle_tpu.observability import attribution

    monkeypatch.setattr(
        attribution, "time_attribution",
        lambda events: {"by_op": {}, "by_layer": {}, "by_bucket": {},
                        "matched_us": 0.0, "unmatched_us": 1.0,
                        "total_us": 1.0})
    path = write_sidecar(tmp_path, fixture_events())
    assert device_region.read(TRACED, "backward", trace_dir=path) is None


# -- idle_by_span --------------------------------------------------------------

def hand_made(host):
    """Two device gaps, [10, 14) and [30, 31), in a span of 40."""
    meta = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
         "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 701, "name": "process_name",
         "args": {"name": "/host:CPU"}},
    ]
    ops = [("while.1", 0.0, 10.0), ("fusion.1", 0.0, 4.0),
           ("fusion.2", 14.0, 16.0), ("fusion.3", 31.0, 9.0)]
    evs = [{"ph": "X", "pid": 3, "tid": 3, "ts": ts, "dur": dur,
            "name": name} for name, ts, dur in ops]
    # a module's event covers the gaps and is not an operation
    evs.append({"ph": "X", "pid": 3, "tid": 2, "ts": 0.0, "dur": 40.0,
                "name": "jit_fn(1)"})
    evs += [{"ph": "X", "pid": 701, "tid": 9, "ts": ts, "dur": dur,
             "name": name} for name, ts, dur in host]
    return meta + evs


def test_a_gap_under_the_executors_span_counts_and_one_under_the_benchmarks_does_not(
        tmp_path, capsys):
    path = write_sidecar(tmp_path, hand_made([
        ("exe.step", 8.0, 8.0), ("exe.dispatch", 9.0, 4.0),
        ("exe.writeback", 12.5, 1.0), ("bench.read_loss", 29.0, 5.0)]))
    # of the gap [10, 14): [10, 13) under exe.dispatch and [12.5, 13.5)
    # under exe.writeback, their union [10, 13.5): 3.5 of a span of 40;
    # the gap [30, 31) lies under bench.read_loss and is not counted
    assert idle_by_span.read(TRACED, EXE_SPANS, trace_dir=path) == \
        pytest.approx(100.0 * 3.5 / 40.0)
    err = capsys.readouterr().err
    assert "2 idle gaps, 5.0 us of a 40.0 us span" in err
    assert "bench.read_loss 1.0 us" in err and "exe.dispatch 3.0 us" in err
    assert idle_by_span.read(TRACED, ["bench.read_loss"], trace_dir=path) \
        == pytest.approx(100.0 * 1.0 / 40.0)
    # a program without the spans leaves none in the profile: nothing
    bare = write_sidecar(tmp_path, hand_made(
        [("bench.read_loss", 29.0, 5.0)]), name="bare.trace.json.gz")
    assert idle_by_span.read(TRACED, EXE_SPANS, trace_dir=bare) is None
    assert idle_by_span.read({"trace": None}, EXE_SPANS,
                             trace_dir=path) is None


def test_the_recorded_step_idles_under_no_executor_span():
    spec = metric("idle_under_executor_pct.tok")
    assert spec == metric("idle_under_executor_pct.img")
    assert spec["reader"] == "idle_by_span" and spec["args"]["spans"] == \
        EXE_SPANS
    value = idle_by_span.read(TRACED, trace_dir=FIXTURE, **spec["args"])
    gaps, span, host = idle_by_span.gaps_and_spans(fixture_events())
    idle_pct = 100.0 * sum(e - s for s, e in gaps) / span
    # the chip is busy all through the recorded step, so what idles
    # under the executor is at most the whole idle share, itself tiny
    assert 0.0 <= value <= idle_pct < 0.05
    # the profile holds the executor's step with its children
    assert {"exe.step", "exe.feed", "exe.bind", "exe.dispatch",
            "exe.writeback"} <= set(host)
    (s0, e0), = [iv for iv in host["exe.step"]
                 if any(iv[0] <= d[0] and d[1] <= iv[1]
                        for d in host["exe.dispatch"])][:1]
    inside = sorted((iv[0], name) for name in EXE_SPANS
                    for iv in host.get(name, ())
                    if s0 <= iv[0] and iv[1] <= e0)
    assert [name for _, name in inside] == [
        "exe.feed", "exe.bind", "exe.bind", "exe.feed", "exe.dispatch",
        "exe.writeback"]


# -- the two counters ----------------------------------------------------------

def test_exe_state_reads_the_breakdown_beside_host_and_nothing_without_it():
    spec = metric("exe_state_ms.tok")
    assert spec == metric("exe_state_ms.img")
    assert spec["reader"] == "phase_breakdown"
    phases = {"steps": 17, "feed_ms": 2.5, "dispatch_ms": 1.2,
              "host_ms": 0.5, "sync_ms": 640.0, "total_ms": 644.2,
              "bind_ms": 0.125, "writeback_ms": 0.25}
    assert phase_breakdown.read({"phases": phases}, **spec["args"]) == 0.375
    # the parent's summary has neither lane; an empty window no steps
    parent = {k: v for k, v in phases.items()
              if k not in ("bind_ms", "writeback_ms")}
    assert phase_breakdown.read({"phases": parent}, **spec["args"]) is None
    assert phase_breakdown.read({"phases": dict(phases, steps=0)},
                                **spec["args"]) is None


def test_setup_compile_reads_the_lifetime_total_and_nothing_without_it(
        monkeypatch):
    from paddle_tpu.fluid import profiler

    assert metric("setup_compile_s") == {"reader": "compile_total",
                                         "args": {}}
    before = compile_total.read({})
    profiler.record_step_phase("compile", 1.5)
    profiler.step_phase_summary(reset=True)     # a window's reset
    assert compile_total.read({}) == pytest.approx(before + 1.5)
    monkeypatch.delattr(profiler, "phase_lifetime_s")
    assert compile_total.read({}) is None
