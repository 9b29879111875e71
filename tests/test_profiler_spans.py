"""`fluid.profiler.span`: the one way the program marks its own time.
A span lies in the jax profile's host plane (on the device trace's
clock), feeds the step-phase counter of its name with a lifetime total
that no reset clears, and feeds the legacy chrome buffer while
`profiler()` is on. `Executor.run` marks its phases with it, nested
under one `exe.step` a step."""
import glob
import os
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, profiler


@pytest.fixture(autouse=True)
def _clean_counters():
    profiler.reset_profiler()
    yield
    profiler.reset_profiler()


def test_span_feeds_counter_lifetime_total_and_legacy_buffer(tmp_path):
    life0 = profiler.phase_lifetime_s("bind")
    into = {}
    with profiler.span("exe.bind", into):
        time.sleep(0.002)
    assert profiler.step_phase_total("bind") >= 0.002
    assert into["bind"] == profiler.step_phase_total("bind")
    assert profiler._trace_events == []     # no profiler() on: no event
    # a reset of the window's counters leaves the lifetime total
    profiler.step_phase_summary(reset=True)
    assert profiler.step_phase_total("bind") == 0.0
    assert profiler.phase_lifetime_s("bind") - life0 >= 0.002
    # under profiler() the span lands in the legacy chrome buffer too
    with profiler.profiler(profile_path=str(tmp_path)):
        t0 = time.perf_counter()
        with profiler.span("exe.writeback"):
            pass
    (name, ts, dur, _tid), = profiler._trace_events
    assert name == "phase/writeback" and ts >= t0 * 1e6 and dur >= 0.0
    assert os.path.exists(tmp_path / "paddle_tpu_trace.json")


def test_span_survives_an_exception_in_its_body():
    """The exception passes through, the profile's annotation is closed
    (the next span works), and the failed interval is in no counter: a
    run that failed is not a step."""
    into = {}
    with pytest.raises(KeyError):
        with profiler.span("exe.dispatch", into):
            raise KeyError("boom")
    assert profiler.step_phase_total("dispatch") == 0.0 and into == {}
    assert profiler.step_phase_summary()["steps"] == 0
    with profiler.span("exe.dispatch", into):
        pass
    assert profiler.step_phase_summary()["steps"] == 1
    assert into["dispatch"] > 0.0


def test_summary_keeps_host_and_total_with_the_breakdown_beside():
    for name, dt in (("feed", 0.001), ("dispatch", 0.002),
                     ("sync", 0.003), ("host", 0.004)):
        profiler.record_step_phase(name, dt)
    before = profiler.step_phase_summary()
    assert "bind_ms" not in before and "writeback_ms" not in before
    with profiler.span("exe.bind"):
        pass
    profiler.record_step_phase("writeback", 0.0005)
    s = profiler.step_phase_summary()
    # bind and writeback are parts of `host`, shown beside it the way
    # the comm lanes are shown beside `comm`: never added to the total
    for k in ("steps", "feed_ms", "dispatch_ms", "sync_ms", "host_ms",
              "comm_ms", "total_ms"):
        assert s[k] == before[k], k
    assert s["total_ms"] == 10.0 and s["host_ms"] == 4.0
    assert s["writeback_ms"] == 0.5 and s["bind_ms"] >= 0.0


def test_move_step_phase_keeps_the_step_count():
    profiler.record_step_phase("dispatch", 0.5)
    profiler.move_step_phase("dispatch", "compile", 0.4)
    s = profiler.step_phase_summary()
    assert s["steps"] == 1
    assert s["dispatch_ms"] == pytest.approx(100.0)
    assert s["compile_ms"] == pytest.approx(400.0)


def _tiny_program():
    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    x = fluid.layers.data("x", shape=[4], dtype="float32")
    y = fluid.layers.data("y", shape=[1], dtype="float32")
    pred = fluid.layers.fc(x, size=1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(0.1).minimize(loss)
    return loss


def test_executor_steps_bind_and_write_back_inside_host():
    loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(framework.default_startup_program())
    feed = {"x": np.ones((8, 4), "float32"), "y": np.ones((8, 1), "float32")}
    exe.run(feed=feed, fetch_list=[loss])          # compiles
    profiler.step_phase_summary(reset=True)
    for _ in range(3):
        exe.run(feed=feed, fetch_list=[loss])
    s = profiler.step_phase_summary()
    assert s["steps"] == 3
    assert s["bind_ms"] > 0.0 and s["writeback_ms"] > 0.0
    assert s["bind_ms"] + s["writeback_ms"] <= s["host_ms"] + 1e-3
    assert s["total_ms"] == pytest.approx(
        s["feed_ms"] + s["dispatch_ms"] + s["comm_ms"] + s["sync_ms"]
        + s["host_ms"], abs=2e-3)
    assert "compile_ms" not in s


def test_two_steps_leave_nested_spans_in_the_profiles_host_plane(tmp_path):
    """Under `jax.profiler.start_trace` the executor's spans lie in the
    profile's host plane: `exe.step` once a step, and inside it
    `exe.feed`, `exe.bind`, `exe.dispatch`, `exe.writeback` in order."""
    import jax
    from jax.profiler import ProfileData

    loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(framework.default_startup_program())
    feed = {"x": np.ones((8, 4), "float32"), "y": np.ones((8, 1), "float32")}
    exe.run(feed=feed, fetch_list=[loss])          # compiles
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name,
                          dict(e.stats))
                         for e in line.events if e.name.startswith("exe."))
    spans.sort()
    steps = [s for s in spans if s[2] == "exe.step"]
    assert len(steps) == 2
    nums = [int(s[3]["step_num"]) for s in steps]
    assert nums[1] == nums[0] + 1
    for start, end, _, _ in steps:
        inside = [s for s in spans
                  if s[2] != "exe.step" and start <= s[0] and s[1] <= end]
        names = [s[2] for s in inside]
        assert names == ["exe.feed", "exe.bind", "exe.bind", "exe.feed",
                         "exe.dispatch", "exe.writeback"], names
        # in order and disjoint: each ends before the next starts
        for a, b in zip(inside, inside[1:]):
            assert a[1] <= b[0]
        dispatch, = [s for s in inside if s[2] == "exe.dispatch"]
        assert int(dispatch[3]["fresh"]) == 0
    # every span of the two steps lies inside one of them
    assert all(any(st[0] <= s[0] and s[1] <= st[1] for st in steps)
               for s in spans)
