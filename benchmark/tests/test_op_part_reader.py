"""CPU tests of the reader of the fold's crossed keys (PR 35,
`readers/op_part_ms.py`), on a fixture cut by
`tools/cut_regions_fixture.py` from that PR's traced run of
`qwen3-next-ep16-longdoc` on the chip: one step's `XLA Ops` with their
scope paths, the parts `ops/hybrid_ops.py` names among them.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_op_part_reader.py -q

Tier-1 does not collect them. The numbers asserted are the recorded
step's, read on the chip; a CPU run gives none."""
import gzip
import inspect
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
from benchmark.readers import op_part_ms, op_type_share  # noqa: E402
from test_tracing_readers import write_sidecar  # noqa: E402

FIXTURE = os.path.join(harness.BENCH_DIR, "fixtures",
                       "parts-qwen3-next-ep16-longdoc.json.gz")
PARENT = os.path.join(harness.BENCH_DIR, "fixtures",
                      "regions-bert-base-s128.json.gz")
TRACED = {"trace": {"steps": 1}}
GDR = "gated_delta_rule"
GDR_PARTS = ("inverse", "local", "walk", "groups")
REGIONS = ("forward", "recompute", "backward")
NEW = ["gdn_inverse_ms", "gdn_local_ms", "gdn_walk_ms", "gdn_groups_ms",
       "ssd_scan_bwd_ms", "moe_experts_move_ms", "attn_backward_ms"]


def read(op_type, trace_dir=FIXTURE, ctx=TRACED, **args):
    return op_part_ms.read(ctx, op_type, trace_dir=trace_dir, **args)


def fixture_events():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)["traceEvents"]


# -- the entries ---------------------------------------------------------------

def test_the_seven_entries_are_appended_and_each_names_the_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    names = [m["name"] for m in bm["per_layer"]]
    # appended together, in the issue's order (a later PR appends after)
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW
    entries = bm["per_layer"][at:at + len(NEW)]
    cells = {w["name"] for w in bm["workloads"]}
    takes = set(inspect.signature(op_part_ms.read).parameters)
    for entry in entries:
        assert {k: entry[k] for k in ("unit", "better", "source", "layer",
                                      "moves")} == {
            "unit": "ms/step", "better": "lower", "source": "device_trace",
            "layer": "kernels", "moves": "train_tokens_per_s"}
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert entry["workloads"] and set(entry["workloads"]) <= cells
        spec = harness.load_json("metrics", entry["name"] + ".json")
        assert spec["reader"] == "op_part_ms"
        assert set(spec["args"]) <= takes - {"ctx", "trace_dir"}
        assert "op_type" in spec["args"]
    # what the fixture's cell lists reads a number there
    for entry in entries:
        if "qwen3-next-ep16-longdoc" in entry["workloads"]:
            spec = harness.load_json("metrics", entry["name"] + ".json")
            got = read(**spec["args"])
            assert got is not None and math.isfinite(got) and got > 0, entry


# -- a reading by part, by region and by both ----------------------------------

def test_the_parts_and_the_unparted_rest_sum_to_the_op_type():
    t = sidecar.fold(FIXTURE)
    assert t["steps"] == 1 and t["devices"] == 1
    whole = read(GDR)
    assert whole * 1e3 == pytest.approx(t["by_op_type"][GDR], rel=1e-9)
    # the yardstick `gdn_scan_share_pct` reads from outside the parts
    assert whole * 1e3 == pytest.approx(
        op_type_share.op_type_us(TRACED, GDR, FIXTURE)[1], rel=1e-9)
    by_part = {p: read(GDR, parts=[p]) for p in GDR_PARTS}
    assert all(v is not None and v > 0 for v in by_part.values())
    unparted = sum(t["by_op_part"][GDR].get("", {}).values()) / 1e3
    assert sum(by_part.values()) + unparted == pytest.approx(whole, rel=1e-9)
    assert unparted < 0.1 * whole
    # several parts in one reading are their sum
    assert read(GDR, parts=list(GDR_PARTS)) == pytest.approx(
        sum(by_part.values()), rel=1e-9)
    # the recorded step: the inverse is the largest part, the kernels'
    # walk among the smallest
    assert by_part["inverse"] > by_part["local"] > by_part["walk"]


def test_a_reading_by_region_and_by_both():
    whole = read(GDR)
    by_region = {r: read(GDR, region=r) for r in REGIONS}
    assert sum(by_region.values()) == pytest.approx(whole, rel=1e-9)
    assert by_region["backward"] > by_region["forward"] > 0
    assert read(GDR, region="update") is None
    # a part in a region; over the regions it is the part
    inverse = {r: read(GDR, parts=["inverse"], region=r) for r in REGIONS}
    assert sum(inverse.values()) == pytest.approx(
        read(GDR, parts=["inverse"]), rel=1e-9)
    # the ten products of the inverse run in each of the three passes
    assert all(v > 0.2 * sum(inverse.values()) for v in inverse.values())
    # an op type with no part is read by region alone
    attention = read("scaled_dot_product_attention", region="backward")
    assert 0 < attention < read("scaled_dot_product_attention")
    assert read("scaled_dot_product_attention", parts=["inverse"]) is None


# -- the five ways of reading nothing --------------------------------------------

def test_an_untraced_run_and_a_profile_without_a_sidecar_read_nothing(
        tmp_path, capsys):
    assert read(GDR, ctx={"trace": None}) is None
    assert read(GDR, trace_dir=str(tmp_path)) is None
    assert "no *.trace.json.gz" in capsys.readouterr().err


def test_a_fold_without_the_crossed_keys_reads_nothing(monkeypatch):
    """A parent commit's `time_attribution` folds regions and op types
    and knows no parts: the reader says nothing and does not raise."""
    from paddle_tpu.observability import attribution

    new = attribution.time_attribution

    def old(events):
        t = new(events)
        return {k: v for k, v in t.items()
                if k not in ("by_op_type_region", "by_op_part")}

    monkeypatch.setattr(attribution, "time_attribution", old)
    sidecar._fold.cache_clear()
    try:
        assert read(GDR) is None
        assert read(GDR, parts=["inverse"]) is None
        assert read("scaled_dot_product_attention",
                    region="backward") is None
    finally:
        sidecar._fold.cache_clear()


def test_a_fold_that_lost_its_names_reads_nothing(tmp_path):
    events = fixture_events()
    ops = sorted((e for e in events if e["ph"] == "X"
                  and "tf_op" in e.get("args", {})),
                 key=lambda e: -e["dur"])
    total = sum(e["dur"] for e in ops)
    stripped = 0.0
    for e in ops:
        if stripped > 0.3 * total:
            break
        if "while" in e["name"]:
            continue            # a loop's own time is not its duration
        stripped += e["dur"]
        del e["args"]["tf_op"]
    # under 80 % coverage, as `device_region` (which says so) holds it
    assert read(GDR, trace_dir=write_sidecar(tmp_path, events)) is None


def test_an_op_type_that_ran_nothing_reads_nothing():
    assert read("ssd_chunk_scan") is None
    assert read("ssd_chunk_scan", region="backward") is None
    assert read("ssd_chunk_scan", parts=["local"]) is None


def test_parts_are_not_read_where_under_nine_tenths_carry_one(tmp_path,
                                                              capsys):
    """A trace of a program whose op names no part (the recorded step
    of `bert-base-s128`, PR 25's fixture), and the fixture with every
    stamp taken off the inverse's operations, half of the op type's
    time: by region the op type is read still."""
    assert read("scaled_dot_product_attention", parts=["inverse"],
                trace_dir=PARENT) is None
    assert "under a pt[...] part" in capsys.readouterr().err
    assert read("scaled_dot_product_attention", region="backward",
                trace_dir=PARENT) > 0
    events = fixture_events()
    for e in events:
        path = e.get("args", {}).get("tf_op", "")
        if "pt[inverse]" in path:
            # every part it lies in: under `groups`, `local` or `walk`
            e["args"]["tf_op"] = path.replace("pt[", "[")
    cut = write_sidecar(tmp_path, events)
    assert read(GDR, parts=["walk"], trace_dir=cut) is None
    assert read(GDR, trace_dir=cut) == pytest.approx(read(GDR), rel=1e-9)
    assert read(GDR, region="backward", trace_dir=cut) == pytest.approx(
        read(GDR, region="backward"), rel=1e-9)


def test_the_folds_table_is_logged_once_a_profile(capsys):
    op_part_ms._logged.clear()
    read(GDR)
    read(GDR, parts=["walk"])
    said = capsys.readouterr().err
    assert said.count("device time by fluid op type and region") == 1
    assert "pt[inverse]" in said and "under a part" in said
