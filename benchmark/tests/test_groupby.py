"""GroupBy in the yardstick (ISSUE 39) and the mix ``groupby``, which no
cell sends yet (PERF.md, Open questions): (a) the PQL of each form, and
the existing decks' PQL unchanged byte for byte; (b) the reference
against a count by hand; (c) the reference against the program's CPU
and device paths at 2 shards, every template, a zero-count group, a
limit, a null value, in a process of their own
(``groupby_program.py``); (d) the bytes a panel needs, by hand; (e) the
manifest. Whole rehearsed runs of the mix (sound, planted wrong answer)
are ``test_served.py``'s, the control ``test_yardstick.py``'s."""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import datagen, roofline, run, traffic
from benchmark.reference import Codes, IntValues, PackedRows, Reference, Undecidable
from benchmark.tests import groupby_program

ROOT = run.ROOT
SHARD_WIDTH = 1 << 20
DENSE = SHARD_WIDTH // 8


def _file(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


TAXI, MIX, MONTH = groupby_program.TAXI, groupby_program.MIX, groupby_program.MONTH
Q1, Q2, Q3, Q4 = groupby_program.Q1, groupby_program.Q2, groupby_program.Q3, groupby_program.Q4


# -- (a) PQL ------------------------------------------------------------------


def test_each_groupby_form_renders_children_first_and_named_arguments_last():
    dims = [["Rows", "a"], ["Rows", "b", [3, 0, 7]]]
    assert traffic.pql(["GroupBy", dims[:1], None, {}]) == "GroupBy(Rows(a))"
    assert traffic.pql(["GroupBy", dims, None, {}]) == "GroupBy(Rows(a), Rows(b, ids=[3, 0, 7]))"
    assert traffic.pql(["GroupBy", dims, ["Intersect", ["Row", "c", 1], ["Range", "v", ">", 4]], {}]) == (
        "GroupBy(Rows(a), Rows(b, ids=[3, 0, 7]), Intersect(Row(c=1), Range(v > 4)))")
    assert traffic.pql(["GroupBy", dims[:1], ["Row", "c", 2], {"sum": "v", "limit": 5}]) == (
        "GroupBy(Rows(a), Row(c=2), Sum(field=v), limit=5)")
    assert traffic.pql(["GroupBy", dims[1:], None, {"limit": 2}]) == "GroupBy(Rows(b, ids=[3, 0, 7]), limit=2)"
    assert traffic.pql(Q4) == (
        "GroupBy(Rows(passenger_count), Rows(pickup_year), Rows(dist_miles), Row(pickup_month=4))")


# sha256 of [the pool's PQL, the deck, the template pairs], taken at PR 38's tree
DECKS = {
    "tall64.topn": "407b3f5e00f4b87a120b91ed5725685854d445a8231e10097d8baa7f7adc2454",
    "taxi96.dashboard": "ed4425da5aac29dd8eaf8173e0294bb26360af9e6c73be7f26e920d176736534",
    "tall128x4.topn": "407b3f5e00f4b87a120b91ed5725685854d445a8231e10097d8baa7f7adc2454",
    "ssb10.flight1": "f6b3ef6bac7edade54fe0f900343d7e8e0d1acea819543088d2e829f8253534a",
    "ssb20x4.flight1": "f6b3ef6bac7edade54fe0f900343d7e8e0d1acea819543088d2e829f8253534a",
}


@pytest.mark.parametrize("cell", sorted(DECKS))
def test_the_existing_cells_send_the_same_pql_byte_for_byte(cell):
    manifest = _file("BENCHMARK.json")
    w, entry = run.find_cell(manifest, cell)
    cfg = _file(entry["file"])
    mix = traffic.load(os.path.join(ROOT, "benchmark", "traffic", f"{w['traffic']}.json"))
    requests = traffic.pool(cfg, mix)
    blob = json.dumps([[traffic.pql(c) for c, _ in requests], traffic.deck(requests, mix["deck"]),
                       traffic.pairs(cfg, mix)])
    assert hashlib.sha256(blob.encode()).hexdigest() == DECKS[cell]


def test_the_traffic_is_the_four_panels_over_twelve_months():
    assert (MIX["loop"], MIX["clients"], MIX["cache"], MIX["deck"]) == ("closed", 2, False, 480)
    assert [t["weight"] for t in MIX["mix"]] == [0.25] * 4
    assert all(t["draw"] == {"m": {"field": "pickup_month", "from": "rows", "dist": "uniform"}} for t in MIX["mix"])
    assert [c[3] for c in (Q1, Q2, Q3, Q4)] == [{}, {"sum": "total_amount"}, {}, {}]
    ks = [int(np.prod([len(d[2]) if len(d) > 2 else datagen.field_of(TAXI, d[1])["rows"] for d in c[1]]))
          for c in (Q1, Q2, Q3, Q4)]
    assert ks == [2, 10, 80, 5120]  # upstream's panels whole
    requests = traffic.pool(TAXI, MIX)
    assert len(requests) == 48 and sum(p for _, p in requests) == pytest.approx(1.0)
    deck = traffic.deck(requests, MIX["deck"])
    assert sorted(deck) == sorted(list(range(48)) * 10)  # ten of each


# -- (b) the reference by hand -------------------------------------------------


def _tiny(seed=5, shards=2, width=256):
    """Three code fields and an int field with holes, ``width`` columns a shard."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 3, (shards, width)).astype(np.uint8)
    b = rng.integers(0, 3, (shards, width)).astype(np.uint8)
    b[a == 2] = 3  # row 3 of b beside row 2 of a, and only there: five groups are empty
    c = rng.integers(0, 2, (shards, width)).astype(np.uint8)
    v = rng.integers(-50, 1000, (shards, width)).astype(np.int32)
    held = rng.random((shards, width)) < 0.7
    return a, b, c, v, held


def _by_hand(a, b, c, v, held, ids_a, ids_b, want_c):
    out = []
    for ra in ids_a:
        for rb in ids_b:
            cols = [(s, w) for s in range(a.shape[0]) for w in range(a.shape[1])
                    if a[s, w] == ra and b[s, w] == rb and (want_c is None or c[s, w] == want_c)]
            if cols:
                out.append({"group": [{"field": "a", "rowID": ra}, {"field": "b", "rowID": rb}],
                            "count": len(cols), "sum": sum(int(v[s, w]) for s, w in cols if held[s, w])})
    return out


def test_the_reference_equals_a_count_by_hand():
    a, b, c, v, held = _tiny()
    ref = Reference({"a": Codes(a, 3), "b": Codes(b, 4), "c": Codes(c, 2), "v": IntValues(v, held)})
    dims = [["Rows", "a"], ["Rows", "b"]]
    want = _by_hand(a, b, c, v, held, [0, 1, 2], [0, 1, 2, 3], 1)
    assert len(want) == 7
    assert ref.answer(["GroupBy", dims, ["Row", "c", 1], {"sum": "v"}]) == want
    assert ref.answer(["GroupBy", dims, ["Row", "c", 1], {"sum": "v", "limit": 4}]) == want[:4]
    no_sum = [{k: g[k] for k in ("group", "count")} for g in _by_hand(a, b, c, v, held, [0, 1, 2], [0, 1, 2, 3], None)]
    assert ref.answer(["GroupBy", dims, None, {}]) == no_sum
    # explicit ids: their order, a row the field does not have counted as empty
    ids = [["Rows", "a", [2, 0]], ["Rows", "b", [3, 9, 1]]]
    want = _by_hand(a, b, c, v, held, [2, 0], [3, 9, 1], None)
    assert ref.answer(["GroupBy", ids, None, {"sum": "v"}]) == want
    assert [g["group"][0]["rowID"] for g in want] == [2] * (len(want) - 1) + [0]


def test_a_dimension_of_packed_rows_is_undecidable_and_one_of_values_refused():
    """No cell groups by packed rows yet: the reference says it cannot
    decide rather than answer by a path that no run reaches."""
    rows = PackedRows(np.zeros((1, 2, 4), np.uint64))
    ref = Reference({"t": rows, "c": Codes(np.zeros((1, 256), np.uint8), 1)})
    with pytest.raises(Undecidable):
        ref.answer(["GroupBy", [["Rows", "t"]], None, {}])
    with pytest.raises(Undecidable):
        ref.answer(["GroupBy", [["Rows", "c"], ["Rows", "t", [0, 5]]], None, {}])
    with pytest.raises(ValueError):
        Reference({"v": IntValues(np.zeros((1, 64), np.int32), None)}).answer(["GroupBy", [["Rows", "v"]], None, {}])


# -- (c) the program against the reference -------------------------------------


@pytest.fixture(scope="module")
def program(tmp_path_factory):
    """The program's answers in a child: an executor imports JAX, and a
    process that has imported it may start no server (``test_served.py``)."""
    tmp = tmp_path_factory.mktemp("groupby_program")
    out = subprocess.run([sys.executable, "-m", "benchmark.tests.groupby_program", str(tmp)], cwd=ROOT,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.splitlines()[-1])


@pytest.mark.parametrize("path", sorted(groupby_program.PATHS))
@pytest.mark.parametrize("case", sorted(groupby_program.CASES))
def test_the_program_answers_as_the_reference(program, path, case):
    got = program["taxi"][path][case]
    want = got["want"]
    assert got["got"] == want, traffic.pql(groupby_program.CASES[case])
    assert got["launched"] == (path != "never")  # one launch a panel, or the CPU's legs
    if case == "Q4":
        assert 0 < len(want) < 5120  # the rare passenger counts' and far buckets' groups are empty, and dropped
    if case == "ids_reordered_and_a_row_with_no_bits":
        assert [g["group"][0]["rowID"] for g in want][::2] == [9, 1] and len(want) == 4
    if case == "limit":
        assert len(want) == 7 and program["groups_without_limit"] > 7
    if groupby_program.CASES[case][3].get("sum"):
        assert all(g["sum"] > 0 for g in want)


def test_a_groups_count_holds_its_columns_without_a_value_and_its_sum_only_the_values(program):
    """``count`` is the group's columns, ``sum`` totals the columns that
    hold a value (``executor/analytics.py``): pinned on the CPU path."""
    holes = program["holes"]
    want = holes["want"]
    assert [g["count"] for g in want] == holes["columns"]
    assert [g["sum"] for g in want] == [h["value"] for h in holes["held"]]
    assert all(h["count"] < 0.6 * g["count"] for g, h in zip(want, holes["held"]))  # about half hold a value
    assert holes["got"] == want


# -- (d) the bytes a panel needs, by hand ---------------------------------------


def test_bytes_needed_of_the_four_panels_equals_the_hand_worked_figures():
    shards = TAXI["shards"]
    assert shards == 96
    dense = shards * DENSE
    # a month's row holds 1/12 of a shard's bits: 4 B a bit is over 128 KiB, dense
    assert roofline.bytes_needed(TAXI, ["Count", MONTH]) == dense
    # Q1: both cab types dense (0.9, 0.1) + the month
    assert roofline.bytes_needed(TAXI, Q1) == 3 * dense
    # Q2: passenger counts 1, 2, 3, 5 dense, the other six (0.06 of the bits) at 4 B a bit;
    # total_amount's 16 value planes and its existence plane dense; the month
    sparse = shards * 4 * 0.06 * SHARD_WIDTH
    assert roofline.bytes_needed(TAXI, Q2) == pytest.approx((4 + 17 + 1) * dense + sparse)
    # Q3: the same passenger rows, the 8 years dense, the month; the 80 group masks are not read
    assert roofline.bytes_needed(TAXI, Q3) == pytest.approx((4 + 8 + 1) * dense + sparse)
    # Q4: a dimension without ids reads every row of its field: dist_miles' 64, most of them sparse
    miles = roofline._all_rows(TAXI, "dist_miles")
    assert 10 * dense < miles < 11 * dense
    assert roofline.bytes_needed(TAXI, Q4) == pytest.approx((4 + 8 + 1) * dense + sparse + miles)
    # listed ids read those rows alone: the six buckets under 6 miles (shares 0.29 down to 0.053) are dense
    six = ["GroupBy", [["Rows", "dist_miles", [0, 1, 2, 3, 4, 5]]], None, {}]
    assert roofline.bytes_needed(TAXI, six) == pytest.approx(6 * dense)


# -- (e) the manifest ---------------------------------------------------------


def test_no_cell_sends_the_mix_yet_and_its_metric_reader_waits_for_one():
    """``taxi96.groupby`` waits for the program's fusion admission to
    fuse a 5,120-group panel without flushing the stager (PERF.md): the
    cell and its ``per_layer`` entry come together, in a later PR."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert "groupby" not in {w["traffic"] for w in manifest["workloads"]}
    name = "executor.groupby_launches_per_query"
    assert name not in {m["name"] for m in manifest["per_layer"]}
    spec = run.layer_metrics.load(name)
    assert spec["numerator"] == [{"metric": "fusion.groupby_launches", "labels": {}}] and spec["per"] == "request"
