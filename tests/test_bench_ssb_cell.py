"""The deployment ``ssb10`` and its cell ``ssb10.flight1`` (ISSUE 33):
Star Schema Benchmark SF=10, flight 1, as Pilosa fields. (a) the data
kind: the six fields of a shard describe the same line items; (b) the
plain reference against flight 1 computed the way the SSB paper writes
it, on the raw columns; (c) the program against the reference, device
and CPU path, lone and fused; (d) the cell through the harness against
a server child; (e) what one request counts and books; (f) the bytes a
request needs, by hand; (g) the manifest."""

import json
import os
import sys
import time

import numpy as np
import pytest

from benchmark import datagen, roofline, run, traffic
from benchmark.kinds import ssb_lineorder
from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import executor as executor_mod
from pilosa_tpu.executor import fusion
from pilosa_tpu.utils import metrics, profiler, trace

ROOT = run.ROOT
CELL = "ssb10.flight1"
SHARD_WIDTH = 1 << 20
DENSE = SHARD_WIDTH // 8


def _file(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CONFIG = _file("benchmark", "configs", "ssb10.json")
MIX = traffic.load(os.path.join(ROOT, "benchmark", "traffic", "flight1.json"))
SMALL = {**CONFIG, "shards": 2}
SEEDS = [3300000011, 2**31 + 3333]

REVENUE = "lo_revenue_computed"


def _q11(year_row, discount=("><", 1, 3), quantity=("<", 25)):
    return ["Sum", REVENUE, ["Intersect", ["Row", "d_year", year_row],
                             ["Range", "lo_discount", *discount], ["Range", "lo_quantity", *quantity]]]


def _q12(month_row):
    return ["Sum", REVENUE, ["Intersect", ["Row", "d_yearmonthnum", month_row],
                             ["Range", "lo_discount", "><", 4, 6], ["Range", "lo_quantity", "><", 26, 35]]]


def _q13(week_row, year_row):
    return ["Sum", REVENUE, ["Intersect", ["Row", "d_weeknuminyear", week_row], ["Row", "d_year", year_row],
                             ["Range", "lo_discount", "><", 5, 7], ["Range", "lo_quantity", "><", 26, 35]]]


# the paper's constants: d_year = 1993; d_yearmonthnum = 199401; week 6 of 1994
PAPER = {"Q1.1": _q11(1993 - 1992), "Q1.2": _q12(12 * (1994 - 1992) + 1 - 1), "Q1.3": _q13(6 - 1, 1994 - 1992)}


def _generated(seed):
    return [datagen.generate_shard(SMALL, seed, s) for s in range(SMALL["shards"])]


# -- (a) the kind -------------------------------------------------------------


@pytest.mark.parametrize("shard", range(SMALL["shards"]))
@pytest.mark.parametrize("seed", SEEDS)
def test_the_six_fields_of_a_shard_describe_the_same_line_items(seed, shard):
    data = dict(zip((f["name"] for f in SMALL["fields"]), datagen.generate_shard(SMALL, seed, shard)))
    raw = ssb_lineorder.lineorder(seed, shard)
    year, month, week = (data[n]["codes"].astype(int) for n in ("d_year", "d_yearmonthnum", "d_weeknuminyear"))
    assert all(a.shape == (SHARD_WIDTH,) for a in (year, month, week))
    # a month's row implies its year's; the raw date says the same
    assert np.array_equal(month // 12, year)
    assert np.array_equal(raw["d_year"], 1992 + year)
    assert np.array_equal(raw["d_yearmonthnum"], (1992 + year) * 100 + month % 12 + 1)
    assert np.array_equal(raw["lo_orderdate"] // 100, raw["d_yearmonthnum"])
    assert np.array_equal(raw["d_weeknuminyear"], week + 1)
    # the stored product is price x discount of the same item
    discount, quantity, revenue = (data[n]["vals"] for n in ("lo_discount", "lo_quantity", REVENUE))
    assert np.array_equal(raw["lo_discount"], discount) and np.array_equal(raw["lo_quantity"], quantity)
    assert np.array_equal(revenue.astype(np.int64), raw["lo_extendedprice"].astype(np.int64) * discount)
    assert raw["lo_extendedprice"].min() >= quantity.min() * 90_000
    # every range held, and reached
    for f in SMALL["fields"]:
        d = data[f["name"]]
        if "codes" in d:
            assert d["codes"].dtype == np.uint8 and d["codes"].max() < f["rows"]
        else:
            assert d["vals"].dtype == np.int32 and "exists" not in d
            assert f["min"] <= d["vals"].min() and d["vals"].max() <= f["max"]
    assert (year.min(), year.max()) == (0, 6) and (week.min(), week.max()) == (0, 52)
    assert (discount.min(), discount.max()) == (0, 10) and (quantity.min(), quantity.max()) == (1, 50)
    assert month.max() == 12 * 6 + 8 - 1  # August 1998 is the last month with an order
    assert revenue.max() > 1 << 26  # the 27th value plane is not empty


def test_the_same_seed_gives_the_same_data_and_another_seed_another():
    a, again, other = (_generated(s) for s in (SEEDS[0], SEEDS[0], SEEDS[1]))
    for s in range(SMALL["shards"]):
        for fa, fb, fo in zip(a[s], again[s], other[s]):
            for name in fa:
                assert np.array_equal(fa[name], fb[name])
                assert not np.array_equal(fa[name], fo[name])
    for field in range(len(SMALL["fields"])):  # and a shard is not its neighbour
        name = next(iter(a[0][field]))
        assert not np.array_equal(a[0][field][name], a[1][field][name])


@pytest.mark.parametrize("column", sorted(ssb_lineorder.SET_COLUMNS))
def test_the_shares_written_out_are_the_calendars(column):
    f = datagen.field_of(CONFIG, column)
    assert f["kind"] == "ssb_lineorder" and f["rows"] == ssb_lineorder.SET_COLUMNS[column]
    assert f["shares"] == pytest.approx(ssb_lineorder.shares(column), abs=1e-12)
    assert sum(f["shares"]) == pytest.approx(1.0, abs=1e-12)
    assert ssb_lineorder.DAYS == 2406
    with pytest.raises(ValueError):  # a share that is not the calendar's is refused, not drawn from
        ssb_lineorder.generate({**f, "shares": f["shares"][::-1]}, np.random.default_rng([1, 0, 0]), 0)


# -- (b) the reference against the paper's own form ---------------------------


def _the_papers_way(seed, name):
    """``select sum(lo_extendedprice*lo_discount) from lineorder, date
    where lo_orderdate = d_datekey and ...``, on the raw columns."""
    raw = [ssb_lineorder.lineorder(seed, s) for s in range(SMALL["shards"])]
    t = {k: np.concatenate([r[k] for r in raw]).astype(np.int64) for k in raw[0]}
    if name == "Q1.1":
        where = (t["d_year"] == 1993) & (t["lo_discount"] >= 1) & (t["lo_discount"] <= 3) & (t["lo_quantity"] < 25)
    elif name == "Q1.2":
        where = ((t["d_yearmonthnum"] == 199401) & (t["lo_discount"] >= 4) & (t["lo_discount"] <= 6)
                 & (t["lo_quantity"] >= 26) & (t["lo_quantity"] <= 35))
    else:
        where = ((t["d_weeknuminyear"] == 6) & (t["d_year"] == 1994) & (t["lo_discount"] >= 5)
                 & (t["lo_discount"] <= 7) & (t["lo_quantity"] >= 26) & (t["lo_quantity"] <= 35))
    return {"value": int((t["lo_extendedprice"] * t["lo_discount"])[where].sum()), "count": int(where.sum())}


@pytest.mark.parametrize("name", sorted(PAPER))
@pytest.mark.parametrize("seed", SEEDS)
def test_flight_1_the_papers_way_equals_the_reference_of_its_pql_form(seed, name):
    ref = datagen.reference_of(SMALL, _generated(seed))
    want = _the_papers_way(seed, name)
    assert want["count"] > 0
    assert ref.answer(PAPER[name]) == want
    # and the published query is one of the cell's own requests
    assert PAPER[name] in [c for c, _ in traffic.pool(CONFIG, MIX)]


def test_answering_a_few_shards_at_a_time_equals_the_plain_classes_over_all(monkeypatch):
    """The kind's ``Codes`` and ``IntValues`` answer ``AT_ONCE`` shards
    at a time (the whole-array temporaries of 58 shards ran the chip's
    machine out of memory, PERF.md section 6); the answers are the plain
    classes', whatever the span, a ragged last one included."""
    from benchmark.reference import Codes, IntValues, Reference

    three = {**CONFIG, "shards": 3}
    ref = datagen.reference_of(three, [datagen.generate_shard(three, SEEDS[1], s) for s in range(3)])
    assert {type(f).__name__ for f in ref.fields.values()} == {"ShardwiseCodes", "ShardwiseInts"}
    plain = Reference({
        name: Codes(f.codes, f.n_rows) if isinstance(f, Codes) else IntValues(f.vals, None)
        for name, f in ref.fields.items()
    })
    calls = [*CASES.values(), ["Sum", REVENUE, None], ["Count", ["Range", "lo_quantity", "!=", 7]],
             ["Union", ["Row", "d_weeknuminyear", 52], ["Row", "d_yearmonthnum", 79]]]
    for at_once in (1, 2, ssb_lineorder.AT_ONCE):
        monkeypatch.setattr(ssb_lineorder, "AT_ONCE", at_once)
        for call in calls:
            assert ref.answer(call) == plain.answer(call), (at_once, call)


# -- (c) the program against the reference ------------------------------------

CASES = {
    **PAPER,
    "quantity_under_1_is_empty": _q11(1, quantity=("<", 1)),
    "discount_0_to_10_is_everything": _q11(1, discount=("><", 0, 10)),
    "a_week_with_no_line_item": _q13(53 - 1, 1998 - 1992),
}
EMPTY = {"value": 0, "count": 0}


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("ssb_small") / "data")
    ref, facts = datagen.build(SMALL, SEEDS[0], data_dir)
    h = Holder(data_dir)
    h.open()
    yield ref, h, facts
    h.close()


@pytest.fixture(scope="module", params=["always", "never"])
def executor(request, built):
    ex = Executor(built[1], device_policy=request.param)
    yield ex
    ex.close()


def _answers(ex, calls):
    got = ex.execute(SMALL["index"], "".join(traffic.pql(c) for c in calls))
    return [{"value": r.val, "count": r.count} for r in got]


def _counter(name: str, **labels) -> float:
    return metrics.snapshot().get(metrics._flat_key(name, metrics._labels_key(labels)), 0)


@pytest.mark.parametrize("form", ["lone", "wave_of_two"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_program_answers_as_the_reference(built, executor, case, form):
    ref = built[0]
    call = CASES[case]
    mate = PAPER["Q1.1"] if call == PAPER["Q1.2"] else PAPER["Q1.2"]
    calls = [call] if form == "lone" else [call, mate]
    fused = _counter(metrics.FUSION_FUSED_LAUNCHES)
    got = _answers(executor, calls)
    want = [ref.answer(c) for c in calls]
    assert got == want, [traffic.pql(c) for c in calls]
    if case in ("quantity_under_1_is_empty", "a_week_with_no_line_item"):
        assert want[0] == EMPTY
    elif case == "discount_0_to_10_is_everything":
        assert want[0]["count"] > 20 * ref.answer(PAPER["Q1.1"])["count"] / 11  # 11 discounts where 3 were
    # a two-call query on the device is one fused launch; the CPU path never fuses
    fused = _counter(metrics.FUSION_FUSED_LAUNCHES) - fused
    assert fused == (1 if form == "wave_of_two" and executor.device_policy == "always" else 0)


# -- (e) what one request counts and books ------------------------------------


def test_one_request_is_one_launch_with_its_filter_inlined(built, monkeypatch):
    """A flight-1 request launches one program: the compares and the
    folds of its filter are traced into the sum's (ISSUE 34); the
    constants are traced values, so other dates and bands compile
    nothing."""
    _, h, _ = built
    ex = Executor(h, device_policy="always")
    ops_ = ("range", "and", "or", "xor", "andnot")
    read = lambda: (  # noqa: E731
        {op: _counter(metrics.FILTER_LAUNCHES, op=op) for op in ops_},
        {op: _counter(metrics.FILTER_INLINED, op=op) for op in ops_},
        _counter(metrics.KERNEL_OPERAND_BYTES, kind="bsi_sum") + _counter(metrics.KERNEL_OPERAND_BYTES, kind="fused_query"),
        _counter(metrics.KERNEL_OPERAND_BYTES, kind="bsi_range"),
    )
    grown = lambda now, was, k: {op: now[k][op] - was[k][op] for op in ops_ if now[k][op] != was[k][op]}  # noqa: E731
    launched = []
    timed = executor_mod._timed_kernel

    def counting(kind, fn, **kw):
        run = timed(kind, fn, **kw)
        return lambda *a: (launched.append(kind), run(*a))[1]

    monkeypatch.setattr(executor_mod, "_timed_kernel", counting)
    monkeypatch.setattr(fusion, "_timed_kernel", counting)
    try:
        # Q1.1: a year's row, two Ranges: 2 range + 2 and; Q1.3 has a second row: 2 + 3
        for call, ands, rows in ((PAPER["Q1.1"], 2, 1), (PAPER["Q1.3"], 3, 2)):
            before = read()
            del launched[:]
            ex.execute(SMALL["index"], traffic.pql(call))
            first = read()
            assert launched == ["bsi_sum"]
            assert grown(first, before, 0) == {}
            assert grown(first, before, 1) == {"range": 2, "and": ands}
            # the leaves' planes are operands of the one program, counted there once:
            # lo_revenue_computed 27 + 1, lo_discount 4 + 1, lo_quantity 6 + 1, the rows,
            # and the predicates' vector (3 or 4 u32)
            preds = 3 if call is PAPER["Q1.1"] else 4
            assert first[2] - before[2] == (28 + 5 + 7 + rows) * SMALL["shards"] * DENSE + 4 * preds
            assert first[3] == before[3]
        assert len(ex._tree_jits) == 2 and not ex._range_jits
        signatures = {r["signature"] for r in profiler.COMPILES.snapshot(top=256)["signatures"]}
        assert {f"bsi_sum:{k!r}" for k in ex._tree_jits} <= signatures

        # other constants, lone and as the wave of two: nothing new is compiled or kept
        ex.execute(SMALL["index"], traffic.pql(PAPER["Q1.1"]) + traffic.pql(PAPER["Q1.3"]))
        kept = set(ex._tree_jits), set(ex.fuser._programs)
        assert len(kept[1]) == 1
        compiled = _counter(metrics.PROFILER_COMPILES, kind="xla")
        before = read()
        del launched[:]
        other = _q11(5, discount=("><", 2, 9), quantity=("<", 40)), _q13(11, 3)
        wf: dict = {}
        with trace.attrib_activate(wf):
            answers = _answers(ex, [other[0]]) + _answers(ex, list(other))
        assert launched == ["bsi_sum", "fused_query"]
        assert (set(ex._tree_jits), set(ex.fuser._programs)) == kept
        assert _counter(metrics.PROFILER_COMPILES, kind="xla") == compiled
        after = read()
        assert grown(after, before, 0) == {} and grown(after, before, 1) == {"range": 6, "and": 7}
        ref = built[0]
        assert answers == [ref.answer(other[0]), ref.answer(other[0]), ref.answer(other[1])]
        assert answers[0] != ref.answer(PAPER["Q1.1"])
        # the lowering's host time is filter.eval's, the staged leaves' probes keep their own leg
        assert wf[trace.WF_FILTER_EVAL] > 0.0 and wf.get(trace.WF_STAGER_LOOKUP, 0.0) > 0.0
        assert wf.get(trace.WF_DEVICE_COMPUTE, 0.0) > 0.0
        assert trace.WF_FILTER_EVAL not in profiler.WaterfallAggregator.DEVICE_STAGES
    finally:
        ex.close()


# -- (f) the bytes a request needs, by hand -----------------------------------


def test_bytes_needed_of_flight_1_at_58_shards_equals_the_hand_worked_figures():
    shards = CONFIG["shards"]
    assert shards == 58
    # lo_discount 0..10: 4 value planes + existence; lo_quantity 1..50: 6 + 1;
    # lo_revenue_computed 0..104,949,500 (50 x 209,899 x 10): 27 + 1; all dense
    planes = (4 + 1) + (6 + 1) + (27 + 1)
    assert datagen.field_of(CONFIG, REVENUE)["max"] == 50 * 209_899 * 10 == 104_949_500
    # Q1.1: a year's row is dense too (a seventh of a shard's bits)
    assert roofline.bytes_needed(CONFIG, PAPER["Q1.1"]) == (1 + planes) * shards * DENSE == 311_689_216
    # Q1.2: January 1994 is 31 of 2,406 days: 13,510 bits a shard, 4 B each
    month = 4.0 * 31 / 2406 * SHARD_WIDTH
    assert month < DENSE
    assert roofline.bytes_needed(CONFIG, PAPER["Q1.2"]) == pytest.approx(planes * shards * DENSE + shards * month)
    # Q1.3: week 6 is 7 days in each of 7 years, sparse; the year's row dense
    week = 4.0 * 49 / 2406 * SHARD_WIDTH
    assert week < DENSE
    assert roofline.bytes_needed(CONFIG, PAPER["Q1.3"]) == pytest.approx((1 + planes) * shards * DENSE + shards * week)
    # a week with no line item still reads its planes
    assert roofline.bytes_needed(CONFIG, CASES["a_week_with_no_line_item"]) > planes * shards * DENSE


# -- (g) the manifest and the traffic -----------------------------------------


def test_the_manifest_names_the_cell_with_one_chip_and_its_four_metrics():
    manifest = _file("BENCHMARK.json")
    cell, entry = run.find_cell(manifest, CELL)
    assert cell == {**cell, "config": "ssb10", "traffic": "flight1", "chips": 1} and len(cell["why"]) <= 200
    assert entry["file"] == "benchmark/configs/ssb10.json" and entry["reduced"] == sorted(CONFIG["reduced"])
    assert entry["reduced"] == ["fields", "flights"] and "scale" in CONFIG["assumed"]
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert all(w in entry["source"] for w in ("Star Schema Benchmark", "SF=10", "flight 1"))
    assert CONFIG["architecture"] is None and CONFIG["chips"] == 1 and CONFIG["index"] == "ssb"
    assert set(CONFIG["guarantees"]) >= {"answers", "availability", "durability"}
    new = {
        "executor.filter_eval_ms": ("latency.stage_seconds_sum", {"stage": "filter.eval"}, "query_p50_ms"),
        "executor.filter_launches_per_query": ("filter.launches", {}, "query_p50_ms"),
        "executor.range_launches_per_query": ("filter.launches", {"op": "range"}, "query_p50_ms"),
        "kernels.bsi_range_operand_mb_per_query": ("kernel.operand_bytes", {"kind": "bsi_range"}, "queries_per_s"),
    }
    new["executor.filter_inlined_per_query"] = ("filter.inlined", {}, "query_p50_ms")  # ISSUE 34's
    # appended in the issues' order; ISSUE 35's mesh metric came after them
    # and its cell was appended to their lists (tests/test_bench_ssb_mesh_cell.py),
    # later issues' entries after that
    at = [m["name"] for m in manifest["per_layer"]].index("executor.filter_eval_ms")
    five = manifest["per_layer"][at : at + 5]
    assert [m["name"] for m in five] == list(new)
    lists = [CELL, "taxi96.dashboard", "ssb20x4.flight1"]
    assert five[-1] == {
        "name": "executor.filter_inlined_per_query", "unit": "count/query", "better": "higher",
        "source": "program_counter", "layer": "executor host side", "moves": "query_p50_ms",
        "workloads": lists,
    }
    for m in five:
        metric, labels, moves = new[m["name"]]
        assert m["workloads"] == lists and m["moves"] == moves
        spec = run.layer_metrics.load(m["name"])
        assert spec["numerator"] == [{"metric": metric, "labels": labels}] and spec["per"] == "request"
        assert metric in metrics.METRICS or metric.removesuffix("_sum") in metrics.METRICS
    assert new["executor.filter_eval_ms"][1]["stage"] in trace.WATERFALL_STAGES
    here = {m["name"] for m in run.metrics_of(manifest, "per_layer", CELL)}
    assert set(new) | {"kernels.hbm_roofline", "executor.unattributed_ms"} <= here
    assert "kernels.hbm_roofline_per_chip" not in here
    assert not set(new) & {m["name"] for m in run.metrics_of(manifest, "per_layer", "tall64.topn")}
    # the accepted one-chip cells stand first and in order; later cells are appended behind them
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 1][:3] == ["tall64.topn", "taxi96.dashboard", CELL]


def test_the_traffic_is_the_three_queries_over_462_requests():
    assert (MIX["loop"], MIX["clients"], MIX["cache"], MIX["deck"]) == ("closed", 2, False, 2226)
    assert [t["weight"] for t in MIX["mix"]] == [1, 1, 1]
    by_template = traffic.by_template(CONFIG, MIX)
    assert [len(t) for t in by_template] == [7, 84, 53 * 7]
    requests = traffic.pool(CONFIG, MIX)
    assert len(requests) == 462 and sum(p for _, p in requests) == pytest.approx(1.0)
    deck = traffic.deck(requests, MIX["deck"])
    # a third of the deck each: 742 = 7 x 106 = 84 x 8 + 70 = 371 x 2
    assert [sum(1 for i in deck if requests[i][0] in [c for c, _ in t]) for t in by_template] == [742, 742, 742]
    bodies = {traffic.pql(c) for c, _ in requests}
    assert ("Sum(Intersect(Row(d_year=1), Range(lo_discount >< [1, 3]), Range(lo_quantity < 25)), "
            "field=lo_revenue_computed)") in bodies
    assert len(traffic.pairs(CONFIG, MIX)) == 9  # every ordered pair of templates, warmed as a wave of two


# -- (d) the cell through the harness -----------------------------------------


def _run_cell(monkeypatch, tmp_path, **hooks):
    """One rehearsal of the cell at 2 shards against a real server child."""
    # the harness refuses to spawn from a process that imported JAX (it
    # would hold the chip); this worker's JAX is held to the CPU
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    monkeypatch.setattr(run, "SCRATCH", str(tmp_path / "scratch"))
    monkeypatch.setattr(run, "WARM_ROUND_S", 0.5)
    lines = []
    monkeypatch.setattr(run, "emit", lambda phase, **kw: lines.append({"phase": phase, **kw}))
    args = run.parse_args([
        "--workload", CELL, "--seed", str(2**31 + 33), "--seconds", "3",
        "--trace", "0", "--allow-cpu", "--shards", "2",
    ])
    return run.run_cell(args, **hooks), {ln["phase"]: ln for ln in lines}


def test_the_cell_runs_correct_against_a_server_child(monkeypatch, tmp_path):
    out, phases = _run_cell(monkeypatch, tmp_path)
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] == out["checks"]["compared"]["value"] > 0  # every answer compared
    assert phases["warm_up"]["distinct_requests"] == 462 and phases["warm_up"]["clients"] == 2
    # no compare is launched on its own; a program a filter structure:
    # three lone sums and the nine ordered pairs of a wave of two
    by_kind = phases["warm_up"]["compiles_by_kind"]
    assert "bsi_range" not in by_kind and (by_kind["bsi_sum"], by_kind["fused_query"]) == (3, 9)
    assert phases["warm_up"]["rounds_compiled"][-2:] == [(0, 0), (0, 0)]
    w = phases["window"]
    assert w["server_exit_code"] == 0 and w["fallbacks_in_window"] == {}
    assert w["compiles_in_window"] == 0 and w["stager_restaged_bytes_in_window"] == 0
    assert w["stage_ms_per_request"]["filter.eval"] > 0 and w["stage_ms_per_request"]["stager"] == 0


def test_a_planted_wrong_sum_makes_the_cell_incorrect(monkeypatch, tmp_path):
    out, _ = _run_cell(monkeypatch, tmp_path, server_module="benchmark.tests.faulty_sum_server")
    assert out["correct"] is False
    assert out["failed"] == out["checks"]["wrong_answers"]["value"] > 0
    assert out["checks"]["unanswered"]["value"] == 0
