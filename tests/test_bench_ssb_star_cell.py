"""The deployment ``ssb10star`` and its cell ``ssb10star.flight2``:
Star Schema Benchmark SF=10, flight 2, as Pilosa fields.
(a) the data kind: the part and supplier attributes and ``lo_revenue``
of the line items ``ssb_lineorder`` draws; (b) the plain reference
against flight 2 computed the way the SSB paper writes it, on the raw
columns with brand and region as strings; (c) the program against the
reference, fused, lone and on the CPU, and what one panel counts; (d)
the traffic; (e) the manifest; (f) what the deployment stages, and the
budget it states for it."""

import json
import os

import numpy as np
import pytest

from benchmark import datagen, run, traffic
from benchmark.kinds import ssb_lineorder, ssb_star
from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.server.config import Config
from pilosa_tpu.utils import metrics, trace

ROOT = run.ROOT
CELL = "ssb10star.flight2"
SHARD_WIDTH = 1 << 20
DENSE = SHARD_WIDTH // 8
W32 = SHARD_WIDTH // 32


def _file(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CONFIG = _file("benchmark", "configs", "ssb10star.json")
TOML = os.path.join(ROOT, "benchmark", "configs", "ssb10star.toml")
MIX = traffic.load(os.path.join(ROOT, "benchmark", "traffic", "flight2.json"))
SMALL = {**CONFIG, "shards": 3}
SEEDS = [4500000011, 2**31 + 4545]
NEW_FIELDS = ("p_brand1", "p_category", "s_region", "lo_revenue")

Q21_BRANDS = list(range(40, 80))  # MFGR#1201..MFGR#1240, category MFGR#12
Q22_BRANDS = list(range(260, 268))  # MFGR#2221..MFGR#2228
EUROPE = ssb_star.REGIONS.index("EUROPE")


def _q21(region):
    return ["GroupBy", [["Rows", "d_year"], ["Rows", "p_brand1", Q21_BRANDS]],
            ["Intersect", ["Row", "p_category", 1], ["Row", "s_region", region]], {"sum": "lo_revenue"}]


def _q22(region):
    return ["GroupBy", [["Rows", "d_year"], ["Rows", "p_brand1", Q22_BRANDS]],
            ["Row", "s_region", region], {"sum": "lo_revenue"}]


def _q23(brand):
    return ["GroupBy", [["Rows", "d_year"], ["Rows", "p_brand1", [brand]]],
            ["Row", "s_region", EUROPE], {"sum": "lo_revenue"}]


# the paper's constants: MFGR#12 in AMERICA; MFGR#2221..2228 in ASIA; MFGR#2221 in EUROPE
PAPER = {
    "Q2.1": _q21(ssb_star.REGIONS.index("AMERICA")),
    "Q2.2": _q22(ssb_star.REGIONS.index("ASIA")),
    "Q2.3": _q23(260),
}


def _generated(seed, config=SMALL):
    return [datagen.generate_shard(config, seed, s) for s in range(config["shards"])]


# -- (a) the kind -------------------------------------------------------------


def test_brand_and_category_rows_are_the_papers_strings():
    assert [ssb_star.brand_name(r) for r in (0, 39, 40, 79, 260, 267, 999)] == [
        "MFGR#1101", "MFGR#1140", "MFGR#1201", "MFGR#1240", "MFGR#2221", "MFGR#2228", "MFGR#5540"]
    assert [ssb_star.category_name(c) for c in (0, 1, 6, 24)] == ["MFGR#11", "MFGR#12", "MFGR#22", "MFGR#55"]
    names = [ssb_star.brand_name(r) for r in range(1000)]
    assert names == sorted(names) and len(set(names)) == 1000  # the strings' order is the rows'
    assert all(ssb_star.brand_name(r).startswith(ssb_star.category_name(r // 40)) for r in range(1000))
    # Q2.2's BETWEEN 'MFGR#2221' AND 'MFGR#2228' is the cell's eight brand rows
    assert [r for r, n in enumerate(names) if "MFGR#2221" <= n <= "MFGR#2228"] == Q22_BRANDS
    assert [r for r, n in enumerate(names) if n.startswith("MFGR#12")] == Q21_BRANDS


@pytest.mark.parametrize("shard", range(SMALL["shards"]))
@pytest.mark.parametrize("seed", SEEDS)
def test_the_four_new_columns_describe_ssb_lineorders_line_items(seed, shard):
    data = dict(zip((f["name"] for f in SMALL["fields"]), datagen.generate_shard(SMALL, seed, shard)))
    raw = ssb_star.star(seed, shard)
    items = ssb_lineorder.lineorder(seed, shard)
    brand, category, region = (data[n]["codes"] for n in ("p_brand1", "p_category", "s_region"))
    revenue = data["lo_revenue"]["vals"]
    assert brand.dtype == np.uint16 and category.dtype == region.dtype == np.uint8
    assert all(a.shape == (SHARD_WIDTH,) for a in (brand, category, region, revenue))
    # a brand implies its category; the strings say the same
    assert np.array_equal(category, brand // 40)
    assert np.array_equal(raw["p_brand1"], np.array([ssb_star.brand_name(b) for b in range(1000)])[brand])
    assert np.array_equal(raw["p_brand1"].astype("<U7"), raw["p_category"])
    assert np.array_equal(raw["s_region"], np.array(ssb_star.REGIONS)[region])
    # the date and measure columns are ssb_lineorder's own items
    for k in items:
        assert np.array_equal(raw[k], items[k]), k
    assert np.array_equal(data["d_year"]["codes"].astype(int), items["d_year"] - 1992)
    assert np.array_equal(data["lo_discount"]["vals"], items["lo_discount"])
    # SSB's lo_revenue: the price less its discount, in whole cents, 24 bits
    want = items["lo_extendedprice"].astype(np.int64) * (100 - items["lo_discount"]) // 100
    assert revenue.dtype == np.int32 and np.array_equal(revenue, want)
    assert np.array_equal(raw["lo_revenue"], revenue)
    f = datagen.field_of(SMALL, "lo_revenue")
    assert f["min"] <= revenue.min() and revenue.max() <= f["max"] == 10_494_950 < 1 << 24
    assert revenue.max() >= 1 << 23  # the 24th value plane is not empty
    assert (brand.min(), brand.max(), region.min(), region.max()) == (0, 999, 0, 4)


def test_keys_are_uniform_and_independent_of_the_date_and_each_other():
    gen = _generated(SEEDS[0])
    names = [f["name"] for f in SMALL["fields"]]
    col = lambda n: np.concatenate([g[names.index(n)]["codes"] for g in gen]).astype(np.int64)  # noqa: E731
    brand, region, year = col("p_brand1"), col("s_region"), col("d_year")
    n = brand.size
    per_brand = np.bincount(brand, minlength=1000)
    assert per_brand.min() > 0.85 * n / 1000 and per_brand.max() < 1.15 * n / 1000
    assert np.bincount(region, minlength=5) / n == pytest.approx([0.2] * 5, abs=0.002)
    # a region's share is the same in every year and every category
    joint = np.bincount(year * 5 + region, minlength=35).reshape(7, 5)
    assert joint / joint.sum(axis=1, keepdims=True) == pytest.approx(np.full((7, 5), 0.2), abs=0.006)
    by_cat = np.bincount((brand // 40) * 5 + region, minlength=125).reshape(25, 5)
    assert by_cat / by_cat.sum(axis=1, keepdims=True) == pytest.approx(np.full((25, 5), 0.2), abs=0.006)


def test_the_same_seed_gives_the_same_keys_and_another_seed_another():
    a, again, other = (_generated(s) for s in (SEEDS[0], SEEDS[0], SEEDS[1]))
    at = [i for i, f in enumerate(SMALL["fields"]) if f["name"] in NEW_FIELDS]
    for s in range(SMALL["shards"]):
        for i in at:
            for name in a[s][i]:
                assert np.array_equal(a[s][i][name], again[s][i][name])
                assert not np.array_equal(a[s][i][name], other[s][i][name])
    for i in at:  # and a shard is not its neighbour
        name = next(iter(a[0][i]))
        assert not np.array_equal(a[0][i][name], a[1][i][name])
    assert ssb_star.TAG != ssb_lineorder.TAG


@pytest.mark.parametrize("column", sorted(ssb_star.SET_COLUMNS))
def test_the_shares_written_out_are_uniform(column):
    f = datagen.field_of(CONFIG, column)
    assert f["kind"] == "ssb_star" and f["rows"] == ssb_star.SET_COLUMNS[column] == len(f["shares"])
    assert f["shares"] == ssb_star.shares(column) == [1 / f["rows"]] * f["rows"]
    assert sum(f["shares"]) == pytest.approx(1.0, abs=1e-12)
    with pytest.raises(ValueError):  # a share that is not the uniform one is refused, not drawn from
        ssb_star.generate({**f, "shares": [2 / f["rows"]] + f["shares"][1:]}, np.random.default_rng([1, 0, 0]), 0)
    with pytest.raises(ValueError):
        ssb_star.generate({**f, "rows": f["rows"] - 1}, np.random.default_rng([1, 0, 0]), 0)


# -- (b) the reference against the paper's own form ---------------------------


def _the_papers_way(seed, name):
    """``select sum(lo_revenue), d_year, p_brand1 from lineorder, date,
    part, supplier where ... group by d_year, p_brand1 order by d_year,
    p_brand1`` on the raw columns, brand and region as strings; with the
    group's count(*) beside its sum, as a GroupBy answers both."""
    raw = [ssb_star.star(seed, s) for s in range(SMALL["shards"])]
    t = {k: np.concatenate([r[k] for r in raw]) for k in ("d_year", "p_brand1", "p_category", "s_region", "lo_revenue")}
    if name == "Q2.1":
        where = (t["p_category"] == "MFGR#12") & (t["s_region"] == "AMERICA")
    elif name == "Q2.2":
        where = (t["p_brand1"] >= "MFGR#2221") & (t["p_brand1"] <= "MFGR#2228") & (t["s_region"] == "ASIA")
    else:
        where = (t["p_brand1"] == "MFGR#2221") & (t["s_region"] == "EUROPE")
    groups: dict = {}
    for year, brand, revenue in zip(t["d_year"][where].tolist(), t["p_brand1"][where].tolist(),
                                    t["lo_revenue"][where].tolist()):
        count, total = groups.get((year, brand), (0, 0))
        groups[(year, brand)] = (count + 1, total + revenue)
    brand_row = {ssb_star.brand_name(r): r for r in range(1000)}
    return [
        {"group": [{"field": "d_year", "rowID": year - 1992}, {"field": "p_brand1", "rowID": brand_row[brand]}],
         "count": count, "sum": total}
        for (year, brand), (count, total) in sorted(groups.items())
    ]


@pytest.mark.parametrize("name", sorted(PAPER))
@pytest.mark.parametrize("seed", SEEDS)
def test_flight_2_the_papers_way_equals_the_reference_of_its_pql_form(seed, name):
    ref = datagen.reference_of(SMALL, _generated(seed))
    want = _the_papers_way(seed, name)
    k = {"Q2.1": 7 * 40, "Q2.2": 7 * 8, "Q2.3": 7}[name]
    assert len(want) == k  # at 3 shards every group of the panel holds line items
    assert ref.answer(PAPER[name]) == want
    # and the published query is one of the cell's own requests
    assert PAPER[name] in [c for c, _ in traffic.pool(CONFIG, MIX)]


# -- (c) the program against the reference ------------------------------------

CASES = {
    **PAPER,
    "Q2.1_middle_east": _q21(4),
    "Q2.3_the_last_brand": _q23(999),
    "Q2.3_a_brand_no_part_has": _q23(1000),
}


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("ssb_star_small") / "data")
    ref, facts = datagen.build(SMALL, SEEDS[0], data_dir)
    h = Holder(data_dir)
    h.open()
    yield ref, h, facts
    h.close()


PATHS = {
    "fused": dict(device_policy="always"),
    "lone": dict(device_policy="always", fusion_enabled=False),
    "cpu": dict(device_policy="never"),
}


@pytest.fixture(scope="module", params=sorted(PATHS))
def executor(request, built):
    ex = Executor(built[1], **PATHS[request.param])
    ex.path = request.param
    yield ex
    ex.close()


def _counter(name: str, **labels) -> float:
    return metrics.snapshot().get(metrics._flat_key(name, metrics._labels_key(labels)), 0)


def _groups_sum() -> float:
    hist = metrics.snapshot().get(metrics._flat_key(metrics.FUSION_GROUPBY_GROUPS + ".hist", metrics._labels_key({})))
    return hist["sum"] if hist else 0.0


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_program_answers_as_the_reference(built, executor, case):
    ref = built[0]
    call = CASES[case]
    launches, groups = _counter(metrics.FUSION_GROUPBY_LAUNCHES), _groups_sum()
    fused = _counter(metrics.FUSION_FUSED_LAUNCHES)
    got = executor.execute(SMALL["index"], traffic.pql(call))
    want = ref.answer(call)
    assert got == [want], traffic.pql(call)
    if case == "Q2.3_a_brand_no_part_has":
        assert want == []
    else:
        assert want and all(g["count"] > 0 and g["sum"] > 0 for g in want)
    # a panel on the device is one launch of K groups, fused alone or lone; the CPU launches none
    device = executor.path != "cpu"
    k = 7 * len(call[1][1][2])
    assert _counter(metrics.FUSION_GROUPBY_LAUNCHES) - launches == (1 if device else 0)
    assert _groups_sum() - groups == (k if device else 0)
    assert _counter(metrics.FUSION_FUSED_LAUNCHES) - fused == (1 if executor.path == "fused" else 0)


# -- (d) the traffic ----------------------------------------------------------


def test_the_traffic_is_the_three_panels_over_1010_requests():
    assert (MIX["loop"], MIX["clients"], MIX["cache"], MIX["deck"]) == ("closed", 2, False, 3000)
    assert [t["weight"] for t in MIX["mix"]] == [1, 1, 1]
    by_template = traffic.by_template(CONFIG, MIX)
    assert [len(t) for t in by_template] == [5, 5, 1000]
    requests = traffic.pool(CONFIG, MIX)
    assert len(requests) == 1010 and sum(p for _, p in requests) == pytest.approx(1.0)
    deck = traffic.deck(requests, MIX["deck"])
    # a third of the deck each: 1,000 = 5 x 200 = 5 x 200 = 1,000 x 1
    keys = [{json.dumps(c) for c, _ in t} for t in by_template]
    assert [sum(1 for i in deck if json.dumps(requests[i][0]) in t) for t in keys] == [1000, 1000, 1000]
    assert len(traffic.pairs(CONFIG, MIX)) == 9  # every ordered pair of templates, warmed as a two-call query
    assert [traffic.pql(t[0][0]) for t in by_template] == [
        "GroupBy(Rows(d_year), Rows(p_brand1, ids=[" + ", ".join(map(str, Q21_BRANDS)) + "]), "
        "Intersect(Row(p_category=1), Row(s_region=0)), Sum(field=lo_revenue))",
        "GroupBy(Rows(d_year), Rows(p_brand1, ids=[260, 261, 262, 263, 264, 265, 266, 267]), "
        "Row(s_region=0), Sum(field=lo_revenue))",
        "GroupBy(Rows(d_year), Rows(p_brand1, ids=[0]), Row(s_region=3), Sum(field=lo_revenue))",
    ]
    # every brand row is asked for, so all 1,000 are staged once warm
    assert sorted(c[1][1][2][0] for c, _ in by_template[2]) == list(range(1000))


# -- (e) the manifest ---------------------------------------------------------


NEW_METRICS = {
    "executor.groupby_launches_per_query": (
        "count/query", "higher", "program_counter", "executor routing", "queries_per_s",
        {"metric": "fusion.groupby_launches", "labels": {}}, 1),
    "executor.groupby_groups_per_query": (
        "groups/query", "lower", "program_counter", "executor routing", "queries_per_s",
        {"metric": "fusion.groupby_groups_sum", "labels": {}}, 1),
    "executor.groupby_lower_ms": (
        "ms", "lower", "program_span", "executor host side", "query_p50_ms",
        {"metric": "latency.stage_seconds_sum", "labels": {"stage": trace.WF_GROUPBY_LOWER}}, 1000),
    "executor.groupby_finalize_ms": (
        "ms", "lower", "program_span", "executor host side", "query_p50_ms",
        {"metric": "latency.stage_seconds_sum", "labels": {"stage": trace.WF_GROUPBY_FINALIZE}}, 1000),
    "kernels.groupby_hbm_roofline": (
        "%", "higher", "device_trace", "kernels", "queries_per_s", None, None),
}


def test_the_manifest_appends_the_config_the_cell_and_five_metrics():
    manifest = _file("BENCHMARK.json")
    cell, entry = run.find_cell(manifest, CELL)
    assert manifest["workloads"][5] is cell and manifest["configs"][5] is entry
    assert cell == {"name": CELL, "config": "ssb10star", "traffic": "flight2", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/ssb10star.json" and entry["reduced"] == sorted(CONFIG["reduced"])
    assert entry["reduced"] == ["fields", "flights"] and "scale" in CONFIG["assumed"]
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert all(w in entry["source"] for w in ("Star Schema Benchmark", "SF=10", "flight 2", "configs[2]"))
    assert CONFIG["architecture"] is None and CONFIG["chips"] == 1 and CONFIG["index"] == "ssb"
    assert CONFIG["shards"] == 58 and CONFIG["server_flags"] == ["-c", os.path.relpath(TOML, ROOT)]
    assert set(CONFIG["guarantees"]) == {"answers", "availability", "replication", "durability"}
    # ssb10's six fields unchanged, then the four flight 2 reads
    assert CONFIG["fields"][:6] == _file("benchmark", "configs", "ssb10.json")["fields"]
    assert [f["name"] for f in CONFIG["fields"][6:]] == list(NEW_FIELDS)
    assert {"keys", "lo_revenue", "constants", "stager-budget-bytes"} <= set(CONFIG["assumed"])
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"][-5:]] == list(NEW_METRICS)
    layers = {m["layer"] for m in manifest["per_layer"][:-5]}
    for name, (unit, better, source, layer, moves, numerator, scale) in NEW_METRICS.items():
        assert by_name[name] == {"name": name, "unit": unit, "better": better, "source": source,
                                 "layer": layer, "moves": moves, "workloads": [CELL]}
        assert layer in layers
        spec = run.layer_metrics.load(name)
        if numerator is None:
            assert spec == {"name": name, "source": "device_trace", "reducer": "hbm_roofline"}
        else:
            assert spec == {"name": name, "source": "server_metrics", "numerator": [numerator],
                            "scale": scale, "per": "request"}
    here = {m["name"] for m in run.metrics_of(manifest, "per_layer", CELL)}
    assert set(NEW_METRICS) | {"kernels.device_ms_per_query", "device.idle_share", "executor.unattributed_ms"} <= here
    # the accepted lists stay as they were: the cell is on none of them
    assert "kernels.hbm_roofline" not in here and "executor.filter_eval_ms" not in here
    for other in ("tall64.topn", "taxi96.dashboard", "tall128x4.topn", "ssb10.flight1", "ssb20x4.flight1"):
        assert not set(NEW_METRICS) & {m["name"] for m in run.metrics_of(manifest, "per_layer", other)}
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 2


# -- (f) what the deployment stages -------------------------------------------


def test_the_staged_set_and_the_largest_admission_fit_the_stated_budget():
    shards = CONFIG["shards"]
    stack = shards * DENSE  # one dense row over the shards
    assert stack == 7_602_176
    rows = {f["name"]: f["rows"] for f in CONFIG["fields"] if "rows" in f}
    planes = datagen.field_of(CONFIG, "lo_revenue")["max"].bit_length() + 1  # 24 value planes + existence
    assert planes == 25
    # the sizing the cell was planned with: the four dimension fields whole and the plane stack
    sizing = (rows["p_brand1"] + rows["p_category"] + rows["s_region"] + rows["d_year"] + planes) * stack
    assert sizing == 8_073_510_912 and rows["p_brand1"] * stack == 7_602_176_000
    # what the traffic stages: every brand, the years, one category, every region, the planes
    staged = (rows["p_brand1"] + rows["d_year"] + 1 + rows["s_region"] + planes) * stack
    assert staged == 7_891_058_688 < sizing < 12 << 30
    # the fuser's admission asks for 2 x the operands + the K x S·W transient
    q21 = 2 * (7 + 40 + 2 + planes) * stack + 7 * 40 * shards * W32 * 4
    assert q21 == 3_253_731_328
    cfg = Config.from_toml(TOML)
    assert cfg.device_policy == "always" and cfg.stager_budget_bytes == 14 << 30
    budget = cfg.stager_budget_bytes + cfg.plan_cache_device_bytes
    # the warm-up's two-call Q2.1 pair fits beside the staged set with no relief; at 12 GiB it would not
    assert staged + 2 * q21 < budget
    assert staged + 2 * q21 > (12 << 30) + cfg.plan_cache_device_bytes
    assert staged + 2 * q21 < 16_909_336_064  # the chip's memory (PERF.md section 4)
