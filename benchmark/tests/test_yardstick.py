"""The yardstick's own arithmetic, checked without a server: the trace
reduction on a small recorded trace, the bytes the data needs against
the hand-worked figures, the traffic deck, the comparison, the control."""

import json
import os

import numpy as np
import pytest

from benchmark import datagen, roofline, trace_reduce, traffic
from benchmark.reference import Undecidable, same_answer
from benchmark.tests import control

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


def mix(name):
    return traffic.load(os.path.join(ROOT, "benchmark", "traffic", f"{name}.json"))


def test_trace_reduction_on_a_small_recorded_trace():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        rec = json.load(f)
    out = trace_reduce.reduce_planes(rec["planes"])
    assert out["device_planes"] == rec["expect"]["device_planes"]
    assert out["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-12)
    assert out["window_s"] == pytest.approx(rec["expect"]["window_s"], rel=1e-12)
    assert out["device_ops"][0][0] == rec["expect"]["top_op"]
    idle = 100 * (1 - out["busy_s"] / out["window_s"])
    assert idle == pytest.approx(rec["expect"]["idle_share_pct"], rel=1e-9)


def test_trace_reduction_merges_nested_and_overlapping_events():
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [["a", 100, 50], ["b", 120, 10], ["c", 140, 30], ["d", 300, 100]]},
            {"name": "XLA Modules", "events": [["m", 0, 1000]]},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [["h", 0, 1000]]}]},
    ]
    out = trace_reduce.reduce_planes(planes)
    assert out["busy_s"] == pytest.approx((70 + 100) / 1e9)  # [100,170) and [300,400); the module line is not ops
    assert out["window_s"] == pytest.approx(300 / 1e9)  # the device's own extent, not the host's
    assert out["idle_gaps"] == [["before:d", pytest.approx(130 / 1e9)]]
    assert "busy_s" not in trace_reduce.reduce_planes(planes[1:])  # no device plane: nothing to read


def test_bytes_needed_equals_the_hand_worked_figures():
    tall, taxi = config("tall64"), config("taxi96")
    # tall64 TopN(f, Row(f=b)): 256 hot rows x 64 shards x 128 KiB dense
    # + 64,000,000 singleton rows x 4 B + the filter row, 64 x 128 KiB
    hot = 256 * 64 * 131072
    want = hot + 64_000_000 * 4 + 64 * 131072
    assert roofline.bytes_needed(tall, ["TopN", "f", ["Row", "f", 16], {"n": 10}]) == pytest.approx(want)
    assert want == pytest.approx(2.4119e9, rel=1e-3)
    # taxi96 Sum(Row(passenger_count=1), field=total_amount): 16 value
    # planes + the existence plane + one row, all dense, x 96 shards
    assert roofline.bytes_needed(
        taxi, ["Sum", "total_amount", ["Row", "passenger_count", 1]]
    ) == pytest.approx(18 * 96 * 131072)
    # passenger_count=9 holds 0.001 x 2^20 bits a shard: 4 B a bit, not dense
    sparse = 96 * 4 * 0.001 * (1 << 20)
    assert roofline.bytes_needed(taxi, ["Count", ["Row", "passenger_count", 9]]) == pytest.approx(sparse)
    # TopN(pickup_year, Row(passenger_count=1)): 8 dense rows + 1
    assert roofline.bytes_needed(
        taxi, ["TopN", "pickup_year", ["Row", "passenger_count", 1], {}]
    ) == pytest.approx(9 * 96 * 131072)
    # unfiltered TopN(cab_type): the kept counts, 2 rows x 96 shards x 8 B
    assert roofline.bytes_needed(taxi, ["TopN", "cab_type", None, {}]) == 2 * 96 * 8
    with pytest.raises(KeyError):
        roofline.peak("cpu")


def test_the_deck_holds_every_request_and_the_seed_only_reorders_it():
    taxi, dash = config("taxi96"), mix("dashboard")
    requests = traffic.pool(taxi, dash)
    assert len(requests) == 101
    assert sum(p for _, p in requests) == pytest.approx(1.0)
    deck = traffic.deck(requests, dash["deck"])
    assert len(deck) == 1000 and set(deck) == set(range(101))
    a, b = traffic.schedule(deck, 1), traffic.schedule(deck, 2**31 + 5)
    first_a, first_b = [next(a) for _ in range(1000)], [next(b) for _ in range(1000)]
    assert first_a != first_b and sorted(first_a) == sorted(first_b) == sorted(deck)
    tall_requests = traffic.pool(config("tall64"), mix("topn"))
    assert [traffic.pql(c) for c, _ in tall_requests][:2] == [
        "TopN(f, Row(f=0), n=10)", "TopN(f, Row(f=16), n=10)"]
    assert len(tall_requests) == 16
    call = ["TopN", "dist_miles", ["Intersect", ["Row", "pickup_year", 3], ["Row", "passenger_count", 1]], {"n": 10}]
    assert traffic.pql(call) == "TopN(dist_miles, Intersect(Row(pickup_year=3), Row(passenger_count=1)), n=10)"
    assert traffic.pql(["Sum", "total_amount", ["Row", "passenger_count", 2]]) == "Sum(Row(passenger_count=2), field=total_amount)"
    assert traffic.pql(["TopN", "cab_type", None, {}]) == "TopN(cab_type)"


def test_same_answer_is_exact_but_for_order_among_equal_counts():
    call = ["TopN", "f", None, {}]
    want = [{"id": 3, "count": 9}, {"id": 1, "count": 5}, {"id": 2, "count": 5}]
    assert same_answer(call, [{"id": 3, "count": 9}, {"id": 2, "count": 5}, {"id": 1, "count": 5}], want)
    assert not same_answer(call, [{"id": 3, "count": 9}, {"id": 1, "count": 5}, {"id": 2, "count": 4}], want)
    assert not same_answer(call, [{"id": 1, "count": 5}, {"id": 3, "count": 9}, {"id": 2, "count": 5}], want)
    assert not same_answer(call, want[:2], want)
    assert same_answer(["Count", []], 7, 7) and not same_answer(["Count", []], 8, 7)


@pytest.mark.parametrize("cell_config,cell_mix", [("tall64", "topn"), ("taxi96", "dashboard"), ("taxi96", "groupby")])
def test_the_control_comes_out_as_not_correct(cell_config, cell_mix):
    """A replica stale by one shard fails every answer of the mix, at a
    size a test run can hold (3 shards, a short tail)."""
    cfg = config(cell_config)
    for f in cfg["fields"]:
        if "tail_rows" in f:
            f["tail_rows"] = 1000
    ref = datagen.reference_of(cfg, [datagen.generate_shard(cfg, 7, s) for s in range(3)])
    calls = [c for c, _ in traffic.pool(cfg, mix(cell_mix))]
    expected = []
    for c in calls:
        try:
            expected.append(ref.answer(c))
        except Undecidable as e:
            expected.append(e)
    wrong, of = control.wrong_answers(ref, calls, expected)
    assert of >= len(calls) - 10 and wrong == of
    for c, want in zip(calls, expected):
        if not isinstance(want, Undecidable):
            assert same_answer(c, want, want)
