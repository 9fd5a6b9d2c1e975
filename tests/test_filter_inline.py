"""A filter reaches a one-program consumer as structure (ISSUE 34):
``Executor._tree_leaves`` lowers boolean calls and BSI Ranges to nodes
that ``_eval_tree`` traces inside the program of the Count, Sum,
Distinct, Percentile or GroupBy that consumes them, and counts them to
``filter.inlined``; a consumer that reads an array (a TopN's source)
still has its filter evaluated before it, counted to
``filter.launches``. (a) inlined against evaluated: every answer equals
the CPU path's; (c) a TopN's source is materialised as before. What one
flight-1 request launches, (b), is in ``test_bench_ssb_cell.py``. A Sum
or a Count on a mesh is such a consumer too (ISSUE 36): its filter is
traced inside the ``shard_map`` kernel, on four virtual devices here,
the module's three shards padded to four."""

import numpy as np
import pytest

from pilosa_tpu import SHARD_WIDTH
from pilosa_tpu.core import Holder
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.executor import _eval_tree
from pilosa_tpu.executor.stager import DeviceStager
from pilosa_tpu.parallel.spmd import make_mesh
from pilosa_tpu.utils import metrics

OPS = ("range", "and", "or", "xor", "andnot")
V_MIN, V_MAX = -5, 1000

# the eight operators, each deciding nothing on the host
OPERATORS = {
    "lt": "Range(v < 300)",
    "lte": "Range(v <= 300)",
    "gt": "Range(v > 300)",
    "gte": "Range(v >= 300)",
    "eq": "Range(v == 7)",
    "neq": "Range(v != 7)",
    "between": "Range(v >< [1, 300])",
    "not_null": "Range(v != null)",
}
# the cases the field's bounds decide: (filter, the node it lowers to)
BOUND_DECIDED = {
    "gt_over_max_is_zeros": (f"Range(v > {V_MAX + 1000})", "zeros"),
    "eq_under_min_is_zeros": (f"Range(v == {V_MIN - 1})", "zeros"),
    "between_outside_is_zeros": (f"Range(v >< [{V_MAX + 1}, {V_MAX + 9}])", "zeros"),
    "lt_min_is_zeros": (f"Range(v < {V_MIN})", "zeros"),
    "gte_min_is_exists": (f"Range(v >= {V_MIN})", "exists"),
    "lt_over_max_is_exists": (f"Range(v < {V_MAX + 1})", "exists"),
    "between_all_is_exists": (f"Range(v >< [{V_MIN}, {V_MAX}])", "exists"),
    "neq_outside_is_exists": (f"Range(v != {V_MAX + 1000})", "exists"),
}
# (filter, what filter.inlined grows by for one lowering)
NESTED = {
    "flight_1": ("Intersect(Row(f=1), Range(v >< [1, 300]), Range(w < 25))", {"range": 2, "and": 2}),
    "union_of_range_and_row": ("Union(Range(v == 7), Row(f=2))", {"range": 1, "or": 1}),
    "difference": ("Difference(Row(f=1), Range(v > 500))", {"range": 1, "andnot": 1}),
    "xor": ("Xor(Row(f=1), Range(v <= 500))", {"range": 1, "xor": 1}),
    "two_ranges_of_one_field": ("Intersect(Range(v > 10), Range(v < 900))", {"range": 2, "and": 1}),
    "two_levels": (
        "Intersect(Union(Row(f=0), Range(w >= 10)), Difference(Range(v != null), Range(v < 100)), Row(f=3))",
        {"range": 3, "and": 2, "or": 1, "andnot": 1},
    ),
    "zeros_under_union": (f"Union(Row(f=1), Range(v > {V_MAX + 1}))", {"or": 1}),
    "rows_only": ("Intersect(Row(f=1), Row(f=2))", {"and": 1}),
}
FILTERS = {
    **OPERATORS,
    **{k: f for k, (f, _) in BOUND_DECIDED.items()},
    **{k: f for k, (f, _) in NESTED.items()},
}
# the consumers that are one program; {} is the filter
CONSUMERS = {
    "sum": "Sum({}, field=w)",
    "sum_as_two_calls": "Sum({}, field=w)Sum(Intersect(Row(f=1), Range(w >< [3, 40])), field=v)",
    "count": "Count({})",
    "distinct": "Distinct({}, field=w)",
    "percentile": "Percentile({}, field=w, nth=50)",
    "groupby": "GroupBy(Rows(f), {})",
}
# the consumers that are one shard_map kernel on a mesh
MESH_CONSUMERS = {"mesh_sum": CONSUMERS["sum"], "mesh_count": CONSUMERS["count"]}


@pytest.fixture(scope="module")
def holder(tmp_path_factory):
    h = Holder(str(tmp_path_factory.mktemp("filter_inline")))
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    v = idx.create_field("v", FieldOptions(type="int", min=V_MIN, max=V_MAX))
    w = idx.create_field("w", FieldOptions(type="int", min=0, max=50))
    rng = np.random.default_rng(34)
    cols = np.concatenate(
        [s * SHARD_WIDTH + rng.choice(50000, size=20000, replace=False) for s in range(3)]
    )
    f.import_bits(rng.integers(0, 4, size=cols.size), cols)
    v.import_values(cols[::2], rng.integers(V_MIN, V_MAX + 1, size=cols[::2].size))
    w.import_values(cols[::3], rng.integers(0, 51, size=cols[::3].size))
    yield h
    h.close()


@pytest.fixture(scope="module")
def device(holder):
    ex = Executor(holder, device_policy="always")
    yield ex
    ex.close()


@pytest.fixture(scope="module")
def mesh(holder):
    import jax

    four = make_mesh(jax.devices()[:4])
    ex = Executor(holder, device_policy="always", mesh=four,
                  stager=DeviceStager(budget_bytes=1 << 30, mesh=four))
    yield ex
    ex.close()


@pytest.fixture(scope="module")
def cpu(holder):
    ex = Executor(holder, device_policy="never")
    yield ex
    ex.close()


def _counted(name):
    snap = metrics.snapshot()
    return {op: snap.get(metrics._flat_key(name, metrics._labels_key({"op": op})), 0) for op in OPS}


def _grown(name, before):
    return {op: n - before[op] for op, n in _counted(name).items() if n != before[op]}


# -- (a) inlined against evaluated --------------------------------------------


@pytest.mark.parametrize("consumer", sorted(CONSUMERS) + sorted(MESH_CONSUMERS))
@pytest.mark.parametrize("case", sorted(FILTERS))
def test_an_inlined_filter_answers_as_the_cpu_path(device, mesh, cpu, case, consumer):
    if consumer in MESH_CONSUMERS:
        device, q = mesh, MESH_CONSUMERS[consumer].format(FILTERS[case])
    else:
        q = CONSUMERS[consumer].format(FILTERS[case])
    launches = _counted(metrics.FILTER_LAUNCHES)
    got = device.execute("i", q)
    # nothing was launched for the filter ahead of its consumer
    assert _grown(metrics.FILTER_LAUNCHES, launches) == {}, q
    assert got == cpu.execute("i", q), q


@pytest.mark.parametrize("case", sorted(OPERATORS))
def test_the_operators_select_some_columns_and_not_all(cpu, case):
    """The eight operator cases reach the compare: none is decided by
    the field's bounds, so their answers differ from the unfiltered."""
    (n,) = cpu.execute("i", f"Count({OPERATORS[case]})")
    (everything,) = cpu.execute("i", "Count(Range(v != null))")
    assert 0 < n <= everything == 30000
    assert (n == everything) == (case == "not_null")


@pytest.mark.parametrize("case", sorted(BOUND_DECIDED))
def test_a_range_the_bounds_decide_lowers_to_a_node_of_its_own(device, case):
    filt, node = BOUND_DECIDED[case]
    from pilosa_tpu.pql import parse

    before = _counted(metrics.FILTER_INLINED)
    inputs, tree = device._tree_leaves("i", parse(filt).calls[0], [0, 1, 2])
    if node == "zeros":
        # no input at all: the program makes its own zeros
        assert (tree, inputs) == (("zeros", 3), [])
        assert _grown(metrics.FILTER_INLINED, before) == {}
    else:
        # the field's plane stack, read at its existence plane; counted
        # as the launch of the copy it replaces would be
        assert tree == ("exists", 0) and [a.shape for a in inputs] == [(3, 11, SHARD_WIDTH // 32)]
        assert _grown(metrics.FILTER_INLINED, before) == {"range": 1}
    words = np.asarray(_eval_tree(tree, inputs))
    want = np.asarray(device._device_bitmap_stack("i", parse(filt).calls[0], [0, 1, 2]))
    assert words.shape == want.shape and np.array_equal(words, want)


@pytest.mark.parametrize("case", sorted(NESTED))
def test_a_lowering_counts_each_node_it_folds_into_the_consumer(device, case):
    from pilosa_tpu.pql import parse

    filt, inlined = NESTED[case]
    call = parse(filt).calls[0]
    before = _counted(metrics.FILTER_INLINED), _counted(metrics.FILTER_LAUNCHES)
    inputs, tree = device._tree_leaves("i", call, [0, 1, 2])
    assert _grown(metrics.FILTER_INLINED, before[0]) == inlined
    assert _grown(metrics.FILTER_LAUNCHES, before[1]) == {}
    # predicates ride as one u32 vector after the leaves, constants nowhere else
    text = repr(tree)
    n_preds = sum(len(node_slots) for node_slots in _slots(tree))
    assert (inputs[-1].dtype == np.uint32 and inputs[-1].shape == (n_preds,)) if n_preds else "range" not in text
    if case == "two_ranges_of_one_field":
        assert tree == ("Intersect", (("range", ">", 10, 0, (0,)), ("range", "<", 10, 0, (1,))))
        assert len(inputs) == 2 and inputs[1].tolist() == [10 - V_MIN, 900 - V_MIN]
    # the eager evaluation of the same filter makes as many launches, and the same words
    want = np.asarray(device._device_bitmap_stack("i", call, [0, 1, 2]))
    assert _grown(metrics.FILTER_LAUNCHES, before[1]) == inlined
    assert np.array_equal(np.asarray(_eval_tree(tree, inputs)), want)


def _slots(tree):
    if tree[0] == "range":
        yield tree[4]
    elif tree[0] in ("Intersect", "Union", "Xor", "Difference"):
        for sub in tree[1]:
            yield from _slots(sub)


@pytest.mark.parametrize("where", ["device", "mesh"])
def test_one_program_serves_every_constant(request, cpu, where):
    """On a mesh the program is a shard_map kernel kept in
    ``_spmd_kernels``, and a two-call query runs call by call (the
    fuser stands down), so it adds none either."""
    ex = request.getfixturevalue(where)
    kept = ex._spmd_kernels if where == "mesh" else ex._tree_jits
    q = "Sum(Intersect(Row(f={}), Range(v >< [{}, {}]), Range(w < {})), field=w)"
    first = ex.execute("i", q.format(1, 1, 300, 25))
    programs = len(kept)
    again = ex.execute("i", q.format(2, 40, 90, 11))
    assert first != again and again == cpu.execute("i", q.format(2, 40, 90, 11))
    ex.execute("i", q.format(1, 1, 300, 25) + q.format(2, 40, 90, 11))
    pair = len(ex.fuser._programs)
    assert (pair == 0) == (where == "mesh")
    other = q.format(3, 2, 7, 50) + q.format(0, 500, 900, 3)
    assert ex.execute("i", other) == cpu.execute("i", other)
    assert (len(kept), len(ex.fuser._programs)) == (programs, pair)


# -- (c) a consumer that reads an array keeps its filter materialised ---------


@pytest.mark.parametrize(
    "filt, launches",
    [
        ("Intersect(Row(f=1), Range(v >< [1, 300]))", {"range": 1, "and": 1}),
        ("Intersect(Row(f=1), Row(f=2))", {"and": 1}),
        ("Range(v != null)", {"range": 1}),
    ],
)
def test_a_topn_source_is_still_materialised(device, cpu, filt, launches):
    q = f"TopN(f, {filt}, n=3)"
    before = _counted(metrics.FILTER_LAUNCHES), _counted(metrics.FILTER_INLINED)
    got = device.execute("i", q)
    assert _grown(metrics.FILTER_LAUNCHES, before[0]) == launches
    assert _grown(metrics.FILTER_INLINED, before[1]) == {}
    assert got == cpu.execute("i", q)
    # and in a fused wave beside a Sum: the Sum's filter is inlined, the TopN's launched
    both = q + "Sum(Intersect(Row(f=1), Range(w < 25)), field=v)"
    before = _counted(metrics.FILTER_LAUNCHES), _counted(metrics.FILTER_INLINED)
    fused = metrics.snapshot().get(metrics.FUSION_FUSED_LAUNCHES, 0)
    got = device.execute("i", both)
    assert metrics.snapshot().get(metrics.FUSION_FUSED_LAUNCHES, 0) == fused + 1
    assert _grown(metrics.FILTER_LAUNCHES, before[0]) == launches
    assert _grown(metrics.FILTER_INLINED, before[1]) == {"range": 1, "and": 1}
    assert got == cpu.execute("i", both)
