"""The four-chip deployment ``ssb20x4`` and its cell ``ssb20x4.flight1``
(ISSUE 35): SSB flight 1 behind a 4-device mesh, on four virtual CPU
devices. (a) the mesh executor, the one-device executor and the plain
reference on the three queries; (b) where every staged stack and every
materialised filter lies, and what a second pass stages; (c) what one
request counts and books, on the guard thread: since ISSUE 36 one
launch, its filter traced inside the mesh kernel; (d) a two-call query and
the fuser's mesh bypass; (e) two callers at once; (f) the configuration
and the manifest; (g) the cell through the harness against a server
child started from the configuration's own TOML."""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from benchmark import datagen, run, traffic
from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.devicehealth import DeviceHealth
from pilosa_tpu.executor.stager import DeviceStager
from pilosa_tpu.parallel.spmd import make_mesh
from pilosa_tpu.server.config import Config
from pilosa_tpu.utils import metrics, profiler, trace

ROOT = run.ROOT
CELL = "ssb20x4.flight1"
DEVICES = 4
DENSE = (1 << 20) // 8


def _file(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CONFIG = _file("benchmark", "configs", "ssb20x4.json")
ONE_CHIP = _file("benchmark", "configs", "ssb10.json")
TOML = os.path.join(ROOT, "benchmark", "configs", "ssb20x4.toml")
MIX = traffic.load(os.path.join(ROOT, "benchmark", "traffic", "flight1.json"))
SMALL = {**CONFIG, "shards": 8}  # two shards a device
SEEDS = [3500000011, 2**31 + 3535]
REVENUE = "lo_revenue_computed"
EMPTY = {"value": 0, "count": 0}


def _q11(year_row, discount=("><", 1, 3), quantity=("<", 25)):
    return ["Sum", REVENUE, ["Intersect", ["Row", "d_year", year_row],
                             ["Range", "lo_discount", *discount], ["Range", "lo_quantity", *quantity]]]


def _q13(week_row, year_row):
    return ["Sum", REVENUE, ["Intersect", ["Row", "d_weeknuminyear", week_row], ["Row", "d_year", year_row],
                             ["Range", "lo_discount", "><", 5, 7], ["Range", "lo_quantity", "><", 26, 35]]]


# beside the cell's own requests: the bounds decide these Ranges on the host
EDGES = {
    "a_week_with_no_line_item": _q13(53 - 1, 1998 - 1992),
    "quantity_under_1_is_empty": _q11(1, quantity=("<", 1)),
    "discount_0_to_10_is_the_existence_plane": _q11(1, discount=("><", 0, 10)),
}


@pytest.fixture(scope="module")
def mesh():
    import jax

    return make_mesh(jax.devices()[:DEVICES])


@pytest.fixture(scope="module")
def build(tmp_path_factory):
    """seed -> (reference, holder), built once a seed."""
    made = {}

    def of(seed):
        if seed not in made:
            data_dir = str(tmp_path_factory.mktemp("ssb_mesh") / "data")
            ref, _ = datagen.build(SMALL, seed, data_dir)
            h = Holder(data_dir)
            h.open()
            made[seed] = ref, h
        return made[seed]

    yield of
    for _, h in made.values():
        h.close()


@pytest.fixture()
def built(build):
    return build(SEEDS[0])


def _mesh_executor(h, mesh, **kw):
    return Executor(h, device_policy="always", mesh=mesh,
                    stager=DeviceStager(budget_bytes=4 << 30, mesh=mesh), **kw)


def _drawn(seed: int, n: int) -> list:
    """``n`` of the cell's 462 requests, a third of each template (of
    Q1.1 no more than its 7)."""
    templates = traffic.by_template(CONFIG, MIX)
    rng = np.random.default_rng([seed, 35])
    return [t[i][0] for t in templates for i in rng.choice(len(t), size=min(n // 3, len(t)), replace=False)]


def _answer(ex, call) -> dict:
    (r,) = ex.execute(SMALL["index"], traffic.pql(call))
    return {"value": r.val, "count": r.count}


def _counter(name: str, **labels) -> float:
    return metrics.snapshot().get(metrics._flat_key(name, metrics._labels_key(labels)), 0)


def _executions(kind: str) -> float:
    hist = metrics.snapshot().get(
        metrics._flat_key(metrics.SPMD_EXECUTE_SECONDS + ".hist", metrics._labels_key({"kind": kind}))
    )
    return hist["count"] if hist else 0


OPS = ("range", "and", "or", "xor", "andnot")


def _by_op(name: str) -> dict:
    snap = metrics.snapshot()
    return {op: snap.get(metrics._flat_key(name, metrics._labels_key({"op": op})), 0) for op in OPS}


def _launches() -> dict:
    return _by_op(metrics.FILTER_LAUNCHES)


def _grown(now: dict, was: dict) -> dict:
    return {k: now[k] - was[k] for k in now if now[k] != was[k]}


def _quarters(arr) -> list:
    return sorted((s.device.id, s.data.shape[0]) for s in arr.addressable_shards)


def _a_quarter_a_device(arr, mesh) -> bool:
    return _quarters(arr) == [(d.id, SMALL["shards"] // DEVICES) for d in mesh.devices.flat]


# -- (a) the three paths agree ------------------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_mesh_one_device_and_reference_agree_on_flight_1(build, mesh, seed):
    ref, h = build(seed)
    one = Executor(h, device_policy="always")
    four = _mesh_executor(h, mesh)
    try:
        calls = _drawn(seed, 12) + list(EDGES.values())
        assert len({c[2][0] for c in calls}) == 1 and len({len(c[2]) for c in calls}) == 2  # Q1.1/Q1.2 and Q1.3
        answered = []
        for call in calls:
            want = ref.answer(call)
            got_four, got_one = _answer(four, call), _answer(one, call)
            assert got_four == want, (traffic.pql(call), got_four, want)
            assert got_one == want, (traffic.pql(call), got_one, want)
            answered.append(want)
        assert ref.answer(EDGES["a_week_with_no_line_item"]) == EMPTY
        assert ref.answer(EDGES["quantity_under_1_is_empty"]) == EMPTY
        whole, band = ref.answer(EDGES["discount_0_to_10_is_the_existence_plane"]), ref.answer(_q11(1))
        assert whole["count"] > 20 * band["count"] / 11  # 11 discounts where 3 were
        assert sum(1 for a in answered if a["count"]) >= 12  # the drawn ones hold line items
        # both traced the compares into the sum's program: one a filter structure
        # (Q1.1, Q1.2, Q1.3, and the two edges the bounds decide), a shard_map kernel on the mesh
        assert not four._range_jits and not one._range_jits and not four._tree_jits
        assert {k[0] for k in four._spmd_kernels} == {"plane_counts"} and {k[0] for k in one._tree_jits} == {"bsi_sum"}
        assert {k[1:] for k in four._spmd_kernels} == {k[1:] for k in one._tree_jits} and len(one._tree_jits) == 5
    finally:
        one.close()
        four.close()


def test_a_count_on_the_mesh_equals_the_reference_and_books_its_fetch(built, mesh):
    ref, h = built
    ex = _mesh_executor(h, mesh)
    try:
        call = ["Count", ["Intersect", ["Row", "d_year", 2], ["Range", "lo_quantity", "><", 26, 35]]]
        q = traffic.pql(call)
        assert ex.execute(SMALL["index"], q)[0] == ref.answer(call) > 0
        wf: dict = {}
        with trace.attrib_activate(wf):
            ex.execute(SMALL["index"], q)
        assert wf[trace.WF_MESH_FETCH] > 0.0 and trace.WF_TRANSFER_DECODE not in wf
    finally:
        ex.close()


# -- (b) placement, and what a second pass stages -----------------------------


def test_a_second_pass_stages_nothing_and_every_stack_and_filter_lies_a_quarter_a_device(built, mesh):
    import jax

    _, h = built
    ex = _mesh_executor(h, mesh)
    try:
        calls = _drawn(SEEDS[0], 9) + list(EDGES.values())
        queries = [traffic.pql(c) for c in calls]
        for q in queries:
            ex.execute(SMALL["index"], q)
        st = ex.stager
        before = (st.misses, st._bytes, _counter(metrics.STAGER_RESTAGED_BYTES), _counter(metrics.STAGER_MISSES))
        for q in queries:
            ex.execute(SMALL["index"], q)
        assert (st.misses, st._bytes, _counter(metrics.STAGER_RESTAGED_BYTES),
                _counter(metrics.STAGER_MISSES)) == before
        # three plane stacks (depth 4, 6, 27) and the date rows the calls named
        arrays = [a for ent in st._cache.values() for a in jax.tree_util.tree_leaves(ent.value)
                  if isinstance(a, jax.Array)]
        depths = sorted(a.shape[1] for a in arrays if a.ndim == 3)
        assert depths == [4 + 1, 6 + 1, 27 + 1]
        rows = {(c[1], c[2]) for call in calls for c in call[2][1:] if c[0] == "Row"}
        assert len(arrays) == 3 + len(rows) == len(st._cache)
        assert st._bytes == (5 + 7 + 28 + len(rows)) * SMALL["shards"] * DENSE
        for a in arrays:
            assert a.shape[0] == SMALL["shards"] and _a_quarter_a_device(a, mesh), (a.shape, _quarters(a))
        # every materialised filter (a TopN's source still reads one): compares, folds,
        # the existence plane's copy
        from pilosa_tpu.pql import parse

        for call in calls:
            child = parse(traffic.pql(call)).calls[0].children[0]
            filt = ex._device_bitmap_stack(SMALL["index"], child, list(range(SMALL["shards"])))
            if isinstance(filt, jax.Array):
                assert filt.shape == (SMALL["shards"], DENSE // 4)
                assert _a_quarter_a_device(filt, mesh), (traffic.pql(call), _quarters(filt))
            else:  # an all-zero filter is decided on the host and shipped as it is
                assert call is EDGES["quantity_under_1_is_empty"] and not filt.any()
    finally:
        ex.close()


# -- (c) what one request counts and books ------------------------------------


def test_one_request_is_one_launch_with_its_filter_inlined_and_one_fetch_on_the_guard_thread(built, mesh):
    """The server's default executor runs a read on the device health
    gate's pool thread: every leg must land in the request's waterfall
    from there, once. The filter is structure inside the mesh kernel:
    nothing is launched ahead of the sum."""
    from pilosa_tpu.pql import parse

    _, h = built
    ex = _mesh_executor(h, mesh, health=DeviceHealth(timeout_s=120.0))
    try:
        for call, want, date_rows in ((_q11(3), {"range": 2, "and": 2}, 1), (_q13(5, 2), {"range": 2, "and": 3}, 2)):
            parsed = parse(traffic.pql(call))
            ex.execute(SMALL["index"], parsed)  # stage and compile
            legs = []
            real = trace.leg

            def spy(stage):
                lg = real(stage)
                if stage == trace.WF_MESH_FETCH:
                    legs.append(lg)
                return lg

            before = (_launches(), _by_op(metrics.FILTER_INLINED), _executions("plane_counts"),
                      _counter(metrics.KERNEL_OPERAND_BYTES, kind="bsi_range"),
                      _counter(metrics.KERNEL_OPERAND_BYTES, kind="plane_counts"))
            wf: dict = {}
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(trace, "leg", spy)
                with trace.attrib_activate(wf):
                    t0 = time.monotonic()
                    ex.execute(SMALL["index"], parsed)
                    total = time.monotonic() - t0
            assert _grown(_by_op(metrics.FILTER_INLINED), before[1]) == want and sum(want.values()) in (4, 5)
            assert _grown(_launches(), before[0]) == {}
            assert _executions("plane_counts") == before[2] + 1
            assert _counter(metrics.KERNEL_OPERAND_BYTES, kind="bsi_range") == before[3]
            # the one program's: lo_revenue_computed's 27 + 1 planes, lo_discount's 4 + 1,
            # lo_quantity's 6 + 1, the date rows, and the predicates' u32 vector
            preds = 3 if date_rows == 1 else 4
            assert (_counter(metrics.KERNEL_OPERAND_BYTES, kind="plane_counts") - before[4]
                    == (28 + 5 + 7 + date_rows) * SMALL["shards"] * DENSE + 4 * preds)
            assert len(legs) == 1
            assert wf[trace.WF_MESH_FETCH] == pytest.approx(legs[0].seconds) and legs[0].seconds > 0.0
            assert wf[trace.WF_FILTER_EVAL] > 0.0 and wf[trace.WF_DEVICE_COMPUTE] > 0.0
            assert trace.WF_TRANSFER_DECODE not in wf  # the copy is the mesh's leg here
            summary = profiler.WATERFALL.summarize(wf, total)
            assert summary["stages"][trace.WF_MESH_FETCH] > 0.0
            assert sum(v for k, v in wf.items() if not k.startswith("_")) <= total * 1.001
    finally:
        ex.close()


# -- (d) a two-call query: the fuser stands down ------------------------------


def test_a_two_call_query_on_the_mesh_counts_the_bypass_and_answers_as_two_single_calls(built, mesh):
    ref, h = built
    ex = _mesh_executor(h, mesh)
    try:
        a, b = _q11(4), _q13(7, 1)
        lone = [_answer(ex, a), _answer(ex, b)]
        bypasses = _counter(metrics.FUSION_BYPASSES, reason="mesh")
        fused = _counter(metrics.FUSION_FUSED_LAUNCHES)
        sums = _executions("plane_counts")
        got = ex.execute(SMALL["index"], traffic.pql(a) + traffic.pql(b))
        assert [{"value": r.val, "count": r.count} for r in got] == lone == [ref.answer(a), ref.answer(b)]
        assert _counter(metrics.FUSION_BYPASSES, reason="mesh") == bypasses + 1
        assert ex.fuser.bypasses == {"mesh": 1}
        assert _counter(metrics.FUSION_FUSED_LAUNCHES) == fused and not ex.fuser._programs
        assert _executions("plane_counts") == sums + 2  # call by call
        # a single call never reaches the fuser
        _answer(ex, a)
        assert _counter(metrics.FUSION_BYPASSES, reason="mesh") == bypasses + 1
    finally:
        ex.close()


# -- (e) two callers at once --------------------------------------------------


def test_two_threads_through_one_mesh_executor_finish_and_agree_with_the_reference(built, mesh):
    """Two clients of the cell: each request's launches span the four
    devices and end in a collective. With two runner slots each request
    leads its own wave (ISSUE 38), so two ``shard_map`` launches run
    side by side on the clients' threads; a wave of two forms only
    while both slots compute, and runs call by call after the bypass.
    Nothing may hang and every answer is the reference's."""
    ref, h = built
    ex = _mesh_executor(h, mesh, health=DeviceHealth(timeout_s=120.0))
    calls = _drawn(SEEDS[0] + 1, 30)
    assert len(calls) == 7 + 10 + 10
    want = {traffic.pql(c): ref.answer(c) for c in calls}
    per_thread = 50
    got: list[list] = [[], []]
    errors: list = []

    def client(k: int):
        try:
            for i in range(per_thread):
                q = traffic.pql(calls[(k * 7 + i * (k + 1)) % len(calls)])
                (r,) = ex.execute(SMALL["index"], q)
                got[k].append((q, {"value": r.val, "count": r.count}))
        except BaseException as e:  # noqa: BLE001 - reported below, with the thread joined
            errors.append(e)

    try:
        for c in calls[:3]:
            _answer(ex, c)  # compile outside the race
        waves = ex.dispatch_engine.stats()
        bypasses = _counter(metrics.FUSION_BYPASSES, reason="mesh")
        threads = [threading.Thread(target=client, args=(k,), daemon=True) for k in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert not any(t.is_alive() for t in threads), "a caller hangs on the mesh"
        assert not errors, errors
        assert [len(g) for g in got] == [per_thread, per_thread]
        for q, answer in got[0] + got[1]:
            assert answer == want[q], q
        # two clients, two slots: nobody queued, every request led
        engine = ex.dispatch_engine
        after = engine.stats()
        assert after["led"] - waves["led"] == 2 * per_thread and after["handed"] == waves["handed"]
        assert after["combined_items"] == waves["combined_items"]
        assert _counter(metrics.FUSION_BYPASSES, reason="mesh") == bypasses
        # both slots computing: the two queue, one wave of two, bypassed and not fused
        pair = [traffic.pql(calls[0]), traffic.pql(calls[-1])]
        held: dict = {}
        for _ in range(engine.max_inflight):
            assert engine._slots.acquire(timeout=10)
        threads = [threading.Thread(target=lambda q=q: held.update({q: ex.execute(SMALL["index"], q)}), daemon=True)
                   for q in pair]
        for t in threads:
            t.start()
        while engine.stats()["queued"] < 2:
            time.sleep(0.002)
        for _ in range(engine.max_inflight):
            engine._slots.release()
        for t in threads:
            t.join(timeout=240)
        assert not any(t.is_alive() for t in threads), "a wave of two hangs on the mesh"
        for q in pair:
            (r,) = held[q]
            assert {"value": r.val, "count": r.count} == want[q], q
        last = engine.stats()
        assert last["handed"] == after["handed"] + 1 and last["combined_items"] == after["combined_items"] + 2
        assert _counter(metrics.FUSION_BYPASSES, reason="mesh") == bypasses + 1
        assert last["fallbacks"] == waves["fallbacks"] and not ex.fuser._programs
    finally:
        ex.close()


# -- (f) the configuration and the manifest -----------------------------------


def test_the_configuration_is_ssb10s_schema_behind_the_mesh_toml():
    cfg = Config.from_toml(TOML)
    assert (cfg.device_policy, cfg.mesh_devices) == ("always", DEVICES)
    assert cfg.stager_budget_bytes == DEVICES * Config().stager_budget_bytes
    assert CONFIG["server_flags"] == ["-c", os.path.relpath(TOML, ROOT)]
    assert CONFIG["fields"] == ONE_CHIP["fields"] and CONFIG["index"] == ONE_CHIP["index"] == "ssb"
    assert CONFIG["chips"] == DEVICES and CONFIG["shards"] == 116 == DEVICES * 29 == 2 * ONE_CHIP["shards"]
    assert CONFIG["architecture"] is None
    assert set(CONFIG["guarantees"]) == {"answers", "availability", "placement", "replication", "durability"}
    assert sorted(CONFIG["reduced"]) == ["fields", "flights", "scale"]
    assert {k: CONFIG["reduced"][k] for k in ("fields", "flights")} == ONE_CHIP["reduced"]
    assert all(w in CONFIG["reduced"]["scale"] for w in ("SF=100", "SF=20", "573", "116", "360 s"))
    # everything ssb10 assumes of the data, and the TOML's three settings
    for k in ("dates", "measures", "lo_revenue_computed", "constants"):
        assert CONFIG["assumed"][k] == ONE_CHIP["assumed"][k]
    assert {"scale", "topology", "device-policy", "mesh-devices", "stager-budget-bytes"} <= set(CONFIG["assumed"])
    assert "121,634,816" in CONFIG["assumed"]["scale"] and 116 << 20 == 121_634_816
    # the cell's traffic is ssb10.flight1's file, and the same 462 requests
    assert traffic.pool(CONFIG, MIX) == traffic.pool(ONE_CHIP, MIX)
    # what the stager holds: 40 planes in three stacks and the 144 date rows, dense
    staged = (5 + 7 + 28 + 7 + 84 + 53) * CONFIG["shards"] * DENSE
    assert staged == 2 * 1_398_800_384 and staged / DEVICES < 0.05 * 16_909_334_528


def test_the_manifest_names_the_cell_with_four_chips_and_the_lists():
    manifest = _file("BENCHMARK.json")
    cell, entry = run.find_cell(manifest, CELL)
    assert cell == {**cell, "config": "ssb20x4", "traffic": "flight1", "chips": DEVICES} and len(cell["why"]) <= 200
    assert entry["file"] == "benchmark/configs/ssb20x4.json" and entry["reduced"] == sorted(CONFIG["reduced"])
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert all(w in entry["source"] for w in ("configs[4]", "SF=100", "Star Schema Benchmark", "flight 1", "SF=20"))
    # where they were appended, behind the four cells before them; later cells come after
    assert manifest["workloads"][4] is cell and manifest["configs"][4] is entry
    assert [w["name"] for w in manifest["workloads"][:5]] == [
        "tall64.topn", "taxi96.dashboard", "tall128x4.topn", "ssb10.flight1", CELL]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 2
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    new = by_name["executor.fusion_mesh_bypasses_per_query"]
    # appended behind ISSUE 34's, which is the last of the five that list this cell
    at = manifest["per_layer"].index(new)
    assert manifest["per_layer"][at - 1]["name"] == "executor.filter_inlined_per_query"
    assert new == {
        "name": "executor.fusion_mesh_bypasses_per_query", "unit": "count/query", "better": "lower",
        "source": "program_counter", "layer": "executor routing", "moves": "query_p50_ms", "workloads": [CELL],
    }
    spec = run.layer_metrics.load(new["name"])
    assert spec["numerator"] == [{"metric": "fusion.bypasses", "labels": {"reason": "mesh"}}]
    assert (spec["per"], spec["scale"]) == ("request", 1) and "fusion.bypasses" in metrics.METRICS
    assert by_name["executor.fallbacks"]["layer"] == new["layer"]
    accepted = ["tall64.topn", "taxi96.dashboard", "tall128x4.topn", "ssb10.flight1"]
    # one chip's peak whatever ran: it would read four times the share here
    assert by_name["kernels.hbm_roofline"]["workloads"] == accepted
    assert by_name["kernels.hbm_roofline_per_chip"]["workloads"] == ["tall128x4.topn", CELL]
    for name in ("executor.filter_eval_ms", "executor.filter_launches_per_query", "executor.range_launches_per_query",
                 "kernels.bsi_range_operand_mb_per_query", "executor.filter_inlined_per_query"):
        assert by_name[name]["workloads"] == ["ssb10.flight1", "taxi96.dashboard", CELL]
    here = {m["name"] for m in run.metrics_of(manifest, "per_layer", CELL)}
    assert "kernels.hbm_roofline" not in here
    assert {"kernels.hbm_roofline_per_chip", "executor.mesh_fetch_ms", "executor.fallbacks", new["name"]} <= here
    for other in accepted:
        assert new["name"] not in {m["name"] for m in run.metrics_of(manifest, "per_layer", other)}


# -- (g) the cell through the harness -----------------------------------------


def _run_cell(monkeypatch, tmp_path, **hooks):
    """One rehearsal of the cell at 4 shards: a real server child from
    the configuration's TOML on four virtual devices."""
    # the harness refuses to spawn from a process that imported JAX (it
    # would hold the chip); this worker's JAX is held to the CPU
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    monkeypatch.setenv("XLA_FLAGS", f"--xla_force_host_platform_device_count={DEVICES}")
    monkeypatch.setattr(run, "SCRATCH", str(tmp_path / "scratch"))
    monkeypatch.setattr(run, "WARM_ROUND_S", 0.5)
    lines = []
    monkeypatch.setattr(run, "emit", lambda phase, **kw: lines.append({"phase": phase, **kw}))
    args = run.parse_args([
        "--workload", CELL, "--seed", str(2**31 + 35), "--seconds", "3",
        "--trace", "0", "--allow-cpu", "--shards", "4",
    ])
    return run.run_cell(args, **hooks), {ln["phase"]: ln for ln in lines}


def test_the_cell_runs_correct_on_a_four_device_mesh(monkeypatch, tmp_path):
    out, phases = _run_cell(monkeypatch, tmp_path)
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] == out["checks"]["compared"]["value"] > 0  # every answer compared
    assert out["device"]["count"] == DEVICES
    assert phases["serve"]["build_info"]["device_count"] == str(DEVICES)
    warm = phases["warm_up"]
    assert warm["distinct_requests"] == 462 and warm["clients"] == 2
    # one mesh kernel a filter structure (Q1.1, Q1.2, Q1.3), the compares inside; nothing is fused on a mesh
    by_kind = warm["compiles_by_kind"]
    assert by_kind["plane_counts"] == 3 and "bsi_range" not in by_kind
    assert "fused_query" not in by_kind and "bsi_sum" not in by_kind
    assert warm["rounds_compiled"][-2:] == [(0, 0), (0, 0)]
    w = phases["window"]
    assert w["server_exit_code"] == 0 and w["fallbacks_in_window"] == {}
    assert w["compiles_in_window"] == 0 and w["stager_restaged_bytes_in_window"] == 0
    stages = w["stage_ms_per_request"]
    assert stages["mesh.fetch"] > 0 and stages["filter.eval"] > 0 and stages["stager"] == 0
    assert not stages.get("transfer.decode")  # the replicated result's copy is the mesh's leg


def test_a_planted_wrong_sum_makes_the_cell_incorrect(monkeypatch, tmp_path):
    out, _ = _run_cell(monkeypatch, tmp_path, server_module="benchmark.tests.faulty_sum_server")
    assert out["correct"] is False
    assert out["failed"] == out["checks"]["wrong_answers"]["value"] > 0
    assert out["checks"]["unanswered"]["value"] == 0
