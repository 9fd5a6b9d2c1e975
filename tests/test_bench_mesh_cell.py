"""The four-chip deployment ``tall128x4`` and its cell ``tall128x4.topn``
(ISSUE 29), on four virtual CPU devices: the cell through the harness
against a server child started from the configuration's own TOML, the
mesh path against the plain reference at a small size, the bytes the
configuration stages against its budget, what stays staged between two
passes of the cell's requests, the per-chip roofline reading, and the
``mesh.fetch`` leg."""

import json
import os
import sys
import threading
import time

import pytest

from benchmark import datagen, run, traffic
from benchmark.reducers import hbm_roofline, hbm_roofline_per_chip
from benchmark.reference import same_answer
from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import executor as executor_mod
from pilosa_tpu.executor.devicehealth import DeviceHealth
from pilosa_tpu.executor.stager import DeviceStager
from pilosa_tpu.parallel.spmd import make_mesh
from pilosa_tpu.server.config import Config
from pilosa_tpu.utils import metrics, profiler, trace

ROOT = run.ROOT
CELL = "tall128x4.topn"
DEVICES = 4
BLOCK_BYTES = 8192  # one 2^16-bit container block


def _file(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CONFIG = _file("benchmark", "configs", "tall128x4.json")
TOML = os.path.join(ROOT, "benchmark", "configs", "tall128x4.toml")


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


# -- (c) the configuration's files --------------------------------------------


def test_the_toml_loads_and_its_budget_holds_what_the_configuration_stages():
    """Counted from the file's own numbers: per shard the head chunk is
    the first FIRST_CHUNK hot rows, and the cell's walks, their
    thresholds fixed by the head, end the second at the last hot row
    (PR 32: it held the ladder's SCORE_CHUNK ids, singletons up to
    there, before). A hot row fills all 16 blocks, a singleton one.
    Blocks are padded to a power of two a shard. A walk that knew no
    end would stage the ladder's second chunk, and going on its third,
    2 x SCORE_CHUNK singletons: the TOML's budget holds those too, one
    default share does not. What the cell's own walks stage is under
    one default share since PR 32 (PERF.md section 7)."""
    cfg = Config.from_toml(TOML)
    assert cfg.device_policy == "always" and cfg.mesh_devices == DEVICES
    assert CONFIG["server_flags"] == ["-c", os.path.relpath(TOML, ROOT)]
    assert CONFIG["chips"] == DEVICES and CONFIG["shards"] % DEVICES == 0
    f = datagen.field_of(CONFIG, "f")
    shards, hot = CONFIG["shards"], f["hot_rows"]
    first, second = executor_mod.FIRST_CHUNK, executor_mod.SCORE_CHUNK
    assert hot > first and f["hot_bits"] > 16 * 1024  # every block of a hot row is set
    assert f["tail_rows"] >= first + second + 2 * second  # the ranked cache reaches the third chunk
    assert executor_mod._bounded_chunk_size(first, hot) == hot - first == first
    per_shard = (
        _pow2(16 * first),
        _pow2(16 * (hot - first)),
        _pow2(16 * (hot - first) + second - (hot - first)),
        _pow2(2 * second),
    )
    head, bounded, ladder2, ladder3 = [shards * b * (BLOCK_BYTES + 8) for b in per_shard]  # + brow, bslot
    row_stacks = 16 * shards * (1 << 20) // 8  # the 16 group-base filter rows
    assert head == bounded == pytest.approx(2 << 30, rel=0.01)
    assert ladder2 == ladder3 == pytest.approx(8 << 30, rel=0.01)
    assert head + bounded + row_stacks < Config().stager_budget_bytes < head + ladder2 + row_stacks
    assert head + ladder2 + ladder3 + row_stacks < cfg.stager_budget_bytes == DEVICES * Config().stager_budget_bytes


def test_the_manifest_names_the_cell_with_four_chips_and_its_metrics():
    manifest = _file("BENCHMARK.json")
    cell, entry = run.find_cell(manifest, CELL)
    assert cell["chips"] == DEVICES and cell["traffic"] == "topn"
    assert entry["file"] == "benchmark/configs/tall128x4.json" and entry["reduced"] == sorted(CONFIG["reduced"])
    assert CONFIG["fields"] == _file("benchmark", "configs", "tall64.json")["fields"]
    names = {m["name"] for m in run.metrics_of(manifest, "per_layer", CELL)}
    assert {"kernels.hbm_roofline_per_chip", "executor.mesh_fetch_ms", "kernels.hbm_roofline"} <= names
    assert "shards" in CONFIG["assumed"]  # twice the source's 64: stated, not listed as a cut
    one_chip = {m["name"] for m in run.metrics_of(manifest, "per_layer", "tall64.topn")}
    assert "kernels.hbm_roofline_per_chip" not in one_chip
    # pass 2's counter moves query_p50_ms, which every cell reports: no list
    assert "executor.topn_pass2_vector_ids_per_query" in names & one_chip


# -- (e) the per-chip reading -------------------------------------------------


@pytest.mark.parametrize("planes", [1, DEVICES])
def test_per_chip_roofline_divides_by_the_planes_that_were_traced(planes):
    trace_ = {  # a recorded reduction's numbers (chiprun_out, PR 28's four-plane slice)
        "device_planes": [f"/device:TPU:{i}" for i in range(planes)],
        "window_s": 4.963919627, "busy_s": 1.06244107225, "slice_s": 5.000373077,
        "slice_requests": 25, "slice_bytes": 2.41e11, "peak_hbm_bytes_per_s": 819e9,
    }
    whole = hbm_roofline.read(trace_)
    assert hbm_roofline_per_chip.read(trace_) == pytest.approx(whole / planes, rel=1e-12)
    assert hbm_roofline_per_chip.read({**trace_, "busy_s": None}) is None
    assert hbm_roofline_per_chip.read({k: v for k, v in trace_.items() if k != "device_planes"}) is None


# -- (a) the cell through the harness -----------------------------------------


def _run_cell(monkeypatch, tmp_path, **hooks):
    """One rehearsal of the cell at 8 shards: a real server child from
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
        "--workload", CELL, "--seed", str(2**31 + 29), "--seconds", "3",
        "--trace", "0", "--allow-cpu", "--shards", "8",
    ])
    return run.run_cell(args, **hooks), {ln["phase"]: ln for ln in lines}


def test_the_cell_runs_correct_on_a_four_device_mesh(monkeypatch, tmp_path):
    out, phases = _run_cell(monkeypatch, tmp_path)
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] == out["checks"]["compared"]["value"] > 0  # every answer compared
    assert out["device"]["count"] == DEVICES
    assert phases["serve"]["build_info"]["device_count"] == str(DEVICES)
    w = phases["window"]
    assert w["server_exit_code"] == 0 and w["fallbacks_in_window"] == {}
    assert w["compiles_in_window"] == 0 and w["stager_restaged_bytes_in_window"] == 0
    # the second chunk is the hot rows the head left: the head's shape, one program
    assert phases["warm_up"]["compiles_by_kind"].get("topn_scores_sparse") == 1
    assert w["stage_ms_per_request"]["mesh.fetch"] > 0
    assert w["stage_ms_per_request"]["stager"] == 0


def test_a_planted_wrong_topn_count_makes_the_cell_incorrect(monkeypatch, tmp_path):
    out, _ = _run_cell(monkeypatch, tmp_path, server_module="benchmark.tests.faulty_server")
    assert out["correct"] is False
    assert out["failed"] == out["checks"]["wrong_answers"]["value"] > 0
    assert out["checks"]["unanswered"]["value"] == 0


# -- (b), (d), (f): the mesh path in this process -----------------------------

SMALL = {
    **{k: CONFIG[k] for k in ("name", "index")},
    "shards": 8,
    "fields": [
        # the cell's shape, thinner: 256 hot rows in groups of 16 (more than
        # FIRST_CHUNK, so the walk enters a second chunk), 6,000 singletons
        {**datagen.field_of(CONFIG, "f"), "hot_bits": 3000, "tail_rows": 6000},
    ],
}


@pytest.fixture(scope="module")
def mesh():
    import jax

    return make_mesh(jax.devices()[:DEVICES])


SEEDS = [2900000011, 2**31 + 2929]


@pytest.fixture(scope="module")
def build(tmp_path_factory):
    """seed -> (reference, holder, the cell's calls), built once a seed."""
    made = {}

    def of(seed):
        if seed not in made:
            data_dir = str(tmp_path_factory.mktemp("tall_small") / "data")
            ref, _ = datagen.build(SMALL, seed, data_dir)
            h = Holder(data_dir)
            h.open()
            mix = traffic.load(os.path.join(ROOT, "benchmark", "traffic", "topn.json"))
            made[seed] = ref, h, [c for c, _ in traffic.pool(SMALL, mix)]
        return made[seed]

    yield of
    for _, h, _ in made.values():
        h.close()


@pytest.fixture()
def built(build):
    return build(SEEDS[0])


def _mesh_executor(h, mesh, **kw):
    return Executor(h, device_policy="always", mesh=mesh,
                    stager=DeviceStager(budget_bytes=4 << 30, mesh=mesh), **kw)


@pytest.mark.parametrize("seed", SEEDS)
def test_mesh_and_one_chip_paths_equal_the_plain_reference(build, mesh, seed):
    ref, h, calls = build(seed)
    one = Executor(h, device_policy="always")
    four = _mesh_executor(h, mesh)
    try:
        assert len(calls) == 16
        for call in calls:
            want = ref.answer(call)
            q = traffic.pql(call)
            got_one, got_four = one.execute(SMALL["index"], q)[0], four.execute(SMALL["index"], q)[0]
            assert len(want) == 10
            assert same_answer(call, got_four, want), (q, got_four, want)
            assert same_answer(call, got_one, want), (q, got_one, want)
            assert same_answer(call, got_four, got_one)
        # the walk went past the head chunk on the mesh, as far as the last
        # hot row: two chunks of the head's size, one compiled program
        staged = sorted(k[-2] for k in four.stager._cache if "sparse_rows_stack" in k)
        assert staged[:2] == [128, 128]
        # at this size two rows can meet in one column: a threshold of 1 is
        # not over the one-bit tail, and that walk reads its whole list, the
        # ladder's chunk and then one to the list's end (6,256)
        assert staged[2:] in ([], [2048, 4096])
        sizes = {k[1] for k in four._spmd_kernels if k[0] == "topn_scores_sparse"}
        assert sizes == set(staged)
        assert not any(k[0] == "topn_scores_sparse" for k in one._spmd_kernels)
        # and on one device no second goes to the mesh's leg
        wf: dict = {}
        with trace.attrib_activate(wf):
            one.execute(SMALL["index"], traffic.pql(calls[0]))
        assert wf.get(trace.WF_DEVICE_COMPUTE, 0.0) > 0.0 and trace.WF_MESH_FETCH not in wf
    finally:
        one.close()
        four.close()


def _counter(name: str, **labels) -> float:
    return metrics.snapshot().get(metrics._flat_key(name, metrics._labels_key(labels)), 0)


def test_a_second_pass_stages_nothing_and_every_stack_lies_a_quarter_a_device(built, mesh):
    """And no pass stages a third chunk ahead: every walk of the cell
    ends in the second, and ends it (128 hot rows; the singletons
    behind them are under any threshold), one bounded chunk a request. At 128 shards that chunk is 8 GiB, and its
    assembly on a side thread ran through the first half of the
    measured window (my chip run, PR 29)."""
    _, h, calls = built
    ex = _mesh_executor(h, mesh)
    try:
        queries = [traffic.pql(c) for c in calls]
        starts = _counter(metrics.TOPN_PREFETCH_STARTS)
        chunks = [_counter(metrics.TOPN_CHUNKS, how=how) for how in ("head", "bounded", "ladder")]
        for q in queries:
            ex.execute(SMALL["index"], q)
        assert _counter(metrics.TOPN_PREFETCH_STARTS) == starts
        grown = [_counter(metrics.TOPN_CHUNKS, how=how) for how in ("head", "bounded", "ladder")]
        assert [g - c for g, c in zip(grown, chunks)] == [len(queries), len(queries), 0]
        assert not any(t.name == "stage-prefetch" for t in threading.enumerate())
        st = ex.stager
        before = (st.misses, st._bytes, _counter(metrics.STAGER_RESTAGED_BYTES),
                  _counter(metrics.STAGER_MISSES))
        for q in queries:
            ex.execute(SMALL["index"], q)
        assert (st.misses, st._bytes, _counter(metrics.STAGER_RESTAGED_BYTES),
                _counter(metrics.STAGER_MISSES)) == before
        # 16 filter-row stacks, the head bundle and the second
        import jax

        arrays = [a for ent in st._cache.values() for a in jax.tree_util.tree_leaves(ent.value)
                  if isinstance(a, jax.Array)]
        assert len(st._cache) == 16 + 2 and len(arrays) == 16 + 2 * 3
        for a in arrays:
            assert a.shape[0] == SMALL["shards"]
            held = sorted((s.device.id, s.data.shape[0]) for s in a.addressable_shards)
            assert held == [(d.id, SMALL["shards"] // DEVICES) for d in mesh.devices.flat], a.shape
    finally:
        ex.close()


def test_mesh_fetch_is_credited_once_on_the_guard_thread(built, mesh):
    """The server's default executor runs a read on the device health
    gate's pool thread: the leg must land in the request's waterfall
    from there, once. (Without a mesh it stays 0: the test above.)"""
    from pilosa_tpu.pql import parse

    _, h, calls = built
    ex = _mesh_executor(h, mesh, health=DeviceHealth(timeout_s=120.0))
    try:
        parsed = parse(traffic.pql(calls[0]))
        ex.execute(SMALL["index"], parsed)  # stage and compile
        legs = []
        real = trace.leg

        def spy(stage):
            lg = real(stage)
            if stage == trace.WF_MESH_FETCH:
                legs.append(lg)
            return lg

        wf: dict = {}
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(trace, "leg", spy)
            with trace.attrib_activate(wf):
                t0 = time.monotonic()
                ex.execute(SMALL["index"], parsed)
                total = time.monotonic() - t0
        assert len(legs) == 2  # head and second chunk; pass 2 rides the carry
        assert wf[trace.WF_MESH_FETCH] == pytest.approx(sum(lg.seconds for lg in legs))
        assert wf[trace.WF_MESH_FETCH] > 0.0 and wf.get(trace.WF_DEVICE_COMPUTE, 0.0) > 0.0
        summary = profiler.WATERFALL.summarize(wf, total)
        assert summary["stages"][trace.WF_MESH_FETCH] > 0.0
        assert summary["stages"].get(trace.WF_OTHER, 0.0) < summary["total_ms"] / 5, summary
        assert sum(v for k, v in wf.items() if not k.startswith("_")) <= total * 1.001
    finally:
        ex.close()
