"""The waterfall's legs (ISSUE 26): ``trace.leg`` itself, the hand-over
across the device-guard thread, the capture's options, compiles counted
wherever they happen, the batched scorers' kernel accounting, and the
benchmark's per-layer metric files against the names the program
publishes. ISSUE 37: the launch apart from the wait at the three sites
that launch, and the three hand-backs booked from the worker's stamp.
ISSUE 38: a request that leads its own dispatch wave: its legs on the
submitter's thread, its queue wait and its hand-back read off one
thread's clock. Last, a GroupBy panel's lowering and its answer's
assembly, each one leg at both device sites."""

import glob
import itertools
import json
import os
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from pilosa_tpu import SHARD_WIDTH
from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor, batcher, devicehealth, dispatch
from pilosa_tpu.executor import executor as executor_mod
from pilosa_tpu.executor.batcher import BatchedScorer
from pilosa_tpu.executor.devicehealth import DeviceHealth
from pilosa_tpu.server import pipeline
from pilosa_tpu.utils import metrics, profiler, trace

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _metric(name: str, **labels) -> float:
    """A counter's value, or a summary's count, from the registry."""
    key = metrics._flat_key(name, metrics._labels_key(labels))
    snap = metrics.snapshot()
    if key in snap:
        return snap[key]
    hist = snap.get(metrics._flat_key(name + ".hist", metrics._labels_key(labels)))
    return hist["count"] if hist else 0


def _seconds(name: str, **labels) -> float:
    """A summary's sum from the registry."""
    hist = metrics.snapshot().get(
        metrics._flat_key(name + ".hist", metrics._labels_key(labels))
    )
    return hist["sum"] if hist else 0.0


# -- the primitive ------------------------------------------------------------


def test_leg_credits_each_second_once_to_the_innermost_leg():
    wf: dict = {}
    with trace.attrib_activate(wf):
        with trace.leg(trace.WF_TOPN_WALK) as outer:
            time.sleep(0.02)
            with trace.leg(trace.WF_DEVICE_COMPUTE) as inner:
                time.sleep(0.03)
    assert inner.seconds >= 0.03 and outer.seconds >= 0.05
    assert wf[trace.WF_DEVICE_COMPUTE] == pytest.approx(inner.seconds)
    assert wf[trace.WF_TOPN_WALK] == pytest.approx(outer.seconds - inner.seconds)
    assert sum(wf.values()) == pytest.approx(outer.seconds)


@pytest.mark.parametrize("stamp", ["sooner", "later"])
def test_leg_ends_at_another_threads_stamp_where_that_is_sooner(stamp):
    wf: dict = {}
    with trace.attrib_activate(wf):
        with trace.leg(trace.WF_GUARD_QUEUE) as lg:
            mid = time.monotonic() + (0.0 if stamp == "sooner" else 1.0)
            time.sleep(0.02)
            lg.until = mid
    if stamp == "sooner":
        assert lg.seconds == mid - lg.t0 < 0.02
    else:
        assert 0.02 <= lg.seconds < 1.0
    assert wf[trace.WF_GUARD_QUEUE] == pytest.approx(lg.seconds)


def test_guard_queue_ends_when_the_worker_picks_the_call_up():
    """Not when the caller wakes: the worker holds the interpreter's
    lock by then and is inside the call's first legs, so the seconds
    between were counted twice and a request's legs summed to more than
    its wall (PR 32: in half the lone runs of
    test_bench_mesh_cell.py::test_mesh_fetch_is_credited_once_on_the_guard_thread
    once a TopN left 5 ms outside every leg, not 20)."""
    health = DeviceHealth(timeout_s=30.0)
    spins = []

    def busy():  # holds the lock from its first statement: the caller wakes late
        with trace.leg(trace.WF_TOPN_WALK):
            t0 = time.monotonic()
            while time.monotonic() - t0 < 0.03:
                spins.append(1)

    try:
        health.guard(lambda: None)  # the worker exists and waits, as in a served request
        wf: dict = {}
        with trace.attrib_activate(wf):
            t0 = time.monotonic()
            health.guard(busy)
            total = time.monotonic() - t0
        assert wf[trace.WF_TOPN_WALK] >= 0.03
        # it read the interpreter's switch interval, 5 ms, more
        assert wf[trace.WF_GUARD_QUEUE] + wf[trace.WF_TOPN_WALK] <= total
    finally:
        health.close()


def test_book_credits_an_interval_that_began_on_another_threads_clock():
    """``trace.book``: the seconds join the stage, an open leg of this
    thread gives them up as to a nested leg, nothing is booked without
    a request or for a stamp that lies ahead."""
    wf: dict = {}
    with trace.attrib_activate(wf):
        trace.book(trace.WF_HANDOFF_WAKE, 0.004)
        trace.book(trace.WF_HANDOFF_WAKE, 0.001)
        trace.book(trace.WF_HANDOFF_WAKE, -0.5)  # the clocks' jitter, not a wake-up
        with trace.leg(trace.WF_REDUCE) as outer:
            trace.book(trace.WF_DISPATCH_QUEUE, 0.25)
    assert wf[trace.WF_HANDOFF_WAKE] == pytest.approx(0.005)
    assert wf[trace.WF_DISPATCH_QUEUE] == 0.25
    assert wf[trace.WF_REDUCE] == max(0.0, outer.seconds - 0.25)
    trace.book(trace.WF_HANDOFF_WAKE, 1.0)  # no request: nothing to credit
    assert trace.attrib_current() is None and wf[trace.WF_HANDOFF_WAKE] == pytest.approx(0.005)


def test_leg_without_attribution_is_a_timer_only():
    assert trace.attrib_current() is None
    with trace.leg(trace.WF_REDUCE) as lg:
        pass
    assert lg.seconds >= 0.0 and trace.attrib_current() is None


def test_leg_off_capture_builds_no_annotation_and_imports_no_jax():
    """Run in a child: this process has long imported jax."""
    import subprocess

    code = (
        "import sys\n"
        "from pilosa_tpu.utils import trace\n"
        "wf = {}\n"
        "with trace.attrib_activate(wf):\n"
        "    with trace.leg(trace.WF_REDUCE) as lg:\n"
        "        pass\n"
        "assert lg._ann is None and trace._annotation is None\n"
        "assert trace.WF_REDUCE in wf\n"
        "assert 'jax' not in sys.modules, 'leg() imported jax'\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True, timeout=60
    )
    assert out.returncode == 0, out.stderr


def test_leg_annotates_with_the_request_id_only_while_capturing(monkeypatch):
    made = []

    class Annotation:
        def __init__(self, name, **kw):
            made.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    with trace.attrib_activate({"_req": 41}):
        with trace.leg(trace.WF_RESPOND):
            pass
        assert made == []
        monkeypatch.setattr(trace, "_annotation", Annotation)
        with trace.leg(trace.WF_RESPOND):
            pass
    assert made == [(trace.WF_RESPOND, {"req": 41})]


def test_carried_hands_span_attribution_and_wave_to_a_pool_thread():
    wf: dict = {}
    seen = {}
    with trace.attrib_activate(wf):
        token = trace.set_wave(7)
        try:
            fn = trace.carried(
                lambda: seen.update(d=trace.attrib_current(), wave=trace.current_wave())
            )
        finally:
            trace.reset_wave(token)
    t = threading.Thread(target=fn)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    assert seen == {"d": wf, "wave": 7}


# -- across the device-guard thread -------------------------------------------


@pytest.fixture()
def gated_holder(tmp_path):
    h = Holder(str(tmp_path))
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    from pilosa_tpu.core.field import FieldOptions

    v = idx.create_field("v", FieldOptions(type="int", min=0, max=1000))
    rng = np.random.default_rng(3)
    rows, cols, vcols = [], [], []
    for shard in range(12):
        base = shard * SHARD_WIDTH
        for row in range(1, 301):  # a ranked cache deep enough to walk
            picked = rng.choice(60000, size=max(4, 400 // row), replace=False)
            rows.append(np.full(picked.size, row))
            cols.append(base + picked)
        vcols.append(base + np.arange(0, 60000, 25))
    f.import_bits(np.concatenate(rows), np.concatenate(cols))
    vcols = np.concatenate(vcols)
    v.import_values(vcols, rng.integers(0, 1000, size=vcols.size))
    yield h
    h.close()


@pytest.fixture()
def gated(gated_holder):
    """An executor as the server builds it by default: reads run on the
    device health gate's pool, another thread."""
    ex = Executor(gated_holder, device_policy="always", health=DeviceHealth(timeout_s=120.0))
    yield ex
    ex.close()


@pytest.fixture()
def gated_mesh(gated_holder):
    """The same behind ``mesh-devices = 4``: 12 shards, 3 a device."""
    import jax

    from pilosa_tpu.executor.stager import DeviceStager
    from pilosa_tpu.parallel.spmd import make_mesh

    mesh = make_mesh(jax.devices()[:4])
    ex = Executor(gated_holder, device_policy="always", health=DeviceHealth(timeout_s=120.0),
                  mesh=mesh, stager=DeviceStager(budget_bytes=1 << 30, mesh=mesh))
    yield ex
    ex.close()


@pytest.mark.parametrize("query", ["TopN(f, Row(f=1), n=3)", "Sum(Row(f=1), field=v)"])
def test_guarded_read_keeps_its_device_legs(gated, query):
    """The parent handed the guard thread the span only: every leg below
    it was dropped and the whole request read as ``other``."""
    from pilosa_tpu.pql import parse

    parsed = parse(query)  # as api.query hands it over, its parse a leg of its own
    gated.execute("i", parsed)  # compile
    stages = set(trace.WATERFALL_STAGES)
    shares = []
    for _ in range(5):
        wf: dict = {}
        with trace.attrib_activate(wf):
            t0 = time.monotonic()
            res = gated.execute("i", parsed)
            total = time.monotonic() - t0
        assert res and res[0]
        # the legs opened on the guard's pool thread reach the request
        assert wf.get(trace.WF_DEVICE_COMPUTE, 0.0) > 0.0
        assert wf.get(trace.WF_GUARD_QUEUE, 0.0) > 0.0
        assert {k for k in wf if not k.startswith("_")} <= stages
        if query.startswith("TopN"):
            assert wf.get(trace.WF_TOPN_WALK, 0.0) > 0.0
            assert wf.get(trace.WF_TOPN_CANDIDATES, 0.0) > 0.0
        # (a lone Sum's program is fenced by ``_timed_kernel`` inside
        # device.compute and its count vector copied by ``_fetch``)
        assert wf.get(trace.WF_TRANSFER_DECODE, 0.0) > 0.0
        summary = profiler.WATERFALL.summarize(wf, total)
        shares.append(summary["stages"].get(trace.WF_OTHER, 0.0) / summary["total_ms"])
    # with the span alone every execution read as ``other``, whole; a
    # wall clock's share under the suite's other workers is judged by
    # the best of the five, not by each
    assert min(shares) < 1 / 3, shares


@pytest.mark.parametrize("query", [
    "Sum(Intersect(Row(f=1), Range(v < 500)), field=v)",
    "Count(Intersect(Row(f=1), Range(v >< [100, 900])))",
])
def test_guarded_read_on_a_mesh_books_the_replicated_results_copy_as_mesh_fetch(gated_mesh, query):
    """A Sum's and a Count's mesh kernels end in a ``psum``: the copy of
    the replicated result from one replica is the leg ``mesh.fetch``, as
    a TopN chunk's gathered scores are (ISSUE 35; it was
    ``transfer.decode`` inside ``_fetch``), and the legs still sum to no
    more than the request."""
    from pilosa_tpu.pql import parse

    parsed = parse(query)
    want = gated_mesh.execute("i", parsed)  # stage and compile
    for _ in range(3):
        wf: dict = {}
        with trace.attrib_activate(wf):
            t0 = time.monotonic()
            res = gated_mesh.execute("i", parsed)
            total = time.monotonic() - t0
        assert res == want and res[0]
        assert wf[trace.WF_MESH_FETCH] > 0.0 and trace.WF_TRANSFER_DECODE not in wf
        assert wf[trace.WF_DEVICE_COMPUTE] > 0.0 and wf[trace.WF_FILTER_EVAL] > 0.0
        assert wf.get(trace.WF_GUARD_QUEUE, 0.0) > 0.0
        assert {k for k in wf if not k.startswith("_")} <= set(trace.WATERFALL_STAGES)
        assert sum(v for k, v in wf.items() if not k.startswith("_")) <= total * 1.001
        assert trace.WF_MESH_FETCH in profiler.WaterfallAggregator.DEVICE_STAGES


# -- the launch apart from the wait (ISSUE 37) --------------------------------


@pytest.mark.parametrize("site", ["timed_kernel", "launch", "batched_scorer"])
def test_launch_leg_nests_in_device_compute_and_leaves_launch_to_ready_whole(monkeypatch, site):
    """At each of the three sites that launch, the jit call is
    ``device.launch`` inside ``device.compute``: a second is credited
    once (the two stages sum to the outer leg), the outer leg's
    ``seconds`` still feed ``spmd.execute_seconds`` whole, the inner
    one's feed ``spmd.launch_seconds``, the operands are counted inside
    the launch, and while a capture runs the launch is an annotation
    with the request's id like every other leg. On a clock that ticks
    once a reading, so every interval is a whole number."""
    ticks = itertools.count(1)
    clock = SimpleNamespace(monotonic=lambda: float(next(ticks)))
    monkeypatch.setattr(trace, "time", clock)
    monkeypatch.setattr(batcher, "time", clock)
    kind = f"probe_{site}"
    operand = np.arange(16, dtype=np.uint32)
    counted = []
    monkeypatch.setattr(
        profiler, "count_operands",
        lambda k, ops_: counted.append((k, getattr(trace._open_leg, "leg", None).stage)),
    )
    if site == "timed_kernel":
        kernel = executor_mod._timed_kernel(kind, lambda a: a + 1)
        kernel(operand)  # the first call is the compile's: spmd.compile_seconds
        assert _metric(metrics.SPMD_LAUNCH_SECONDS, kind=kind) == 0

        def call():
            return kernel(operand)
    elif site == "launch":
        def call():
            return executor_mod._launch(kind, lambda a: a + 1, operand)
    else:
        scorer = BatchedScorer(single_fn=lambda src, mat: mat + src, kind=kind)

        def call():
            return scorer.score(("k", id(operand)), operand, np.uint32(1))

    made = []

    class Annotation:
        def __init__(self, name, **kw):
            made.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace, "_annotation", Annotation)
    counted.clear()
    ready0 = _seconds(metrics.SPMD_EXECUTE_SECONDS, kind=kind)
    launch0 = _seconds(metrics.SPMD_LAUNCH_SECONDS, kind=kind)
    slot0 = _seconds(metrics.BATCHER_SLOT_WAIT_SECONDS)
    wf: dict = {"_req": 41}
    with trace.attrib_activate(wf):
        out = call()
    np.testing.assert_array_equal(out, operand + 1)
    launch = _seconds(metrics.SPMD_LAUNCH_SECONDS, kind=kind) - launch0
    ready = _seconds(metrics.SPMD_EXECUTE_SECONDS, kind=kind) - ready0
    assert wf[trace.WF_DEVICE_LAUNCH] == launch == 1.0
    assert ready > launch  # launch to ready holds the launch, whole
    if site == "batched_scorer":
        # the outer leg is the slot's (enqueue → result); launch → fetched lies in it
        whole = _seconds(metrics.BATCHER_SLOT_WAIT_SECONDS) - slot0
        assert ready < whole
    else:
        whole = ready  # the outer leg's own seconds
    assert wf[trace.WF_DEVICE_LAUNCH] + wf[trace.WF_DEVICE_COMPUTE] == whole
    assert set(wf) == {"_req", trace.WF_DEVICE_LAUNCH, trace.WF_DEVICE_COMPUTE}
    assert counted == [(kind, trace.WF_DEVICE_LAUNCH)]
    assert made == [
        (trace.WF_DEVICE_COMPUTE, {"req": 41}),
        (trace.WF_DEVICE_LAUNCH, {"req": 41}),
    ]


# -- the hand-backs (ISSUE 37) ------------------------------------------------


class _Stamps:
    """The real clock, every reading kept with the thread that took it."""

    def __init__(self):
        self.by_thread: dict = {}

    def monotonic(self) -> float:
        t = time.monotonic()
        self.by_thread.setdefault(threading.get_ident(), []).append(t)
        return t

    def last(self, ident: int) -> float:
        return self.by_thread[ident][-1]


@pytest.mark.parametrize("hop", ["guard", "dispatch", "pipeline"])
def test_handoff_wake_runs_from_the_workers_finishing_stamp_to_the_waiter(monkeypatch, hop):
    """Each of the three hops a result comes back over books
    ``handoff.wake``: the last reading of the clock the worker took
    before it handed over (its finishing stamp) → the waiter's reading
    once it runs again, to the request's waterfall on the waiter's
    thread, whatever the call's outcome."""
    stamps = _Stamps()
    worker: list[int] = []
    me = threading.get_ident()

    def work():
        worker.append(threading.get_ident())
        return "answer"

    wf: dict = {}
    if hop == "guard":
        monkeypatch.setattr(devicehealth, "time", stamps)
        health = DeviceHealth(timeout_s=30.0)
        try:
            with trace.attrib_activate(wf):
                assert health.guard(work) == "answer"
                first = wf[trace.WF_HANDOFF_WAKE]
                assert first == stamps.last(me) - stamps.last(worker[0])

                def broken():
                    work()
                    raise KeyError("the call's own error")

                with pytest.raises(KeyError):
                    health.guard(broken)
            assert wf[trace.WF_HANDOFF_WAKE] == first + (
                stamps.last(me) - stamps.last(worker[-1])
            )
        finally:
            health.close()
    elif hop == "dispatch":
        monkeypatch.setattr(dispatch, "time", stamps)
        from pilosa_tpu.pql import parse

        item = dispatch._Item("i", parse("Count(Row(f=1))"), None, None, None, "sig")
        t = threading.Thread(target=lambda: item.finish(result=[work()]))
        with trace.attrib_activate(wf):
            t.start()
            assert item.result() == ["answer"]
        t.join(timeout=10)
        assert not t.is_alive()
        assert item.t_done == stamps.last(worker[0])
        assert wf[trace.WF_HANDOFF_WAKE] == stamps.last(me) - item.t_done
    else:
        monkeypatch.setattr(pipeline, "time", stamps)
        pl = pipeline.QueryPipeline()
        try:
            with trace.attrib_activate(wf):  # the transport's waterfall
                assert pl.submit(pipeline.CLASS_INTERACTIVE, work) == "answer"
            woke = stamps.last(me) - stamps.last(worker[0])
        finally:
            assert pl.close()
        assert wf[trace.WF_HANDOFF_WAKE] == woke
    assert wf[trace.WF_HANDOFF_WAKE] > 0.0


def test_the_handlers_wake_up_joins_the_summary_as_admission_does():
    """The third hand-back lies outside ``api.query``'s total: the
    transport's waterfall holds it and ``_record_waterfall`` adds it to
    the summary, stage and total, with ``admission`` (so
    ``profile=waterfall`` shows both); ``respond`` follows at the last
    write; ``other`` is untouched."""
    from pilosa_tpu.server.http_handler import Handler

    summary = profiler.WATERFALL.summarize(
        {trace.WF_REDUCE: 0.002, trace.WF_HANDOFF_WAKE: 0.001, "_req": 5}, 0.010
    )
    other = summary["stages"][trace.WF_OTHER]
    transport = {"_req": 5, trace.WF_ADMISSION: 0.0005, trace.WF_HANDOFF_WAKE: 0.0015}
    with trace.attrib_activate(transport):
        Handler._record_waterfall("interactive", summary, "i")
    assert transport == {"_req": 5, "_record": ("interactive", summary, "i")}
    assert summary["stages"][trace.WF_HANDOFF_WAKE] == pytest.approx(2.5)  # both hops' sum
    assert summary["stages"][trace.WF_ADMISSION] == pytest.approx(0.5)
    assert summary["total_ms"] == pytest.approx(12.0)
    assert summary["stages"][trace.WF_OTHER] == other
    assert sum(summary["stages"].values()) == pytest.approx(summary["total_ms"])
    assert list(summary["stages"]) == [
        n for n in trace.WATERFALL_STAGES if n in summary["stages"]
    ]


# -- a request that leads its own wave (ISSUE 38) ------------------------------


def _stub_engine(execute):
    """A dispatch engine over a stand-in executor, two slots."""
    ex = SimpleNamespace(_execute=execute, stager=SimpleNamespace())
    return dispatch.DispatchEngine(ex, max_inflight=2)


def _submit(engine, query="Count(Row(f=1))"):
    from pilosa_tpu.pql import parse

    opt = SimpleNamespace(remote=False, exclude_row_attrs=False, exclude_columns=False, cache=True)
    return engine.submit("i", parse(query), None, opt, text=query)


@pytest.mark.parametrize("how", ["led", "handed"])
def test_a_requests_stages_sum_to_its_total_whoever_runs_its_wave(gated, how):
    """Behind the device guard, as served: the wave's legs reach the
    request's waterfall once and sum, with ``dispatch.queue`` and the
    hand-backs, to no more than the request; a led wave's are opened on
    the submitter's own thread."""
    from pilosa_tpu.pql import parse

    parsed = parse("TopN(f, Row(f=1), n=3)")
    want = gated.execute("i", parsed)  # compile
    engine = gated.dispatch_engine
    stages = set(trace.WATERFALL_STAGES)
    for _ in range(3):
        before = engine.stats()
        wf: dict = {}
        out = {}

        def request():
            with trace.attrib_activate(wf):
                t0 = time.monotonic()
                out["res"] = gated.execute("i", parsed)
                out["total"] = time.monotonic() - t0

        if how == "led":
            request()
        else:  # every slot computes: the request queues, the loop hands it on
            for _ in range(engine.max_inflight):
                assert engine._slots.acquire(timeout=10)
            t = threading.Thread(target=request)
            t.start()
            while engine.stats()["queued"] < 1:
                time.sleep(0.002)
            for _ in range(engine.max_inflight):
                engine._slots.release()
            t.join(timeout=60)
            assert not t.is_alive()
        after = engine.stats()
        assert after[how] == before[how] + 1 and after["waves"] == before["waves"] + 1
        assert out["res"] == want
        assert wf["_wave"] == after["waves"]
        legs = {k: v for k, v in wf.items() if not k.startswith("_")}
        assert set(legs) <= stages
        for stage in (trace.WF_DISPATCH_QUEUE, trace.WF_HANDOFF_WAKE, trace.WF_GUARD_QUEUE,
                      trace.WF_DEVICE_COMPUTE, trace.WF_TOPN_WALK, trace.WF_TOPN_CANDIDATES):
            assert legs.get(stage, 0.0) > 0.0, stage
        assert trace.WF_WAVE_MATES not in legs
        assert sum(legs.values()) <= out["total"] * 1.001
        summary = profiler.WATERFALL.summarize(wf, out["total"])
        assert sum(summary["stages"].values()) == pytest.approx(summary["total_ms"], rel=1e-3)
        assert summary["wave"] == after["waves"]


def test_a_led_waves_queue_wait_and_hand_back_are_read_off_the_submitters_own_clock(monkeypatch):
    """No thread is crossed: ``dispatch.queue`` runs from the admission
    stamp to the wave's start and hand-back (b) from the wave's
    finishing stamp to ``result()``, all four readings of this thread's
    clock, microseconds apart; ``handoff.wake`` is left with the guard's
    hand-back (a) and the pipeline's (c)."""
    stamps = _Stamps()
    monkeypatch.setattr(dispatch, "time", stamps)
    ran = []
    engine = _stub_engine(lambda index, q, shards, opt: ran.append(threading.get_ident()) or [7])
    me = threading.get_ident()
    wf: dict = {"_req": 9}
    try:
        with trace.attrib_activate(wf):
            item = _submit(engine)
            assert item.event.is_set() and item.req == 9  # resolved before submit returned
            assert item.result() == [7]
            woke = stamps.last(me)
        assert ran == [me] and set(stamps.by_thread) == {me}
        mine = stamps.by_thread[me]
        assert item.t_enq in mine and item.t_done in mine and item.t_enq < item.t_done
        assert wf[trace.WF_DISPATCH_QUEUE] == item.wait_s
        assert item.t_enq + item.wait_s in mine  # the wave's starting stamp
        assert wf[trace.WF_HANDOFF_WAKE] == woke - item.t_done
        assert wf["_wave"] == 1 and engine.stats()["led"] == 1
        # nothing of the wave's own scope is left on this thread
        assert trace.attrib_current() is None and trace.current_wave() == 0
        assert not engine.in_wave()
    finally:
        assert engine.close(drain=1.0)


def test_a_leg_inside_a_led_wave_is_credited_once_under_the_submitters_open_leg():
    """The wave's legs open on a thread that has a leg and a scope of
    its own open: they are credited to the wave's scope, which
    ``result()`` merges into the request's once, the outer leg gives
    their seconds up as to any nested leg, and both the scope and the
    innermost-leg pointer are the submitter's again afterwards."""
    inner_legs = []

    def execute(index, q, shards, opt):
        assert trace.attrib_current() == {"_req": 9}  # the wave's scope, not the request's
        with trace.leg(trace.WF_DEVICE_COMPUTE) as lg:
            time.sleep(0.02)
        inner_legs.append(lg)
        time.sleep(0.01)  # the wave's glue: no leg of the wave's covers it
        return [7]

    engine = _stub_engine(execute)
    wf: dict = {"_req": 9}
    try:
        with trace.attrib_activate(wf):
            with trace.leg(trace.WF_REDUCE) as outer:
                time.sleep(0.01)
                assert _submit(engine).result() == [7]
                assert trace.attrib_current() is wf
                assert trace._open_leg.leg is outer
            assert trace._open_leg.leg is None
        (inner,) = inner_legs
        assert inner.seconds >= 0.02 and outer.seconds >= 0.04
        assert wf[trace.WF_DEVICE_COMPUTE] == pytest.approx(inner.seconds)  # once
        booked = wf[trace.WF_DISPATCH_QUEUE] + wf.get(trace.WF_HANDOFF_WAKE, 0.0)
        assert wf[trace.WF_REDUCE] == pytest.approx(outer.seconds - inner.seconds - booked)
        assert sum(v for k, v in wf.items() if not k.startswith("_")) == pytest.approx(outer.seconds)
    finally:
        assert engine.close(drain=1.0)


# -- the capture --------------------------------------------------------------


@pytest.mark.parametrize("python_tracer, level", [(False, 0), (True, 1)])
def test_capture_runs_without_the_python_tracer_unless_asked(
    monkeypatch, tmp_path, python_tracer, level
):
    import jax

    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda d, **kw: calls.append((d, kw))
    )
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    try:
        out = profiler.start_capture(str(tmp_path), python_tracer=python_tracer)
        assert out["ok"], out
        assert trace._annotation is jax.profiler.TraceAnnotation
    finally:
        assert profiler.stop_capture()["ok"]
    assert trace._annotation is None
    (d, kw), = calls
    assert d == str(tmp_path)
    assert kw["profiler_options"].python_tracer_level == level


def test_debug_profile_python_param_reaches_the_capture(monkeypatch):
    from pilosa_tpu.server.http_handler import Handler, Request

    seen = []

    def start_capture(log_dir, python_tracer=False):
        seen.append(python_tracer)
        return {"ok": True}

    monkeypatch.setattr(profiler, "start_capture", start_capture)
    h = Handler.__new__(Handler)
    for q in ({"capture": ["start"]}, {"capture": ["start"], "python": ["1"]}):
        h.get_debug_profile(Request({}, q, b""))
    assert seen == [False, True]


# -- compiles, wherever they happen -------------------------------------------


def test_module_level_jit_compile_is_counted_as_xla():
    import jax
    import jax.numpy as jnp

    profiler.COMPILES.listen()
    profiler.COMPILES.listen()  # once per process, however often asked

    @jax.jit
    def fresh(x):
        return x * 3 + 1

    x = jnp.asarray(np.arange(7, dtype=np.int32))  # no program of its own
    before = _metric(metrics.PROFILER_COMPILES, kind=profiler.XLA_KIND)
    fresh(x).block_until_ready()
    after = _metric(metrics.PROFILER_COMPILES, kind=profiler.XLA_KIND)
    assert after == before + 1
    fresh(x).block_until_ready()  # warm: no compile
    assert _metric(metrics.PROFILER_COMPILES, kind=profiler.XLA_KIND) == after
    rows = profiler.COMPILES.snapshot(top=256)["signatures"]
    assert any(r["kind"] == profiler.XLA_KIND and "fresh" in r["signature"] for r in rows)


# -- the batched scorers' launches --------------------------------------------


def test_batched_scorer_launch_lands_in_kernel_families():
    import jax.numpy as jnp

    kind = "topn_score_dense"
    mat = jnp.asarray(np.arange(8 * 64, dtype=np.uint32).reshape(8, 64))
    src = jnp.asarray(np.full(64, 0xFFFF, dtype=np.uint32))
    scorer = BatchedScorer()
    assert scorer.kind == kind
    launches = _metric(metrics.SPMD_EXECUTE_SECONDS, kind=kind)
    handed = _metric(metrics.KERNEL_OPERAND_BYTES, kind=kind)
    wf: dict = {}
    with trace.attrib_activate(wf):
        out = scorer.score(("k", id(mat)), mat, src)
    assert out.shape == (8,)
    assert _metric(metrics.SPMD_EXECUTE_SECONDS, kind=kind) == launches + 1
    assert _metric(metrics.KERNEL_OPERAND_BYTES, kind=kind) == handed + mat.nbytes + src.nbytes
    assert wf.get(trace.WF_DEVICE_COMPUTE, 0.0) > 0.0


def test_operand_bytes_walks_nested_operands():
    a = np.zeros((4, 8), dtype=np.uint32)
    assert profiler.operand_bytes(((a, [a, 3]), "static", None)) == 2 * a.nbytes


# -- the transport's legs -----------------------------------------------------


def test_extend_adds_transport_legs_to_stage_and_total():
    s = profiler.WATERFALL.summarize({trace.WF_REDUCE: 0.002, "_req": 5}, 0.010)
    other = s["stages"][trace.WF_OTHER]
    profiler.WATERFALL.extend(
        s, {trace.WF_ADMISSION: 0.001, trace.WF_RESPOND: 0.003, "_req": 5, "_record": ()}
    )
    assert s["total_ms"] == pytest.approx(14.0)
    assert s["stages"][trace.WF_ADMISSION] == pytest.approx(1.0)
    assert s["stages"][trace.WF_RESPOND] == pytest.approx(3.0)
    assert s["stages"][trace.WF_OTHER] == other
    order = [n for n in trace.WATERFALL_STAGES if n in s["stages"]]
    assert list(s["stages"]) == order


# -- a GroupBy panel's host legs -----------------------------------------------


@pytest.mark.parametrize("site", ["fused", "lone"])
def test_groupby_legs_are_booked_once_a_panel_at_both_device_sites_and_for_no_other_call(
    gated_holder, monkeypatch, site
):
    """``groupby.lower`` (the panel's rows resolved, staged and stacked)
    before the launch and ``groupby.finalize`` (the counts turned into
    groups) after it, once each: in the fused program a lone panel
    launches and on the per-call path behind ``fusion-enabled = false``.
    No other call opens either."""
    ex = Executor(gated_holder, device_policy="always", fusion_enabled=site == "fused")
    cpu = Executor(gated_holder, device_policy="never")
    seen: list = []

    class counted(trace.leg):
        __slots__ = ()

        def __enter__(self):
            seen.append(self.stage)
            return super().__enter__()

    monkeypatch.setattr(trace, "leg", counted)
    ours = {trace.WF_GROUPBY_LOWER, trace.WF_GROUPBY_FINALIZE}
    try:
        for q in ("GroupBy(Rows(f, ids=[1, 2, 3]), Sum(field=v))",
                  "GroupBy(Rows(f, ids=[2, 1]), Row(f=4), Sum(field=v))",
                  "GroupBy(Rows(f, ids=[1, 5]), Row(f=3))"):
            want = cpu.execute("i", q)
            ex.execute("i", q)  # stage and compile
            launches = _metric(metrics.FUSION_GROUPBY_LAUNCHES)
            fused = _metric(metrics.FUSION_FUSED_LAUNCHES)
            del seen[:]
            wf: dict = {}
            with trace.attrib_activate(wf):
                assert ex.execute("i", q) == want
            assert _metric(metrics.FUSION_GROUPBY_LAUNCHES) - launches == 1
            assert _metric(metrics.FUSION_FUSED_LAUNCHES) - fused == (1 if site == "fused" else 0)
            assert [st for st in seen if st in ours] == [trace.WF_GROUPBY_LOWER, trace.WF_GROUPBY_FINALIZE]
            lower, finalize = seen.index(trace.WF_GROUPBY_LOWER), seen.index(trace.WF_GROUPBY_FINALIZE)
            assert lower < seen.index(trace.WF_DEVICE_COMPUTE, lower) < finalize
            assert wf[trace.WF_GROUPBY_LOWER] > 0.0 and wf[trace.WF_GROUPBY_FINALIZE] > 0.0
            assert not ours & set(profiler.WaterfallAggregator.DEVICE_STAGES)
        for q in ("Count(Row(f=1))", "Sum(Row(f=1), field=v)", "TopN(f, Row(f=1), n=3)",
                  "Count(Row(f=1))Sum(Row(f=2), field=v)"):
            del seen[:]
            ex.execute("i", q)
            assert trace.WF_DEVICE_COMPUTE in seen and not ours & set(seen), q
    finally:
        ex.close()
        cpu.close()


# -- the benchmark's readers against the program's names ----------------------


def test_layer_metric_files_name_published_metrics_and_stages():
    """A ``/metrics`` sample that is renamed reads 0 in its
    ``layer_metrics`` file, silently (PERF.md §7): hold the files to the
    registry's and the waterfall's names."""
    files = sorted(glob.glob(os.path.join(ROOT, "benchmark", "layer_metrics", "*.json")))
    assert len(files) >= 20
    for path in files:
        with open(path) as fh:
            spec = json.load(fh)
        assert spec["name"] == os.path.basename(path)[: -len(".json")]
        for sample in spec.get("numerator", ()):
            name = sample["metric"]
            # a summary is published as <name>_sum and <name>_count
            base = name if name in metrics.METRICS else name.rsplit("_", 1)[0]
            assert base in metrics.METRICS, f"{spec['name']}: {name} is not declared in utils/metrics.py"
            stage = sample.get("labels", {}).get("stage")
            if stage is not None:
                assert base == metrics.LATENCY_STAGE_SECONDS
                assert stage in trace.WATERFALL_STAGES, f"{spec['name']}: no stage {stage!r}"


def test_the_manifest_holds_issue_37s_seven_metrics_and_each_is_a_data_file():
    """Appended in the issue's order, every cell (no ``workloads``
    list), each read from ``/metrics`` by a data file alone; the layer
    ``process`` is new: the collector, the flush and the CPU belong to
    no one layer of a request."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    stage = metrics.LATENCY_STAGE_SECONDS + "_sum"
    want = [
        ("executor.device_launch_ms", "ms", "program_span", "executor host side", "query_p50_ms",
         stage, {"stage": trace.WF_DEVICE_LAUNCH}, 1000, "request"),
        ("dispatch.handoff_wake_ms", "ms", "program_span", "dispatch", "query_p50_ms",
         stage, {"stage": trace.WF_HANDOFF_WAKE}, 1000, "request"),
        ("dispatch.wave_mates_ms", "ms", "program_span", "dispatch", "query_p50_ms",
         stage, {"stage": trace.WF_WAVE_MATES}, 1000, "request"),
        ("dispatch.guard_queue_ms", "ms", "program_span", "dispatch", "query_p95_ms",
         stage, {"stage": trace.WF_GUARD_QUEUE}, 1000, "request"),
        ("process.gc_pause_ms_per_query", "ms", "program_counter", "process", "query_p95_ms",
         metrics.GC_PAUSE_SECONDS + "_sum", {}, 1000, "request"),
        ("process.cpu_ms_per_query", "ms", "program_counter", "process", "queries_per_s",
         metrics.PROCESS_CPU_SECONDS, {}, 1000, "request"),
        ("holder.cache_flush_s_in_window", "s", "program_counter", "process", "query_p95_ms",
         metrics.CACHE_FLUSH_SECONDS + "_sum", {}, 1, "window"),
    ]
    at = [m["name"] for m in manifest["per_layer"]].index("executor.device_launch_ms")
    layers = {m["layer"] for m in manifest["per_layer"][:at]}
    for entry, (name, unit, source, layer, moves, metric, labels, scale, per) in zip(
        manifest["per_layer"][at : at + 7], want, strict=True
    ):
        assert entry == {"name": name, "unit": unit, "better": "lower", "source": source,
                         "layer": layer, "moves": moves}
        assert layer in layers or layer == "process"
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as fh:
            spec = json.load(fh)
        assert spec == {"name": name, "source": "server_metrics", "scale": scale, "per": per,
                        "numerator": [{"metric": metric, "labels": labels}]}
    assert "process" not in layers


def test_the_manifest_ends_with_issue_38s_metric_and_it_is_a_data_file():
    """``dispatch.waves{how=led}`` a request, every cell: the share of
    requests that crossed no thread into their wave. Nothing the
    benchmark had is edited: one entry appended, one data file. Later
    entries are appended behind it (the five GroupBy metrics first), so
    it is found where it was appended, not last."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    name = "dispatch.waves_led_per_query"
    at = [m["name"] for m in manifest["per_layer"]].index(name)
    assert manifest["per_layer"][at] == {
        "name": name, "unit": "count/query", "better": "higher", "source": "program_counter",
        "layer": "dispatch", "moves": "query_p50_ms"}
    assert manifest["per_layer"][at - 1]["name"] == "holder.cache_flush_s_in_window"
    assert manifest["per_layer"][at + 1]["name"] == "executor.groupby_launches_per_query"
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as fh:
        spec = json.load(fh)
    assert spec == {"name": name, "source": "server_metrics", "scale": 1, "per": "request",
                    "numerator": [{"metric": metrics.DISPATCH_WAVES, "labels": {"how": "led"}}]}
    assert metrics.METRICS[metrics.DISPATCH_WAVES][0] == "counter"
