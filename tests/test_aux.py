"""Auxiliary components: StatsD client, gcnotify, iterators, B+tree
container store (reference statsd/, gcnotify/, iterator.go,
enterprise/b)."""

import gc
import random
import socket
import time

import numpy as np
import pytest

from pilosa_tpu.core import (
    BufIterator,
    LimitIterator,
    RoaringIterator,
    SliceIterator,
)
from pilosa_tpu import SHARD_WIDTH
from pilosa_tpu.roaring import (
    Bitmap,
    BTreeContainers,
    get_default_container_store,
    set_default_container_store,
)
from pilosa_tpu.utils import metrics
from pilosa_tpu.utils.gcnotify import GCNotifier
from pilosa_tpu.utils.stats import StatsDClient


# -- StatsD ----------------------------------------------------------------


@pytest.fixture
def udp_server():
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.bind(("127.0.0.1", 0))
    sock.settimeout(2.0)
    yield sock
    sock.close()


def _recv(sock) -> str:
    return sock.recvfrom(4096)[0].decode()


def test_statsd_wire_format(udp_server):
    port = udp_server.getsockname()[1]
    c = StatsDClient(host=f"127.0.0.1:{port}")
    c.count("setBit", 3)
    assert _recv(udp_server) == "pilosa.setBit:3|c"
    c.gauge("goroutines", 12.0)
    assert _recv(udp_server) == "pilosa.goroutines:12.0|g"
    c.timing("query", 1.5)
    assert _recv(udp_server) == "pilosa.query:1.5|ms"
    c.set("user", "a")
    assert _recv(udp_server) == "pilosa.user:a|s"
    c.histogram("h", 2.0)
    assert _recv(udp_server) == "pilosa.h:2.0|h"
    c.close()


def test_statsd_tags_propagate(udp_server):
    port = udp_server.getsockname()[1]
    c = StatsDClient(host=f"127.0.0.1:{port}")
    tagged = c.with_tags("index:i", "field:f")
    assert tagged.tags() == ["field:f", "index:i"]
    tagged.count("importBit", 1)
    assert _recv(udp_server) == "pilosa.importBit:1|c|#field:f,index:i"
    # parent unaffected
    assert c.tags() == []
    c.close()


def test_statsd_sampling(udp_server, monkeypatch):
    port = udp_server.getsockname()[1]
    c = StatsDClient(host=f"127.0.0.1:{port}")
    monkeypatch.setattr(random, "random", lambda: 0.99)
    c.count("dropped", 1, rate=0.5)  # 0.99 >= 0.5 → dropped
    monkeypatch.setattr(random, "random", lambda: 0.01)
    c.count("kept", 1, rate=0.5)
    assert _recv(udp_server) == "pilosa.kept:1|c|@0.5"
    c.close()


def test_statsd_bare_hostname_defaults_port():
    c = StatsDClient(host="localhost")
    assert c._addr == ("localhost", 8125)
    c.close()


def test_statsd_closed_socket_swallows_errors():
    """UDP fire-and-forget: a dead socket must never surface into the
    serving path (uses the _sock injection point)."""
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    c = StatsDClient(host="127.0.0.1:9", _sock=sock)
    sock.close()
    c.count("x", 1)
    c.gauge("g", 1.0)
    c.timing("t", 0.5)
    c.histogram("h", 2.0)
    c.set("s", "v")
    c.close()  # double-close of the injected socket is swallowed too


def test_statsd_tagged_child_shares_socket(udp_server):
    """with_tags returns a view over the SAME socket — closing the
    parent closes the child; tags ride every metric type."""
    port = udp_server.getsockname()[1]
    c = StatsDClient(host=f"127.0.0.1:{port}")
    t = c.with_tags("shard:3")
    assert t._sock is c._sock
    t.timing("q", 2.5)
    assert _recv(udp_server) == "pilosa.q:2.5|ms|#shard:3"
    t.gauge("g", 7)
    assert _recv(udp_server) == "pilosa.g:7|g|#shard:3"
    c.close()


# -- expvar percentile histograms ------------------------------------------


def test_expvar_histogram_percentiles():
    from pilosa_tpu.utils.stats import ExpvarStatsClient

    c = ExpvarStatsClient()
    for v in range(1, 101):
        c.histogram("h", float(v))
    h = c.snapshot()["h.hist"]
    assert h["count"] == 100
    assert h["min"] == 1.0 and h["max"] == 100.0
    assert abs(h["sum"] - 5050.0) < 1e-9
    # log-spaced buckets: estimates carry bounded relative error
    assert 40 <= h["p50"] <= 60
    assert h["p50"] <= h["p95"] <= h["p99"] <= 100.0


def test_expvar_timing_reports_percentiles():
    from pilosa_tpu.utils.stats import ExpvarStatsClient

    c = ExpvarStatsClient(tags=["index:i"])
    for _ in range(10):
        c.timing("query_time", 0.25)
    h = c.snapshot()["query_time.timing.hist;index:i"]
    assert h["count"] == 10
    for k in ("p50", "p95", "p99"):
        assert 0.15 <= h[k] <= 0.35


def test_multi_stats_snapshot_keeps_expvar_lit():
    """satellite: with metric='statsd' the server fans out through a
    MultiStatsClient whose snapshot merges in-process children, so
    /debug/vars never goes dark."""
    from pilosa_tpu.utils.stats import (
        ExpvarStatsClient,
        MultiStatsClient,
        NopStatsClient,
    )

    ev = ExpvarStatsClient()
    m = MultiStatsClient(ev, NopStatsClient())
    m.count("c", 2)
    m.timing("t", 0.5)
    snap = m.snapshot()
    assert snap["c"] == 2
    assert snap["t.timing.hist"]["count"] == 1


# -- gcnotify --------------------------------------------------------------


def _gc_pauses(generation: int) -> tuple:
    snap = metrics.snapshot()
    h = snap.get(f"{metrics.GC_PAUSE_SECONDS}.hist;generation:{generation}")
    return (h["count"], h["sum"]) if h else (0, 0.0)


@pytest.mark.parametrize("generation", [0, 1, 2])
def test_gcnotifier_books_pauses_by_generation_and_unhooks(generation):
    """Each collection is one observation of start → stop under its
    generation and one ``garbage_collection``, in the metric registry;
    ``close()`` takes the callback out."""
    was = gc.isenabled()
    gc.disable()  # only the collections asked for below
    n = GCNotifier()
    try:
        count, seconds = _gc_pauses(generation)
        cycles = metrics.snapshot().get(metrics.GARBAGE_COLLECTION, 0)
        t0 = time.monotonic()
        gc.collect(generation)
        gc.collect(generation)
        spent = time.monotonic() - t0
        count2, seconds2 = _gc_pauses(generation)
        assert count2 == count + 2
        assert 0.0 < seconds2 - seconds <= spent
        assert metrics.snapshot()[metrics.GARBAGE_COLLECTION] == cycles + 2
        assert n._on_gc in gc.callbacks
    finally:
        n.close()
        n.close()  # idempotent
        if was:
            gc.enable()
    assert n._on_gc not in gc.callbacks
    gc.collect(generation)
    assert _gc_pauses(generation)[0] == count + 2  # closed: no longer timing


def test_gcnotifier_never_waits_for_the_registry_lock_it_may_be_inside():
    """A collection starts between any two bytecodes, also on a thread
    that holds the registry's lock: the callback must not wait for it
    (that would never end). The pause is kept and booked with the next
    collection."""
    was = gc.isenabled()
    gc.disable()
    n = GCNotifier()
    try:
        count, _ = _gc_pauses(2)
        with metrics.REGISTRY._mu:
            assert not metrics.REGISTRY.try_observe(metrics.GC_PAUSE_SECONDS, 1.0, generation=2)
            gc.collect()  # returns: the callback found the lock taken
        assert _gc_pauses(2)[0] == count
        gc.collect()
        assert _gc_pauses(2)[0] == count + 2
    finally:
        n.close()
        if was:
            gc.enable()


# -- iterators (reference iterator.go) -------------------------------------


PAIRS = [(0, 1), (0, 5), (2, 0), (2, 9), (7, 3)]


def _slice_iter():
    return SliceIterator([p[0] for p in PAIRS], [p[1] for p in PAIRS])


def test_slice_iterator():
    assert list(_slice_iter()) == PAIRS
    it = _slice_iter()
    it.seek(2, 1)
    assert it.next_pair() == (2, 9, False)


def test_limit_iterator():
    assert list(LimitIterator(_slice_iter(), 3)) == PAIRS[:3]
    assert list(LimitIterator(_slice_iter(), 99)) == PAIRS


def test_buf_iterator_unread_and_peek():
    it = BufIterator(_slice_iter())
    assert it.peek() == (0, 1, False)
    assert it.next_pair() == (0, 1, False)  # peek did not consume
    it.unread()
    assert it.next_pair() == (0, 1, False)  # unread re-returns
    assert it.next_pair() == (0, 5, False)
    it.unread()
    with pytest.raises(RuntimeError):
        it.unread()  # single-slot buffer


def test_roaring_iterator():
    b = Bitmap()
    for r, c in PAIRS:
        b.add(r * SHARD_WIDTH + c)
    it = RoaringIterator(b)
    assert list(it) == PAIRS
    it.seek(2, 1)
    assert it.next_pair() == (2, 9, False)
    it.seek(99, 0)
    assert it.next_pair() == (0, 0, True)


# -- B+tree container store (reference enterprise/b) -----------------------


def test_btree_containers_basics():
    t = BTreeContainers()
    keys = list(range(0, 1000, 3))
    random.Random(5).shuffle(keys)
    for k in keys:
        t[k] = f"v{k}"
    assert len(t) == len(keys)
    assert list(t) == sorted(keys)  # in-order iteration
    assert t[999 // 3 * 3] == f"v{999 // 3 * 3}"
    assert t.get(1) is None
    assert 6 in t and 7 not in t
    del t[6]
    assert 6 not in t and len(t) == len(keys) - 1
    with pytest.raises(KeyError):
        del t[6]
    assert t.pop(9) == "v9"
    assert t.pop(9, "dflt") == "dflt"
    assert list(t.keys() & {0, 3, 6, 9, 1}) != []
    t.clear()
    assert len(t) == 0 and list(t) == []


def test_btree_containers_overwrite():
    t = BTreeContainers()
    t[5] = "a"
    t[5] = "b"
    assert len(t) == 1 and t[5] == "b"


def test_bitmap_algebra_with_btree_store():
    """Same results dict-store vs btree-store across the full algebra."""
    rng = np.random.default_rng(11)
    vals_a = np.unique(rng.integers(0, 5_000_000, 4000).astype(np.uint64))
    vals_b = np.unique(rng.integers(0, 5_000_000, 4000).astype(np.uint64))

    da, db = Bitmap.from_sorted(vals_a), Bitmap.from_sorted(vals_b)
    set_default_container_store(BTreeContainers)
    try:
        ba, bb = Bitmap.from_sorted(vals_a), Bitmap.from_sorted(vals_b)
        assert isinstance(ba.containers, BTreeContainers)
        for op in ("intersect", "union", "difference", "xor"):
            want = getattr(da, op)(db).slice_all()
            got = getattr(ba, op)(bb).slice_all()
            np.testing.assert_array_equal(want, got)
        assert da.intersection_count(db) == ba.intersection_count(bb)
        assert da.count() == ba.count()
        # point ops + serialization round-trip through the btree store
        ba.add(10_000_000)
        assert ba.contains(10_000_000)
        ba.remove(10_000_000)
        assert not ba.contains(10_000_000)
        data = ba.to_bytes()
    finally:
        set_default_container_store(dict)
    rt = Bitmap.unmarshal_binary(data)
    np.testing.assert_array_equal(rt.slice_all(), ba.slice_all())
    assert get_default_container_store() is dict


def test_btree_store_survives_many_containers():
    set_default_container_store(BTreeContainers)
    try:
        b = Bitmap()
        # >64 containers forces splits (one container per 2^16 block)
        positions = [i << 16 for i in range(300)]
        b.add(*positions)
        assert b.count() == 300
        assert [int(v) for v in b.slice_all()] == positions
    finally:
        set_default_container_store(dict)


# -- stager pow2 padding + pprof route --------------------------------------


def test_stager_rows_pow2_padding(tmp_path):
    from pilosa_tpu.core import Holder
    from pilosa_tpu.executor import DeviceStager

    h = Holder(str(tmp_path))
    h.open()
    idx = h.create_index("sp")
    f = idx.create_field("f")
    f.import_bits([0, 1, 2, 3, 4], [1, 2, 3, 4, 5])
    frag = h.fragment("sp", "f", "standard", 0)
    st = DeviceStager()
    mat = st.rows(frag, (0, 1, 2, 3, 4), pad_pow2=True)
    assert mat.shape[0] == 8  # 5 rows → next pow2
    assert np.asarray(mat)[5:].sum() == 0  # padding rows are zero
    unpadded = st.rows(frag, (0, 1, 2, 3, 4))
    assert unpadded.shape[0] == 5  # separate cache entries
    np.testing.assert_array_equal(np.asarray(mat)[:5], np.asarray(unpadded))
    h.close()


def test_debug_pprof_route(tmp_path):
    from pilosa_tpu.core import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.server.api import API
    from pilosa_tpu.server.http_handler import Handler, RawResponse

    h = Holder(str(tmp_path))
    h.open()
    handler = Handler(API(h, Executor(h)))
    out = handler.handle("GET", "/debug/pprof", {}, b"")
    assert isinstance(out, RawResponse)
    assert b"goroutine-analog" in out.data and b"test_debug_pprof_route" in out.data
    h.close()
