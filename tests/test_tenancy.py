"""Multi-tenant QoS (ISSUE 19, server/tenancy.py): per-index admission
token buckets (429 + Retry-After, distinct from the 503 overload shed),
virtual-time weighted-fair scheduling in the pipeline class queues,
HbmGovernor per-index quotas with over-quota-first relief, and
per-tenant SLO/waterfall attribution.

The fairness tests are property-style: over a backlogged window the WFQ
dequeue mix must track the configured weights within a bound, and a
single 100x-flooding tenant must not push another tenant's queue wait
past its deadline budget."""

import os
import random
import threading
import time

import pytest

from pilosa_tpu.executor.hbm import HbmGovernor
from pilosa_tpu.server.pipeline import (
    Overloaded,
    QueryPipeline,
    _Entry,
    _TenantFairQueue,
)
from pilosa_tpu.server.tenancy import (
    TenancyManager,
    TenantThrottled,
    parse_tenant_map,
)


def entry(index):
    return _Entry(cls="interactive", thunk=lambda: None, index=index)


# -- config parsing ----------------------------------------------------------


def test_parse_tenant_map_basics_and_default():
    m, default = parse_tenant_map("a=4, b=1.5, *=2")
    assert m == {"a": 4.0, "b": 1.5}
    assert default == 2.0
    m, default = parse_tenant_map("")
    assert m == {} and default is None
    # malformed / negative entries are skipped, never fatal
    m, default = parse_tenant_map("a=oops,=3,b=-1,c=7")
    assert m == {"c": 7.0}
    assert default is None


def test_manager_disabled_by_default_is_passthrough():
    tn = TenancyManager()
    assert not tn.enabled
    # no lock taken, no bucket created, nothing raised
    tn.admit("anything", "interactive", nbytes=1 << 20)
    tn.release("anything", "interactive", nbytes=1 << 20)
    assert tn.snapshot()["tenants"] == {}


# -- admission ---------------------------------------------------------------


def test_token_bucket_throttles_429_with_retry_after():
    tn = TenancyManager(qps="a=5")
    assert tn.enabled
    codes = []
    for _ in range(50):
        try:
            tn.admit("a", "interactive")
            codes.append(200)
        except TenantThrottled as e:
            codes.append(e.status)
            assert e.status == 429
            assert e.retry_after > 0
    # burst = 2s * 5qps = 10 tokens admitted, the rest throttled
    assert codes.count(200) == 10
    assert codes.count(429) == 40
    # an unrelated tenant is untouched (no explicit qps, no default)
    tn.admit("b", "interactive")


def test_throttle_is_per_tenant_not_global():
    tn = TenancyManager(qps="noisy=1")
    with pytest.raises(TenantThrottled):
        for _ in range(10):
            tn.admit("noisy", "interactive")
    # the quiet tenant admits freely while the noisy one is throttled
    for _ in range(100):
        tn.admit("quiet", "interactive")


def test_internal_class_exempt_from_admission():
    tn = TenancyManager(qps="a=1")
    for _ in range(50):
        tn.admit("a", "internal")  # never throttled


def test_inflight_byte_cap():
    tn = TenancyManager(inflight_bytes="a=1000")
    tn.admit("a", "interactive", nbytes=900)
    with pytest.raises(TenantThrottled) as ei:
        tn.admit("a", "interactive", nbytes=900)
    assert ei.value.status == 429
    tn.release("a", "interactive", nbytes=900)
    tn.admit("a", "interactive", nbytes=900)


def test_throttled_is_429_overload_is_503():
    # the two failure modes clients must distinguish: per-tenant flow
    # control (back off, your own bucket) vs whole-server overload
    # (retry elsewhere / later)
    assert TenantThrottled("x").status == 429
    assert Overloaded("x").status == 503


# -- weighted-fair queue -----------------------------------------------------


def test_wfq_without_weights_is_exactly_fifo():
    q = _TenantFairQueue(None)
    es = [entry(f"t{i % 3}") for i in range(64)]
    for e in es:
        q.append(e)
    assert [q.popleft() for _ in range(len(es))] == es


def test_wfq_dequeue_tracks_weights_within_bound():
    """Property: over any backlogged window, each tenant's dequeue
    share tracks weight/total within a small absolute bound."""
    weights = {"a": 4.0, "b": 2.0, "c": 1.0}
    q = _TenantFairQueue(lambda t: weights[t])
    rng = random.Random(19)
    per_tenant = 400
    backlog = [entry(t) for t in weights for _ in range(per_tenant)]
    rng.shuffle(backlog)
    for e in backlog:
        q.append(e)
    window = 350  # every tenant stays backlogged throughout
    got = {t: 0 for t in weights}
    for _ in range(window):
        got[q.popleft().index] += 1
    total_w = sum(weights.values())
    for t, w in weights.items():
        expect = window * w / total_w
        # unit-cost WFQ is within one quantum per tenant per round;
        # 5% absolute slack is generous and version-stable
        assert abs(got[t] - expect) <= window * 0.05 + 2.0, (t, got)


def test_wfq_flooder_cannot_starve_light_tenant():
    """One tenant enqueues 100x the other's load; the light tenant's
    entries still dequeue near the front (bounded queue positions), so
    its queue wait stays inside any sane deadline budget."""
    weights = {"noisy": 1.0, "quiet": 1.0}
    q = _TenantFairQueue(lambda t: weights[t])
    for _ in range(200):
        q.append(entry("noisy"))
    quiet = entry("quiet")
    q.append(quiet)  # arrives dead last
    pos = 0
    while True:
        pos += 1
        if q.popleft() is quiet:
            break
    # FIFO would put it at position 201; WFQ interleaves it immediately
    assert pos <= 3, pos


def test_wfq_idle_tenant_gets_no_banked_credit():
    weights = {"a": 1.0, "b": 1.0}
    q = _TenantFairQueue(lambda t: weights[t])
    # a drains 100 entries alone, advancing virtual time
    for _ in range(100):
        q.append(entry("a"))
    for _ in range(100):
        q.popleft()
    # b was idle the whole time: it may NOT monopolize the next window
    for _ in range(20):
        q.append(entry("a"))
        q.append(entry("b"))
    first10 = [q.popleft().index for _ in range(10)]
    assert 3 <= first10.count("b") <= 7, first10


def test_starved_tenant_queue_wait_stays_inside_deadline_budget():
    """End-to-end pipeline regression: a 100x flooder on one tenant
    must not push the other tenant's queue wait past its deadline
    budget (here 250ms — the interactive default objective)."""
    tn = TenancyManager(weights="noisy=1,quiet=1")
    pl = QueryPipeline(
        workers={"interactive": 1, "bulk": 1, "internal": 1},
        queue_limits={"interactive": 512, "bulk": 1, "internal": 1},
        tenancy=tn,
    )
    stop = time.monotonic() + 1.2
    budget_s = 0.25

    def flood():
        while time.monotonic() < stop:
            try:
                pl.submit(
                    "interactive",
                    lambda: time.sleep(0.002),
                    index="noisy",
                )
            except Overloaded:
                time.sleep(0.001)

    flooders = [threading.Thread(target=flood) for _ in range(4)]
    for t in flooders:
        t.start()
    time.sleep(0.1)  # let the backlog build
    waits = []
    while time.monotonic() < stop - 0.2:
        t0 = time.monotonic()
        pl.submit("interactive", lambda: None, index="quiet")
        waits.append(time.monotonic() - t0)
        time.sleep(0.01)
    for t in flooders:
        t.join(10)
    pl.close(drain=5.0)
    assert waits, "no quiet-tenant samples collected"
    assert max(waits) < budget_s, (max(waits), len(waits))
    stats = pl.stats()
    assert stats["weighted_fair"]
    assert stats["tenants"]["quiet"]["admitted"] == len(waits)
    assert stats["tenants"]["noisy"]["admitted"] > 0


def test_pipeline_tenant_counters_shed_and_throttle():
    tn = TenancyManager(qps="limited=1")
    pl = QueryPipeline(
        workers={"interactive": 1, "bulk": 1, "internal": 1},
        queue_limits={"interactive": 1, "bulk": 1, "internal": 1},
        tenancy=tn,
    )
    try:
        with pytest.raises(TenantThrottled) as ei:
            for _ in range(10):
                pl.submit("interactive", lambda: None, index="limited")
        assert ei.value.status == 429
        row = pl.stats()["tenants"]["limited"]
        assert row["throttled"] >= 1
        assert row["admitted"] >= 1
    finally:
        pl.close(drain=1.0)


# -- HBM governor sub-tenant accounting --------------------------------------


def test_governor_by_index_charges_and_releases_balance():
    gov = HbmGovernor(budget_bytes=1 << 30)
    gov.register("stager", share_bytes=1 << 30, evict_fn=lambda need: 0)
    gov.reserve("stager", 100, index="a")
    gov.reserve("stager", 50, index="b")
    gov.reserve("stager", 25, index="a")
    assert gov.index_used("a") == 125
    assert gov.index_used("b") == 50
    gov.release("stager", 125, index="a")
    gov.release("stager", 50, index="b")
    assert gov.index_used("a") == 0
    assert gov.index_used("b") == 0
    st = gov.stats()
    # fully-released indexes are pruned from the attribution map
    assert st["tenants"]["stager"].get("by_index", {}) == {}


def test_governor_by_index_balances_under_concurrency():
    """Satellite 4: concurrent per-index reserve/release (staging) with
    interleaved relief sweeps — the per-index ledger must balance to
    exactly the net outstanding bytes per index."""
    gov = HbmGovernor(budget_bytes=1 << 30)
    evicted = threading.Event()

    def evict_fn(need, prefer=None):
        evicted.set()
        return 0  # nothing actually freed: pure accounting pressure

    gov.register("stager", share_bytes=1 << 30, evict_fn=evict_fn)
    indexes = ["a", "b", "c", "d"]
    outstanding = {i: 0 for i in indexes}
    mu = threading.Lock()
    stop = threading.Event()

    def churn(seed):
        rng = random.Random(seed)
        held = []  # (index, nbytes) this thread still owes a release
        for _ in range(400):
            idx = rng.choice(indexes)
            n = rng.randrange(1, 4096)
            gov.reserve("stager", n, index=idx)
            held.append((idx, n))
            with mu:
                outstanding[idx] += n
            if len(held) > 3:
                ridx, rn = held.pop(rng.randrange(len(held)))
                gov.release("stager", rn, index=ridx)
                with mu:
                    outstanding[ridx] -= rn
        for ridx, rn in held[: len(held) // 2]:
            gov.release("stager", rn, index=ridx)
            with mu:
                outstanding[ridx] -= rn

    def sweeper():
        while not stop.is_set():
            gov.relieve(4096)
            time.sleep(0.001)

    threads = [threading.Thread(target=churn, args=(s,)) for s in range(6)]
    sw = threading.Thread(target=sweeper)
    sw.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    stop.set()
    sw.join(10)
    for idx in indexes:
        assert gov.index_used(idx) == outstanding[idx], idx
    # total tenant usage equals the sum of per-index attributions
    st = gov.stats()["tenants"]["stager"]
    assert st["used"] == sum(outstanding.values())
    assert st["by_index"] == {
        i: n for i, n in outstanding.items() if n > 0
    }


def test_reserve_past_quota_sweeps_only_that_index():
    gov = HbmGovernor(budget_bytes=1 << 30)
    calls = []

    def evict_fn(need, prefer=None):
        calls.append((need, tuple(prefer) if prefer is not None else None))
        return 0

    gov.register("stager", share_bytes=1 << 30, evict_fn=evict_fn)
    gov.set_index_quotas({"capped": 1000})
    gov.reserve("stager", 800, index="capped")
    assert calls == []  # under quota: no sweep
    gov.reserve("stager", 800, index="capped")
    # over quota by 600: a targeted sweep of ONLY this index's blocks
    assert calls and calls[-1][1] == ("capped",)
    assert calls[-1][0] >= 600
    # an uncapped index never triggers a quota sweep
    calls.clear()
    gov.reserve("stager", 1 << 20, index="free")
    assert calls == []


def test_relief_prefers_over_quota_index_first():
    """Satellite 4: under global pressure, the over-quota tenant's
    blocks go first; an under-quota tenant loses nothing until the
    preferred pass came up short."""
    gov = HbmGovernor(budget_bytes=10_000)
    sweep_log = []
    # an over-quota-preferring tier that can free everything asked
    freed_pool = {"n": 100_000}

    def evict_fn(need, prefer=None):
        sweep_log.append(tuple(prefer) if prefer is not None else None)
        take = min(need, freed_pool["n"])
        freed_pool["n"] -= take
        # relief accounting: evictions release from the over-quota index
        if take:
            gov.release("stager", take, index="hog")
        return take

    gov.register("stager", share_bytes=10_000, evict_fn=evict_fn)
    gov.set_index_quotas({"hog": 2_000})
    gov.reserve("stager", 6_000, index="innocent")
    # hog blows past its quota AND pushes the ledger over budget
    gov.reserve("stager", 6_000, index="hog")
    # the first sweep pass targeted the over-quota index, not global LRU
    assert sweep_log[0] == ("hog",)
    # the innocent tenant kept every byte
    assert gov.index_used("innocent") == 6_000


def test_quota_stats_surface():
    gov = HbmGovernor(budget_bytes=1 << 20)
    gov.register("stager", share_bytes=1 << 20, evict_fn=lambda need: 0)
    gov.set_index_quotas({"a": 4096}, default=8192)
    gov.reserve("stager", 5000, index="b")
    st = gov.stats()
    assert st["index_quotas"] == {"a": 4096, "default": 8192}
    assert st["index_used"]["b"] == 5000
    assert gov.index_over_quota("b") == 0  # 5000 < 8192 default
    gov.reserve("stager", 5000, index="b")
    assert gov.index_over_quota("b") == 10_000 - 8192
    assert gov.over_quota_indexes() == ["b"]


# -- SLO + snapshot -----------------------------------------------------------


def test_tenant_objectives_register_and_burn():
    from pilosa_tpu.utils import slo

    tn = TenancyManager(objectives="gold=100@0.999,*=500@0.99")
    objs = tn.slo_objectives()
    assert objs == {"tenant:gold": (0.1, 0.999)}
    mon = slo.SLOMonitor(objectives={})
    old = slo.MONITOR
    slo.MONITOR = mon
    try:
        tn.observe("gold", 0.05, ok=True)  # explicit objective
        tn.observe("lazy", 0.05, ok=True)  # registered from the * default
        assert mon.has_class("tenant:gold")
        assert mon.has_class("tenant:lazy")
        rates = mon.burn_rates()
        assert "tenant:lazy" in rates
    finally:
        slo.MONITOR = old


def test_snapshot_lists_every_known_tenant():
    tn = TenancyManager(weights="a=4", qps="b=2")
    tn.admit("c", "interactive")  # touched at runtime only
    snap = tn.snapshot()
    assert set(snap["tenants"]) >= {"a", "b"}
    assert snap["tenants"]["a"]["weight"] == 4.0
    assert snap["tenants"]["b"]["qps"] == 2.0


# -- config + docs ------------------------------------------------------------

TENANT_KNOBS = {
    "tenant-weights": '""',
    "tenant-qps": '""',
    "tenant-hbm-quota": '""',
    "tenant-inflight-bytes": '""',
    "tenant-objectives": '""',
}


def test_config_tenant_knobs_roundtrip():
    from pilosa_tpu.server.config import Config

    cfg = Config.from_dict(
        {
            "tenant-weights": "a=4,*=1",
            "tenant-qps": "a=100",
            "tenant-hbm-quota": "a=1048576",
            "tenant-inflight-bytes": "a=65536",
            "tenant-objectives": "a=250@0.999",
        }
    )
    assert cfg.tenant_weights == "a=4,*=1"
    toml = cfg.to_toml()
    for key in TENANT_KNOBS:
        assert key in toml, key
    from pilosa_tpu.server import config as config_mod

    cfg2 = Config.from_dict(config_mod.tomllib.loads(toml))
    assert cfg2.tenant_qps == "a=100"
    assert cfg2.tenant_objectives == "a=250@0.999"


def test_docs_configuration_names_tenant_knobs():
    root = os.path.join(os.path.dirname(__file__), "..", "docs")
    with open(os.path.join(root, "configuration.md")) as f:
        doc = f.read()
    for knob, default in TENANT_KNOBS.items():
        assert f"`{knob}`" in doc, f"configuration.md missing {knob}"
    # the 429-vs-503 contract is operator-facing administration doc
    with open(os.path.join(root, "administration.md")) as f:
        admin = f.read()
    assert "429" in admin and "tenant" in admin
    assert "/debug/tenancy" in admin
