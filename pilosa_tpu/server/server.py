"""Server runtime (L7) — wires holder/executor/API/HTTP + background
loops (reference server.go / server/server.go Command).

Single-node mode runs with cluster=None (the reference's
``cluster.disabled`` static mode); the cluster layer plugs in through
the same seams the reference uses: a broadcaster (send_sync/send_async),
a message receiver, and the executor's cluster hook.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

from pilosa_tpu.core import Holder
from pilosa_tpu.executor import DeviceStager, Executor
from pilosa_tpu.executor.hbm import HbmGovernor
from pilosa_tpu.server.api import API
from pilosa_tpu.server.config import Config
from pilosa_tpu.server.http_handler import Handler, make_http_server
from pilosa_tpu import __version__
from pilosa_tpu.utils.attrstore import new_attr_store
from pilosa_tpu.utils.diagnostics import DiagnosticsCollector
from pilosa_tpu.utils.logger import NOP_LOGGER, StandardLogger
from pilosa_tpu.utils import (
    events,
    heat,
    logger as logger_mod,
    metrics,
    profiler,
    slo,
    telemetry_export,
    trace,
)
from pilosa_tpu.utils.gcnotify import GCNotifier
from pilosa_tpu.utils.stats import (
    ExpvarStatsClient,
    MultiStatsClient,
    NOP_STATS,
    StatsDClient,
)
from pilosa_tpu.translate import Translator


def _host_resolves_to_local(host: str, bind_host: str) -> bool:
    """True when ``host`` DNS-resolves to the address this server is
    bound to. With a specific bind IP the check is exact; with a
    wildcard bind (0.0.0.0 / ::) the host must resolve to one of this
    machine's own addresses. Resolution failures are False — an
    unresolvable advertised name can't be proven to be us."""
    import socket

    host = host.strip("[]")
    try:
        remote = {ai[4][0] for ai in socket.getaddrinfo(host, None)}
    except OSError:
        return False
    bind_host = bind_host.strip("[]")
    if bind_host not in ("", "0.0.0.0", "::"):
        try:
            local = {ai[4][0] for ai in socket.getaddrinfo(bind_host, None)}
        except OSError:
            local = {bind_host}
        return bool(remote & local)
    # wildcard bind: gather this machine's interface addresses
    local = {"127.0.0.1", "::1"}
    try:
        local.update(
            ai[4][0] for ai in socket.getaddrinfo(socket.gethostname(), None)
        )
    except OSError:
        pass
    return bool(remote & local)


class Server:
    def __init__(self, config: Optional[Config] = None, cluster=None) -> None:
        # entry point for every serving deployment (in-process servers
        # in tests and dry runs never pass through the CLI)
        from pilosa_tpu.utils.jaxplatform import bootstrap

        bootstrap()
        self.config = config or Config()
        data_dir = os.path.expanduser(self.config.data_dir)
        self.logger = (
            StandardLogger(verbose=self.config.verbose)
            if self.config.log_path != "nop"
            else NOP_LOGGER
        )
        # reference server/server.go:353-364 (expvar/statsd/none selection;
        # unknown names error there too). An in-process ExpvarStatsClient
        # is ALWAYS kept so /debug/vars and /metrics have a snapshot:
        # with the statsd sink, stats fan out to both.
        self._expvar = ExpvarStatsClient()
        if self.config.metric == "expvar":
            self.stats = self._expvar
        elif self.config.metric == "statsd":
            self.stats = MultiStatsClient(
                self._expvar, StatsDClient(host=self.config.metric_host)
            )
        elif self.config.metric in ("none", "nop", ""):
            self.stats = NOP_STATS
        else:
            raise ValueError(f"invalid metric service: {self.config.metric!r}")
        # tracer knobs (process-global tracer: the last server configured
        # in-process wins — one server per process in any real deployment)
        tracer = trace.TRACER
        tracer.sample_rate = self.config.trace_sample_rate
        tracer.slow_threshold = self.config.slow_query_time
        if self.config.slow_query_time > 0:
            import json as _json

            logger = self.logger

            def _log_slow(tree: dict) -> None:
                logger.printf(
                    "%.3fs SLOW QUERY trace %s",
                    tree.get("duration_ms", 0.0) / 1000.0,
                    _json.dumps(tree),
                )

            tracer.on_slow = _log_slow
        else:
            tracer.on_slow = None
        # workload heat ledger knobs (process-global like the tracer)
        heat.LEDGER.configure(
            self.config.heat_enabled, self.config.heat_decay_halflife
        )
        # durable event journal backing: the ring becomes a
        # write-through cache over segments in journal-dir (default
        # <data-dir>/.events); 0 bytes keeps the ring-only journal
        if self.config.journal_max_bytes > 0:
            events.JOURNAL.open_backing(
                self.config.journal_dir or os.path.join(data_dir, ".events"),
                self.config.journal_max_bytes,
            )
        # telemetry export pipeline: with no sink configured this is
        # None and the journal/tracer taps stay unset — the disabled
        # hot path pays one is-not-None branch, no allocations
        self.exporter = telemetry_export.build_exporter(
            path=self.config.export_path,
            url=self.config.export_url,
            queue_max=self.config.export_queue,
            interval=self.config.export_interval,
            metrics_fn=metrics.snapshot,
        )
        if self.exporter is not None:
            events.JOURNAL.on_record = self.exporter.tap_event
            trace.TRACER.on_export = self.exporter.tap_span
        self.gc_notifier: Optional[GCNotifier] = None  # hooked while open
        self.holder = Holder(
            data_dir,
            new_attr_store=new_attr_store,
            broadcaster=self._broadcast_create_shard,
        )
        # key translation (ISSUE 20, pilosa_tpu/translate/): partitioned
        # durable key↔id stores under <data>/translate; ownership,
        # forwarding and replication are wired in open() once the
        # listener (and so this node's own URI) is known
        self.translate_store = Translator(
            os.path.join(data_dir, "translate"),
            partitions=self.config.translate_partitions,
            cache_bytes=self.config.translate_cache_bytes,
        )
        self.cluster = cluster
        # multihost serving (parallel/multihost.py): bootstrap the
        # jax.distributed runtime BEFORE the mesh is built, so
        # jax.devices() below is the GLOBAL device set spanning every
        # process. Rank 0 is the serving leader; followers replay.
        self.multihost = None
        self._mh_rank, self._mh_world = 0, 1
        if self.config.distributed_enabled:
            from pilosa_tpu.parallel import multihost as multihost_mod

            self._mh_rank, self._mh_world = multihost_mod.initialize_distributed(
                self.config.distributed_coordinator,
                self.config.distributed_num_processes,
                self.config.distributed_process_id,
                use_gloo=self.config.distributed_gloo,
            )
            self.logger.printf(
                "multihost: rank %d/%d initialized",
                self._mh_rank,
                self._mh_world,
            )
        self.mesh = self._build_mesh()
        self.stager = DeviceStager(
            budget_bytes=self.config.stager_budget_bytes,
            mesh=self.mesh,
            delta_enabled=self.config.stager_delta_enabled,
            delta_max_ratio=self.config.stager_delta_max_ratio,
            tier1_max_bytes=self.config.tier1_max_bytes,
            compressed_min_ratio=self.config.compressed_upload_min_ratio,
        )
        # the delta log capacity rides on the fragment class (fragments
        # are created deep inside the holder tree; a process-wide
        # default is the right scope for a process-wide stager)
        from pilosa_tpu.core import fragment as fragment_mod

        fragment_mod.DELTA_LOG_MAX = self.config.stager_delta_log_max
        # bulk-import cliff threshold + storage fault injection are
        # process-wide for the same reason
        fragment_mod.DELTA_MAX_BATCH = self.config.ingest_delta_max_batch
        fragment_mod.install_storage_faults(self.config.storage_faults)
        # device fault injection (utils/chaos.py) is process-wide for
        # the same reason; the chaos endpoint re-installs at runtime
        from pilosa_tpu.utils import chaos as chaos_mod

        chaos_mod.install_device_faults(self.config.device_faults)
        # serving deployments get the device health gate: a wedged
        # accelerator (hung PJRT call) degrades reads to the CPU
        # roaring path instead of hanging them, and a background probe
        # restores the device path when it answers again
        health = None
        if self.config.distributed_enabled:
            # gang determinism: the health guard runs calls through a
            # worker pool with per-call timeouts — a rank-0-only trip
            # or pool-timeout would change which collectives execute
            # and deadlock the mesh. The gang's own deadline fencing
            # (dispatch timeout → degrade-to-local-mesh) is the
            # recovery story in distributed mode.
            pass
        elif self.config.device_policy != "never" and self.config.device_timeout > 0:
            from pilosa_tpu.executor.devicehealth import DeviceHealth

            health = DeviceHealth(
                timeout_s=self.config.device_timeout, logger=self.logger
            )
        # plan result cache (plan/cache.py): generation-stamped cross-
        # request result cache; the executor consults it around call
        # dispatch and the planner substitutes cached subtrees
        self.plan_cache = None
        if self.config.plan_cache_enabled:
            from pilosa_tpu.plan.cache import PlanCache

            self.plan_cache = PlanCache(
                max_bytes=self.config.plan_cache_max_bytes,
                min_cost=self.config.plan_cache_min_cost,
            )
        self.executor = Executor(
            self.holder,
            cluster=cluster,
            stager=self.stager,
            device_policy=self.config.device_policy,
            translate_store=self.translate_store,
            max_writes_per_request=self.config.max_writes_per_request,
            mesh=self.mesh,
            health=health,
            auto_min_containers=(
                self.config.auto_device_min_containers
                if self.config.auto_device_min_containers > 0
                else None
            ),
            plan_cache=self.plan_cache,
            dispatch_enabled=self.config.dispatch_enabled,
            dispatch_max_wave=self.config.dispatch_max_wave,
            dispatch_max_inflight=self.config.dispatch_max_inflight,
            dispatch_stage_ahead=self.config.dispatch_stage_ahead,
            prefetch_enabled=self.config.prefetch_enabled,
            prefetch_depth=self.config.prefetch_depth,
            fusion_enabled=self.config.fusion_enabled,
            fusion_max_calls=self.config.fusion_max_calls,
            plan_cache_device_bytes=self.config.plan_cache_device_bytes,
            governor=HbmGovernor(budget_bytes=self.config.hbm_budget_bytes),
            analytics_max_groups=self.config.analytics_max_groups,
        )
        self.api = API(self.holder, self.executor, cluster=cluster, server=self)
        # federation (parallel/federation.py): epoch adopted from the
        # gang leader at rejoin; -1 = never joined, every epoch-stamped
        # apply is refused until the leader's state push lands
        self.gang_epoch = -1
        self._gang_apply_fn = None
        if self.config.distributed_enabled:
            from pilosa_tpu.parallel.multihost import (
                MultiHostRuntime,
                make_apply_fn,
            )

            self.multihost = MultiHostRuntime(
                rank=self._mh_rank,
                world=self._mh_world,
                apply_fn=make_apply_fn(self),
                frame_bytes=self.config.distributed_frame_bytes,
                idle_interval=self.config.distributed_idle_interval,
                dispatch_timeout=self.config.distributed_dispatch_timeout,
                leader_timeout=self.config.distributed_leader_timeout,
                on_degrade=self._degrade_to_local_mesh,
                logger=self.logger,
                faults=self.config.distributed_faults,
            )
            # the executor routes every non-remote query through the
            # gang on the leader; followers re-enter execute() from the
            # worker loop with the in-gang flag set
            self.executor.gang = self.multihost
        elif self.config.federation_leader:
            # restarted gang leader: the old collective plane died with
            # its peers (a poisoned gloo context cannot be rebuilt
            # in-process), so come back replicated-solo — DEGRADED until
            # a follower rejoins through /internal/gang/rejoin
            from pilosa_tpu.parallel.multihost import (
                MultiHostRuntime,
                make_apply_fn,
            )

            self.multihost = MultiHostRuntime.replicated(
                apply_fn=make_apply_fn(self),
                dispatch_timeout=self.config.distributed_dispatch_timeout,
                logger=self.logger,
            )
            self.executor.gang = self.multihost
        # fleet identity (ISSUE 10): stamp every trace root and journal
        # event with this process's gang/rank, and give log records the
        # live epoch. Standalone servers keep empty tags — span meta
        # stays exactly what the caller passed.
        ident: dict = {}
        if self.config.distributed_coordinator:
            ident["gang"] = self.config.distributed_coordinator
        if self.config.distributed_enabled:
            ident["rank"] = self._mh_rank
        if ident:
            trace.TRACER.tags = dict(ident)
            events.JOURNAL.tags = dict(ident)
        if self.multihost is not None:
            _mh = self.multihost

            logger_mod.set_context_provider(
                lambda: {
                    "gang": self.config.distributed_coordinator or "",
                    "rank": self._mh_rank,
                    "epoch": _mh.epoch,
                }
            )
        # fleet telemetry collector (server/fleet.py): every server owns
        # one; only a gang/federation leader accumulates members
        from pilosa_tpu.server.fleet import FleetCollector

        self.fleet = FleetCollector(self)
        # multi-tenant QoS (server/tenancy.py): per-index admission
        # buckets, weighted-fair scheduling, HBM quotas, per-tenant
        # SLOs. Disabled (zero-cost passthrough) when no tenant-* knob
        # is configured — the single-tenant default stays bit-identical
        from pilosa_tpu.server.tenancy import TenancyManager

        self.tenancy = TenancyManager(
            weights=self.config.tenant_weights,
            qps=self.config.tenant_qps,
            hbm_quota=self.config.tenant_hbm_quota,
            inflight_bytes=self.config.tenant_inflight_bytes,
            objectives=self.config.tenant_objectives,
        )
        if self.tenancy.enabled and (
            self.tenancy.hbm_quotas() or self.tenancy.default_hbm_quota
        ):
            self.executor.governor.set_index_quotas(
                self.tenancy.hbm_quotas(),
                default=self.tenancy.default_hbm_quota,
            )
        # serving pipeline (server/pipeline.py): every query/import
        # request flows through bounded per-class admission queues with
        # deadline scheduling and singleflight coalescing
        self.pipeline = None
        if self.config.pipeline_enabled:
            from pilosa_tpu.server.pipeline import QueryPipeline

            self.pipeline = QueryPipeline(
                workers={
                    "interactive": self.config.pipeline_interactive_workers,
                    "bulk": self.config.pipeline_bulk_workers,
                    "internal": self.config.pipeline_internal_workers,
                },
                queue_limits={
                    "interactive": self.config.pipeline_interactive_queue,
                    "bulk": self.config.pipeline_bulk_queue,
                    "internal": self.config.pipeline_internal_queue,
                },
                shed_retry_after=self.config.pipeline_shed_retry_after,
                drain_timeout=self.config.pipeline_drain_timeout,
                tenancy=self.tenancy,
            )
        # durable ingest queue (server/ingest.py): its own admission
        # class beside interactive/bulk — bounded write-ahead queue,
        # group-committed write waves, acks only after fsync
        self.ingest = None
        if self.config.ingest_enabled:
            from pilosa_tpu.server.ingest import IngestQueue

            self.ingest = IngestQueue(
                self.api,
                queue_limit=self.config.ingest_queue_limit,
                wave_max=self.config.ingest_wave_max,
                wave_interval=self.config.ingest_wave_interval,
                retry_after=self.config.ingest_retry_after,
            )
        self.handler = Handler(
            self.api,
            logger=self.logger,
            stats=self.stats,
            long_query_time=self.config.cluster.long_query_time,
            pipeline=self.pipeline,
            default_timeout=self.config.pipeline_default_timeout,
            analytics_timeout=self.config.analytics_timeout,
            ingest=self.ingest,
            tenancy=self.tenancy,
        )
        self.diagnostics = DiagnosticsCollector(
            host=getattr(self.config, "diagnostics_host", ""),
            version=__version__,
            logger=self.logger,
        )
        from pilosa_tpu.server.scrub import Scrubber

        self.scrubber = Scrubber(self)
        self.httpd = None
        self._serve_thread: Optional[threading.Thread] = None
        self.node_id: str = ""
        self._closed = threading.Event()
        # memoized translate-primary resolution (see translate_primary)
        # (value, monotonic-expiry-or-None); see translate_primary
        self._translate_primary_cache: Optional[tuple] = None
        # memoized key-space ownership: (index, field, partition) ->
        # (owner uri or "", monotonic expiry); see _translate_owner
        self._translate_owner_cache: dict = {}

    def _build_mesh(self):
        """Resolve config.mesh_devices into a jax Mesh over the shard
        axis (None = single-device execution). Accepts an int count or
        "all"; more devices requested than visible is an error — a
        silent clamp would hide a misconfigured slice."""
        if self.config.distributed_enabled:
            # distributed serving: one GLOBAL mesh over every process's
            # devices — the whole point; mesh_devices is ignored (a
            # partial global mesh would strand follower devices)
            import jax

            from pilosa_tpu.parallel.spmd import make_mesh

            devices = jax.devices()
            mesh = make_mesh(devices)
            self.logger.printf(
                "multihost SPMD mesh: %d global devices over %d processes",
                len(devices),
                self._mh_world,
            )
            return mesh
        want = self.config.mesh_devices
        if isinstance(want, str):
            want = want.strip().lower()
            if want in ("", "0", "none"):
                return None
            if want != "all":
                want = int(want)
        if want in (0, 1):
            return None
        if isinstance(want, int) and want < 0:
            raise ValueError(f"mesh_devices must be >= 0, got {want}")
        import jax

        from pilosa_tpu.parallel.spmd import make_mesh

        devices = jax.devices()
        if want == "all":
            want = len(devices)
        if want > len(devices):
            raise ValueError(
                f"mesh_devices={want} but only {len(devices)} devices visible"
            )
        mesh = make_mesh(devices[:want])
        self.logger.printf("SPMD mesh: %d devices over shard axis", want)
        return mesh

    def _degrade_to_local_mesh(self) -> None:
        """Multihost failure path: the gang is dead (follower loss),
        so the global mesh can never complete another collective. Hand
        the executor a mesh over THIS process's own devices (or none,
        single-device) and fresh staging — serving continues locally,
        reads stay correct (every rank holds the full replayed state),
        capacity shrinks to one host.

        On the CPU backend the local mesh is skipped entirely: CPU
        cross-device collectives ride the same gloo context the dead
        gang poisoned (observed: post-degrade local psum fails with
        'Gloo all-reduce failed: Connection reset by peer'), so the
        degraded executor runs the collective-free single-device
        batched path. Real TPU deployments keep a local ICI mesh."""
        import jax

        from pilosa_tpu.parallel.spmd import make_mesh

        local = jax.local_devices()
        mesh = (
            make_mesh(local)
            if len(local) > 1 and jax.default_backend() != "cpu"
            else None
        )
        stager = DeviceStager(
            budget_bytes=self.config.stager_budget_bytes,
            mesh=mesh,
            delta_enabled=self.config.stager_delta_enabled,
            delta_max_ratio=self.config.stager_delta_max_ratio,
            tier1_max_bytes=self.config.tier1_max_bytes,
            compressed_min_ratio=self.config.compressed_upload_min_ratio,
        )
        ex = self.executor
        if self.multihost is None or not self.multihost.federated:
            # PR 5 single-plane semantics: the gang is gone for good.
            # A FEDERATED runtime keeps the gang attached — it re-enters
            # service replicated-solo and reform() needs the hook chain.
            ex.gang = None
        with ex._spmd_mu:
            ex._spmd_kernels = {}
        ex.mesh = mesh
        ex.stager = stager
        # scorer queues may hold work aimed at dead global arrays, and
        # results computed on the dead gang epoch must not be served
        # (resets the new stager too — a no-op on a fresh instance)
        ex._on_device_restore()
        self.stager = stager
        self.mesh = mesh
        self.logger.printf(
            "multihost degraded: serving on local mesh (%d devices)",
            len(local),
        )

    def serve_follower(self) -> str:
        """Run the multihost follower worker loop on the calling thread
        until the leader's poison pill (clean shutdown) or leader loss
        (deadline-fenced abort). Returns the stop reason."""
        if self.multihost is None:
            raise RuntimeError("serve_follower requires distributed-enabled")
        return self.multihost.serve_follower()

    # -- lifecycle (reference Server.Open:312) --

    def open(self) -> None:
        tls = self.config.tls
        if bool(tls.certificate_path) != bool(tls.certificate_key_path):
            # half-configured TLS must not silently serve plaintext
            raise ValueError(
                "TLS misconfigured: both certificate-path and "
                "certificate-key-path are required"
            )
        self._set_file_limit()
        self.logger.printf(
            "pilosa_tpu %s starting, data=%s", __version__, self.holder.path
        )
        self.holder.open()
        self.node_id = self.holder.load_node_id()
        # HTTP up first: join/resize messages must be receivable before
        # the cluster attaches (reference SetupNetworking before Open).
        self.httpd = make_http_server(
            self.handler, self.config.host, self.config.port
        )
        if self.config.tls.enabled:
            # TLS on the listener (reference server/server.go:166-240:
            # getListener wraps with tls.NewListener from the config's
            # certificate paths)
            import ssl

            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(
                os.path.expanduser(self.config.tls.certificate_path),
                os.path.expanduser(self.config.tls.certificate_key_path),
            )
            self.httpd.socket = ctx.wrap_socket(self.httpd.socket, server_side=True)
        # wire key-translation ownership + forwarding BEFORE serving: a
        # keyed write arriving in the startup window would otherwise
        # mint locally and permanently diverge the cluster id space
        self._wire_translate_plane()
        self._serve_thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True
        )
        self._serve_thread.start()
        self.logger.printf(
            "pilosa_tpu server listening on %s://%s:%d", self.scheme, *self.address()
        )
        # build_info gauge: one constant-1 sample whose labels identify
        # this process in a fleet scrape (version, backend and devices
        # as JAX reports them, gang, rank) — a JAX-free client learns
        # what the queries ran on from here
        import jax

        from pilosa_tpu import native_bridge

        devices = jax.devices()
        metrics.gauge(
            metrics.BUILD_INFO,
            1.0,
            version=__version__,
            jax=jax.__version__,
            backend=devices[0].platform,
            device_kind=devices[0].device_kind,
            device_count=str(len(devices)),
            native=str(native_bridge.available()).lower(),
            pid=str(os.getpid()),
            gang=self.config.distributed_coordinator or "",
            rank=str(self._mh_rank),
            leader=str(self._mh_rank == 0).lower(),
        )
        # performance attribution plane (ISSUE 12): uptime/start-time
        # gauges for fleet restart detection, SLO objectives from
        # config, and the always-on samplers. All of it degrades to
        # no-ops when the knobs disable it — serving never depends on
        # the observers.
        self.started_at = time.time()
        metrics.gauge(metrics.PROCESS_START_TIME_SECONDS, round(self.started_at, 3))
        metrics.gauge(metrics.UPTIME_SECONDS, 0.0)
        # the collector's pauses, timed into the metric registry
        self.gc_notifier = GCNotifier()
        slo.MONITOR.configure(
            objectives=slo.parse_objectives(self.config.slo_objectives),
            burn_threshold=self.config.slo_burn_threshold,
        )
        # per-tenant SLOs ride the same monitor as tenant:<index>
        # classes — one tick, one scrape (server/tenancy.py); tenants
        # covered only by the "*" default register lazily at first query
        if self.tenancy.enabled:
            slo.MONITOR.merge(self.tenancy.slo_objectives())
        profiler.TELEMETRY.watermark_pct = self.config.hbm_watermark_pct
        stager = self.stager

        def _stager_probe() -> tuple[int, int]:
            return stager._bytes, stager.budget_bytes

        profiler.TELEMETRY.stager_probe = _stager_probe
        profiler.TELEMETRY.start()
        profiler.COMPILES.listen()
        if self.exporter is not None:
            self.exporter.start()
        if self.config.profiler_hz > 0:
            profiler.SAMPLER.hz = self.config.profiler_hz
            profiler.SAMPLER.start()
        if self.cluster is None and not self.config.cluster.disabled:
            if self.config.distributed_enabled and self._mh_rank != 0:
                # federation: the cluster plane runs on gang LEADERS
                # only — a follower's holder is a replica of its
                # leader's, reachable through the leader
                self.logger.printf(
                    "federation: rank %d leaves the cluster plane to its "
                    "gang leader",
                    self._mh_rank,
                )
            else:
                if self.config.distributed_enabled:
                    self.logger.printf(
                        "federation: gang leader joins the cluster plane "
                        "(sharded gang federation)"
                    )
                self.cluster = self._build_cluster()
        if self.cluster is not None:
            self.executor.cluster = self.cluster
            self.api.cluster = self.cluster
            self.cluster.attach_server(self)
            if self.multihost is not None:
                # compose the planes: gang-replaying local executor,
                # replication + epoch-fence + state-gossip hooks
                from pilosa_tpu.parallel import federation

                federation.wire(self)
        if self.config.federation_rejoin:
            # restarted follower: announce to the gang leader off-thread
            # (the leader's schema/fragment push needs OUR listener)
            from pilosa_tpu.parallel import federation

            federation.start_rejoin(self)
        if self.multihost is not None and self._mh_rank == 0:
            # leader-URI handshake: followers learn where to push replay
            # spans and register their scrape endpoints (gang-only — the
            # cluster plane's peer leaders announce their own)
            try:
                self._gang_message({"type": "leader-uri", "uri": self.uri})
            except Exception as e:
                self.logger.printf("leader-uri broadcast failed: %s", e)
        # measure the device-policy crossover for THIS deployment
        # (dispatch time / per-container CPU cost) unless the operator
        # pinned one via config — measured beats guessed
        # (executor/autotune.py). Non-blocking: serving starts on the
        # default and adopts the measurement when it lands; a wedged
        # device can't stall startup.
        if (
            self.config.device_policy == "auto"
            and self.config.auto_device_min_containers <= 0
            # gang determinism: a per-rank MEASURED crossover would make
            # ranks disagree on device-vs-CPU routing — one rank enters
            # a collective the other skips, and the mesh deadlocks. In
            # distributed mode the crossover must be config-pinned
            # (auto-device-min-containers) or the shared default.
            and self.multihost is None
        ):
            from pilosa_tpu.executor.autotune import autotune_executor

            autotune_executor(self.executor, logger=self.logger)
        # startup node-status sync runs SYNCHRONOUSLY before open()
        # returns (memberlist's join-time full state sync): a restarted
        # node must know its live peers' schema + maxShards the moment
        # it serves, or cross-shard counts collapse to local shards
        # until the periodic exchange. Peers that are still down are
        # skipped — their own boot-time push heals the other direction.
        if (
            self.cluster is not None
            and len(self.cluster.nodes) > 1
            and self.config.cluster.status_interval > 0
        ):
            try:
                self.cluster.push_node_status(sync=True)
                self.cluster.pull_node_status()
            except Exception as e:
                self.logger.printf("startup node-status sync error: %s", e)
        self._start_background_loops()

    def _normalize_host_uri(self, h: str) -> str:
        """host[:port] or URI → canonical URI string: missing scheme
        defaults to this server's, missing port to the reference's
        10101 (utils/uri.py; reference uri.go:82-264). Canonicalizing
        here kills the bind-vs-advertise bug class where equivalent
        spellings fail string comparison. An address the strict parser
        rejects (uppercase/underscore hostnames the reference's
        hostRegexp also rejects) falls back to the legacy
        scheme-prefix form with a warning — a weird-but-working
        config must not become a boot crash."""
        from pilosa_tpu.utils.uri import URI, URIError

        try:
            return URI.from_address(h, default_scheme=self.scheme).normalize()
        except URIError:
            self.logger.printf(
                "address %r does not parse as a URI (reference uri.go "
                "host rules); using it verbatim", h
            )
            return h if h.startswith("http") else f"{self.scheme}://{h}"

    def _is_self(self, uri_str: str) -> bool:
        """Does this address name this server's listener? Compares
        scheme/host/port through URI equivalence (localhost spellings,
        default ports), then — for a bind-vs-advertise hostname/IP
        mismatch — through DNS: same port and the advertised host
        resolves to this server's bound IP (or to any local interface
        when bound to a wildcard). DNS results are config-controlled,
        unlike a request's Host header, so this cannot be spoofed by
        a client."""
        from pilosa_tpu.utils.uri import URI, URIError, same_endpoint

        if same_endpoint(uri_str, self.uri, default_scheme=self.scheme):
            return True
        try:
            other = URI.from_address(uri_str, default_scheme=self.scheme)
        except URIError:
            return False
        host, port = self.address()
        if other.port != port:
            return False
        return _host_resolves_to_local(other.host, bind_host=host)

    def translate_primary(self) -> str:
        """URI of the cluster's ONE id-minting translate store — this
        node replicates from (and forwards new keys to) it unless it IS
        it. Resolution: explicit translate-primary-url > the coordinator
        (join mode) > the first static host. Config-only, so it resolves
        before the listener starts. Deterministic across nodes — every
        node agrees without extra config. Empty = self is primary (or
        no cluster).

        The answer is MEMOIZED after the listener is bound: resolution
        can consult DNS (``_is_self``), and re-resolving on every
        forwarded mint would put blocking getaddrinfo calls on the
        keyed-write hot path. The SELF answer ("") is final; a
        NON-empty answer is cached with a TTL, because it may be the
        product of a transient resolver failure at boot (containers) —
        pinning it forever would leave the true primary 409ing every
        keyed write until restart."""
        cached = self._translate_primary_cache
        if cached is not None:
            value, expires = cached
            if expires is None or time.monotonic() < expires:
                return value
        out = self._resolve_translate_primary()
        if self.httpd is not None:  # port known → answer is cacheable
            self._translate_primary_cache = (
                out,
                None if out == "" else time.monotonic() + 60.0,
            )
        return out

    def _resolve_translate_primary(self) -> str:
        explicit = self.config.translate_primary_url
        if explicit:
            p = self._normalize_host_uri(explicit)
            return "" if self._is_self(p) else p
        cc = self.config.cluster
        if cc.disabled:
            return ""
        if cc.hosts:
            p = self._normalize_host_uri(cc.hosts[0])
            return "" if self._is_self(p) else p
        if cc.coordinator:
            return ""
        if cc.coordinator_host:
            p = self._normalize_host_uri(cc.coordinator_host)
            # same self-detection as the other branches: a node whose
            # coordinator_host names ITSELF under an alternate spelling
            # must not forward-and-409 its own keyed writes
            return "" if self._is_self(p) else p
        return ""

    def _translate_owner(self, index: str, field: str, partition: int) -> str:
        """Owning node's URI for one key space ("" = this node owns it).
        Explicit ``translate-primary-url`` is the legacy override — one
        node owns everything; otherwise each column partition / row
        space lands on a cluster node by jump hash over the sorted
        member list, so every node computes the same owner with no
        coordinator. Memoized with a TTL: resolution consults DNS
        (``_is_self``), which must stay off the keyed-write hot path,
        but membership can change, so a cached answer may not outlive
        the TTL."""
        key = (index, field, partition)
        cached = self._translate_owner_cache.get(key)
        if cached is not None and time.monotonic() < cached[1]:
            return cached[0]
        explicit = self.config.translate_primary_url
        if explicit:
            p = self._normalize_host_uri(explicit)
            out = "" if self._is_self(p) else p
        else:
            cl = self.cluster
            if cl is None or len(cl.nodes) <= 1:
                out = ""
            else:
                from pilosa_tpu.parallel.hashing import fnv64a, jump_hash

                nodes = cl.nodes  # kept sorted by node id
                i = jump_hash(
                    fnv64a(f"{index}/{field}/{partition}".encode()), len(nodes)
                )
                uri = self._normalize_host_uri(nodes[i].uri)
                out = "" if self._is_self(uri) else uri
        if self.httpd is not None:  # port known → answer is cacheable
            self._translate_owner_cache[key] = (out, time.monotonic() + 60.0)
        return out

    def _wire_translate_plane(self) -> None:
        """Wire the translate subsystem's server seams: ownership
        (jump-hash partitioned, or the legacy single primary), minting
        forwards over InternalClient, and assignment push replication
        over the existing gang-descriptor + cluster message planes."""
        ts = self.translate_store
        from pilosa_tpu.parallel.client import InternalClient

        client = InternalClient(ssl_context=self.client_ssl_context())
        ts.owner_resolver = self._translate_owner

        def forward_to(uri, index, field, keys):
            return client.translate_keys(uri, index, field, keys)

        def on_assign(index, field, keys, ids):
            # locally-minted assignments ride the same broadcast plane
            # as schema ops (gang descriptors + cluster messages); the
            # per-store pull loop below is the catch-up backstop
            self.send_async(
                {
                    "type": "translate-keys",
                    "index": index,
                    "field": field,
                    "keys": list(keys),
                    "ids": [int(i) for i in ids],
                }
            )

        ts.forward_to = forward_to
        ts.on_assign = on_assign

    def _set_file_limit(self) -> None:
        """Raise RLIMIT_NOFILE toward the reference's 262,144 target
        (holder.setFileLimit, holder.go:40,470) — one mmapped file per
        fragment adds up. Best-effort: capped at the hard limit."""
        try:
            import resource

            target = 262_144
            soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
            want = min(target, hard) if hard != resource.RLIM_INFINITY else target
            if soft < want:
                resource.setrlimit(resource.RLIMIT_NOFILE, (want, hard))
                self.logger.printf("raised open-file limit to %d", want)
        except (ImportError, ValueError, OSError) as e:
            self.logger.printf("could not raise file limit: %s", e)

    def flush_caches(self) -> None:
        """One pass of the cache-flush loop. It runs under the
        interpreter's lock beside the requests, so its seconds
        (``holder.cache_flush_seconds``) say what a slow minute held."""
        t0 = time.monotonic()
        try:
            for idx in list(self.holder.indexes.values()):
                for fld in list(idx.fields.values()):
                    for view in list(fld.views.values()):
                        for frag in list(view.fragments.values()):
                            if frag._open:
                                frag.flush_cache()
        except Exception as e:
            self.logger.printf("cache flush error: %s", e)
        metrics.observe(metrics.CACHE_FLUSH_SECONDS, time.monotonic() - t0)

    def _start_background_loops(self) -> None:
        """reference server.go: monitorAntiEntropy:400, monitorRuntime:683,
        monitorDiagnostics:633."""

        def cache_flush_loop():
            # reference monitorCacheFlush (holder.go:425): persist every
            # OPENED fragment's TopN cache periodically so a crash loses
            # at most one interval of ranking state. Never-touched lazy
            # fragments have nothing new to flush.
            interval = self.config.cache_flush_interval
            if interval <= 0:
                return
            while not self._closed.wait(interval):
                self.flush_caches()

        def anti_entropy_loop():
            interval = self.config.anti_entropy_interval
            if interval <= 0:
                return
            while not self._closed.wait(interval):
                try:
                    if self.cluster is not None:
                        t0 = time.monotonic()
                        self.cluster.sync_holder()
                        self.stats.histogram(
                            metrics.ANTI_ENTROPY_SECONDS, time.monotonic() - t0
                        )
                except Exception as e:
                    # a silently dead syncer is an availability bug:
                    # count + journal so the failure is fleet-visible
                    self.stats.count(metrics.ANTI_ENTROPY_ERRORS)
                    events.record(events.ANTI_ENTROPY_ERROR, error=str(e))
                    self.logger.printf("anti-entropy sync error: %s", e)

        def scrub_loop():
            # background data-integrity sweep (server/scrub.py) — sleep
            # first so boot-time opens (which verify digests themselves)
            # aren't doubled, then sweep on the interval
            interval = self.scrubber.interval
            if interval <= 0:
                return
            while not self._closed.wait(interval):
                try:
                    self.scrubber.sweep()
                except Exception as e:
                    self.logger.printf("scrub sweep error: %s", e)

        def runtime_monitor_loop():
            import gc

            while not self._closed.wait(10.0):
                try:
                    import resource

                    usage = resource.getrusage(resource.RUSAGE_SELF)
                    self.stats.gauge(metrics.MAX_RSS_KB, usage.ru_maxrss)
                    self.stats.gauge(metrics.THREADS, threading.active_count())
                    counts = gc.get_count()
                    self.stats.gauge(metrics.GC_GEN0, counts[0])
                    self.stats.gauge(metrics.OPEN_FRAGMENTS, self._count_fragments())
                except Exception:
                    pass

        def diagnostics_loop():
            if self.diagnostics.host == "":
                return
            while not self._closed.wait(3600.0):
                self.diagnostics.enrich_with_os_info()
                self.diagnostics.enrich_with_schema(self.holder)
                self.diagnostics.flush()

        def translate_replication_loop():
            # pull catch-up for key assignments: every peer's stores
            # are polled from a per-(peer, store) byte offset and raw
            # CRC frames are applied locally (by-key idempotent). This
            # is the backstop under the broadcast push (translate-keys
            # messages) — a node that missed a broadcast converges
            # here. Offsets are in-memory only: logs are append-only,
            # so a restart just re-pulls from 0 and applies no-ops.
            from pilosa_tpu.parallel.client import ClientError, InternalClient

            client = InternalClient(ssl_context=self.client_ssl_context())
            ts = self.translate_store
            offsets: dict = {}
            self_uris: dict = {}
            while not self._closed.wait(1.0):
                uris = []
                if self.cluster is not None and len(self.cluster.nodes) > 1:
                    for n in self.cluster.nodes:
                        u = n.uri
                        if u not in self_uris:
                            self_uris[u] = self._is_self(
                                self._normalize_host_uri(u)
                            )
                        if not self_uris[u]:
                            uris.append(u)
                else:
                    legacy = self.translate_primary()
                    if legacy:
                        uris.append(legacy)
                for uri in uris:
                    try:
                        for entry in client.translate_stores(uri):
                            name = entry.get("name", "")
                            off = offsets.get((uri, name), 0)
                            if int(entry.get("offset", 0)) <= off:
                                continue
                            data = client.translate_data(uri, off, store=name)
                            if data:
                                offsets[(uri, name)] = off + ts.apply_frames(
                                    data
                                )
                    except (ClientError, ValueError):
                        pass

        def liveness_loop():
            # reference memberlist probing (gossip/gossip.go:431-494):
            # mark unresponsive peers SUSPECT → DOWN so query planning
            # fails over before paying a timeout
            interval = self.config.cluster.probe_interval
            if interval <= 0:
                return
            while not self._closed.wait(interval):
                try:
                    if self.cluster is not None and len(self.cluster.nodes) > 1:
                        self.cluster.probe_nodes()
                except Exception as e:
                    self.logger.printf("liveness probe error: %s", e)

        def slo_tick_loop():
            # evaluate burn-rate windows even when nobody scrapes: the
            # journal event (events.SLO_BURN) must fire on wall-clock,
            # not on observer traffic. Also refreshes the uptime gauge
            # so a scrape between ticks is at most 5s stale.
            while not self._closed.wait(5.0):
                try:
                    metrics.gauge(
                        metrics.UPTIME_SECONDS,
                        round(time.time() - self.started_at, 3),
                    )
                    slo.MONITOR.tick()
                except Exception as e:
                    self.logger.printf("slo tick error: %s", e)

        def node_status_loop():
            # reference periodic NodeStatus push/pull (server.go:565-630)
            interval = self.config.cluster.status_interval
            if interval <= 0:
                return
            # (the join-time full state sync runs synchronously in
            # open() — see there; this loop is only the periodic drift
            # healer, reference server.go:565-630)
            while not self._closed.wait(interval):
                try:
                    if self.cluster is not None and len(self.cluster.nodes) > 1:
                        self.cluster.push_node_status()
                except Exception as e:
                    self.logger.printf("node-status push error: %s", e)

        for fn in (
            cache_flush_loop,
            anti_entropy_loop,
            scrub_loop,
            runtime_monitor_loop,
            diagnostics_loop,
            translate_replication_loop,
            liveness_loop,
            slo_tick_loop,
            node_status_loop,
        ):
            threading.Thread(target=fn, daemon=True).start()

    def _count_fragments(self) -> int:
        n = 0
        for idx in self.holder.indexes.values():
            for f in idx.fields.values():
                for v in f.views.values():
                    n += len(v.fragments)
        return n

    def _build_cluster(self):
        from pilosa_tpu.parallel.cluster import Cluster
        from pilosa_tpu.parallel.node import Node

        cc = self.config.cluster
        data_dir = os.path.expanduser(self.config.data_dir)
        topology_path = os.path.join(data_dir, ".topology")
        ssl_ctx = self.client_ssl_context()
        scheme = self.scheme
        if cc.hosts:
            # Static topology: node identity = URI so every node derives
            # the identical ring (the reference's cluster-disabled mode
            # generalised to N fixed hosts).
            cluster = Cluster(
                node_id=self.uri,
                uri=self.uri,
                replica_n=cc.replicas,
                static=True,
                coordinator=cc.coordinator,
                topology_path=topology_path,
                logger=self.logger,
                probe_timeout=cc.probe_timeout,
                down_after=cc.down_after,
                ssl_context=ssl_ctx,
            )
            cluster.set_nodes(
                [Node(id=self._normalize_host_uri(h), uri=self._normalize_host_uri(h))
                 for h in cc.hosts]
            )
            return cluster
        return Cluster(
            node_id=self.node_id,
            uri=self.uri,
            replica_n=cc.replicas,
            static=False,
            coordinator=cc.coordinator,
            coordinator_uri=(
                self._normalize_host_uri(cc.coordinator_host)
                if cc.coordinator_host
                else None
            ),
            topology_path=topology_path,
            logger=self.logger,
            probe_timeout=cc.probe_timeout,
            down_after=cc.down_after,
            ssl_context=ssl_ctx,
        )

    def address(self) -> tuple[str, int]:
        if self.httpd is None:
            return (self.config.host, self.config.port)
        return self.httpd.server_address[:2]

    @property
    def scheme(self) -> str:
        return "https" if self.config.tls.enabled else "http"

    @property
    def uri(self) -> str:
        host, port = self.address()
        return f"{self.scheme}://{host}:{port}"

    def client_ssl_context(self):
        """SSL context for node-to-node clients; honors skip-verify
        (reference http/client.go transport from TLS config)."""
        if not self.config.tls.enabled:
            return None
        import ssl

        ctx = ssl.create_default_context()
        if self.config.tls.skip_verify:
            ctx.check_hostname = False
            ctx.verify_mode = ssl.CERT_NONE
        return ctx

    def close(self) -> None:
        self._closed.set()
        # drain the ingest queue to durability first: every queued wave
        # group-commits and its submitters ack before we take down the
        # layers a wave needs (new submits answer 503)
        if self.ingest is not None:
            self.ingest.close()
        # graceful drain FIRST: stop admitting (new requests get 503),
        # complete queued + in-flight work within the drain budget, so
        # a restart loses nothing the server had accepted and could
        # still finish
        if self.pipeline is not None:
            clean = self.pipeline.close()
            if not clean:
                self.logger.printf(
                    "pipeline drain timed out after %.1fs; remaining work failed 503",
                    self.config.pipeline_drain_timeout,
                )
        # after the pipeline drained (no new gang work), poison the
        # follower loops so every rank exits cleanly
        if self.multihost is not None:
            self.multihost.close()
        if self.gc_notifier is not None:
            self.gc_notifier.close()
        # observer planes stop after the workers they observe
        profiler.SAMPLER.stop()
        profiler.TELEMETRY.stop()
        if self.exporter is not None:
            # detach the taps before the final flush so late producers
            # can't race a closed queue, then flush-on-close (compare
            # the bound method's receiver: ``x.m is x.m`` is False)
            if getattr(events.JOURNAL.on_record, "__self__", None) is self.exporter:
                events.JOURNAL.on_record = None
            if getattr(trace.TRACER.on_export, "__self__", None) is self.exporter:
                trace.TRACER.on_export = None
            self.exporter.close()
        events.JOURNAL.close_backing()
        self.stats.close()
        if self.httpd is not None:
            self.httpd.shutdown()
            self.httpd.server_close()
        if self.cluster is not None:
            self.cluster.close()
        self.executor.close()
        self.holder.close()
        self.translate_store.close()

    # -- broadcaster seam (reference broadcast.go:27-31) --

    def _broadcast_create_shard(self, index: str, shard: int) -> None:
        """New max shard appeared locally → tell the cluster (reference
        view.go:216-247 CreateShardMessage)."""
        self.send_async({"type": "create-shard", "index": index, "shard": shard})

    def _gang_message(self, msg: dict) -> bool:
        """Replicate a broadcast message to the multihost gang: schema
        ops and shard announcements must reach follower holders the
        same way cluster peers get them. No-op inside a gang replay
        (followers apply the op themselves) and after degrade. Returns
        True when the message WAS gang-dispatched — the replay applies
        it locally, so the caller must not apply it again."""
        mh = self.multihost
        if mh is not None and mh.should_dispatch():
            from pilosa_tpu.parallel.multihost import Descriptor, KIND_MESSAGE

            mh.dispatch(Descriptor(KIND_MESSAGE, msg))
            return True
        return False

    def send_sync(self, msg: dict) -> None:
        self._gang_message(msg)
        if self.cluster is not None:
            self.cluster.send_sync(msg)

    def send_async(self, msg: dict) -> None:
        self._gang_message(msg)
        if self.cluster is not None:
            self.cluster.send_async(msg)

    def send_to(self, node, msg: dict) -> None:
        if self.cluster is not None:
            self.cluster.send_to(node, msg)

    # -- federation (parallel/federation.py) --

    def gang_apply(self, kind: int, payload: dict, epoch: int) -> None:
        """Replicated-mode follower: apply one descriptor pushed by the
        gang leader. The epoch is the staleness fence — a LOWER epoch
        is a pre-re-form descriptor (a stale leader thread, a delayed
        frame) and must never land on post-re-form state (409, the
        sender rejoins). A HIGHER epoch is adopted: the leader only
        replicates to followers it just re-staged, and the bump may
        race the rejoin response that carries it."""
        from pilosa_tpu.server.api import APIError

        if epoch < self.gang_epoch:
            raise APIError(
                f"gang epoch mismatch: have {self.gang_epoch}, got {epoch} "
                "— stale descriptor refused, sender must re-form",
                status=409,
            )
        if epoch > self.gang_epoch:
            self.logger.printf(
                "gang epoch %d -> %d (leader re-formed)", self.gang_epoch, epoch
            )
            self.gang_epoch = epoch
        if self._gang_apply_fn is None:
            from pilosa_tpu.parallel.multihost import make_apply_fn

            self._gang_apply_fn = make_apply_fn(self)
        self._gang_apply_fn(kind, payload)

    def gang_rejoin(self, follower_uri: str) -> dict:
        """Gang leader: re-form around a re-staged follower (anti-
        entropy catch-up, schema + fragment push, epoch bump, ACTIVE)."""
        from pilosa_tpu.parallel import federation

        return federation.handle_rejoin(self, follower_uri)

    # -- message application (reference Server.ReceiveMessage:435-517) --

    def receive_message(self, msg: dict) -> None:
        # a message arriving from a cluster PEER replays through the
        # gang first, so this gang's followers see the same schema ops
        # its leader does; the replay re-enters here with the in-gang
        # flag set and falls through to the local apply below
        if self._gang_message(msg):
            return
        typ = msg.get("type")
        if typ == "create-index":
            self.holder.create_index_if_not_exists(
                msg["index"], msg.get("keys", False)
            )
        elif typ == "delete-index":
            try:
                self.holder.delete_index(msg["index"])
            except ValueError:
                pass
        elif typ == "create-field":
            from pilosa_tpu.core.field import FieldOptions

            idx = self.holder.index(msg["index"])
            if idx is not None:
                idx.create_field_if_not_exists(
                    msg["field"], FieldOptions.from_dict(msg.get("options", {}))
                )
        elif typ == "delete-field":
            idx = self.holder.index(msg["index"])
            if idx is not None:
                try:
                    idx.delete_field(msg["field"])
                except ValueError:
                    pass
        elif typ == "create-shard":
            idx = self.holder.index(msg["index"])
            if idx is not None:
                idx.set_remote_max_shard(msg["shard"])
        elif typ == "recalculate-caches":
            for idx in self.holder.indexes.values():
                for f in idx.fields.values():
                    for v in f.views.values():
                        for frag in v.fragments.values():
                            frag.recalculate_cache()
        elif typ == "schema":
            self.holder.apply_schema(msg.get("schema", []))
        elif typ == "translate-keys":
            # push replication of key→id assignments minted elsewhere:
            # adopt durably (by-key idempotent; never re-broadcast)
            try:
                self.translate_store.adopt(
                    msg["index"],
                    msg.get("field", ""),
                    msg.get("keys", []),
                    msg.get("ids", []),
                )
            except (ValueError, KeyError, IndexError) as e:
                self.logger.printf("translate-keys apply error: %s", e)
        elif typ == "leader-uri":
            # gang replay of the leader's boot-time handshake: followers
            # adopt the push target and register with the leader's fleet
            # collector; the leader (and peer leaders) ignore it
            if self.multihost is not None and self._mh_rank != 0:
                self.multihost.leader_uri = msg.get("uri", "")
                self._register_with_leader()
        elif self.cluster is not None:
            self.cluster.receive_message(msg)

    def _register_with_leader(self) -> None:
        """Best-effort, off-thread: the gang apply loop must not block
        on an HTTP round-trip back to the leader."""
        mh = self.multihost
        if mh is None or not mh.leader_uri:
            return
        target = mh.leader_uri

        def _go():
            try:
                from pilosa_tpu.parallel.client import InternalClient

                InternalClient(
                    timeout=5.0, ssl_context=self.client_ssl_context()
                ).fleet_register(
                    target,
                    self.uri,
                    rank=self._mh_rank,
                    gang=self.config.distributed_coordinator or "",
                )
            except Exception as e:
                self.logger.printf("fleet register with %s failed: %s", target, e)

        threading.Thread(target=_go, name="fleet-register", daemon=True).start()
