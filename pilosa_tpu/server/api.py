"""Programmatic API (L6) — validated surface over holder/executor/cluster
(reference api.go).

Each method is gated on cluster state like the reference's
validAPIMethods (api.go:70-93): while the cluster is RESIZING only a
restricted set is callable.
"""

from __future__ import annotations

import io
import json
import time
from typing import Optional

import numpy as np

from pilosa_tpu import SHARD_WIDTH, __version__
from pilosa_tpu.core import FieldOptions, Row
from pilosa_tpu.core.view import VIEW_STANDARD
from pilosa_tpu.executor import ExecOptions
from pilosa_tpu.pql import parse
from pilosa_tpu.server import deadline, pipeline
from pilosa_tpu.utils import events, heat, metrics, profiler, trace

# cluster states (reference cluster.go:42-45)
STATE_STARTING = "STARTING"
STATE_NORMAL = "NORMAL"
STATE_RESIZING = "RESIZING"

# Methods permitted while RESIZING/STARTING (reference api.go:70-93;
# fragment streaming must stay available mid-resize — it IS the resize)
_RESIZING_METHODS = {
    "cluster_message",
    "state",
    "status",
    "resize_abort",
    "fragment_data",
    "fragment_blocks",
    "fragment_block_data",
    "schema",
}


class APIError(Exception):
    def __init__(self, message: str, status: int = 400) -> None:
        super().__init__(message)
        self.status = status


from pilosa_tpu.utils.errors import NotFoundError as _SharedNotFound  # noqa: E402


class NotFoundError(APIError, _SharedNotFound):
    """API-level 404. Subclasses BOTH APIError (carries the status for
    the HTTP layer) and the shared utils.errors.NotFoundError, so
    ``except`` on either type catches it — no same-named-type trap."""

    def __init__(self, message: str) -> None:
        APIError.__init__(self, message, status=404)


class API:
    def __init__(self, holder, executor, cluster=None, server=None) -> None:
        self.holder = holder
        self.executor = executor
        self.cluster = cluster
        self.server = server

    # -- state gate --

    def _state(self) -> str:
        if self.cluster is None:
            return STATE_NORMAL
        return self.cluster.state

    def _validate(self, method: str) -> None:
        state = self._state()
        if state == STATE_NORMAL:
            return
        if state == STATE_RESIZING and method in _RESIZING_METHODS:
            return
        if state == STATE_STARTING and method in _RESIZING_METHODS | {"schema"}:
            return
        raise APIError(
            f"api method {method} unavailable in cluster state {state}", status=503
        )

    # -- query (reference api.Query:96-150) --

    def query(
        self,
        index: str,
        query: str,
        shards: Optional[list[int]] = None,
        remote: bool = False,
        exclude_row_attrs: bool = False,
        exclude_columns: bool = False,
        column_attrs: bool = False,
        profile: bool = False,
        cache: bool = True,
        trace_ctx: Optional[tuple] = None,
        waterfall: bool = False,
        req_id: int = 0,
    ) -> dict:
        self._validate("query")
        # deadline boundary: cancel BEFORE the parse — an expired
        # request must cost the server nothing past this line
        dl = deadline.current()
        if dl is not None:
            dl.check(metrics.STAGE_QUERY)
        opt = ExecOptions(
            remote=remote,
            exclude_row_attrs=exclude_row_attrs,
            exclude_columns=exclude_columns,
            # cache=false bypasses the plan result cache; profile=true
            # does too — a profiled query must show real execution, not
            # a cache hit's absence of spans. profile=waterfall likewise:
            # a cache hit has no device leg to attribute
            cache=cache and not profile and not waterfall,
        )
        # root span: forced by profile=true or a sampled upstream
        # traceparent (the ingress point ADOPTS the caller's trace id),
        # else admitted by the tracer's sample rate / slow-query
        # threshold (NOP when off — the untraced query allocates no
        # span anywhere below)
        root = trace.TRACER.trace(
            metrics.STAGE_QUERY, force=profile, ctx=trace_ctx, index=index
        )
        # always-on attribution (ISSUE 12): every served query carries a
        # waterfall accumulator — a plain dict in a contextvar, one get
        # + float add per instrumented leg, no spans, no sampling gate.
        # Created HERE (not the HTTP thread) because pipeline thunks run
        # on worker threads where the handler's contextvars don't reach.
        # _req names the request in every leg's profiler annotation; the
        # transport hands its own id down so its legs carry the same one
        wf: dict = {"_req": req_id or trace.next_request_id()}
        t_q0 = time.monotonic()
        # an UNSAMPLED upstream context still propagates its ids to
        # dispatch items and outbound RPC headers, span-free
        with root, trace.push_ctx(
            trace_ctx if root is trace.NOP_SPAN else None
        ), trace.attrib_activate(wf):
            # when this query came through the serving pipeline, its
            # admission-queue wait predates the root span — backfill it
            # so profile=true shows where serving latency went
            wait = pipeline.current_queue_wait()
            if wait > 0:
                wf[trace.WF_PIPELINE_QUEUE] = wait
                if root is not trace.NOP_SPAN:
                    root.record(
                        metrics.STAGE_PIPELINE_WAIT, root.t0 - wait, wait
                    )
            try:
                with trace.leg(trace.WF_PLAN_CANON):
                    q = parse(query)
            except Exception as e:
                raise APIError(f"parsing: {e}") from e
            idx = self.holder.index(index)
            if idx is None:
                raise NotFoundError(f"index not found: {index}")
            results = self.executor.execute(index, q, shards, opt)
        resp: dict = {"results": results}
        # total covers parse → results plus the pre-span pipeline wait;
        # the handler pops _waterfall into the aggregator + SLO monitor
        total_s = (time.monotonic() - t_q0) + wf.get(trace.WF_PIPELINE_QUEUE, 0.0)
        resp["_waterfall"] = profiler.WATERFALL.summarize(wf, total_s)
        if waterfall:
            resp["profile"] = {"waterfall": resp["_waterfall"]}
        if profile:
            resp["profile"] = trace.TRACER.stitched(root.to_dict())
        if remote and root is not trace.NOP_SPAN:
            # federation remote leg: return this process's serialized
            # span tree in the response envelope so the root process
            # grafts it into ONE stitched trace (Dapper-style)
            # stitched: a rank-0 replay span grafts into this leader's
            # buffer synchronously, so it rides back in the envelope too
            resp["spans"] = [trace.TRACER.stitched(root.to_dict())]
        if column_attrs and idx.column_attrs is not None:
            cols = set()
            for r in results:
                if isinstance(r, Row):
                    cols.update(int(c) for c in r.columns())
            attr_sets = []
            for col in sorted(cols):
                attrs = idx.column_attrs.attrs(col)
                if attrs:
                    attr_sets.append({"id": col, "attrs": attrs})
            resp["columnAttrs"] = attr_sets
        return resp

    # -- schema CRUD --

    def create_index(self, name: str, keys: bool = False) -> None:
        self._validate("create_index")
        try:
            self.holder.create_index(name, keys=keys)
        except ValueError as e:
            raise APIError(str(e), status=409 if "exists" in str(e) else 400)
        if self.server is not None:
            self.server.send_sync({"type": "create-index", "index": name, "keys": keys})

    def delete_index(self, name: str) -> None:
        self._validate("delete_index")
        try:
            self.holder.delete_index(name)
        except ValueError as e:
            raise NotFoundError(str(e))
        if self.server is not None:
            self.server.send_sync({"type": "delete-index", "index": name})

    def create_field(self, index: str, field: str, options: dict) -> None:
        self._validate("create_field")
        idx = self.holder.index(index)
        if idx is None:
            raise NotFoundError(f"index not found: {index}")
        try:
            idx.create_field(field, FieldOptions.from_dict(options or {}))
        except ValueError as e:
            raise APIError(str(e), status=409 if "exists" in str(e) else 400)
        if self.server is not None:
            self.server.send_sync(
                {"type": "create-field", "index": index, "field": field,
                 "options": options or {}}
            )

    def delete_field(self, index: str, field: str) -> None:
        self._validate("delete_field")
        idx = self.holder.index(index)
        if idx is None:
            raise NotFoundError(f"index not found: {index}")
        try:
            idx.delete_field(field)
        except ValueError as e:
            raise NotFoundError(str(e))
        if self.server is not None:
            self.server.send_sync(
                {"type": "delete-field", "index": index, "field": field}
            )

    def delete_view(self, index: str, field: str, view: str) -> None:
        self._validate("delete_view")
        f = self.holder.field(index, field)
        if f is None:
            raise NotFoundError(f"field not found: {field}")
        v = f.views.pop(view, None)
        if v is not None:
            v.close()
            if v.path:
                import shutil

                shutil.rmtree(v.path, ignore_errors=True)

    def schema(self) -> list[dict]:
        self._validate("schema")
        return self.holder.schema()

    def fragment_inventory(self) -> list[dict]:
        """Every (index, field, view, shard) this node holds — the
        resize coordinator unions these across old owners so fragment
        moves enumerate what EXISTS, not the whole shard space (the
        reference's availableShards bitmaps serve the same purpose,
        cluster.go:689-773)."""
        out = []
        for iname, idx in self.holder.indexes.items():
            for fname, fld in idx.fields.items():
                for vname, view in fld.views.items():
                    for shard in sorted(view.fragments):
                        out.append(
                            {
                                "index": iname,
                                "field": fname,
                                "view": vname,
                                "shard": shard,
                            }
                        )
        return out

    def views(self, index: str, field: str) -> list[str]:
        self._validate("views")
        f = self.holder.field(index, field)
        if f is None:
            raise NotFoundError(f"field not found: {field}")
        return sorted(f.views)

    # -- imports (reference api.Import:652-696) --

    def _gang_import(self, op: str, payload: dict, local: bool = False) -> bool:
        """Multihost leader: broadcast an import descriptor so every
        rank's holder replays the identical mutation; True when the
        gang handled it (the leader thread and every follower re-enter
        this method with the gang flag set and fall through to the
        local body). In a FEDERATED deployment the cluster plane routes
        shard groups first, so only the ``import_*_local`` legs
        (local=True) replay through the gang. timestamps may be
        datetimes on internal callers — gang payloads are JSON, so
        those callers (cluster legs) never run in multihost mode."""
        mh = getattr(self.server, "multihost", None) if self.server else None
        if mh is None or not mh.should_dispatch_import(local):
            return False
        from pilosa_tpu.parallel.multihost import (
            Descriptor,
            KIND_IMPORT,
            KIND_IMPORT_VALUES,
        )

        kind = KIND_IMPORT if op == "import" else KIND_IMPORT_VALUES
        mh.dispatch(Descriptor(kind, payload), deadline=deadline.current())
        return True

    def import_bits(
        self,
        index: str,
        field: str,
        row_ids: list[int],
        column_ids: list[int],
        timestamps: Optional[list] = None,
        row_keys: Optional[list[str]] = None,
        column_keys: Optional[list[str]] = None,
    ) -> None:
        self._validate("import")
        if self._gang_import(
            "import",
            {
                "index": index,
                "field": field,
                "row_ids": list(row_ids),
                "column_ids": list(column_ids),
                "timestamps": list(timestamps) if timestamps else None,
                "row_keys": list(row_keys) if row_keys else None,
                "column_keys": list(column_keys) if column_keys else None,
            },
        ):
            return
        idx = self.holder.index(index)
        if idx is None:
            raise NotFoundError(f"index not found: {index}")
        f = idx.field(field)
        if f is None:
            raise NotFoundError(f"field not found: {field}")
        ts = self.executor.translate_store
        if column_keys:
            if ts is None:
                raise APIError("translate store not configured")
            column_ids = ts.translate_columns_to_ids(index, column_keys)
        if row_keys:
            if ts is None:
                raise APIError("translate store not configured")
            row_ids = ts.translate_rows_to_ids(index, field, row_keys)
        # Route bit groups to their shard owners (the reference's client
        # groups by owner before POSTing, http/client.go:276,922; routing
        # server-side keeps single-endpoint imports correct in a cluster).
        if self.cluster is not None and len(self.cluster.nodes) > 1:
            self._route_import(
                index, field, row_ids, column_ids, timestamps, local_only=False
            )
            return
        parsed_ts = _parse_timestamps(timestamps)
        f.import_bits(row_ids, column_ids, parsed_ts)

    def import_bits_local(self, index, field, row_ids, column_ids, timestamps=None):
        """Internal: import bits into this node only (owner-side leg).
        On a federated gang leader this leg replays through the gang so
        follower holders receive the identical shard group."""
        if self._gang_import(
            "import",
            {
                "index": index,
                "field": field,
                "row_ids": list(row_ids),
                "column_ids": list(column_ids),
                "timestamps": list(timestamps) if timestamps else None,
                "local": True,
            },
            local=True,
        ):
            return
        f = self.holder.field(index, field)
        if f is None:
            raise NotFoundError(f"field not found: {field}")
        f.import_bits(row_ids, column_ids, _parse_timestamps(timestamps))

    def _route_import(self, index, field, row_ids, column_ids, timestamps, local_only):
        from pilosa_tpu import SHARD_WIDTH as SW

        groups: dict[int, list[int]] = {}
        for i, col in enumerate(column_ids):
            groups.setdefault(col // SW, []).append(i)
        ts = timestamps or [0] * len(column_ids)
        for shard, idxs in sorted(groups.items()):
            rows = [row_ids[i] for i in idxs]
            cols = [column_ids[i] for i in idxs]
            tss = [ts[i] for i in idxs] if timestamps else None
            for node in self.cluster.shard_nodes(index, shard):
                if node.id == self.cluster.node_id:
                    self.import_bits_local(index, field, rows, cols, tss)
                else:
                    self.cluster.client.import_bits_local(
                        node.uri, index, field, rows, cols, tss
                    )

    # -- ingest write waves (server/ingest.py group commit) --

    def apply_write_wave(
        self, index: str, field: str, row_ids, column_ids, sets=None
    ) -> int:
        """Apply one coalesced ingest write wave: sets AND clears in a
        single batch, one op-log group commit + fsync and one
        generation bump per touched fragment, one KIND_WRITE_WAVE gang
        frame. Returns the number of bits that changed (or the wave
        size when the gang replays it — follower counts aren't
        collected). In a multi-node cluster, shard groups route to
        their owners first; a remote owner acks only after its own
        ingest queue group-commits, so durability is owner-side."""
        self._validate("import")
        if self.cluster is not None and len(self.cluster.nodes) > 1:
            groups: dict[int, list[int]] = {}
            for i, col in enumerate(column_ids):
                groups.setdefault(int(col) // SHARD_WIDTH, []).append(i)
            flags = sets if sets is not None else [True] * len(column_ids)
            total = 0
            for shard, idxs in sorted(groups.items()):
                rows = [int(row_ids[i]) for i in idxs]
                cols = [int(column_ids[i]) for i in idxs]
                ss = [bool(flags[i]) for i in idxs]
                # every replica applies the group, but it counts ONCE
                # toward the wave total (replication factor > 1 must
                # not inflate the acked/changed count); prefer the
                # local replica's exact changed count when we hold one
                local_changed = None
                remote_changed = None
                for node in self.cluster.shard_nodes(index, shard):
                    if node.id == self.cluster.node_id:
                        local_changed = self.apply_write_wave_local(
                            index, field, rows, cols, ss
                        )
                    else:
                        c = self.cluster.client.ingest(
                            node.uri, index, field, rows, cols, ss
                        )
                        remote_changed = max(remote_changed or 0, c)
                if local_changed is not None:
                    total += local_changed
                elif remote_changed is not None:
                    total += remote_changed
            return total
        return self.apply_write_wave_local(index, field, row_ids, column_ids, sets)

    def apply_write_wave_local(
        self, index: str, field: str, row_ids, column_ids, sets=None
    ) -> int:
        """Owner-side wave leg: on a gang leader the wave crosses the
        collective plane as ONE replayed frame (vs one broadcast per
        bit on the interactive path); every rank then applies the
        identical batch below."""
        mh = getattr(self.server, "multihost", None) if self.server else None
        # dispatch flag mirrors _gang_import: a federated gang replays
        # only local legs (pass local=True), a single-plane gang owns
        # the top-level wave (local=False)
        if mh is not None and mh.should_dispatch_import(mh.federated):
            from pilosa_tpu.parallel.multihost import Descriptor, KIND_WRITE_WAVE

            mh.dispatch(
                Descriptor(
                    KIND_WRITE_WAVE,
                    {
                        "index": index,
                        "field": field,
                        "row_ids": [int(r) for r in row_ids],
                        "column_ids": [int(c) for c in column_ids],
                        "sets": [bool(s) for s in sets] if sets is not None else None,
                    },
                ),
                deadline=deadline.current(),
            )
            return len(row_ids)
        f = self.holder.field(index, field)
        if f is None:
            raise NotFoundError(f"field not found: {field}")
        flags = sets if sets is not None else [True] * len(row_ids)
        groups: dict[int, list[int]] = {}
        for i, col in enumerate(column_ids):
            groups.setdefault(int(col) // SHARD_WIDTH, []).append(i)
        v = f.create_view_if_not_exists(VIEW_STANDARD)
        changed = 0
        for shard, idxs in sorted(groups.items()):
            frag = v.create_fragment_if_not_exists(shard)
            changed += frag.apply_bit_batch(
                [int(row_ids[i]) for i in idxs],
                [int(column_ids[i]) for i in idxs],
                [bool(flags[i]) for i in idxs],
            )
            # heat write hook lives in the local-apply leg, so gang
            # replay (every rank re-enters here with dispatch false)
            # records the wave exactly once per rank
            heat.record_write(index, field, shard, len(idxs))
        return changed

    def import_values(
        self,
        index: str,
        field: str,
        column_ids: list[int],
        values: list[int],
        column_keys: Optional[list[str]] = None,
    ) -> None:
        self._validate("import_value")
        if self._gang_import(
            "import_values",
            {
                "index": index,
                "field": field,
                "column_ids": list(column_ids),
                "values": list(values),
                "column_keys": list(column_keys) if column_keys else None,
            },
        ):
            return
        f = self.holder.field(index, field)
        if f is None:
            raise NotFoundError(f"field not found: {field}")
        ts = self.executor.translate_store
        if column_keys:
            if ts is None:
                raise APIError("translate store not configured")
            column_ids = ts.translate_columns_to_ids(index, column_keys)
        if self.cluster is not None and len(self.cluster.nodes) > 1:
            from pilosa_tpu import SHARD_WIDTH as SW

            groups: dict[int, list[int]] = {}
            for i, col in enumerate(column_ids):
                groups.setdefault(col // SW, []).append(i)
            for shard, idxs in sorted(groups.items()):
                cols = [column_ids[i] for i in idxs]
                vals = [values[i] for i in idxs]
                for node in self.cluster.shard_nodes(index, shard):
                    if node.id == self.cluster.node_id:
                        # through the local entry point, not f.import_values:
                        # on a federated gang leader the owner-side leg must
                        # replay through the gang so follower holders stay
                        # bit-identical (same as _route_import for bits)
                        self.import_values_local(index, field, cols, vals)
                    else:
                        self.cluster.client.import_values_local(
                            node.uri, index, field, cols, vals
                        )
            return
        f.import_values(column_ids, values)

    def import_values_local(self, index, field, column_ids, values):
        if self._gang_import(
            "import_values",
            {
                "index": index,
                "field": field,
                "column_ids": list(column_ids),
                "values": list(values),
                "local": True,
            },
            local=True,
        ):
            return
        f = self.holder.field(index, field)
        if f is None:
            raise NotFoundError(f"field not found: {field}")
        f.import_values(column_ids, values)

    # -- export (reference api.ExportCSV:328) --

    def export_csv(self, index: str, field: str, shard: int) -> bytes:
        """CSV bytes for one shard, "row,col\\n" lines (the reference's
        Go csv writer likewise emits bare \\n, http/handler.go
        handleGetExport) — both paths byte-identical so cross-node
        export diffs can't depend on whether the native library built."""
        self._validate("export_csv")
        f = self.holder.field(index, field)
        if f is None:
            raise NotFoundError(f"field not found: {field}")
        frag = self.holder.fragment(index, field, VIEW_STANDARD, shard)
        if frag is None:
            return b""
        positions = np.asarray(frag.storage.slice_all(), dtype=np.uint64)
        if positions.size == 0:
            return b""
        rows = positions // np.uint64(SHARD_WIDTH)
        cols = np.uint64(frag.shard * SHARD_WIDTH) + (
            positions % np.uint64(SHARD_WIDTH)
        )
        # native formatter (inverse of the import parser); Python
        # fallback when the library isn't built
        from pilosa_tpu import native_bridge

        out = native_bridge.format_csv_pairs(rows, cols)
        if out is not None:
            return out
        return (
            "".join(f"{r},{c}\n" for r, c in zip(rows.tolist(), cols.tolist()))
        ).encode()

    # -- fragment sync endpoints (reference api.go:376-472) --

    def fragment_blocks(
        self, index: str, field: str, shard: int, view: str = VIEW_STANDARD
    ) -> list[dict]:
        self._validate("fragment_blocks")
        frag = self.holder.fragment(index, field, view, shard)
        if frag is None:
            raise NotFoundError("fragment not found")
        return [
            {"id": bid, "checksum": digest.hex()} for bid, digest in frag.blocks()
        ]

    def apply_block_fixes(
        self,
        index: str,
        field: str,
        view: str,
        shard: int,
        rows,
        columns,
        clear_rows,
        clear_columns,
    ) -> None:
        """Anti-entropy push target: apply a peer's consensus block merge
        to ANY view (time quantums, bsig_*) — the view-aware replacement
        for the reference's standard-only Set/Clear PQL push
        (reference fragment.go:1874 'Only sync the standard block')."""
        import numpy as np

        self._validate("import")
        fld = self.holder.field(index, field)
        if fld is None:
            raise NotFoundError(f"field not found: {field}")
        v = fld.create_view_if_not_exists(view)
        frag = v.create_fragment_if_not_exists(shard)
        frag.import_block_pairs(
            np.asarray(rows, dtype=np.uint64),
            np.asarray(columns, dtype=np.uint64),
            np.asarray(clear_rows, dtype=np.uint64),
            np.asarray(clear_columns, dtype=np.uint64),
        )

    def fragment_block_data(
        self, index: str, field: str, view: str, shard: int, block: int
    ) -> dict:
        self._validate("fragment_block_data")
        frag = self.holder.fragment(index, field, view, shard)
        if frag is None:
            raise NotFoundError("fragment not found")
        rows, cols = frag.block_data(block)
        return {"rows": rows.tolist(), "columns": cols.tolist()}

    def marshal_fragment(self, index: str, field: str, view: str, shard: int) -> bytes:
        """Fragment backup archive: a tar with "data" (roaring bytes),
        "cache" (protobuf id list), and "digest" (blake2b-128 hex of
        the data entry) entries, the reference's WriteTo format
        (fragment.go:1511-1568) extended with the checksum the restore
        side verifies before applying. A quarantined fragment refuses
        (503): its bits are poisoned and must not propagate to peers."""
        import hashlib
        import io
        import tarfile

        self._validate("fragment_data")
        frag = self.holder.fragment(index, field, view, shard)
        if frag is None:
            raise NotFoundError("fragment not found")
        frag.check_serving()
        from pilosa_tpu.core.cache import encode_cache

        with frag.mu:  # consistent (data, cache) snapshot under writers
            data = frag.storage.to_bytes()
            cbuf = encode_cache(frag.cache.ids())
        digest = hashlib.blake2b(data, digest_size=16).hexdigest().encode()
        out = io.BytesIO()
        with tarfile.open(fileobj=out, mode="w") as tw:
            for name, blob in (("data", data), ("cache", cbuf), ("digest", digest)):
                info = tarfile.TarInfo(name)
                info.size = len(blob)
                info.mode = 0o600
                tw.addfile(info, io.BytesIO(blob))
        return out.getvalue()

    def unmarshal_fragment(
        self, index: str, field: str, view: str, shard: int, data: bytes
    ) -> None:
        """Restore a fragment from a tar archive (reference ReadFrom,
        fragment.go:1570-1681) or from raw roaring bytes (this
        framework's pre-tar wire format). The archive's checksum (the
        "digest" entry, when present) is verified and the bytes fully
        PARSED before the live fragment is touched — a corrupt backup
        can never clobber a healthy fragment mid-apply."""
        import hashlib
        import io
        import tarfile

        self._validate("fragment_data")
        f = self.holder.field(index, field)
        if f is None:
            raise NotFoundError(f"field not found: {field}")
        v = f.create_view_if_not_exists(view)
        frag = v.create_fragment_if_not_exists(shard)
        from pilosa_tpu.core.cache import decode_cache
        from pilosa_tpu.roaring import Bitmap

        cache_ids = None
        want_digest = None
        try:
            with tarfile.open(fileobj=io.BytesIO(data)) as tr:
                members = {m.name: m for m in tr.getmembers()}
                entry = members.get("data")
                blob = tr.extractfile(entry) if entry is not None else None
                if blob is None:
                    raise APIError("fragment archive has no 'data' entry")
                data = blob.read()
                centry = members.get("cache")
                cfile = tr.extractfile(centry) if centry is not None else None
                if cfile is not None:
                    cache_ids = decode_cache(cfile.read())
                dentry = members.get("digest")
                dfile = tr.extractfile(dentry) if dentry is not None else None
                if dfile is not None:
                    want_digest = dfile.read().decode("ascii", "replace").strip()
        except tarfile.ReadError:
            pass  # raw roaring bytes

        if want_digest is not None:
            got = hashlib.blake2b(data, digest_size=16).hexdigest()
            if got != want_digest:
                metrics.count(metrics.RESTORE_REFUSED)
                events.record(
                    events.RESTORE_REFUSED,
                    index=index,
                    field=field,
                    view=view,
                    shard=shard,
                    reason="fragment archive digest mismatch",
                )
                raise APIError(
                    "fragment archive checksum mismatch; restore refused",
                    status=400,
                )
        try:
            storage = Bitmap.unmarshal_binary(data)
        except Exception as e:
            metrics.count(metrics.RESTORE_REFUSED)
            events.record(
                events.RESTORE_REFUSED,
                index=index,
                field=field,
                view=view,
                shard=shard,
                reason="fragment archive unparseable",
            )
            raise APIError(
                f"fragment archive unparseable; restore refused: {e}",
                status=400,
            )
        self._replace_fragment_storage(frag, storage, cache_ids)

    def _replace_fragment_storage(self, frag, storage, cache_ids=None) -> None:
        """Swap a fragment's bitmap for an already-verified one and
        rebuild every derived structure. Clears any quarantine: the
        incoming storage passed verification, so this IS the repair."""
        with frag.mu:
            op_writer = frag.storage.op_writer
            frag.storage = storage
            frag.storage.op_writer = op_writer
            frag.generation += 1
            frag.quarantined = False
            frag.quarantine_reason = ""
            frag._delta_reset()  # wholesale replace: no replayable deltas
            frag._row_cache.clear()
            frag.checksums.clear()
            frag._occ = None
            frag._recompute_max_row_id()
            frag.cache.clear()
            if cache_ids is None:
                # raw-bytes restore carries no cache entry: rebuild from
                # the restored rows so TopN answers immediately
                cache_ids = frag.row_ids()
            for row_id in cache_ids:
                # already under frag.mu — use the unlocked row read
                frag.cache.bulk_add(
                    row_id, frag._unprotected_row(row_id).count()
                )
            frag.cache.invalidate()
            frag.snapshot()

    # -- holder backup / restore (ISSUE 15) --

    BACKUP_MANIFEST_VERSION = 1

    def backup(self) -> bytes:
        """Full-holder backup: a tar of the schema plus every fragment's
        roaring bytes, led by a MANIFEST.json naming every member with
        its blake2b-128 digest and size. The manifest is written FIRST
        so a restore can verify the whole archive before applying a
        byte. A quarantined fragment refuses the backup (503) — backing
        up known-poisoned bits would launder the corruption into the
        recovery path."""
        import hashlib
        import io
        import tarfile

        self._validate("fragment_data")
        entries: list[tuple[str, bytes]] = []
        schema_blob = json.dumps(self.holder.schema()).encode()
        entries.append(("schema.json", schema_blob))
        for iname, idx in self.holder.indexes.items():
            for fname, fld in idx.fields.items():
                for vname, view in fld.views.items():
                    for shard, frag in sorted(view.fragments.items()):
                        frag.check_serving()
                        with frag.mu:
                            data = frag.storage.to_bytes()
                        entries.append(
                            (f"fragments/{iname}/{fname}/{vname}/{shard}", data)
                        )
        # key translation logs ride along: a restored holder must
        # resolve exactly the archive's keys (translate/<store>.log
        # members; older restore targets verify-then-ignore unknown
        # prefixes, so the manifest version stays 1)
        ts = self.executor.translate_store
        if ts is not None and hasattr(ts, "store_files"):
            for name, blob in ts.store_files():
                entries.append((f"translate/{name}.log", blob))
        manifest = {
            "version": self.BACKUP_MANIFEST_VERSION,
            "entries": {
                name: {
                    "blake2b": hashlib.blake2b(blob, digest_size=16).hexdigest(),
                    "size": len(blob),
                }
                for name, blob in entries
            },
        }
        out = io.BytesIO()
        with tarfile.open(fileobj=out, mode="w") as tw:
            for name, blob in [
                ("MANIFEST.json", json.dumps(manifest, indent=1).encode())
            ] + entries:
                info = tarfile.TarInfo(name)
                info.size = len(blob)
                info.mode = 0o600
                tw.addfile(info, io.BytesIO(blob))
        metrics.count(metrics.BACKUP_ARCHIVES)
        return out.getvalue()

    def restore(self, archive: bytes) -> dict:
        """Restore a holder backup. EVERYTHING is verified before
        ANYTHING is applied: the manifest must name exactly the members
        present, every blob must match its recorded digest and size,
        the schema must parse, and every fragment blob must parse as a
        roaring bitmap. Any failure refuses the whole restore (400)
        with the holder untouched."""
        import hashlib
        import io
        import tarfile

        self._validate("fragment_data")
        from pilosa_tpu.roaring import Bitmap
        from pilosa_tpu.translate.store import SpaceStore

        def refuse(reason: str) -> APIError:
            metrics.count(metrics.RESTORE_REFUSED)
            events.record(events.RESTORE_REFUSED, reason=reason)
            return APIError(f"{reason}; restore refused", status=400)

        try:
            with tarfile.open(fileobj=io.BytesIO(archive)) as tr:
                blobs = {}
                for m in tr.getmembers():
                    f = tr.extractfile(m)
                    if f is not None:
                        blobs[m.name] = f.read()
        except tarfile.ReadError:
            raise refuse("backup archive is not a tar")
        mblob = blobs.pop("MANIFEST.json", None)
        if mblob is None:
            raise refuse("backup archive has no MANIFEST.json")
        try:
            manifest = json.loads(mblob)
            version = manifest["version"]
            want = manifest["entries"]
        except Exception:
            raise refuse("backup manifest unparseable")
        if version != self.BACKUP_MANIFEST_VERSION:
            raise refuse(f"backup manifest version {version} unsupported")
        if set(want) != set(blobs):
            missing = sorted(set(want) - set(blobs))[:3]
            extra = sorted(set(blobs) - set(want))[:3]
            raise refuse(
                f"backup members diverge from manifest"
                f" (missing={missing} extra={extra})"
            )
        for name, meta in want.items():
            blob = blobs[name]
            if len(blob) != meta.get("size"):
                raise refuse(f"backup entry {name} size mismatch")
            got = hashlib.blake2b(blob, digest_size=16).hexdigest()
            if got != meta.get("blake2b"):
                raise refuse(f"backup entry {name} checksum mismatch")
        try:
            schema = json.loads(blobs["schema.json"])
        except Exception:
            raise refuse("backup schema.json unparseable")
        fragments = []
        for name, blob in blobs.items():
            if not name.startswith("fragments/"):
                continue
            parts = name.split("/")
            if len(parts) != 5 or not parts[4].isdigit():
                raise refuse(f"backup entry {name} has a malformed path")
            try:
                storage = Bitmap.unmarshal_binary(blob)
            except Exception:
                raise refuse(f"backup entry {name} unparseable")
            fragments.append((parts[1], parts[2], parts[3], int(parts[4]), storage))
        translate_blobs = {}
        ts = self.executor.translate_store
        for name, blob in blobs.items():
            if not name.startswith("translate/") or not name.endswith(".log"):
                continue
            store = name[len("translate/") : -len(".log")]
            if (
                "/" not in store
                or ".." in store
                or store.startswith(("/", "\\"))
            ):
                raise refuse(f"backup entry {name} has a malformed path")
            # a tampered translate log would silently rebind every key
            # written through it — every frame must verify (intact CRC
            # prefix covering the whole member), same
            # verify-everything-before-apply bar as fragments
            probe = SpaceStore(None, "probe")
            if probe._replay(blob) != len(blob):
                raise refuse(f"backup entry {name} unparseable")
            translate_blobs[store] = blob
        if translate_blobs and (ts is None or not hasattr(ts, "restore_stores")):
            raise refuse("backup has translate entries but no translate store")
        # -- verification complete: apply --
        self.holder.apply_schema(schema)
        for iname, fname, vname, shard, storage in fragments:
            fld = self.holder.field(iname, fname)
            view = fld.create_view_if_not_exists(vname)
            frag = view.create_fragment_if_not_exists(shard)
            self._replace_fragment_storage(frag, storage)
        if translate_blobs:
            # replace-all semantics WITHIN the translate plane: the
            # restored holder resolves exactly the archive's keys
            # (archives without translate members leave local stores
            # untouched, like fragments the archive doesn't name)
            ts.restore_stores(translate_blobs)
        metrics.count(metrics.RESTORE_APPLIED)
        if self.server is not None:
            self.server.send_sync({"type": "schema", "schema": schema})
        return {"fragments": len(fragments), "version": version}

    # -- caches --

    def recalculate_caches(self) -> None:
        self._validate("recalculate_caches")
        for idx in self.holder.indexes.values():
            for f in idx.fields.values():
                for v in f.views.values():
                    for frag in v.fragments.values():
                        frag.recalculate_cache()
        # rank reorders can change TopN candidate walks without any
        # fragment generation bump — cached TopN results are stale
        pc = getattr(self.executor, "plan_cache", None)
        if pc is not None:
            pc.epoch_reset()
        if self.server is not None:
            self.server.send_sync({"type": "recalculate-caches"})

    # -- info / status --

    def version(self) -> str:
        return __version__

    def info(self) -> dict:
        import os

        return {
            "shardWidth": SHARD_WIDTH,
            "cpuPhysicalCores": os.cpu_count(),
            "cpuLogicalCores": os.cpu_count(),
        }

    def state(self) -> str:
        return self._state()

    def status(self) -> dict:
        nodes = []
        if self.cluster is not None:
            nodes = [n.to_dict() for n in self.cluster.nodes]
        out = {
            "state": self._state(),
            "nodes": nodes,
            "localID": getattr(self.cluster, "node_id", "") if self.cluster else "",
        }
        # gang health (ISSUE 7 bugfix): a degraded gang was previously
        # indistinguishable from a healthy one on the public route
        mh = getattr(self.server, "multihost", None) if self.server else None
        if mh is not None:
            out["gang"] = mh.health()
        job = (
            self.cluster.resize_job_status()
            if self.cluster is not None and hasattr(self.cluster, "resize_job_status")
            else None
        )
        if job is not None:
            out["resizeJob"] = job
        integ = self._integrity_status()
        if integ:
            out["integrity"] = integ
        return out

    def _integrity_status(self) -> dict:
        """Quarantined fragments + scrub-unrecoverable records for
        /status — empty dict when the holder is healthy so the common
        path stays unchanged."""
        quarantined = []
        for iname, idx in self.holder.indexes.items():
            for fname, fld in idx.fields.items():
                for vname, view in fld.views.items():
                    for shard, frag in view.fragments.items():
                        if frag.quarantined:
                            quarantined.append(
                                {
                                    "index": iname,
                                    "field": fname,
                                    "view": vname,
                                    "shard": shard,
                                    "reason": frag.quarantine_reason,
                                }
                            )
        out: dict = {}
        if quarantined:
            out["quarantined"] = quarantined
        scrubber = getattr(self.server, "scrubber", None) if self.server else None
        if scrubber is not None:
            unrec = scrubber.unrecoverable_list()
            if unrec:
                out["unrecoverable"] = unrec
        return out

    def hosts(self) -> list[dict]:
        if self.cluster is None:
            return []
        return [n.to_dict() for n in self.cluster.nodes]

    def shard_nodes(self, index: str, shard: int) -> list[dict]:
        self._validate("shard_nodes")
        if self.cluster is None:
            return []
        return [n.to_dict() for n in self.cluster.shard_nodes(index, shard)]

    def max_shards(self) -> dict[str, int]:
        return {
            name: idx.max_shard() for name, idx in self.holder.indexes.items()
        }

    # -- cluster ops (wired by the cluster layer) --

    def cluster_message(self, msg: dict) -> None:
        if self.server is None:
            raise APIError("cluster not configured")
        self.server.receive_message(msg)

    def gang_apply(self, kind: int, payload: dict, epoch: int) -> None:
        """Replicated-mode gang follower: apply one epoch-stamped
        descriptor pushed by the gang leader (parallel/federation.py)."""
        if self.server is None:
            raise APIError("gang not configured")
        self.server.gang_apply(kind, payload, epoch)

    def gang_rejoin(self, follower_uri: str) -> dict:
        """Gang leader: re-form the gang around a re-staged follower;
        returns the post-re-form health block (new epoch included)."""
        if self.server is None:
            raise APIError("gang not configured")
        return self.server.gang_rejoin(follower_uri)

    def set_coordinator(self, node_id: str) -> None:
        self._validate("set_coordinator")
        if self.cluster is None:
            raise APIError("cluster not configured")
        self.cluster.set_coordinator(node_id)

    def remove_node(self, node_id: str) -> None:
        self._validate("remove_node")
        if self.cluster is None:
            raise APIError("cluster not configured")
        self.cluster.remove_node(node_id)

    def resize_abort(self) -> None:
        if self.cluster is None:
            raise APIError("cluster not configured")
        self.cluster.resize_abort()

    def column_attr_diff(self, index: str, blocks: list) -> dict:
        """Return column attrs for blocks that differ from the caller's
        checksums (reference api.go attr-diff path / holder.go:654-740)."""
        idx = self.holder.index(index)
        if idx is None:
            raise NotFoundError(f"index not found: {index}")
        store = idx.column_attrs
        if store is None:
            return {}
        theirs = [(b[0], bytes.fromhex(b[1])) for b in blocks]
        mine = store.blocks()
        their_map = dict(theirs)
        out = {}
        for bid, digest in mine:
            if their_map.get(bid) != digest:
                out.update(store.block_data(bid))
        return {str(k): v for k, v in out.items()}

    def row_attr_diff(self, index: str, field: str, blocks: list) -> dict:
        f = self.holder.field(index, field)
        if f is None:
            raise NotFoundError(f"field not found: {field}")
        store = f.row_attr_store
        if store is None:
            return {}
        theirs = dict((b[0], bytes.fromhex(b[1])) for b in blocks)
        out = {}
        for bid, digest in store.blocks():
            if theirs.get(bid) != digest:
                out.update(store.block_data(bid))
        return {str(k): v for k, v in out.items()}

    def probe_node(self, uri: str) -> bool:
        """Probe ``uri``'s /status with the cluster's short probe
        timeout; the relay half of SWIM indirect probing. Only URIs
        belonging to known cluster members are probed — the reference's
        memberlist ping-req likewise only targets members — so the
        endpoint cannot be used as an open relay into arbitrary
        internal addresses (SSRF)."""
        if self.cluster is None:
            return False
        from pilosa_tpu.utils.uri import same_endpoint

        with self.cluster.mu:
            known = any(
                same_endpoint(n.uri, uri) for n in self.cluster.nodes
            )
        if not known:
            return False
        try:
            self.cluster._probe_client.status(uri)
            return True
        except Exception:
            return False

    def get_translate_data(self, offset: int, store: str = "") -> bytes:
        ts = self.executor.translate_store
        if ts is None:
            raise APIError("translate store not configured")
        if store:
            try:
                return ts.read_store(store, offset)
            except ValueError as e:
                raise APIError(str(e), status=400)
        data, _ = ts.read_from(offset)
        return data

    def translate_stores(self) -> list:
        """Durable translate stores with byte offsets — what a peer
        polls to pull-replicate key assignments."""
        ts = self.executor.translate_store
        if ts is None:
            raise APIError("translate store not configured")
        return ts.stores()

    def translate_debug(self) -> dict:
        ts = self.executor.translate_store
        if ts is None:
            return {"enabled": False}
        out = ts.stats()
        out["enabled"] = True
        return out

    def translate_ingest_keys(
        self, index: str, field: str, row_keys, column_keys
    ) -> tuple:
        """Keyed-ingest resolution: translate the batch's key lists to
        id lists BEFORE the ingest queue sees it, so write waves (and
        their routed local legs) carry integer ids only. One translate
        batch per ingest wave — assignments group-commit with one
        fsync per store touched."""
        ts = self.executor.translate_store
        if ts is None:
            raise APIError("translate store not configured")
        rows = cols = None
        if column_keys:
            cols = ts.translate_columns_to_ids(
                index, [str(k) for k in column_keys]
            )
        if row_keys:
            rows = ts.translate_rows_to_ids(
                index, field, [str(k) for k in row_keys]
            )
        return rows, cols

    def translate_keys(self, index: str, field: str, keys: list) -> list:
        """Mint (or look up) ids for keys — the federated-forward
        target; this node must OWN every key space the batch touches.
        Mints LOCALLY unconditionally (never re-forwards — see
        Translator.mint).

        When this node's OWN ownership resolution names a different
        owner for any key, the request is rejected with 409: minting
        here would permanently fork the cluster's id space (each mint
        is durable in the local log). The bind-vs-advertise case — an
        owner's advertised name differing from its bind address — is
        handled inside ``Server._translate_owner`` via URI equivalence
        + DNS resolution (``Server._is_self``), NOT via anything
        request-controlled: a client-supplied header must never be
        able to open the mint gate on a non-owner."""
        ts = self.executor.translate_store
        if ts is None:
            raise APIError("translate store not configured")
        keys = [str(k) for k in keys]
        check = getattr(ts, "misowned", None)
        if check is not None:
            owner = check(index, field, keys)
        elif self.server is not None:
            owner = self.server.translate_primary()
        else:
            owner = ""
        if owner:
            raise APIError(
                f"not the owner of these keys (owner={owner}); minting "
                "here would fork the cluster id space — post to the "
                "owner or fix translate-primary-url",
                status=409,
            )
        return ts.mint(index, field, keys)


def _parse_timestamps(timestamps):
    if not timestamps or not any(t for t in timestamps):
        return None
    from datetime import datetime

    return [
        datetime.fromtimestamp(t) if isinstance(t, (int, float)) and t else None
        for t in timestamps
    ]
