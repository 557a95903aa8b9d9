"""Volume server: HTTP data path + admin API + master heartbeat loop.

Mirrors the reference volume server surface
(weed/server/volume_server_handlers_read.go / _write.go for the data path;
weed/server/volume_grpc_*.go for admin — here as JSON-over-HTTP):

  data:   GET/HEAD/POST/DELETE /<vid>,<fid>
  admin:  POST /admin/assign_volume       (AllocateVolume)
          POST /admin/vacuum              (VacuumVolume*)
          POST /admin/volume/delete
          POST /admin/volume/readonly
          POST /admin/ec/generate         (VolumeEcShardsGenerate)
          POST /admin/ec/mount            (VolumeEcShardsMount)
          POST /admin/ec/unmount          (VolumeEcShardsUnmount)
          POST /admin/ec/rebuild          (VolumeEcShardsRebuild)
          POST /admin/ec/copy             (VolumeEcShardsCopy — pull model)
          POST /admin/ec/delete_shards    (VolumeEcShardsDelete)
          POST /admin/ec/blob_delete      (VolumeEcBlobDelete)
          POST /admin/ec/to_volume        (VolumeEcShardsToVolume)
          GET  /admin/ec/shard_read?volume=&shard=&offset=&size=
          GET  /status, /metrics, /healthz

Replicated writes fan out with type=replicate exactly like the reference
(weed/topology/store_replicate.go:21-161): the first server writes locally
then POSTs the same body to every replica; all must ack.
"""

from __future__ import annotations

import asyncio
import contextvars
import email.parser
import functools
import logging
import os
import socket
import time
import uuid
from typing import Optional

import aiohttp
import grpc
from aiohttp import web

from .. import faults, observe, overload
from ..ec.coder import DISPATCH_WIDTHS
from ..lifecycle.heat import HeatTracker
from ..pb import volume_server_pb2 as vpb
from ..pb.rpc import VolumeServerStub, dial, grpc_address
from ..storage.file_id import FileId
from ..utils import compression, fast_multipart
from ..utils import retry as _retry
from ..storage.needle import (FLAG_IS_COMPRESSED,
                              FLAG_HAS_LAST_MODIFIED, FLAG_HAS_MIME,
                              FLAG_HAS_NAME, FLAG_HAS_TTL, CrcError,
                              Needle)
from ..storage import types as t
from ..storage.store import Store, safe_collection
from ..storage.volume import (NeedleDeleted, NeedleExpired, NeedleNotFound,
                              VolumeReadOnly)
from ..security.guard import Guard, token_from_request
from ..utils import metrics as metrics_mod

log = logging.getLogger("volume")

# labels of `ec_read_nowait` (`VolumeServer.read_ec_needle`): made once,
# a GET only counts
_SERVED = {"result": "served"}
_DECLINED = {"result": "declined"}
# and of `ec_peer_call` (`_make_shard_reader`): a remote read only counts
_CALL_REUSED = {"result": "reused"}
_CALL_BUILT = {"result": "built"}


def _resize_image(data: bytes, mime: str, width: int, height: int,
                  mode: str) -> bytes:
    """Resize an image payload (weed/images/resizing.go): 'fit' keeps the
    aspect ratio inside the box, 'fill' crops to exactly fill it."""
    import io

    from PIL import Image

    img = Image.open(io.BytesIO(data))
    fmt = img.format or mime.split("/")[-1].upper()
    w = width or img.width
    h = height or img.height
    if mode == "fill":
        from PIL import ImageOps
        img = ImageOps.fit(img, (w, h))
    else:
        img.thumbnail((w, h))
    out = io.BytesIO()
    img.save(out, format=fmt)
    return out.getvalue()


class WriteBatcher:
    """Per-volume async write coalescing — the server half of the
    reference's batching worker (volume_read_write.go:297-327): up to 128
    requests or 4MB land in one executor call and one engine flush, so
    concurrent small writes stop paying a thread-pool hop each.
    """

    MAX_BATCH = 128
    MAX_BYTES = 4 * 1024 * 1024
    INLINE_BYTES = 256 * 1024  # below this a batch writes on the loop
    IDLE_SECONDS = 30.0  # worker exits after this long with no writes

    def __init__(self, store: Store, group_commit_us: Optional[int] = None):
        self.store = store
        self._queues: dict[int, asyncio.Queue] = {}
        self._workers: dict[int, asyncio.Task] = {}
        # group commit: hold the batch open for this many µs so
        # concurrent small writes coalesce into ONE gathered writev +
        # ONE fsync barrier; acks release only after the barrier
        # (storage/volume.py _write_needles_group). 0 = off (default):
        # the proven drain-what's-queued path with no added latency.
        if group_commit_us is None:
            try:
                group_commit_us = int(os.environ.get(
                    "WEED_VOLUME_GROUP_COMMIT_US", "0") or 0)
            except ValueError:
                group_commit_us = 0
        self.group_commit_us = max(0, group_commit_us)

    async def write(self, vid: int, needle) -> tuple[int, int, bool]:
        # (measured: an uncontended inline shortcut here is neutral at
        # c=16 — the queue is rarely empty under load and the probe cost
        # is paid on every write — so the single queue path stays)
        q = self._queues.get(vid)
        if q is None:
            q = self._queues[vid] = asyncio.Queue()
            self._workers[vid] = asyncio.create_task(self._worker(vid, q))
        fut = asyncio.get_event_loop().create_future()
        q.put_nowait((needle, fut))
        result = await fut
        if isinstance(result, Exception):
            raise result
        return result

    async def _worker(self, vid: int, q: asyncio.Queue) -> None:
        loop = asyncio.get_event_loop()
        while True:
            try:
                needle, fut = await asyncio.wait_for(
                    q.get(), timeout=self.IDLE_SECONDS)
            except asyncio.TimeoutError:
                # submit's critical section (dict get → put_nowait) has no
                # awaits, so checking emptiness here and deleting is safe:
                # anything enqueued after the timeout fired makes q
                # non-empty and we keep running
                if q.empty():
                    self._queues.pop(vid, None)
                    self._workers.pop(vid, None)
                    return
                continue
            batch = [(needle, fut)]
            size = len(needle.data)
            while (len(batch) < self.MAX_BATCH and size < self.MAX_BYTES
                   and not q.empty()):
                n2, f2 = q.get_nowait()
                batch.append((n2, f2))
                size += len(n2.data)
            if self.group_commit_us > 0:
                # hold the commit window open: anything arriving before
                # the deadline rides this group's single fsync
                deadline = loop.time() + self.group_commit_us / 1e6
                while (len(batch) < self.MAX_BATCH
                       and size < self.MAX_BYTES):
                    remaining = deadline - loop.time()
                    if remaining <= 0:
                        break
                    try:
                        n2, f2 = await asyncio.wait_for(q.get(), remaining)
                    except asyncio.TimeoutError:
                        break
                    batch.append((n2, f2))
                    size += len(n2.data)
                    while (len(batch) < self.MAX_BATCH
                           and size < self.MAX_BYTES and not q.empty()):
                        n3, f3 = q.get_nowait()
                        batch.append((n3, f3))
                        size += len(n3.data)
            v = self.store.find_volume(vid)
            if v is None:
                # volume deleted/unmounted (or bogus vid): fail the batch
                # and retire this worker instead of idling forever
                err = KeyError(f"volume {vid} not found")
                for _, f in batch:
                    if not f.done():
                        f.set_exception(err)
                if q.empty():
                    self._queues.pop(vid, None)
                    self._workers.pop(vid, None)
                    return
                continue
            try:
                ns = [n for n, _ in batch]
                results = None
                if self.group_commit_us > 0:
                    # the group path always takes the executor: it ends
                    # in an fsync barrier (never loop-inline), and the
                    # acks below release only after that barrier
                    results = await loop.run_in_executor(
                        None, functools.partial(
                            v.write_needles_batch, ns, group_commit=True))
                elif size <= self.INLINE_BYTES:
                    # small batches: buffered page-cache appends finish in
                    # microseconds, while the executor handoff costs two GIL
                    # convoys (~ms on few-core hosts). The nowait variant
                    # declines (None) when the volume lock is contended
                    # (vacuum) or the backend isn't local disk, so the loop
                    # is never blocked on slow IO.
                    results = v.write_needles_batch_nowait(ns)
                if results is None:
                    results = await loop.run_in_executor(
                        None, v.write_needles_batch, ns)
            except Exception as e:
                results = [e] * len(batch)
            for (_, f), res in zip(batch, results):
                if f.done():
                    continue
                # engine errors come back in-place; surface per-request
                f.set_result(res)

    def stop(self) -> None:
        for t in self._workers.values():
            t.cancel()


class VolumeServer:
    def __init__(self, store: Store, master_url: str, url: str,
                 public_url: str = "", data_center: str = "", rack: str = "",
                 pulse_seconds: float = 5.0, read_redirect: bool = False,
                 guard: Optional[Guard] = None,
                 use_grpc_heartbeat: bool = False,
                 master_grpc_target: str = "",
                 grpc_port: int = 0,
                 tls=None,
                 scrub_interval_seconds: Optional[float] = None,
                 internal_token: Optional[str] = None,
                 shard_ctx=None):
        self.use_grpc_heartbeat = use_grpc_heartbeat
        # explicit gRPC endpoint override; default follows the
        # HTTP-port+10000 convention (grpc_client_server.go)
        self.master_grpc_target = master_grpc_target
        self.store = store
        # master_url may be a comma-separated HA list; heartbeats follow the
        # raft leader hint and rotate on failure
        # (weed/server/volume_grpc_client_to_master.go:50-86)
        self.masters = [m.strip() for m in master_url.split(",") if m.strip()]
        self.master_url = self.masters[0]
        self.url = url
        self.public_url = public_url or url
        self.data_center = data_center
        self.rack = rack
        self.pulse_seconds = pulse_seconds
        self.read_redirect = read_redirect
        self.guard = guard or Guard()
        self.volume_size_limit = 30 * 1024 * 1024 * 1024
        self.metrics = metrics_mod.Registry("volume")
        self._hb_task: Optional[asyncio.Task] = None
        self._session: Optional[aiohttp.ClientSession] = None
        self._batcher: Optional[WriteBatcher] = None
        self.grpc_port = grpc_port
        self.tls = tls
        self._grpc_server = None
        self._replica_cache: dict[int, tuple[list[str], float]] = {}
        self._shard_loc_cache: dict[int, tuple[dict, float]] = {}
        # peer url -> (channel, its prepared VolumeEcShardRead call): the
        # call lives and dies with the channel it was made from
        self._peer_grpc_channels: dict[str, tuple] = {}
        self._peer_grpc_dead: dict[str, float] = {}
        self._repair_neg: dict[str, float] = {}
        self._repair_inflight = 0
        # EC scrubber: low-priority digest verify of local shards
        # (WEED_EC_SCRUB_INTERVAL seconds; 0 disables)
        if scrub_interval_seconds is None:
            try:
                scrub_interval_seconds = float(
                    os.environ.get("WEED_EC_SCRUB_INTERVAL", "3600"))
            except ValueError:
                scrub_interval_seconds = 3600.0
        self.scrub_interval_seconds = scrub_interval_seconds
        self._scrub_task: Optional[asyncio.Task] = None
        # per-volume access heat (lifecycle plane): O(1) sampling on the
        # read/write paths — both this app's handlers and the fastpath
        # listener's inline shapes — drained as deltas into heartbeats.
        # WEED_LIFECYCLE_HEAT_HALFLIFE shrinks the EWMA window so tests
        # (and aggressive un-EC policies) see rate changes quickly.
        try:
            halflife = float(
                os.environ.get("WEED_LIFECYCLE_HEAT_HALFLIFE", "0") or 0)
        except ValueError:
            halflife = 0.0
        self.heat = HeatTracker(halflife=halflife) if halflife > 0 \
            else HeatTracker()
        # per-process secret marking requests proxied from the fastpath
        # listener (server/fastpath.py): they arrive from 127.0.0.1 but
        # were already whitelist-checked against the REAL peer IP.  In a
        # shard fleet the token is minted pre-fork and shared, so any
        # shard's fastpath can proxy cross-shard to the owner's loopback
        # app and still be treated as pre-admitted.
        if internal_token:
            self._internal_token = internal_token
        else:
            import secrets as _secrets
            self._internal_token = _secrets.token_hex(16)
        self._fast_srv = None
        # share-nothing shard fleet handle (server/sharded.py); None in
        # the single-process path
        self.shard_ctx = shard_ctx
        self._stripe_task: Optional[asyncio.Task] = None
        # overload plane: repair/scrub/vacuum traffic (tagged bg by its
        # originators) sheds before the user data plane
        self.admission = overload.AdmissionController(
            "volume", metrics=self.metrics,
            system_paths=overload.VOLUME_SYSTEM_PATHS)
        # which plane answered an EC GET (server/fastpath.py): both
        # counters on /metrics from the start, a 0 and not an absence
        self.metrics.count("ec_read_inline", 0)
        self.metrics.count("ec_read_proxied", 0)
        # and how often the loop's thread read the needle itself
        # (`read_ec_needle`): born at 0 likewise
        for result in (_SERVED, _DECLINED):
            self.metrics.count("ec_read_nowait", 0, labels=result)
        # what an EC read took from peers (`_make_shard_reader`), and how
        # often it asked the master where a shard is: born at 0 likewise
        for via in ("grpc", "http"):
            self.metrics.count("ec_remote_shard_reads", 0,
                               labels={"via": via})
        self.metrics.count("ec_remote_shard_read_bytes", 0)
        # whether a remote read found its peer's call prepared
        for result in (_CALL_REUSED, _CALL_BUILT):
            self.metrics.count("ec_peer_call", 0, labels=result)
        # and, as the peer, where it read a shard range that was asked of
        # it (server/volume_grpc.py counts them)
        for how in ("inline", "executor"):
            self.metrics.count("ec_shard_read_served", 0,
                               labels={"how": how})
        for result in ("holder", "none"):
            self.metrics.count("ec_shard_location_lookups", 0,
                               labels={"result": result})
        # how an EC volume fetched the .ecx entries of a lookup
        # (ec/ec_volume.py counts them, in the shared registry of this
        # subsystem's name): born at 0 too
        for via in ("mmap", "pread"):
            metrics_mod.shared("volume").count("ecx_lookups", 0,
                                               labels={"via": via})
        # and what an encode handed to the coder (ec/pipeline.py counts
        # it, in the shared `ec` registry): born at 0 too
        for name in ("encode_input_bytes", "encode_batches"):
            metrics_mod.shared("ec").count(name, 0)
        # and what a degraded read handed to the device: the intervals as
        # asked, as padded, and each dispatch by its width and whether
        # that width was compiled when the read met it
        # (ops/rs_pallas.py counts them, same registry): born at 0 too
        for name in ("reconstruct_interval_bytes",
                     "reconstruct_padded_bytes"):
            metrics_mod.shared("ec").count(name, 0)
        for width in DISPATCH_WIDTHS:
            for warm in ("yes", "no"):
                metrics_mod.shared("ec").count(
                    "reconstruct_dispatch", 0,
                    labels={"width": str(width), "warm": warm})
        self.app = self._build_app()
        # the EC read path fetches missing shards from peers through this
        store._remote_shard_reader = self._make_shard_reader

    def shard_route(self, vid: int) -> Optional[int]:
        """Loopback port of the sibling shard owning ``vid``, or None to
        handle locally.  Local store ALWAYS wins (legacy volumes all
        live in shard 0's base dir — the modulo map must never shadow
        them); EC volumes stay local too (the EC read path does its own
        peer fetches).  Called per-request from the fastpath dispatch,
        so the checks are dict probes, not IO."""
        ctx = self.shard_ctx
        if ctx is None or ctx.shards <= 1:
            return None
        if self.store.find_volume(vid) is not None \
                or self.store.find_ec_volume(vid) is not None:
            return None
        return ctx.lookup_volume_port(vid)

    def _build_app(self) -> web.Application:
        @web.middleware
        async def guard_mw(request: web.Request, handler):
            # IP whitelist wraps every route except liveness, admin surface
            # included (Guard.WhiteList, weed/security/guard.go:53); the
            # per-fid JWT check on the data path happens in data_handler.
            # Requests proxied from the fastpath listener carry the
            # per-process token: they were already checked against the
            # real peer IP (this listener only sees 127.0.0.1 for them).
            if request.path != "/healthz":
                if (request.headers.get("X-Swfs-Internal")
                        != self._internal_token
                        and not self.guard.check_whitelist(
                            request.remote or "")):
                    return web.json_response({"error": "ip not allowed"},
                                             status=403)
            return await handler(request)

        # tracing outermost: denied requests still record a span; the
        # whitelist guard BEFORE admission — an off-whitelist flood
        # must burn a cheap 403, not drain admission tokens and queue
        # slots (shedding whitelisted traffic and locking out bg
        # repair with zero real overload); requests proxied from the
        # fastpath were admitted there already (internal token)
        app = web.Application(
            client_max_size=256 * 1024 * 1024,
            middlewares=[observe.trace_middleware("volume", self.url),
                         guard_mw,
                         overload.admission_middleware(
                             self.admission,
                             internal_token=lambda: self._internal_token)])
        app.router.add_post("/admin/assign_volume", self.admin_assign_volume)
        app.router.add_post("/admin/vacuum", self.admin_vacuum)
        app.router.add_get("/admin/vacuum/check", self.admin_vacuum_check)
        app.router.add_post("/admin/vacuum/compact",
                            self.admin_vacuum_compact)
        app.router.add_post("/admin/vacuum/commit", self.admin_vacuum_commit)
        app.router.add_post("/admin/vacuum/cleanup",
                            self.admin_vacuum_cleanup)
        app.router.add_post("/admin/volume/delete", self.admin_volume_delete)
        app.router.add_post("/admin/volume/readonly", self.admin_readonly)
        app.router.add_post("/admin/volume/mount", self.admin_volume_mount)
        app.router.add_post("/admin/volume/unmount",
                            self.admin_volume_unmount)
        app.router.add_post("/admin/volume/configure_replication",
                            self.admin_volume_configure)
        app.router.add_get("/admin/volume/needle_ids", self.admin_needle_ids)
        app.router.add_get("/admin/needle_raw", self.admin_needle_raw)
        app.router.add_post("/admin/tier/upload", self.admin_tier_upload)
        app.router.add_post("/admin/tier/download", self.admin_tier_download)
        app.router.add_post("/admin/ec/generate", self.admin_ec_generate)
        app.router.add_post("/admin/ec/fused", self.admin_ec_fused)
        app.router.add_post("/admin/ec/mount", self.admin_ec_mount)
        app.router.add_post("/admin/ec/unmount", self.admin_ec_unmount)
        app.router.add_post("/admin/ec/rebuild", self.admin_ec_rebuild)
        app.router.add_post("/admin/ec/copy", self.admin_ec_copy)
        app.router.add_post("/admin/ec/delete_shards",
                            self.admin_ec_delete_shards)
        app.router.add_post("/admin/ec/blob_delete", self.admin_ec_blob_delete)
        app.router.add_post("/admin/ec/to_volume", self.admin_ec_to_volume)
        app.router.add_get("/admin/ec/shard_read", self.admin_ec_shard_read)
        app.router.add_post("/admin/ec/scrub", self.admin_ec_scrub)
        app.router.add_get("/admin/ec/mesh_status",
                           self.admin_ec_mesh_status)
        _faults_handler = faults.admin_handler()
        app.router.add_get("/admin/faults", _faults_handler)
        app.router.add_post("/admin/faults", _faults_handler)
        app.router.add_get("/admin/file_copy", self.admin_file_copy)
        app.router.add_get("/admin/tail", self.admin_tail)
        app.router.add_post("/admin/volume/copy", self.admin_volume_copy)
        app.router.add_post("/admin/batch_delete", self.admin_batch_delete)
        app.router.add_post("/admin/query", self.admin_query)
        app.router.add_get("/status", self.status)
        app.router.add_get("/metrics", self.metrics_handler)
        app.router.add_get("/healthz",
                           overload.healthz_handler(self.admission,
                                                    shard_ctx=self.shard_ctx))
        from ..observe import profiler, wideevents
        app.router.add_get("/debug/profile", profiler.profile_handler())
        app.router.add_get("/debug/trace", observe.trace_handler())
        overload.reserve_ops(app, "/debug/pprof", profiler.pprof_handler())
        overload.reserve_ops(app, "/debug/xprof",
                             profiler.xprof_handler(
                                 lambda: self._ec_on_device()))
        overload.reserve_ops(app, "/debug/events",
                             wideevents.events_handler())
        app.router.add_get("/ui", self.status_ui)
        app.router.add_route("*", "/{fid:[^{}]*}", self.data_handler)
        app.on_startup.append(self._on_startup)
        app.on_cleanup.append(self._on_cleanup)
        return app

    def _ec_on_device(self) -> bool:
        """Whether an EC coder of this server computes on an accelerator
        (as each reports it: the status surface's `coder.resolved`)."""
        return any((c.get("device") or {}).get("platform", "cpu") != "cpu"
                   for c in self.store.coder_status()["resolved"])

    async def _on_startup(self, app) -> None:
        from ..observe import profiler
        profiler.ensure_started()
        self._session = aiohttp.ClientSession(
            # connect/inactivity bounds with no total cap: replicate
            # fan-out and heartbeats must never hang on a dead peer,
            # while multi-GB volume/shard copies stream as long as bytes
            # keep flowing
            timeout=aiohttp.ClientTimeout(total=None, sock_connect=10,
                                          sock_read=60),
            trace_configs=[observe.client_trace_config()])
        await self.admission.start()
        self._batcher = WriteBatcher(self.store)
        self._hb_task = asyncio.create_task(self._heartbeat_loop())
        if self.scrub_interval_seconds > 0:
            self._scrub_task = asyncio.create_task(self._scrub_loop())
        if self.grpc_port:
            from .volume_grpc import serve_volume_grpc
            host = self.url.rsplit(":", 1)[0]
            self._grpc_server = await serve_volume_grpc(
                self, host, self.grpc_port, tls=self.tls)

    async def _on_cleanup(self, app) -> None:
        self.admission.stop()
        if getattr(self, "_fast_srv", None) is not None:
            self._fast_srv.close()
            await self._fast_srv.wait_closed()
            self._fast_srv = None
        for ch, _ in self._peer_grpc_channels.values():
            try:
                ch.close()
            except Exception:
                pass
        self._peer_grpc_channels.clear()
        if self._grpc_server is not None:
            await self._grpc_server.stop(grace=0.5)
        if self._hb_task:
            self._hb_task.cancel()
        if self._scrub_task:
            self._scrub_task.cancel()
        if self._stripe_task:
            self._stripe_task.cancel()
        if self._batcher is not None:
            self._batcher.stop()
        if self._session:
            await self._session.close()
        self.store.close()

    # --- heartbeat (weed/server/volume_grpc_client_to_master.go:50-222) ---
    async def _heartbeat_loop(self) -> None:
        while True:
            try:
                await self._periodic_maintenance()
                if self.use_grpc_heartbeat:
                    # the bidi stream carries beats until it breaks; the
                    # HTTP beat below is the fallback for that round
                    await self._grpc_heartbeat_stream()
                await self.send_heartbeat()
            except Exception as e:
                log.warning("heartbeat to %s failed: %s", self.master_url, e)
                self._rotate_master()
            await asyncio.sleep(self.pulse_seconds)

    async def _periodic_maintenance(self) -> None:
        expired = await asyncio.get_event_loop().run_in_executor(
            None, self.store.delete_expired_volumes)
        if expired:
            log.info("deleted expired TTL volumes %s", expired)
        # min-free-space watchdog: volumes on a filling disk seal
        # themselves readonly before the disk is full (disk_location.go:304)
        was_low = self.store.low_disk_space
        low = await asyncio.get_event_loop().run_in_executor(
            None, self.store.check_free_space)
        if low != was_low:
            log.warning("low disk space: %s", low)

    def _hb_payload(self, include_heat: bool = True) -> dict:
        payload = self.store.heartbeat()
        payload.update({
            "node_id": self.url,
            "url": self.url,
            "public_url": self.public_url,
            "data_center": self.data_center,
            "rack": self.rack,
        })
        if include_heat:
            # changed-volumes-only deltas: an idle node's heartbeat
            # carries no heat entries at all (payload stays O(changed));
            # draining also prunes tracker state for departed volumes
            held = ({v["id"] for v in payload["volumes"]}
                    | {s["id"] for s in payload["ec_shards"]})
            deltas = self.heat.deltas(known_vids=held)
            if deltas:
                payload["heat"] = deltas
        return payload

    async def _report_heat(self) -> None:
        """Deliver heat deltas over HTTP for nodes whose heartbeats
        ride the gRPC stream (no pb heat field). Failures requeue the
        drained window and never break the stream — heat is advisory,
        the heartbeat is not."""
        deltas = self.heat.deltas()
        if not deltas:
            return
        try:
            async with self._session.post(
                    f"http://{self.master_url}/vol/heat/report",
                    json={"node_id": self.url, "heat": deltas},
                    timeout=aiohttp.ClientTimeout(total=5)) as r:
                if r.status != 200:
                    raise RuntimeError(f"status {r.status}")
        except asyncio.CancelledError:
            self.heat.requeue(deltas)
            raise
        except Exception as e:
            self.heat.requeue(deltas)
            log.debug("heat report to %s failed: %s", self.master_url, e)

    async def _grpc_heartbeat_stream(self) -> None:
        """Hold the bidi gRPC heartbeat stream
        (volume_grpc_client_to_master.go:50-222): full-state beats up
        every pulse, volume-size-limit + leader hints down. Returns when
        the stream breaks; the caller falls back to HTTP and retries."""
        import grpc

        from ..pb.rpc import MasterStub, grpc_address
        from .master_grpc import heartbeat_to_pb

        target = self.master_grpc_target or grpc_address(self.master_url)
        stop = asyncio.Event()

        async def beats():
            while not stop.is_set():
                await self._periodic_maintenance()
                # the pb schema has no heat field: don't drain deltas
                # into a beat that can't carry them — side-channel them
                # to /vol/heat/report right after, so gRPC-heartbeat
                # clusters still feed the lifecycle heat view
                yield heartbeat_to_pb(self._hb_payload(include_heat=False))
                await self._report_heat()
                try:
                    await asyncio.wait_for(stop.wait(), self.pulse_seconds)
                except asyncio.TimeoutError:
                    pass

        from ..pb.rpc import aio_dial
        async with aio_dial(target) as channel:
            call = MasterStub(channel).Heartbeat(beats())
            try:
                async for resp in call:
                    self.volume_size_limit = (resp.volume_size_limit
                                              or self.volume_size_limit)
                    leader = resp.leader
                    if leader and leader not in ("self", self.master_url):
                        log.info("grpc heartbeat: following leader %s",
                                 leader)
                        self.master_url = leader
                        # the explicit target (tests) only described the
                        # old master; the new leader is reached via the
                        # port convention
                        self.master_grpc_target = ""
                        return  # redial the leader's gRPC port
            finally:
                stop.set()

    def _rotate_master(self) -> None:
        if len(self.masters) > 1:
            i = self.masters.index(self.master_url) \
                if self.master_url in self.masters else 0
            self.master_url = self.masters[(i + 1) % len(self.masters)]

    def _update_volume_gauges(self, payload: dict) -> None:
        """Per-collection volume gauges (the reference's labeled
        volumeServer gauges, weed/stats/metrics.go + store.go:40)."""
        by_col: dict[str, list[int]] = {}
        for v in payload.get("volumes", []):
            agg = by_col.setdefault(v.get("collection", "") or "default",
                                    [0, 0])
            agg[0] += 1
            agg[1] += v.get("size", 0)
        for col, (n, size) in by_col.items():
            self.metrics.gauge("volumes", n, labels={"collection": col,
                                                     "type": "normal"})
            self.metrics.gauge("volume_bytes", size,
                               labels={"collection": col})
        for s in payload.get("ec_shards", []):
            self.metrics.gauge(
                "ec_shards", len(s.get("shard_ids", [])),
                labels={"collection": s.get("collection", "") or "default",
                        "volume": str(s.get("id"))})
        # EC read-coalescing totals: how many cold interval reads led a
        # flight vs rode one (singleflight in ec/ec_volume.py)
        leaders = shared = 0
        for loc in self.store.locations:
            for ev in loc.ec_volumes.values():
                st = ev.read_flight.stats()
                leaders += st["leaders"]
                shared += st["shared"]
        self.metrics.gauge("ec_read_flight_leaders", leaders)
        self.metrics.gauge("ec_read_flight_shared", shared)

    async def send_heartbeat(self) -> None:
        ctx = self.shard_ctx
        if ctx is not None and ctx.shards > 1 and ctx.index != 0:
            # non-zero shards publish their volume list through the
            # shared segment (stripe tick blob); shard 0 unions it into
            # the single master heartbeat.  Heat stays queued in the
            # tracker (advisory — see _report_heat's contract).
            self._update_volume_gauges(self._hb_payload(include_heat=False))
            return
        payload = self._hb_payload()
        self._update_volume_gauges(payload)
        if ctx is not None and ctx.shards > 1:
            payload = ctx.merged_heartbeat(payload)
        try:
            await self._send_heartbeat(payload)
        except BaseException:
            # the heat deltas were drained into this payload; a failed
            # delivery must not lose the window's access records (a
            # lost last_access makes an active volume look idle to the
            # warm rule one window early)
            if payload.get("heat"):
                self.heat.requeue(payload["heat"])
            raise

    async def _send_heartbeat(self, payload: dict) -> None:
        async with self._session.post(
                f"http://{self.master_url}/heartbeat", json=payload,
                timeout=aiohttp.ClientTimeout(total=10)) as r:
            body = await r.json()
            self.volume_size_limit = body.get("volume_size_limit",
                                              self.volume_size_limit)
            # follow the raft leader so deltas land on the node that owns
            # the topology (volume_grpc_client_to_master.go:60-86)
            leader = body.get("leader", "")
            if leader and leader != self.master_url and leader != "self":
                log.info("heartbeat: following master leader %s", leader)
                self.master_url = leader

    # --- data path ---
    async def data_handler(self, request: web.Request) -> web.Response:
        fid_str = request.match_info["fid"].lstrip("/")
        if not fid_str or "," not in fid_str:
            return web.json_response({"error": "missing file id"}, status=400)
        try:
            fid = FileId.parse(fid_str)
        except ValueError as e:
            return web.json_response({"error": str(e)}, status=400)
        token = token_from_request(request.headers, request.query)
        canonical = str(fid)
        if request.method in ("GET", "HEAD"):
            err = self.guard.verify_read(token, canonical)
            if err:
                return web.json_response({"error": err}, status=401)
            return await self._read(request, fid)
        if request.method in ("POST", "PUT"):
            err = self.guard.verify_write(token, canonical)
            if err:
                return web.json_response({"error": err}, status=401)
            return await self._write(request, fid)
        if request.method == "DELETE":
            err = self.guard.verify_write(token, canonical)
            if err:
                return web.json_response({"error": err}, status=401)
            return await self._delete(request, fid)
        return web.json_response({"error": "method not allowed"}, status=405)

    async def _read(self, request: web.Request, fid: FileId) -> web.Response:
        """GetOrHeadHandler (volume_server_handlers_read.go:28-272)."""
        if (self.store.find_volume(fid.volume_id) is not None
                or self.store.find_ec_volume(fid.volume_id) is None):
            self.metrics.count("read")
            return await self._read_needle(request, fid)
        # an EC GET: every stage of it is named (observe.stage; PERF.md
        # has the table). The fast path answers the plain shape itself
        # through the same `read_ec_needle`; what arrives here is a
        # Range, a resize, a needle whose CRC failed over there, a shard
        # fleet sibling's request, or every EC GET of a server that runs
        # without a fast path. Behind the fast path's hop `ec.get` is
        # open over there and `ec.get.handler` is this side of it.
        if request.headers.get("X-Swfs-Internal") == self._internal_token:
            with observe.stage("ec.get.handler", enclosing=True):
                return await self._read_ec(request, fid)
        with observe.stage("ec.get", enclosing=True), \
                observe.stage("ec.get.handler", enclosing=True):
            return await self._read_ec(request, fid)

    async def read_ec_needle(self, fid: FileId, respond):
        """The read of one EC GET, for whichever data plane answers it
        (nothing of aiohttp in here; the caller holds `ec.get.handler`
        open): the `volume.read` fault point, the read counter and
        timer, the read itself, heat, and `respond(needle)`, which makes
        the answer of the caller's plane. Gives what `respond` gave, or
        None for an injected drop; raises FaultError, or what the store
        raises (NeedleExpired, NeedleNotFound / KeyError, NeedleDeleted,
        CrcError).

        The loop's thread tries the read first
        (`EcVolume.read_needle_nowait`: every interval in a mapped shard
        file here, so no system call and a hold of the loop that
        `ec_volume.NOWAIT_MAX_SIZE` bounds, where the hand-off to an
        executor thread and back costs two GIL convoys) and
        `ec_read_nowait{result=}` says how it went. A read that declined
        (a lost or remote shard, a needle over that limit, nothing
        mapped) runs in
        the executor with what the loop located, under a copy of the
        request's context: its stages are children of the open
        `ec.get.handler` and reach the request's wide event. Those
        hand-offs are stages: `ec.get.queue` from the submit to the
        worker's first line (a declined read alone), `ec.get.resume`
        from the read's last line, over the wait for the loop if there
        was one, to the response in hand."""
        self.metrics.count("read")
        if await faults.fire_async("volume.read"):
            return None
        with self.metrics.timed("read"):
            served = True  # an error of the search is the loop's answer too
            try:
                n, located = self.store.read_ec_needle_nowait(
                    fid.volume_id, fid.key, fid.cookie)
                served = n is not None
            finally:
                self.metrics.count("ec_read_nowait",
                                   labels=_SERVED if served else _DECLINED)
            done_s, t_done = time.time(), time.perf_counter()
            if not served:
                submit_us, t_submit = int(done_s * 1e6), t_done

                def work():
                    observe.record_span(
                        "ec.get.queue", None, submit_us,
                        int((time.perf_counter() - t_submit) * 1e6))
                    read = self.store.read_needle(
                        fid.volume_id, fid.key, fid.cookie, located=located)
                    return read, time.time(), time.perf_counter()

                n, done_s, t_done = await asyncio.get_event_loop(
                    ).run_in_executor(None, contextvars.copy_context().run,
                                      work)
        # lifecycle heat: EC reads are the warm tier's un-EC signal
        self.heat.record_read(fid.volume_id)
        resp = respond(n)
        observe.record_span("ec.get.resume", None, int(done_s * 1e6),
                            int((time.perf_counter() - t_done) * 1e6))
        return resp

    async def _read_ec(self, request: web.Request,
                       fid: FileId) -> web.Response:
        """`read_ec_needle` for the aiohttp plane (`ec.get.handler`
        stands where `volume.read` does for a plain volume: a span that
        wraps the stages would be the largest entry of the wide event
        whatever the GET waited for)."""
        try:
            resp = await self.read_ec_needle(
                fid, lambda n: self._respond(request, n))
        except faults.FaultError as e:
            return web.json_response({"error": str(e)}, status=500)
        except NeedleDeleted:  # (a KeyError too: ahead of it)
            return web.json_response({"error": "deleted"}, status=404)
        except (NeedleExpired, NeedleNotFound, KeyError):
            return web.json_response({"error": "not found"}, status=404)
        except CrcError as rot:
            n = await self._repair_rot(fid, rot)
            if n is None:
                return web.json_response({"error": "data corruption"},
                                         status=500)
            return self._respond(request, n)
        if resp is None:
            return web.json_response({"error": "injected drop"}, status=404)
        return resp

    async def _read_needle(self, request: web.Request,
                           fid: FileId) -> web.Response:
        try:
            if await faults.fire_async("volume.read"):
                # injected drop: the needle "isn't here" — clients fall
                # back to replicas / degraded EC paths
                return web.json_response({"error": "injected drop"},
                                         status=404)
        except faults.FaultError as e:
            return web.json_response({"error": str(e)}, status=500)
        with self.metrics.timed("read"), \
                observe.span("volume.read", tags={"fid": str(fid)}):
            try:
                # small needles (the request-rate-bound workload) read
                # inline: a page-cache pread is microseconds while the
                # executor handoff costs two GIL convoys. The nowait
                # variant declines (None) for big needles, contended locks
                # (vacuum), or non-local backends (tiered volumes) so the
                # loop never blocks on real IO.
                vol = self.store.find_volume(fid.volume_id)
                n = (vol.read_needle_nowait(fid.key, fid.cookie)
                     if vol is not None else None)
                if n is None:
                    n = await asyncio.get_event_loop().run_in_executor(
                        None, lambda: self.store.read_needle(
                            fid.volume_id, fid.key, fid.cookie))
            except NeedleExpired:
                # TTL expiry is not data loss: never repair it back
                return web.json_response({"error": "not found"}, status=404)
            except (NeedleNotFound, KeyError) as miss:
                if (self.read_redirect
                        and self.store.find_volume(fid.volume_id) is None
                        and self.store.find_ec_volume(fid.volume_id) is None):
                    url = await self._lookup_replica(fid.volume_id)
                    if url:
                        raise web.HTTPMovedPermanently(
                            f"http://{url}/{fid}")
                # read repair: a replica of a volume we host may still have
                # the needle (lost local write / corruption); fetch it,
                # rewrite locally, and serve (the repair hook at
                # weed/topology/store_replicate.go:163-194). Guarded by a
                # negative cache + concurrency cap so scans of bogus fids
                # cannot amplify into replica storms.
                if (isinstance(miss, NeedleNotFound)
                        and self.store.find_volume(fid.volume_id)
                        is not None
                        and self._repair_permitted(str(fid))):
                    repaired = await self._read_repair(fid)
                    if repaired is not None:
                        n = repaired
                    else:
                        return web.json_response({"error": "not found"},
                                                 status=404)
                else:
                    return web.json_response({"error": "not found"},
                                             status=404)
            except NeedleDeleted:
                return web.json_response({"error": "deleted"}, status=404)
            except CrcError as rot:
                n = await self._repair_rot(fid, rot)
                if n is None:
                    return web.json_response(
                        {"error": "data corruption"}, status=500)
        # lifecycle heat: one dict update per served read
        self.heat.record_read(fid.volume_id)
        return self._respond(request, n)

    async def _repair_rot(self, fid: FileId, rot: CrcError):
        """On-disk corruption (bit-rot / torn write) on a volume we
        host: repair from a healthy replica and serve the good copy
        instead of surfacing the rot to the client. The repair
        re-appends the intact needle locally (the corrupt bytes become
        vacuumable garbage) and the event is reported for the
        scrubber/operators via metric+log. None when nothing repaired."""
        self.metrics.count("read_crc_repair")
        log.error("volume %d: CRC mismatch on needle %s (%s); "
                  "attempting read-repair from replicas",
                  fid.volume_id, fid, rot)
        if self._repair_permitted(str(fid)):
            return await self._read_repair(fid)
        return None

    @staticmethod
    def _respond(request: web.Request, n) -> web.Response:
        """A read needle as the response: etag, headers, body."""
        etag = f'"{n.etag()}"'
        if request.headers.get("If-None-Match") == etag:
            return web.Response(status=304)
        headers = {"ETag": etag, "Accept-Ranges": "bytes"}
        if n.has(FLAG_HAS_LAST_MODIFIED):
            headers["X-Last-Modified"] = str(n.last_modified)
        mime = (n.mime.decode("utf-8", "replace")
                if n.has(FLAG_HAS_MIME) else "application/octet-stream")
        if n.has(FLAG_HAS_NAME) and n.name:
            headers["Content-Disposition"] = (
                f'inline; filename="{n.name.decode("utf-8", "replace")}"')
        body = n.data
        if n.is_compressed:
            # serve gzip verbatim only to clients that accept it; otherwise
            # decompress server-side (volume_server_handlers_read.go:170-200)
            if "gzip" in request.headers.get("Accept-Encoding", ""):
                headers["Content-Encoding"] = "gzip"
            else:
                body = compression.decompress(body)
        # image resize on read (?width=&height=&mode=fit|fill,
        # volume_server_handlers_read.go:240-272 via images.Resized);
        # skipped when the body is being served gzip-encoded. Detection by
        # mime or stored filename extension (the reference keys on ext).
        is_image = mime.startswith("image/") or (
            n.has(FLAG_HAS_NAME) and n.name
            and n.name.lower().endswith((b".jpg", b".jpeg", b".png",
                                         b".gif", b".webp")))
        if (is_image
                and "Content-Encoding" not in headers
                and (request.query.get("width")
                     or request.query.get("height"))):
            try:
                body = _resize_image(
                    body, mime,
                    int(request.query.get("width", 0)),
                    int(request.query.get("height", 0)),
                    request.query.get("mode", "fit"))
            except Exception as e:
                log.warning("image resize failed: %s", e)
        # range support
        rng = request.headers.get("Range")
        if rng and rng.startswith("bytes=") and \
                "Content-Encoding" not in headers:
            try:
                start_s, _, end_s = rng[6:].partition("-")
                if not start_s:
                    # suffix range: last N bytes (RFC 7233)
                    suffix = int(end_s)
                    if suffix <= 0:
                        raise ValueError
                    start = max(0, len(body) - suffix)
                    end = len(body) - 1
                else:
                    start = int(start_s)
                    end = int(end_s) if end_s else len(body) - 1
                end = min(end, len(body) - 1)
                if start > end:
                    raise ValueError
                headers["Content-Range"] = (
                    f"bytes {start}-{end}/{len(body)}")
                body = body[start:end + 1]
                status = 206
            except ValueError:
                return web.Response(status=416)
        else:
            status = 200
        if request.method == "HEAD":
            headers["Content-Length"] = str(len(body))
            return web.Response(status=status, headers=headers,
                                content_type=mime)
        return web.Response(status=status, body=body, headers=headers,
                            content_type=mime)

    _REPAIR_NEG_TTL = 10.0
    _REPAIR_MAX_INFLIGHT = 8

    def _repair_permitted(self, fid_str: str) -> bool:
        now = time.monotonic()
        if len(self._repair_neg) > 4096:
            self._repair_neg = {k: v for k, v in self._repair_neg.items()
                                if now - v < self._REPAIR_NEG_TTL}
        seen = self._repair_neg.get(fid_str)
        if seen is not None and now - seen < self._REPAIR_NEG_TTL:
            return False
        if self._repair_inflight >= self._REPAIR_MAX_INFLIGHT:
            return False
        return True

    async def _read_repair(self, fid: FileId):
        """Fetch a locally-missing needle from a replica, re-append it
        locally, and return it (None when no replica has it)."""
        from ..storage.needle import Needle as NeedleCls
        self._repair_inflight += 1
        try:
            with observe.span("volume.read_repair",
                              tags={"fid": str(fid)}):
                return await self._read_repair_inner(fid, NeedleCls)
        finally:
            self._repair_inflight -= 1

    async def _read_repair_inner(self, fid: FileId, NeedleCls):

        from ..utils.retry import BreakerOpen, shared_breaker
        breaker = shared_breaker()
        auth = (self.guard.sign_write(str(fid))
                if self.guard.signing_key else "")
        for url in await self._replica_urls(fid.volume_id):
            # unified failure discipline: a replica that keeps refusing
            # dials is skipped fast instead of paying a connect timeout
            # per missing needle
            try:
                breaker.check(url)
            except BreakerOpen:
                continue
            try:
                headers = ({"Authorization": f"BEARER {auth}"}
                           if auth else {})
                async with self._session.get(
                        f"http://{url}/admin/needle_raw",
                        params={"fid": str(fid)}, headers=headers,
                        timeout=aiohttp.ClientTimeout(total=10)) as r:
                    if r.status != 200:
                        breaker.record_success(url)  # host is alive
                        continue
                    raw = await r.read()
                breaker.record_success(url)
                v = self.store.find_volume(fid.volume_id)
                if v is None:
                    return None
                n = NeedleCls.from_bytes(raw, v.version)
                await asyncio.get_event_loop().run_in_executor(
                    None, lambda: v.write_needle(
                        n, preserve_append_at_ns=True))
                log.info("read-repaired needle %s from %s", fid, url)
                self.metrics.count("read_repair")
                return n
            except Exception as e:
                if isinstance(e, (aiohttp.ClientConnectionError, OSError,
                                  asyncio.TimeoutError)):
                    breaker.record_failure(url)
                log.warning("read repair of %s from %s failed: %s",
                            fid, url, e)
        self._repair_neg[str(fid)] = time.monotonic()
        return None

    async def admin_needle_raw(self, request: web.Request) -> web.Response:
        """Raw needle record bytes for peer read-repair. With a signing
        key configured the peer must present a write or read JWT for the
        fid — this endpoint returns needle content, so it enforces the
        same token regime as the data path."""
        try:
            fid = FileId.parse(request.query["fid"])
            token = token_from_request(request.headers, request.query)
            canonical = str(fid)
            # With any key configured, at least one configured regime must
            # affirmatively validate the token. verify_* returns None both
            # on success AND when its own key is unconfigured, so an
            # "all regimes failed" check would silently pass whenever one
            # key is absent.
            if self.guard.signing_key or self.guard.read_signing_key:
                ok = (self.guard.signing_key and
                      not self.guard.verify_write(token, canonical)) or \
                     (self.guard.read_signing_key and
                      not self.guard.verify_read(token, canonical))
                if not ok:
                    return web.json_response({"error": "unauthorized"},
                                             status=401)
            v = self.store.find_volume(fid.volume_id)
            if v is None:
                return web.json_response({"error": "no volume"}, status=404)
            n = await asyncio.get_event_loop().run_in_executor(
                None, lambda: v.read_needle(fid.key, cookie=fid.cookie))
            return web.Response(body=n.to_bytes(v.version),
                                content_type="application/octet-stream")
        except (NeedleNotFound, NeedleDeleted, KeyError, ValueError) as e:
            return web.json_response({"error": str(e)}, status=404)

    async def _lookup_replica(self, vid: int) -> Optional[str]:
        try:
            async with self._session.get(
                    f"http://{self.master_url}/dir/lookup",
                    params={"volumeId": str(vid)}) as r:
                if r.status != 200:
                    return None
                body = await r.json()
                locs = body.get("locations", [])
                return locs[0]["url"] if locs else None
        except Exception:
            return None

    async def _write(self, request: web.Request, fid: FileId) -> web.Response:
        """PostHandler + ReplicatedWrite (volume_server_handlers_write.go:19,
        weed/topology/store_replicate.go:21-161)."""
        self.metrics.count("write")
        try:
            if await faults.fire_async("volume.write"):
                return web.json_response({"error": "injected drop"},
                                         status=503)
        except faults.FaultError as e:
            return web.json_response({"error": str(e)}, status=500)
        n = Needle(cookie=fid.cookie, id=fid.key)
        # raw header compare, NOT request.content_type: that property (and
        # request.multipart()) routes through email.parser — ~40% of write
        # CPU at 1KB payloads. Single-part uploads (the overwhelming case)
        # parse with fast_multipart; anything irregular falls back.
        raw_ct = request.headers.get("Content-Type", "")
        filename, ctype = "", ""
        already_gzipped = False
        if raw_ct[:10].lower().startswith("multipart/"):  # MIME types are case-insensitive
            body = await request.read()
            part = fast_multipart.parse_single_part(body, raw_ct)
            if part is None:
                # irregular shape (multi-part, escaped quoting, base64
                # parts): full mime parse of the buffered body
                msg = email.parser.BytesParser().parsebytes(
                    b"Content-Type: " + raw_ct.encode("utf-8", "replace")
                    + b"\r\n\r\n" + body)
                subs = msg.get_payload()
                if not msg.is_multipart() or not subs:
                    return web.json_response(
                        {"error": "empty multipart body"}, status=400)
                first = subs[0]
                part = fast_multipart.Part(
                    first.get_payload(decode=True) or b"",
                    first.get_filename() or "",
                    first.get("Content-Type", ""),
                    first.get("Content-Encoding", ""))
            n.data = part.data
            filename = part.filename
            if filename:
                n.set_flag(FLAG_HAS_NAME)
                n.name = filename.encode()[:255]
            ctype = part.content_type
            if ctype and ctype != "application/octet-stream":
                n.set_flag(FLAG_HAS_MIME)
                n.mime = ctype.encode()[:255]
            already_gzipped = part.content_encoding == "gzip"
        else:
            n.data = await request.read()
            already_gzipped = request.headers.get(
                "Content-Encoding", "") == "gzip"
        # write-path compression (needle_parse_upload.go via
        # util/compression.go): client-gzipped payloads keep the flag;
        # compressable content gets gzipped when it actually shrinks.
        # ?compress=false opts out (e.g. filer-ciphered chunks).
        # The Content-Encoding header alone is NOT trusted: aiohttp
        # auto-inflates gzip request bodies on the raw path, so the flag is
        # only set when the bytes really are a gzip stream.
        if already_gzipped and compression.is_gzipped(n.data):
            n.set_flag(FLAG_IS_COMPRESSED)
        elif request.query.get("compress") != "false":
            ext = os.path.splitext(filename)[1] if filename else ""
            payload, compressed = compression.maybe_compress(
                n.data, ext, ctype)
            if compressed:
                n.data = payload
                n.set_flag(FLAG_IS_COMPRESSED)
        if len(n.data) > 32 * 1024 * 1024:
            return web.json_response({"error": "entry too large"}, status=413)
        ttl_s = request.query.get("ttl", "")
        if ttl_s:
            n.set_flag(FLAG_HAS_TTL)
            n.ttl = t.TTL.parse(ttl_s)
        n.set_flag(FLAG_HAS_LAST_MODIFIED)
        n.last_modified = int(time.time())

        with self.metrics.timed("write"), \
                observe.span("volume.write", tags={"fid": str(fid)}):
            try:
                _, size, unchanged = await self._batcher.write(
                    fid.volume_id, n)
            except KeyError:
                return web.json_response({"error": "volume not found"},
                                         status=404)
            except VolumeReadOnly as e:
                return web.json_response({"error": str(e)}, status=409)
            except ValueError as e:
                return web.json_response({"error": str(e)}, status=409)
        self.heat.record_write(fid.volume_id)

        if request.query.get("type") != "replicate":
            with observe.span("volume.replicate", tags={"fid": str(fid)}):
                ok = await self._replicate(request, fid, n)
            if not ok:
                return web.json_response(
                    {"error": "replication failed"}, status=500)
        return web.json_response({"name": (n.name or b"").decode("utf-8",
                                                                 "replace"),
                                  "size": len(n.data),
                                  "eTag": n.etag(),
                                  "unchanged": unchanged}, status=201)

    async def _replicate(self, request: web.Request, fid: FileId,
                         n: Needle) -> bool:
        try:
            if await faults.fire_async("volume.replicate"):
                # injected drop: fan-out silently skipped — exactly the
                # lost-replica divergence read-repair must later heal
                return True
        except faults.FaultError:
            return False
        replicas = await self._replica_urls(fid.volume_id)
        if not replicas:
            return True


        def body_for_replica() -> tuple[bytes, str]:
            # raw multipart so name/mime survive on the replica and its
            # needle bytes match the primary's; already-compressed payloads
            # carry Content-Encoding so the replica sets the compressed
            # flag instead of re-compressing/mis-flagging
            boundary = uuid.uuid4().hex
            name = (n.name.decode("utf-8", "replace")
                    if n.has(FLAG_HAS_NAME) else "file")
            ctype = (n.mime.decode("utf-8", "replace")
                     if n.has(FLAG_HAS_MIME) else "application/octet-stream")
            head = (f"--{boundary}\r\n"
                    f'Content-Disposition: form-data; name="file"; '
                    f'filename="{name}"\r\n'
                    f"Content-Type: {ctype}\r\n")
            if n.is_compressed:
                head += "Content-Encoding: gzip\r\n"
            body = head.encode() + b"\r\n" + n.data + \
                f"\r\n--{boundary}--\r\n".encode()
            return body, boundary

        # forward the caller's write jwt (header or query form) so the peer's
        # guard admits the replicated write (weed/topology/store_replicate.go
        # fans the original request out, jwt included)
        fwd = {k: v for k, v in request.query.items() if k == "ttl"}
        token = token_from_request(request.headers, request.query)
        if token:
            fwd["jwt"] = token
        payload, boundary = body_for_replica()
        results = await asyncio.gather(
            *[self._session.post(
                f"http://{url}/{fid}",
                params={"type": "replicate", **fwd},
                data=payload,
                headers={"Content-Type":
                         f"multipart/form-data; boundary={boundary}"})
              for url in replicas], return_exceptions=True)
        ok = True
        for url, res in zip(replicas, results):
            if isinstance(res, Exception):
                log.warning("replicate %s to %s failed: %s", fid, url, res)
                ok = False
            else:
                if res.status >= 300:
                    ok = False
                res.release()
        return ok

    async def _replica_urls(self, vid: int) -> list[str]:
        # short-TTL cache: the replicated-write fan-out otherwise pays a
        # master lookup per request (getWritableRemoteReplications caches
        # the same way, weed/topology/store_replicate.go:163)
        cached = self._replica_cache.get(vid)
        if cached and time.monotonic() - cached[1] < 10.0:
            return cached[0]
        try:
            async with self._session.get(
                    f"http://{self.master_url}/dir/lookup",
                    params={"volumeId": str(vid)}) as r:
                if r.status != 200:
                    return []
                body = await r.json()
                urls = [loc["url"] for loc in body.get("locations", [])
                        if loc["url"] != self.url]
                self._replica_cache[vid] = (urls, time.monotonic())
                return urls
        except Exception:
            return []

    async def _delete(self, request: web.Request, fid: FileId) -> web.Response:
        self.metrics.count("delete")
        ev = self.store.find_ec_volume(fid.volume_id)
        if ev is not None and self.store.find_volume(fid.volume_id) is None:
            # EC delete: local tombstone + propagate to all shard holders
            try:
                self.store.ec_blob_delete(fid.volume_id, fid.key)
            except KeyError:
                return web.json_response({"error": "not found"}, status=404)
            self.heat.record_write(fid.volume_id)
            if request.query.get("type") != "replicate":
                await self._propagate_ec_delete(fid)
            return web.json_response({"size": 0})
        n = Needle(cookie=fid.cookie, id=fid.key)
        try:
            size = await asyncio.get_event_loop().run_in_executor(
                None, lambda: self.store.delete_needle(fid.volume_id, n))
        except KeyError:
            return web.json_response({"error": "volume not found"},
                                     status=404)
        self.heat.record_write(fid.volume_id)
        if request.query.get("type") != "replicate":
            replicas = await self._replica_urls(fid.volume_id)
            for url in replicas:
                try:
                    fwd = {}
                    token = token_from_request(request.headers, request.query)
                    if token:
                        fwd["jwt"] = token
                    async with self._session.delete(
                            f"http://{url}/{fid}",
                            params={"type": "replicate", **fwd}) as r:
                        pass
                except Exception as e:
                    log.warning("delete replicate to %s: %s", url, e)
        return web.json_response({"size": size})

    async def _propagate_ec_delete(self, fid: FileId) -> None:
        try:
            async with self._session.get(
                    f"http://{self.master_url}/col/lookup/ec",
                    params={"volumeId": str(fid.volume_id)}) as r:
                if r.status != 200:
                    return
                shards = (await r.json()).get("shards", {})
        except Exception:
            return
        urls = {u for us in shards.values() for u in us if u != self.url}
        for url in urls:
            try:
                async with self._session.delete(
                        f"http://{url}/{fid}",
                        params={"type": "replicate"}) as r:
                    pass
            except Exception as e:
                log.warning("ec delete propagate to %s: %s", url, e)

    # --- admin ---
    async def admin_assign_volume(self, request: web.Request) -> web.Response:
        body = await request.json()
        ctx = self.shard_ctx
        if ctx is not None and ctx.shards > 1:
            # new volumes land on their modulo owner so the fleet's
            # capacity actually spreads; forward if that's not me
            owner = ctx.owner(int(body["volume_id"]))
            if owner != ctx.index:
                m = ctx.read_meta(owner)
                if m["alive"] and m["internal_port"]:
                    try:
                        async with self._session.post(
                                f"http://127.0.0.1:{m['internal_port']}"
                                "/admin/assign_volume", json=body,
                                headers={"X-Swfs-Internal":
                                         self._internal_token},
                                timeout=aiohttp.ClientTimeout(
                                    total=15)) as r:
                            return web.json_response(await r.json(),
                                                     status=r.status)
                    except Exception as e:
                        log.warning("assign forward to shard %d failed:"
                                    " %s; allocating locally", owner, e)
                # owner dead/unpublished: allocate locally — capacity
                # beats placement purity, and routing follows the
                # published volume lists anyway
        try:
            self.store.add_volume(
                int(body["volume_id"]), body.get("collection", ""),
                body.get("replication", "000"), body.get("ttl", ""))
        except (ValueError, RuntimeError) as e:
            return web.json_response({"error": str(e)}, status=409)
        try:
            await self.send_heartbeat()
        except Exception as e:
            # the allocation itself succeeded; the periodic heartbeat will
            # report it shortly
            log.warning("post-allocate heartbeat failed: %s", e)
        return web.json_response({"ok": True})

    async def admin_vacuum(self, request: web.Request) -> web.Response:
        body = await request.json()
        vid = int(body["volume_id"])
        v = self.store.find_volume(vid)
        if v is None:
            return web.json_response({"error": "volume not found"},
                                     status=404)
        garbage = v.garbage_level()
        await asyncio.get_event_loop().run_in_executor(None, v.compact)
        return web.json_response({"ok": True, "garbage_level": garbage})

    async def admin_vacuum_check(self, request: web.Request) -> web.Response:
        """VacuumVolumeCheck (weed/server/volume_grpc_vacuum.go): report the
        garbage ratio so the master can decide whether to compact."""
        try:
            garbage = self.store.vacuum_check(
                int(request.query["volume_id"]))
        except KeyError:
            return web.json_response({"error": "volume not found"},
                                     status=404)
        return web.json_response({"garbage_level": garbage})

    async def admin_vacuum_compact(self,
                                   request: web.Request) -> web.Response:
        body = await request.json()
        vid = int(body["volume_id"])
        rate = int(body.get("compaction_byte_per_second", 0))
        try:
            await asyncio.get_event_loop().run_in_executor(
                None, lambda: self.store.vacuum_compact(vid, rate))
        except KeyError:
            return web.json_response({"error": "volume not found"},
                                     status=404)
        except RuntimeError as e:
            return web.json_response({"error": str(e)}, status=409)
        return web.json_response({"ok": True})

    async def admin_vacuum_commit(self,
                                  request: web.Request) -> web.Response:
        body = await request.json()
        vid = int(body["volume_id"])
        try:
            await asyncio.get_event_loop().run_in_executor(
                None, lambda: self.store.vacuum_commit(vid))
        except KeyError:
            return web.json_response({"error": "volume not found"},
                                     status=404)
        except RuntimeError as e:
            return web.json_response({"error": str(e)}, status=409)
        return web.json_response({"ok": True})

    async def admin_vacuum_cleanup(self,
                                   request: web.Request) -> web.Response:
        body = await request.json()
        try:
            self.store.vacuum_cleanup(int(body["volume_id"]))
        except KeyError:
            return web.json_response({"error": "volume not found"},
                                     status=404)
        return web.json_response({"ok": True})

    async def admin_volume_delete(self, request: web.Request) -> web.Response:
        body = await request.json()
        ok = self.store.delete_volume(int(body["volume_id"]))
        await self.send_heartbeat()
        return web.json_response({"ok": ok})

    async def admin_readonly(self, request: web.Request) -> web.Response:
        body = await request.json()
        ok = self.store.mark_readonly(int(body["volume_id"]),
                                      body.get("read_only", True))
        return web.json_response({"ok": ok})

    async def admin_volume_mount(self, request: web.Request) -> web.Response:
        """VolumeMount (weed/server/volume_grpc_admin.go)."""
        body = await request.json()
        try:
            v = await asyncio.get_event_loop().run_in_executor(
                None, lambda: self.store.mount_volume(
                    int(body["volume_id"]), body.get("collection", "")))
        except (KeyError, ValueError) as e:
            return web.json_response({"error": str(e)}, status=409)
        await self.send_heartbeat()
        return web.json_response({"ok": True,
                                  "file_count": v.file_count()})

    async def admin_volume_unmount(self,
                                   request: web.Request) -> web.Response:
        """VolumeUnmount: stop serving, keep files."""
        body = await request.json()
        ok = self.store.unmount_volume(int(body["volume_id"]))
        await self.send_heartbeat()
        return web.json_response({"ok": ok})

    async def admin_volume_configure(self,
                                     request: web.Request) -> web.Response:
        """VolumeConfigure: rewrite superblock replication in place."""
        body = await request.json()
        try:
            self.store.configure_replication(int(body["volume_id"]),
                                             body["replication"])
        except (KeyError, ValueError) as e:
            return web.json_response({"error": str(e)}, status=400)
        await self.send_heartbeat()
        return web.json_response({"ok": True})

    async def admin_needle_ids(self, request: web.Request) -> web.Response:
        """Live needle inventory for fsck (command_volume_fsck.go collects
        the same per-volume id set)."""
        try:
            vid = int(request.query["volume_id"])
            entries = await asyncio.get_event_loop().run_in_executor(
                None, lambda: self.store.needle_ids(vid))
        except KeyError as e:
            return web.json_response({"error": str(e)}, status=404)
        return web.json_response({"volume_id": vid,
                                  "needles": [[k, s] for k, s in entries]})

    async def admin_tier_upload(self, request: web.Request) -> web.Response:
        """Move a sealed volume's .dat to an object-store tier
        (VolumeTierMoveDatToRemote, volume_grpc_tier_upload.go:14)."""
        body = await request.json()
        try:
            info = await asyncio.get_event_loop().run_in_executor(
                None, lambda: self.store.tier_upload(
                    int(body["volume_id"]), body["backend"],
                    keep_local=body.get("keep_local", False)))
        except (KeyError, ValueError) as e:
            return web.json_response({"error": str(e)}, status=400)
        except Exception as e:
            # upload failure (unreachable store etc.): volume already
            # un-sealed by the store's rollback
            return web.json_response({"error": str(e)}, status=502)
        await self.send_heartbeat()
        return web.json_response({"ok": True, "info": info})

    async def admin_tier_download(self,
                                  request: web.Request) -> web.Response:
        """Bring a tiered .dat back local (VolumeTierMoveDatFromRemote)."""
        body = await request.json()
        try:
            out = await asyncio.get_event_loop().run_in_executor(
                None, lambda: self.store.tier_download(
                    int(body["volume_id"])))
        except (KeyError, ValueError) as e:
            return web.json_response({"error": str(e)}, status=400)
        await self.send_heartbeat()
        return web.json_response({"ok": True, **out})

    async def admin_ec_generate(self, request: web.Request) -> web.Response:
        """One volume (volume_id) or a WINDOW (volume_ids): the batched
        form streams every volume through one governed executable
        back-to-back (store.ec_generate_many), which is how the
        lifecycle daemon's encode queue amortizes compiles + program
        loads across a whole batch of sealed volumes. ``"fused": true``
        (or the /admin/ec/fused route) runs the one-pass warm-down
        instead: compaction + gzip + encode + digests fused
        (store.ec_fused_generate), so the shard set holds the COMPACTED
        volume and no separate vacuum precedes the encode."""
        body = await request.json()
        return await self._ec_generate_impl(
            body, fused=bool(body.get("fused", False)))

    async def admin_ec_fused(self, request: web.Request) -> web.Response:
        """The one-pass warm-down route (always fused)."""
        return await self._ec_generate_impl(await request.json(),
                                            fused=True)

    async def _ec_generate_impl(self, body: dict,
                                fused: bool) -> web.Response:
        vids = ([int(v) for v in body["volume_ids"]]
                if "volume_ids" in body else [int(body["volume_id"])])
        if not vids:
            return web.json_response({"error": "empty volume_ids"},
                                     status=400)
        gen_one = (self.store.ec_fused_generate if fused
                   else self.store.ec_generate)
        gen_many = (self.store.ec_fused_generate_many if fused
                    else self.store.ec_generate_many)
        tctx = observe.capture()
        try:
            if len(vids) == 1:
                shards = await asyncio.get_event_loop().run_in_executor(
                    None, lambda: observe.run_with(
                        tctx, gen_one, vids[0]))
                per_volume = {str(vids[0]): shards}
            else:
                per_volume_raw = await asyncio.get_event_loop() \
                    .run_in_executor(
                        None, lambda: observe.run_with(
                            tctx, gen_many, vids))
                per_volume = {str(k): v for k, v in per_volume_raw.items()}
                shards = per_volume.get(str(vids[0]), [])
        except KeyError as e:
            return web.json_response({"error": str(e)}, status=404)
        return web.json_response({"ok": True, "shards": shards,
                                  "fused": fused, "volumes": per_volume})

    async def admin_ec_mount(self, request: web.Request) -> web.Response:
        body = await request.json()
        try:
            mounted = self.store.ec_mount(
                int(body["volume_id"]), body.get("collection", ""),
                [int(s) for s in body["shard_ids"]])
        except (KeyError, FileNotFoundError) as e:
            return web.json_response({"error": str(e)}, status=404)
        await self.send_heartbeat()
        return web.json_response({"ok": True, "mounted": mounted})

    async def admin_ec_unmount(self, request: web.Request) -> web.Response:
        body = await request.json()
        removed = self.store.ec_unmount(int(body["volume_id"]),
                                        [int(s) for s in body["shard_ids"]])
        await self.send_heartbeat()
        return web.json_response({"ok": True, "unmounted": removed})

    async def admin_ec_rebuild(self, request: web.Request) -> web.Response:
        body = await request.json()
        tctx = observe.capture()
        try:
            rebuilt = await asyncio.get_event_loop().run_in_executor(
                None, lambda: observe.run_with(
                    tctx, self.store.ec_rebuild,
                    int(body["volume_id"]), body.get("collection", "")))
        except (KeyError, ValueError) as e:
            return web.json_response({"error": str(e)}, status=409)
        return web.json_response({"ok": True, "rebuilt": rebuilt})

    async def admin_ec_copy(self, request: web.Request) -> web.Response:
        """Pull shard files from a source server (VolumeEcShardsCopy,
        volume_grpc_erasure_coding.go:104 — pull model like the reference)."""
        body = await request.json()
        vid = int(body["volume_id"])
        collection = body.get("collection", "")
        if not safe_collection(collection):
            return web.json_response({"error": "bad collection"},
                                     status=400)
        shard_ids = [int(s) for s in body["shard_ids"]]
        source = body["source"]
        copy_ecx = body.get("copy_ecx_file", False)
        from .. import ec as ec_mod
        loc = self.store.locations[0]
        prefix = f"{collection}_" if collection else ""
        base = os.path.join(loc.directory, f"{prefix}{vid}")
        try:
            exts = [ec_mod.to_ext(sid) for sid in shard_ids]
            # a server that has the volume mounted has its index and
            # keeps it: its own tombstones and journal do not give way
            # to the giver's, and the .ecx it has mapped is not
            # rewritten under its readers (a truncated mapping is a
            # SIGBUS at the next probe)
            if copy_ecx and self.store.find_ec_volume(vid) is None:
                exts += [".ecx", ".ecj", ".ecm"]
            for ext in exts:
                async with self._session.get(
                        f"http://{source}/admin/file_copy",
                        params={"volume_id": str(vid),
                                "collection": collection,
                                "ext": ext}) as r:
                    if r.status == 404 and ext in (".ecj", ".ecm"):
                        continue  # delete journal / layout marker optional
                    if r.status != 200:
                        return web.json_response(
                            {"error": f"copy {ext} from {source}: "
                             f"{r.status}"}, status=502)
                    with open(base + ext, "wb") as f:
                        async for chunk in r.content.iter_chunked(1 << 20):
                            f.write(chunk)
        except aiohttp.ClientError as e:
            return web.json_response({"error": str(e)}, status=502)
        return web.json_response({"ok": True})

    async def admin_ec_delete_shards(self, request: web.Request
                                     ) -> web.Response:
        body = await request.json()
        self.store.ec_delete_shards(int(body["volume_id"]),
                                    body.get("collection", ""),
                                    [int(s) for s in body["shard_ids"]])
        await self.send_heartbeat()
        return web.json_response({"ok": True})

    async def admin_ec_blob_delete(self, request: web.Request) -> web.Response:
        body = await request.json()
        try:
            self.store.ec_blob_delete(int(body["volume_id"]),
                                      int(body["needle_id"]))
        except KeyError as e:
            return web.json_response({"error": str(e)}, status=404)
        return web.json_response({"ok": True})

    async def admin_ec_to_volume(self, request: web.Request) -> web.Response:
        body = await request.json()
        try:
            await asyncio.get_event_loop().run_in_executor(
                None, lambda: self.store.ec_to_volume(
                    int(body["volume_id"]), body.get("collection", "")))
        except (KeyError, FileNotFoundError) as e:
            return web.json_response({"error": str(e)}, status=404)
        await self.send_heartbeat()
        return web.json_response({"ok": True})

    async def admin_ec_shard_read(self, request: web.Request) -> web.Response:
        q = request.query
        try:
            if await faults.fire_async("ec.shard_read"):
                return web.json_response({"error": "injected drop"},
                                         status=404)
            data = self.store.ec_shard_read(
                int(q["volume"]), int(q["shard"]),
                int(q.get("offset", 0)), int(q["size"]))
        except faults.FaultError as e:
            return web.json_response({"error": str(e)}, status=500)
        except KeyError as e:
            return web.json_response({"error": str(e)}, status=404)
        return web.Response(body=faults.corrupt("ec.shard_read", data),
                            content_type="application/octet-stream")

    # shard-location freshness tiers (store_ec.go:221-262): a missing
    # shard re-polls the master after 11s, a known one after 7m; a total
    # read miss forces an immediate refresh (see _make_shard_reader)
    _SHARD_LOC_MISSING_TTL = 11.0
    _SHARD_LOC_KNOWN_TTL = 7 * 60.0

    def _shard_locations(self, vid: int, shard_id: int,
                         force: bool = False) -> list[str]:
        """Tiered-TTL cache of vid -> shard -> holder urls."""
        import json as _json
        import urllib.request
        now = time.monotonic()
        cached = self._shard_loc_cache.get(vid)
        if cached is not None and not force:
            shards, fetched = cached
            age = now - fetched
            have = str(shard_id) in shards
            if age < self._SHARD_LOC_MISSING_TTL or \
                    (have and age < self._SHARD_LOC_KNOWN_TTL):
                return [u for u in shards.get(str(shard_id), [])
                        if u != self.url]
        try:
            req = urllib.request.Request(
                f"http://{self.master_url}/col/lookup/ec?volumeId={vid}",
                headers=_retry.inject_deadline({}))
            with urllib.request.urlopen(
                    req, timeout=_retry.cap_timeout(5)) as r:
                shards = _json.load(r).get("shards", {})
            self._shard_loc_cache[vid] = (shards, now)
        except Exception as e:
            log.warning("ec shard lookup for %d failed: %s", vid, e)
            shards = cached[0] if cached else {}
        urls = [u for u in shards.get(str(shard_id), []) if u != self.url]
        # one blocking round trip to the master, and what it was for
        self.metrics.count("ec_shard_location_lookups", labels={
            "result": "holder" if urls else "none"})
        return urls

    def _make_shard_reader(self, ev):
        """Shard reader for non-local shards, used by the EC read path
        (store_ec.go:282-320). Prefers the peer's VolumeEcShardRead gRPC
        stream (volume_grpc_erasure_coding.go:270-328) and falls back to
        its /admin/ec/shard_read HTTP analog for peers running without a
        gRPC port. Synchronous (runs in executor threads); a total miss
        forces one location-cache refresh so reads survive shard moves."""
        import urllib.request

        def fetch_grpc(url: str, shard_id: int, offset: int,
                       size: int) -> Optional[bytes]:
            # peers whose +10000 gRPC port is closed/filtered go HTTP-first
            # for a while instead of paying the deadline on every shard
            if time.time() < self._peer_grpc_dead.get(url, 0):
                return None
            # channels are thread-safe and reconnect internally: one per
            # peer with the one call a read makes on it, prepared once
            # and not once per fetch (setdefault so racing executor
            # threads don't leak a loser channel)
            entry = self._peer_grpc_channels.get(url)
            result = _CALL_REUSED
            if entry is None:
                ch = dial(grpc_address(url))
                new = (ch, VolumeServerStub(ch).VolumeEcShardRead)
                entry = self._peer_grpc_channels.setdefault(url, new)
                if entry is new:
                    result = _CALL_BUILT
                else:
                    ch.close()
            self.metrics.count("ec_peer_call", labels=result)
            _, shard_read = entry
            try:
                buf = bytearray()
                for chunk in shard_read(
                        vpb.EcShardReadRequest(
                            volume_id=ev.vid, shard_id=shard_id,
                            offset=offset, size=size),
                        timeout=5):
                    if chunk.error:
                        return None
                    buf += chunk.data
                    if chunk.is_last:
                        break
                return bytes(buf) if len(buf) == size else None
            except grpc.RpcError as e:
                if e.code() in (grpc.StatusCode.UNAVAILABLE,
                                grpc.StatusCode.DEADLINE_EXCEEDED):
                    self._peer_grpc_dead[url] = time.time() + 60.0
                return None

        def fetch_http(url: str, shard_id: int, offset: int,
                       size: int) -> Optional[bytes]:
            try:
                from ..cache import shared_pool
                r = shared_pool().request(
                    "GET",
                    f"http://{url}/admin/ec/shard_read?volume="
                    f"{ev.vid}&shard={shard_id}&offset={offset}"
                    f"&size={size}", timeout=10)
                if r.status != 200:
                    return None
                return r.data if len(r.data) == size else None
            except Exception:
                return None

        def fetch(url: str, shard_id: int, offset: int,
                  size: int) -> Optional[bytes]:
            """One interval from one peer: the stage `ec.get.remote_read`
            (record form: a survivor's fetch ends on a pool thread),
            within `ec.get.survivors` or `ec.get.peer_fetch`."""
            start_s, t0 = time.time(), time.perf_counter()
            via = "grpc"
            data = fetch_grpc(url, shard_id, offset, size)
            if data is None:
                via = "http"
                data = fetch_http(url, shard_id, offset, size)
            observe.record_span(
                "ec.get.remote_read", None, int(start_s * 1e6),
                int((time.perf_counter() - t0) * 1e6))
            if data is not None:
                self.metrics.count("ec_remote_shard_reads",
                                   labels={"via": via})
                self.metrics.count("ec_remote_shard_read_bytes",
                                   value=size)
            return data

        def read(shard_id: int, offset: int, size: int) -> Optional[bytes]:
            for force in (False, True):
                for url in self._shard_locations(ev.vid, shard_id,
                                                 force=force):
                    data = fetch(url, shard_id, offset, size)
                    if data is not None:
                        return data
            return None

        return read

    # --- EC scrubber: bit-rot -> self-heal, closing the repair loop ---

    async def _scrub_loop(self) -> None:
        while True:
            await asyncio.sleep(self.scrub_interval_seconds)
            try:
                await self.scrub_pass()
            except asyncio.CancelledError:
                raise
            except Exception as e:
                log.warning("ec scrub pass failed: %s", e)

    async def scrub_pass(self, throttle_seconds: float = 0.05) -> dict:
        """Verify every locally mounted EC shard against the digest
        stamped into its .ecm at encode time (ec/pipeline.py). Low
        priority by construction: each shard digests in an executor
        thread and the loop sleeps between shards, so serving traffic is
        never starved. Mismatches are reported to the master, whose
        repair daemon drops the rotten copy and schedules a targeted
        rebuild. Returns {vid: [bad shard ids]}."""
        from ..ec.pipeline import read_stamped_digests, shard_file_digest
        loop = asyncio.get_event_loop()
        bad_by_vid: dict[int, list[int]] = {}
        # scrub is background by definition: its report POST (and any
        # repair traffic it triggers) tags X-Seaweed-Priority: bg and
        # sheds first under overload
        _ptok = overload.set_priority(overload.CLASS_BG)
        try:
            with observe.span("volume.scrub"):
                for loc in self.store.locations:
                    for vid, ev in list(loc.ec_volumes.items()):
                        base = ev.base_file_name()
                        stamped = read_stamped_digests(base)
                        if not stamped:
                            continue
                        bad: list[int] = []
                        for sid in ev.shard_ids():
                            want = stamped.get(sid)
                            if want is None:
                                continue
                            try:
                                got = await loop.run_in_executor(
                                    None, lambda s=sid: int(
                                        shard_file_digest(base, [s])[0]))
                            except OSError:
                                continue  # shard unmounted/moved mid-scan
                            self.metrics.count("scrub_shards_checked")
                            if got != want:
                                bad.append(sid)
                                self.metrics.count("scrub_shards_bad")
                                log.warning(
                                    "scrub: shard %d of volume %d digest "
                                    "mismatch (%d != %d)", sid, vid, got,
                                    want)
                            await asyncio.sleep(throttle_seconds)
                        if bad:
                            bad_by_vid[vid] = bad
            for vid, bad in bad_by_vid.items():
                await self._report_bad_shards(vid, bad)
        finally:
            overload.reset_priority(_ptok)
        return bad_by_vid

    async def _report_bad_shards(self, vid: int, bad: list[int]) -> None:
        try:
            async with self._session.post(
                    f"http://{self.master_url}/ec/scrub_report",
                    json={"volume_id": vid, "url": self.url,
                          "bad_shards": bad},
                    timeout=aiohttp.ClientTimeout(total=10)) as r:
                await r.read()
        except Exception as e:
            log.warning("scrub report for volume %d failed: %s", vid, e)

    async def admin_ec_mesh_status(self,
                                   request: web.Request) -> web.Response:
        """This process's EC device view: which coder the store's name
        resolved to and on which device (`coder`, empty until a coder
        exists — asking initialises nothing), the configured
        WEED_EC_MESH_DEVICES, live devices, and the per-chip staging
        counters + governor gauges from the shared "ec" registry (the
        JSON twin of what /metrics exposes, for the ec.mesh.status shell
        command)."""
        from ..parallel.mesh_coder import mesh_status
        out = await asyncio.get_event_loop().run_in_executor(
            None, mesh_status)
        out["coder"] = self.store.coder_status()
        return web.json_response(out)

    async def admin_ec_scrub(self, request: web.Request) -> web.Response:
        """Run one scrub pass now (operators / chaos tests)."""
        body = {}
        if request.can_read_body:
            try:
                body = await request.json()
            except Exception:
                body = {}
        bad = await self.scrub_pass(
            throttle_seconds=float(body.get("throttle_seconds", 0.0)))
        return web.json_response(
            {"ok": True,
             "bad": {str(vid): sids for vid, sids in bad.items()}})

    async def admin_file_copy(self, request: web.Request) -> web.StreamResponse:
        """Stream a volume/shard file to a pulling peer (CopyFile,
        weed/server/volume_grpc_copy.go:24-281)."""
        q = request.query
        vid = int(q["volume_id"])
        collection = q.get("collection", "")
        ext = q["ext"]
        if not ext.startswith(".") or "/" in ext or ".." in ext \
                or not safe_collection(collection):
            return web.json_response({"error": "bad ext or collection"},
                                     status=400)
        prefix = f"{collection}_" if collection else ""
        for loc in self.store.locations:
            path = os.path.join(loc.directory, f"{prefix}{vid}{ext}")
            if os.path.exists(path):
                resp = web.StreamResponse()
                resp.headers["Content-Length"] = str(os.path.getsize(path))
                await resp.prepare(request)
                with open(path, "rb") as f:
                    while True:
                        chunk = f.read(1 << 20)
                        if not chunk:
                            break
                        await resp.write(chunk)
                await resp.write_eof()
                return resp
        return web.json_response({"error": "file not found"}, status=404)

    async def admin_tail(self, request: web.Request) -> web.StreamResponse:
        """Stream needle records appended after since_ns, length-framed
        (VolumeTailSender, weed/server/volume_grpc_tail.go:16-79).
        Frame: u32 big-endian record length + raw v3 needle record."""
        from ..storage import volume_backup
        q = request.query
        vid = int(q["volume_id"])
        since_ns = int(q.get("since_ns", 0))
        v = self.store.find_volume(vid)
        if v is None:
            return web.json_response({"error": "volume not found"},
                                     status=404)
        resp = web.StreamResponse()
        resp.headers["Content-Type"] = "application/octet-stream"
        await resp.prepare(request)
        loop = asyncio.get_event_loop()
        # pull records one at a time off the executor so a full-volume tail
        # streams in O(record) memory instead of materializing the volume
        it = volume_backup.iter_needles_since(v, since_ns)

        def next_record():
            try:
                n = next(it)
            except StopIteration:
                return None
            return n.to_bytes(v.version)

        while True:
            rec = await loop.run_in_executor(None, next_record)
            if rec is None:
                break
            await resp.write(len(rec).to_bytes(4, "big") + rec)
        await resp.write_eof()
        return resp

    async def admin_volume_copy(self, request: web.Request) -> web.Response:
        """Pull a whole volume (.dat + .idx) from a source server and mount
        it (VolumeCopy pull model, weed/server/volume_grpc_copy.go:24-151)."""
        body = await request.json()
        vid = int(body["volume_id"])
        collection = body.get("collection", "")
        if not safe_collection(collection):
            return web.json_response({"error": "bad collection"},
                                     status=400)
        source = body["source"]
        if self.store.find_volume(vid) is not None:
            return web.json_response({"error": "volume exists"}, status=409)
        open_locs = [l for l in self.store.locations
                     if len(l.volumes) < l.max_volume_count]
        if not open_locs:
            return web.json_response({"error": "no free slots"}, status=500)
        loc = min(open_locs, key=lambda l: len(l.volumes))
        prefix = f"{collection}_" if collection else ""
        base = os.path.join(loc.directory, f"{prefix}{vid}")
        try:
            for ext in (".dat", ".idx"):
                async with self._session.get(
                        f"http://{source}/admin/file_copy",
                        params={"volume_id": str(vid),
                                "collection": collection, "ext": ext}) as r:
                    if r.status != 200:
                        raise IOError(f"{source} has no {vid}{ext}")
                    with open(base + ext, "wb") as f:
                        async for chunk in r.content.iter_chunked(1 << 20):
                            f.write(chunk)
            from ..storage.volume import Volume
            v = await asyncio.get_event_loop().run_in_executor(
                None, lambda: Volume(loc.directory, collection, vid,
                     needle_map_kind=self.store.needle_map_kind))
            loc.volumes[vid] = v
        except Exception as e:
            for ext in (".dat", ".idx"):
                if os.path.exists(base + ext):
                    os.remove(base + ext)
            return web.json_response({"error": str(e)}, status=500)
        await self.send_heartbeat()
        return web.json_response({"ok": True,
                                  "file_count": v.file_count()})

    async def admin_batch_delete(self, request: web.Request) -> web.Response:
        """Delete many fids in one RPC (BatchDelete,
        weed/server/volume_grpc_batch_delete.go:15)."""
        body = await request.json()
        results = []
        for fid_str in body.get("fids", []):
            try:
                fid = FileId.parse(fid_str)
                n = Needle(cookie=fid.cookie, id=fid.key)
                size = await asyncio.get_event_loop().run_in_executor(
                    None,
                    lambda f=fid, nn=n: self.store.delete_needle(
                        f.volume_id, nn))
                results.append({"fid": fid_str, "size": size})
            except Exception as e:
                results.append({"fid": fid_str, "error": str(e)})
        return web.json_response({"results": results})

    async def admin_query(self, request: web.Request) -> web.StreamResponse:
        """S3-Select-lite over needle payloads (Query,
        weed/server/volume_grpc_query.go:13-69): filter + project JSON
        documents named by fid, emitting NDJSON."""
        from ..query import QueryFilter, query_json_lines
        body = await request.json()
        flt = None
        if body.get("filter"):
            f = body["filter"]
            flt = QueryFilter(f["field"], f.get("op", "="), f.get("value"))
        projections = body.get("projections") or None
        payloads = []
        for fid_str in body.get("fids", []):
            try:
                fid = FileId.parse(fid_str)
                n = self.store.read_needle(fid.volume_id, fid.key,
                                           cookie=fid.cookie)
                payloads.append(n.data)
            except Exception:
                continue
        resp = web.StreamResponse()
        resp.headers["Content-Type"] = "application/x-ndjson"
        await resp.prepare(request)
        for line in query_json_lines(payloads, flt, projections):
            await resp.write(line.encode() + b"\n")
        await resp.write_eof()
        return resp

    async def status(self, request: web.Request) -> web.Response:
        return web.json_response({"url": self.url, **self.store.status()})

    async def metrics_handler(self, request: web.Request) -> web.Response:
        # shared registries carry non-server subsystems hosted in this
        # process (the EC feed governor's operating point + stage model)
        text = metrics_mod.exposition(self.metrics, request)
        if self.shard_ctx is not None and self.shard_ctx.shards > 1:
            # whatever shard the LB's scrape landed on appends the
            # fleet-wide per-shard series from the shared segment, so
            # one node keeps looking like one node
            text += self.shard_ctx.metrics_lines()
        return web.Response(text=text, content_type="text/plain")

    async def status_ui(self, request: web.Request) -> web.Response:
        """Status page with volume + EC tables
        (weed/server/volume_server_ui/templates.go)."""
        from ..utils.status_ui import render_status
        st = self.store.status()
        volumes = [{
            "id": v.get("id"), "collection": v.get("collection") or "-",
            "size": v.get("size"), "files": v.get("file_count"),
            "deleted": v.get("delete_count"),
            "garbage bytes": v.get("deleted_bytes"),
            "replication": v.get("replica_placement"),
            "ttl": v.get("ttl") or "-",
            "version": v.get("version"),
            "read only": v.get("read_only", False),
        } for v in st.get("volumes", [])]
        ec = [{
            "volume": s.get("id"),
            "collection": s.get("collection") or "-",
            "shards": s.get("shard_ids"),
            "shard size": s.get("shard_size"),
        } for s in st.get("ec_shards", [])]
        disks = [{
            "directory": loc.directory,
            "volumes": len(loc.volumes),
            "ec volumes": len(loc.ec_volumes),
            "max": loc.max_volume_count,
        } for loc in self.store.locations]
        return web.Response(
            text=render_status(
                "seaweedfs-tpu volume server", {
                    "server": {"master": self.master_url,
                               "volumes": len(volumes),
                               "ec volumes": len(ec)},
                    "disks": disks,
                    "volumes": volumes,
                    "ec shards": ec,
                    "metrics": self.metrics.render(),
                }, subtitle=self.url),
            content_type="text/html")


async def run_volume_server(host: str, port: int, store: Store,
                            master_url: str, fastpath: bool = True,
                            **kwargs) -> web.AppRunner:
    """Public listener is the hand-rolled data-plane protocol
    (server/fastpath.py) with the aiohttp app on an internal loopback
    port for everything it proxies; fastpath=False (or env
    SEAWEEDFS_NO_FASTPATH) serves aiohttp directly on the public port."""
    if os.environ.get("SEAWEEDFS_NO_FASTPATH"):
        fastpath = False
    server = VolumeServer(store, master_url, url=f"{host}:{port}", **kwargs)
    runner = web.AppRunner(server.app, access_log=None)
    await runner.setup()
    tls = kwargs.get("tls")
    ssl_ctx = tls.server_ssl_context() if tls is not None else None
    ctx = server.shard_ctx
    sharding = ctx is not None and ctx.shards > 1
    internal_port = 0
    if fastpath:
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        internal_port = site._server.sockets[0].getsockname()[1]
        from .fastpath import start_fastpath
        server._fast_srv = await start_fastpath(
            server, host, port, internal_port, ssl_context=ssl_ctx,
            reuse_port=sharding)
    else:
        if sharding:
            log.warning("WEED_SERVE_SHARDS>1 without the fastpath: "
                        "cross-shard volume routing is unavailable")
        site = web.TCPSite(runner, host, port, ssl_context=ssl_ctx,
                           reuse_port=sharding or None)
        await site.start()
    if sharding:
        from . import sharded

        # the loopback app port is the fleet-visible address for
        # cross-shard proxying; publish it before the first tick so
        # siblings can route immediately, and start this shard at an
        # even 1/N stripe until demand data accumulates
        ctx.publish_meta(internal_port=internal_port,
                         stripe_share=1.0 / ctx.shards)
        server.admission.apply_stripe(1.0 / ctx.shards)

        def _blob() -> dict:
            if ctx.index == 0 and ctx.child_pids:
                died = ctx.reap_children()
                if died:
                    log.warning("shard children died: %s", died)
            return {"heartbeat": server._hb_payload(include_heat=False)}

        server._stripe_task = asyncio.create_task(
            sharded.run_stripe_loop(ctx, server.admission, blob_fn=_blob))
        log.info("volume shard %d/%d on %s:%d (internal %d)",
                 ctx.index, ctx.shards, host, port, internal_port)
    log.info("volume server on %s:%d -> master %s", host, port, master_url)
    return runner
