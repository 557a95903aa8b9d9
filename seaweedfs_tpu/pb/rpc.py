"""Hand-rolled gRPC service plumbing for the cluster control plane.

The image ships grpcio + protoc but not grpc_tools, so instead of
generated *_pb2_grpc stubs each service is registered through gRPC's
generic-handler API and clients use multicallables with explicit
serializers — byte-identical on the wire to what generated stubs produce.

Four services (parity with the reference's 4 proto files):
  seaweedfs_tpu.master.Master             proto/master.proto        (13 RPCs)
  seaweedfs_tpu.volume.VolumeServer       proto/volume_server.proto (33 RPCs)
  seaweedfs_tpu.filer.SeaweedFiler        proto/filer.proto         (19 RPCs)
  seaweedfs_tpu.messaging.SeaweedMessaging proto/messaging.proto    (6 RPCs)

Port convention: gRPC listens on HTTP port + 10000
(weed/pb/grpc_client_server.go).
"""

from __future__ import annotations

import grpc

from .. import faults, observe
from ..utils import retry as retry_mod
from . import filer_pb2 as fpb
from . import master_pb2 as mpb
from . import messaging_pb2 as msgpb
from . import volume_server_pb2 as vpb

GRPC_PORT_OFFSET = 10000

MASTER_SERVICE = "seaweedfs_tpu.master.Master"
VOLUME_SERVICE = "seaweedfs_tpu.volume.VolumeServer"
FILER_SERVICE = "seaweedfs_tpu.filer.SeaweedFiler"
MESSAGING_SERVICE = "seaweedfs_tpu.messaging.SeaweedMessaging"

# back-compat alias (pre-round-3 callers)
SERVICE = MASTER_SERVICE


def grpc_address(http_url: str) -> str:
    """host:port -> host:(port+10000)."""
    host, _, port = http_url.rpartition(":")
    return f"{host}:{int(port) + GRPC_PORT_OFFSET}"


_tls_config = None
_tls_loaded = False


def set_tls_config(cfg) -> None:
    """Override the cluster TLS config (tests / embedded use)."""
    global _tls_config, _tls_loaded
    _tls_config = cfg
    _tls_loaded = True


def _tls():
    global _tls_config, _tls_loaded
    if not _tls_loaded:
        from ..security.tls import load_tls_config
        cfg = load_tls_config()
        _tls_config = cfg if cfg.enabled else None
        _tls_loaded = True
    return _tls_config


def dial(target: str):
    """Open a sync channel to a cluster gRPC endpoint, secured with the
    [tls] certs from security.toml when configured (the reference wraps
    every internal grpc link the same way, weed/security/tls.go)."""
    import grpc
    cfg = _tls()
    if cfg is not None:
        return grpc.secure_channel(target, cfg.grpc_channel_credentials())
    return grpc.insecure_channel(target)


def aio_dial(target: str):
    """grpc.aio variant of dial()."""
    import grpc
    cfg = _tls()
    if cfg is not None:
        return grpc.aio.secure_channel(target,
                                       cfg.grpc_channel_credentials())
    return grpc.aio.insecure_channel(target)


# --- service specs: name -> (kind, request type, response type) ---
# kind: uu unary-unary, us unary-stream, ss stream-stream

MASTER_SPEC = {
    "Assign": ("uu", mpb.AssignRequest, mpb.AssignResponse),
    "Lookup": ("uu", mpb.LookupRequest, mpb.LookupResponse),
    "LookupEc": ("uu", mpb.LookupEcRequest, mpb.LookupEcResponse),
    "Heartbeat": ("ss", mpb.HeartbeatRequest, mpb.HeartbeatResponse),
    "KeepConnected": ("us", mpb.KeepConnectedRequest,
                      mpb.VolumeLocationMessage),
    "ClusterStatus": ("uu", mpb.ClusterStatusRequest,
                      mpb.ClusterStatusResponse),
    "LeaseAdminToken": ("uu", mpb.LeaseAdminTokenRequest,
                        mpb.LeaseAdminTokenResponse),
    "ReleaseAdminToken": ("uu", mpb.ReleaseAdminTokenRequest,
                          mpb.ReleaseAdminTokenResponse),
    "VolumeList": ("uu", mpb.VolumeListRequest, mpb.VolumeListResponse),
    "Statistics": ("uu", mpb.StatisticsRequest, mpb.StatisticsResponse),
    "CollectionList": ("uu", mpb.CollectionListRequest,
                       mpb.CollectionListResponse),
    "CollectionDelete": ("uu", mpb.CollectionDeleteRequest,
                         mpb.CollectionDeleteResponse),
    "GetMasterConfiguration": ("uu", mpb.GetMasterConfigurationRequest,
                               mpb.GetMasterConfigurationResponse),
}

VOLUME_SPEC = {
    "BatchDelete": ("uu", vpb.BatchDeleteRequest, vpb.BatchDeleteResponse),
    "VolumeNeedleStatus": ("uu", vpb.NeedleStatusRequest,
                           vpb.NeedleStatusResponse),
    "VacuumVolumeCheck": ("uu", vpb.VolumeRef, vpb.VacuumCheckResponse),
    "VacuumVolumeCompact": ("uu", vpb.VacuumCompactRequest, vpb.Ok),
    "VacuumVolumeCommit": ("uu", vpb.VolumeRef, vpb.Ok),
    "VacuumVolumeCleanup": ("uu", vpb.VolumeRef, vpb.Ok),
    "AllocateVolume": ("uu", vpb.AllocateVolumeRequest, vpb.Ok),
    "VolumeMount": ("uu", vpb.VolumeRef, vpb.Ok),
    "VolumeUnmount": ("uu", vpb.VolumeRef, vpb.Ok),
    "VolumeDelete": ("uu", vpb.VolumeRef, vpb.Ok),
    "VolumeMarkReadonly": ("uu", vpb.VolumeRef, vpb.Ok),
    "VolumeMarkWritable": ("uu", vpb.VolumeRef, vpb.Ok),
    "VolumeConfigure": ("uu", vpb.VolumeConfigureRequest, vpb.Ok),
    "VolumeStatus": ("uu", vpb.VolumeRef, vpb.VolumeStatusResponse),
    "DeleteCollection": ("uu", vpb.DeleteCollectionRequest, vpb.Ok),
    "VolumeCopy": ("uu", vpb.VolumeCopyRequest, vpb.Ok),
    "ReadVolumeFileStatus": ("uu", vpb.VolumeRef,
                             vpb.VolumeFileStatusResponse),
    "CopyFile": ("us", vpb.CopyFileRequest, vpb.DataChunk),
    "VolumeTail": ("us", vpb.TailRequest, vpb.DataChunk),
    "VolumeTailSender": ("us", vpb.TailRequest, vpb.DataChunk),
    "VolumeTailReceiver": ("uu", vpb.TailReceiverRequest, vpb.Ok),
    "VolumeSyncStatus": ("uu", vpb.VolumeRef,
                         vpb.VolumeSyncStatusResponse),
    "VolumeIncrementalCopy": ("us", vpb.TailRequest, vpb.DataChunk),
    "VolumeEcShardsGenerate": ("uu", vpb.EcGenerateRequest, vpb.Ok),
    "VolumeEcShardsRebuild": ("uu", vpb.EcRebuildRequest,
                              vpb.EcRebuildResponse),
    "VolumeEcShardsCopy": ("uu", vpb.EcCopyRequest, vpb.Ok),
    "VolumeEcShardsDelete": ("uu", vpb.EcShardsRequest, vpb.Ok),
    "VolumeEcShardsMount": ("uu", vpb.EcShardsRequest, vpb.Ok),
    "VolumeEcShardsUnmount": ("uu", vpb.EcShardsRequest, vpb.Ok),
    "VolumeEcShardRead": ("us", vpb.EcShardReadRequest, vpb.DataChunk),
    "VolumeEcBlobDelete": ("uu", vpb.EcBlobDeleteRequest, vpb.Ok),
    "VolumeEcShardsToVolume": ("uu", vpb.VolumeRef, vpb.Ok),
    "VolumeTierMoveDatToRemote": ("uu", vpb.TierMoveRequest, vpb.Ok),
    "VolumeTierMoveDatFromRemote": ("uu", vpb.TierMoveRequest, vpb.Ok),
    "VolumeServerStatus": ("uu", vpb.Empty,
                           vpb.VolumeServerStatusResponse),
    "VolumeServerLeave": ("uu", vpb.Empty, vpb.Ok),
    "Query": ("us", vpb.QueryRequest, vpb.DataChunk),
}

FILER_SPEC = {
    "LookupDirectoryEntry": ("uu", fpb.LookupEntryRequest,
                             fpb.EntryResponse),
    "ListEntries": ("us", fpb.ListEntriesRequest, fpb.EntryResponse),
    "CreateEntry": ("uu", fpb.EntryRequest, fpb.Ok),
    "UpdateEntry": ("uu", fpb.EntryRequest, fpb.Ok),
    "AppendToEntry": ("uu", fpb.AppendToEntryRequest, fpb.Ok),
    "DeleteEntry": ("uu", fpb.DeleteEntryRequest, fpb.Ok),
    "AtomicRenameEntry": ("uu", fpb.RenameEntryRequest, fpb.Ok),
    "AssignVolume": ("uu", fpb.AssignVolumeRequest,
                     fpb.AssignVolumeResponse),
    "LookupVolume": ("uu", fpb.LookupVolumeRequest,
                     fpb.LookupVolumeResponse),
    "CollectionList": ("uu", fpb.Empty, fpb.CollectionListResponse),
    "DeleteCollection": ("uu", fpb.DeleteCollectionRequest, fpb.Ok),
    "Statistics": ("uu", fpb.StatisticsRequest, fpb.StatisticsResponse),
    "GetFilerConfiguration": ("uu", fpb.Empty,
                              fpb.FilerConfigurationResponse),
    "SubscribeMetadata": ("us", fpb.SubscribeMetadataRequest,
                          fpb.MetaEvent),
    "SubscribeLocalMetadata": ("us", fpb.SubscribeMetadataRequest,
                               fpb.MetaEvent),
    "KeepConnected": ("ss", fpb.KeepConnectedRequest,
                      fpb.KeepConnectedResponse),
    "LocateBroker": ("uu", fpb.LocateBrokerRequest,
                     fpb.LocateBrokerResponse),
    "KvGet": ("uu", fpb.KvRequest, fpb.KvResponse),
    "KvPut": ("uu", fpb.KvRequest, fpb.Ok),
}

_HANDLER_FACTORY = {
    "uu": grpc.unary_unary_rpc_method_handler,
    "us": grpc.unary_stream_rpc_method_handler,
    "ss": grpc.stream_stream_rpc_method_handler,
}


def peer_ip(context) -> str:
    """Remote IP from a ServicerContext peer string
    ("ipv4:1.2.3.4:56" / "ipv6:[::1]:56")."""
    peer = context.peer()
    if peer.startswith("ipv4:"):
        return peer[5:].rsplit(":", 1)[0]
    if peer.startswith("ipv6:"):
        return peer[5:].rsplit(":", 1)[0].strip("[]")
    return peer


def _trace_ctx_from(context, service: str,
                    instance: str) -> "observe.TraceCtx":
    """Build the span context from incoming x-seaweed-trace metadata (the
    gRPC twin of the X-Seaweed-Trace HTTP header)."""
    tid = parent = ""
    try:
        for k, v in (context.invocation_metadata() or ()):
            if k == observe.GRPC_TRACE_KEY:
                tid, parent = observe.parse_header(
                    v if isinstance(v, str) else v.decode())
                break
    except Exception:
        pass
    return observe.TraceCtx(tid or observe.new_id(), parent, service,
                            instance)


def _traced(method, kind: str, service: str, rpc_name: str,
            instance: str = ""):
    """Wrap a servicer method in a per-RPC root span so gRPC-plane work
    joins the same trace as the HTTP surfaces; slow RPCs log like slow
    HTTP requests."""
    name = f"grpc {rpc_name}"

    if kind in ("us", "ss"):
        # streams can live for hours (Heartbeat/KeepConnected): record the
        # span at close but never slow-log — lifetime is not latency
        async def stream_wrapper(request, context):
            with observe.Span(
                    name, ctx=_trace_ctx_from(context, service, instance)):
                async for item in method(request, context):
                    yield item
        return stream_wrapper

    async def unary_wrapper(request, context):
        sp = observe.Span(name,
                          ctx=_trace_ctx_from(context, service, instance))
        try:
            with sp:
                return await method(request, context)
        finally:
            observe.maybe_log_slow(sp)
    return unary_wrapper


def _faulted(method, kind: str, rpc_name: str):
    """Wrap a servicer method in a fault-point gate named
    ``rpc.<Method>`` — the gRPC planes' injection surface. drop aborts
    UNAVAILABLE (a vanished peer), error aborts INTERNAL."""
    point = f"rpc.{rpc_name.rsplit('/', 1)[-1]}"

    if kind in ("us", "ss"):
        async def stream_wrapper(request, context):
            try:
                dropped = await faults.fire_async(point)
            except faults.FaultError as e:
                await context.abort(grpc.StatusCode.INTERNAL, str(e))
            if dropped:
                await context.abort(grpc.StatusCode.UNAVAILABLE,
                                    "injected drop")
            async for item in method(request, context):
                yield item
        return stream_wrapper

    async def unary_wrapper(request, context):
        try:
            dropped = await faults.fire_async(point)
        except faults.FaultError as e:
            await context.abort(grpc.StatusCode.INTERNAL, str(e))
        if dropped:
            await context.abort(grpc.StatusCode.UNAVAILABLE,
                                "injected drop")
        return await method(request, context)
    return unary_wrapper


def _guarded(method, kind: str, guard):
    """Wrap a servicer method with the same IP-whitelist envelope the HTTP
    surface gets from guard_mw — without this, -whitelist deployments
    would 403 /admin/* over HTTP while serving the identical operations
    openly on port+10000 (the reference wraps its gRPC plane in the same
    security.toml whitelist/TLS envelope, weed/security/guard.go).

    `guard` may be a Guard or a zero-arg callable returning one — the
    callable form re-resolves per call, matching guard_mw's dynamic
    self.guard lookup (tests and admins swap guards on live servers)."""
    def _denied(context) -> bool:
        g = guard() if callable(guard) else guard
        return g is not None and not g.check_whitelist(peer_ip(context))

    if kind in ("us", "ss"):
        async def stream_wrapper(request, context):
            if _denied(context):
                await context.abort(grpc.StatusCode.PERMISSION_DENIED,
                                    "ip not allowed")
            async for item in method(request, context):
                yield item
        return stream_wrapper

    async def unary_wrapper(request, context):
        if _denied(context):
            await context.abort(grpc.StatusCode.PERMISSION_DENIED,
                                "ip not allowed")
        return await method(request, context)
    return unary_wrapper


def service_handler(service: str, spec: dict, servicer,
                    guard=None, trace_service: str = "",
                    trace_instance: str = "") -> grpc.GenericRpcHandler:
    """Bind a servicer object (async methods named like the RPCs) into a
    generic handler grpc.aio can serve. Methods the servicer doesn't
    implement are simply not registered (grpc returns UNIMPLEMENTED).
    With a guard, every RPC enforces its IP whitelist. Every RPC runs
    inside a trace span (tracing is outermost so denied calls still show
    up in /debug/trace with their abort)."""
    svc_label = trace_service or service.rsplit(".", 1)[-1].lower()
    handlers = {}
    for name, (kind, req, resp) in spec.items():
        method = getattr(servicer, name, None)
        if method is None:
            continue
        method = _faulted(method, kind, name)
        if guard is not None:
            method = _guarded(method, kind, guard)
        method = _traced(method, kind, svc_label, f"{service}/{name}",
                         instance=trace_instance)
        handlers[name] = _HANDLER_FACTORY[kind](
            method, request_deserializer=req.FromString,
            response_serializer=resp.SerializeToString)
    return grpc.method_handlers_generic_handler(service, handlers)


def _traced_call(multicallable):
    """Wrap a client multicallable so every RPC carries the ambient trace
    as x-seaweed-trace metadata (the gRPC twin of the HTTP header the
    aiohttp sessions inject). Works for sync and aio channels and all
    stream kinds — the metadata kwarg is uniform."""
    def call(request, **kwargs):
        meta = observe.grpc_metadata(kwargs.get("metadata"))
        if meta is not None:
            kwargs["metadata"] = meta
        return multicallable(request, **kwargs)
    return call


_RPC_RETRY = retry_mod.RetryPolicy(max_attempts=3, base_delay=0.05,
                                   max_delay=1.0)

# Only these unary RPCs are transparently retried on UNAVAILABLE — the
# gRPC twin of http_pool's _POOLED_METHODS rule. UNAVAILABLE *usually*
# means the request never reached a serving peer, but a connection can
# also break after the server executed (killed mid-response, GOAWAY),
# and re-sending a destructive op (VolumeDelete, VacuumVolumeCommit,
# shard deletes...) would double-execute it. Reads/lookups/status are
# always safe; Assign merely mints fresh ids (a burned fid is garbage,
# not corruption). Everything else fails fast to its caller.
_RETRYABLE_RPCS = frozenset({
    "Assign", "Lookup", "LookupEc", "ClusterStatus", "VolumeList",
    "Statistics", "CollectionList", "GetMasterConfiguration",
    "VolumeNeedleStatus", "VacuumVolumeCheck", "VolumeStatus",
    "ReadVolumeFileStatus", "VolumeSyncStatus", "VolumeServerStatus",
    "LookupDirectoryEntry", "LookupVolume", "GetFilerConfiguration",
    "KvGet", "LocateBroker", "FindBroker", "GetTopicConfiguration",
})


def _retried_unary(call_fn):
    """Retry a unary multicallable on UNAVAILABLE with the unified
    jittered backoff (utils/retry.py) — the gRPC twin of the HTTP
    clients' rotation loops, applied only to the idempotent RPCs in
    _RETRYABLE_RPCS. When the caller gives no timeout, the ambient
    X-Seaweed-Deadline budget becomes the grpc deadline. Streams are
    never retried (redelivery semantics belong to their callers)."""

    def call(request, **kwargs):
        if kwargs.get("timeout") is None:
            left = retry_mod.remaining_budget()
            if left is not None:
                kwargs["timeout"] = max(left, 0.001)
        attempt = 0
        while True:
            try:
                result = call_fn(request, **kwargs)
            except grpc.RpcError as e:  # sync channel raises inline
                if (e.code() != grpc.StatusCode.UNAVAILABLE
                        or attempt >= _RPC_RETRY.max_attempts - 1):
                    raise
                import time as time_mod
                time_mod.sleep(_RPC_RETRY.backoff(attempt))
                attempt += 1
                continue
            if hasattr(result, "__await__"):  # aio: errors surface at await
                async def awaited(first_call=result):
                    import asyncio
                    a, c = 0, first_call
                    while True:
                        try:
                            return await c
                        except grpc.RpcError as e:
                            if (e.code() != grpc.StatusCode.UNAVAILABLE
                                    or a >= _RPC_RETRY.max_attempts - 1):
                                raise
                            await asyncio.sleep(_RPC_RETRY.backoff(a))
                            a += 1
                            c = call_fn(request, **kwargs)
                return awaited()
            return result

    return call


class _SpecStub:
    """Client multicallables (what a generated stub would contain), each
    built when it is first asked for and kept: a caller that makes one
    kind of call pays for one multicallable, not for the whole spec."""

    def __init__(self, channel, service: str, spec: dict):
        self._channel, self._service, self._spec = channel, service, spec

    def __getattr__(self, name: str):
        # reached only for what is not an attribute yet
        if name.startswith("_") or name not in self._spec:
            raise AttributeError(name)
        kind, req, resp = self._spec[name]
        factory = {"uu": self._channel.unary_unary,
                   "us": self._channel.unary_stream,
                   "ss": self._channel.stream_stream}[kind]
        call = _traced_call(factory(
            f"/{self._service}/{name}",
            request_serializer=req.SerializeToString,
            response_deserializer=resp.FromString))
        if kind == "uu" and name in _RETRYABLE_RPCS:
            # retries re-enter _traced_call, so every attempt
            # re-injects fresh trace metadata
            call = _retried_unary(call)
        setattr(self, name, call)
        return call


class MasterStub(_SpecStub):
    def __init__(self, channel):
        super().__init__(channel, MASTER_SERVICE, MASTER_SPEC)


class VolumeServerStub(_SpecStub):
    def __init__(self, channel):
        super().__init__(channel, VOLUME_SERVICE, VOLUME_SPEC)


MESSAGING_SPEC = {
    "Subscribe": ("ss", msgpb.SubscriberMessage, msgpb.BrokerMessage),
    "Publish": ("ss", msgpb.PublishRequest, msgpb.PublishResponse),
    "DeleteTopic": ("uu", msgpb.DeleteTopicRequest,
                    msgpb.DeleteTopicResponse),
    "ConfigureTopic": ("uu", msgpb.ConfigureTopicRequest,
                       msgpb.ConfigureTopicResponse),
    "GetTopicConfiguration": ("uu", msgpb.GetTopicConfigurationRequest,
                              msgpb.GetTopicConfigurationResponse),
    "FindBroker": ("uu", msgpb.FindBrokerRequest, msgpb.FindBrokerResponse),
}


class FilerStub(_SpecStub):
    def __init__(self, channel):
        super().__init__(channel, FILER_SERVICE, FILER_SPEC)


class MessagingStub(_SpecStub):
    def __init__(self, channel):
        super().__init__(channel, MESSAGING_SERVICE, MESSAGING_SPEC)


def messaging_service_handler(servicer, guard=None,
                              trace_service: str = "broker",
                              trace_instance: str = ""
                              ) -> grpc.GenericRpcHandler:
    return service_handler(MESSAGING_SERVICE, MESSAGING_SPEC, servicer,
                           guard, trace_service=trace_service,
                           trace_instance=trace_instance)


def master_service_handler(servicer, guard=None,
                           trace_service: str = "master",
                           trace_instance: str = ""
                           ) -> grpc.GenericRpcHandler:
    return service_handler(MASTER_SERVICE, MASTER_SPEC, servicer, guard,
                           trace_service=trace_service,
                           trace_instance=trace_instance)


def volume_service_handler(servicer, guard=None,
                           trace_service: str = "volume",
                           trace_instance: str = ""
                           ) -> grpc.GenericRpcHandler:
    return service_handler(VOLUME_SERVICE, VOLUME_SPEC, servicer, guard,
                           trace_service=trace_service,
                           trace_instance=trace_instance)


def filer_service_handler(servicer, guard=None,
                          trace_service: str = "filer",
                          trace_instance: str = ""
                          ) -> grpc.GenericRpcHandler:
    return service_handler(FILER_SERVICE, FILER_SPEC, servicer, guard,
                           trace_service=trace_service,
                           trace_instance=trace_instance)
