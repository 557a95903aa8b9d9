"""gRPC face of the master (role of weed/server/master_grpc_server.go).

Serves the Master service from proto/master.proto on HTTP port + 10000:
assign/lookup, the bidirectional heartbeat stream (a dropped stream
unregisters the node and broadcasts its DeletedVids immediately —
master_grpc_server.go:22-49), KeepConnected location push, and the admin
lease. All handlers delegate to the same MasterServer internals the
HTTP surface uses.
"""

from __future__ import annotations

import asyncio
import logging
from typing import Optional

import grpc

from ..ec import shard_bits
from ..pb import master_pb2 as pb
from ..pb.rpc import master_service_handler

log = logging.getLogger("master.grpc")


def _hb_to_dict(req: pb.HeartbeatRequest) -> dict:
    return {
        "node_id": req.node_id,
        "url": req.url,
        "public_url": req.public_url or req.url,
        "data_center": req.data_center,
        "rack": req.rack,
        "max_volume_count": req.max_volume_count or 8,
        "max_file_key": req.max_file_key,
        "volumes": [{
            "id": v.id, "collection": v.collection, "size": v.size,
            "file_count": v.file_count, "delete_count": v.delete_count,
            "deleted_bytes": v.deleted_bytes, "read_only": v.read_only,
            "replica_placement": v.replica_placement or "000",
            "ttl": v.ttl, "version": v.version or 3,
        } for v in req.volumes],
        "ec_shards": [{
            "id": s.id, "collection": s.collection,
            "shard_ids": shard_bits.to_ids(s.ec_index_bits),
            "shard_size": s.shard_size,
        } for s in req.ec_shards],
    }


def heartbeat_to_pb(payload: dict) -> pb.HeartbeatRequest:
    """Store heartbeat dict -> wire message (client side)."""
    return pb.HeartbeatRequest(
        node_id=payload["node_id"],
        url=payload["url"],
        public_url=payload.get("public_url", ""),
        data_center=payload.get("data_center", ""),
        rack=payload.get("rack", ""),
        max_volume_count=payload.get("max_volume_count", 8),
        max_file_key=payload.get("max_file_key", 0),
        volumes=[pb.VolumeInformation(
            id=v["id"], collection=v.get("collection", ""),
            size=v.get("size", 0), file_count=v.get("file_count", 0),
            delete_count=v.get("delete_count", 0),
            deleted_bytes=v.get("deleted_bytes", 0),
            read_only=v.get("read_only", False),
            replica_placement=str(v.get("replica_placement", "000")),
            ttl=str(v.get("ttl", "")), version=v.get("version", 3),
        ) for v in payload.get("volumes", [])],
        ec_shards=[pb.EcShardInformation(
            id=s["id"], collection=s.get("collection", ""),
            ec_index_bits=shard_bits.from_ids(s.get("shard_ids", [])),
            shard_size=s.get("shard_size", 0),
        ) for s in payload.get("ec_shards", [])])


class MasterGrpcServicer:
    def __init__(self, master):
        self.master = master

    async def Assign(self, request: pb.AssignRequest, context):
        if not await self.master.ensure_assign_ready():
            return pb.AssignResponse(error="not the leader / not ready")
        resp, status = await self.master.assign_api(
            count=request.count or 1,
            collection=request.collection,
            replication=request.replication,
            ttl=request.ttl,
            data_center=request.data_center)
        if status != 200:
            return pb.AssignResponse(error=resp.get("error", "failed"))
        return pb.AssignResponse(
            fid=resp["fid"], url=resp["url"],
            public_url=resp["publicUrl"], count=resp["count"],
            auth=resp.get("auth", ""), replicas=resp.get("replicas", []))

    async def Lookup(self, request: pb.LookupRequest, context):
        master = self.master
        if request.file_id:
            from ..storage.file_id import FileId
            try:
                fid = FileId.parse(request.file_id)
            except ValueError:
                return pb.LookupResponse(error="invalid fileId")
            vid = fid.volume_id
            auth = (master.guard.sign_read(str(fid))
                    if master.guard.read_signing_key else "")
        else:
            vid = request.volume_id
            auth = ""
        nodes = master.topology.lookup(vid, request.collection)
        if nodes:
            return pb.LookupResponse(
                volume_id=vid, auth=auth,
                locations=[pb.Location(url=n.url, public_url=n.public_url)
                           for n in nodes])
        shards = master.topology.lookup_ec_shards(vid)
        if shards:
            seen, locs = set(), []
            for nlist in shards.values():
                for n in nlist:
                    if n.url not in seen:
                        seen.add(n.url)
                        locs.append(pb.Location(url=n.url,
                                                public_url=n.public_url))
            return pb.LookupResponse(volume_id=vid, ec=True, auth=auth,
                                     locations=locs)
        return pb.LookupResponse(volume_id=vid, error="volume not found")

    async def LookupEc(self, request: pb.LookupEcRequest, context):
        shards = self.master.topology.lookup_ec_shards(request.volume_id)
        if not shards:
            return pb.LookupEcResponse(volume_id=request.volume_id,
                                       error="ec volume not found")
        return pb.LookupEcResponse(
            volume_id=request.volume_id,
            shards=[pb.EcShardLocations(
                shard_id=sid,
                locations=[pb.Location(url=n.url, public_url=n.public_url)
                           for n in nodes])
                    for sid, nodes in sorted(shards.items())])

    async def Heartbeat(self, request_iterator, context):
        """Bidi heartbeat stream: beats up, config down; a dropped stream
        unregisters the node immediately and pushes its DeletedVids."""
        master = self.master
        node_id: Optional[str] = None
        try:
            async for req in request_iterator:
                body = _hb_to_dict(req)
                node_id = body["node_id"]
                out = master.apply_heartbeat(body)
                yield pb.HeartbeatResponse(
                    volume_size_limit=out["volume_size_limit"],
                    leader=out["leader"])
        finally:
            if node_id is not None:
                ev = master.topology.unregister_node(node_id)
                master._broadcast_location(ev)
                log.info("heartbeat stream from %s closed; unregistered",
                         node_id)

    async def KeepConnected(self, request: pb.KeepConnectedRequest,
                            context):
        master = self.master
        if not master.raft.is_leader:
            yield pb.VolumeLocationMessage(
                leader=master.raft.leader_id or "")
            return
        q: asyncio.Queue = asyncio.Queue()
        master._watchers.add(q)
        try:
            for node in master.topology.nodes.values():
                vids = sorted(set(node.volumes) | set(node.ec_shards))
                yield pb.VolumeLocationMessage(
                    url=node.url, public_url=node.public_url,
                    new_vids=vids, is_snapshot=True,
                    leader=master.raft.leader_id or "")
            while True:
                msg = await q.get()
                yield pb.VolumeLocationMessage(
                    url=msg.get("url", ""),
                    public_url=msg.get("public_url", ""),
                    new_vids=msg.get("new_vids", []),
                    deleted_vids=msg.get("deleted_vids", []),
                    leader=master.raft.leader_id or "")
        finally:
            master._watchers.discard(q)

    async def ClusterStatus(self, request, context):
        raft = self.master.raft
        return pb.ClusterStatusResponse(
            is_leader=raft.is_leader, leader=raft.leader_id or "",
            peers=raft.peers, raft_term=raft.term)

    async def VolumeList(self, request, context):
        """Full per-node inventory (master_grpc_server_volume.go:117)."""
        topo = self.master.topology
        return pb.VolumeListResponse(
            volume_size_limit_mb=topo.volume_size_limit // (1024 * 1024),
            nodes=[pb.NodeVolumes(
                url=n.url, public_url=n.public_url,
                data_center=n.data_center, rack=n.rack,
                max_volume_count=n.max_volume_count,
                volumes=[pb.VolumeInformation(
                    id=v.id, collection=v.collection, size=v.size,
                    file_count=v.file_count, delete_count=v.delete_count,
                    deleted_bytes=v.deleted_bytes, read_only=v.read_only,
                    replica_placement=str(v.replica_placement),
                    ttl=str(v.ttl), version=v.version)
                    for v in n.volumes.values()],
                ec_shards=[pb.EcShardInformation(
                    id=e.id, collection=e.collection,
                    ec_index_bits=shard_bits.from_ids(e.shard_ids),
                    shard_size=e.shard_size)
                    for e in n.ec_shards.values()])
                for n in topo.nodes.values()])

    async def Statistics(self, request, context):
        """Aggregate usage, optionally filtered by collection
        (master_grpc_server_volume.go:176)."""
        topo = self.master.topology
        total = used = files = 0
        for n in topo.nodes.values():
            total += n.max_volume_count * topo.volume_size_limit
            for v in n.volumes.values():
                if request.collection and \
                        v.collection != request.collection:
                    continue
                used += v.size
                files += v.file_count
        return pb.StatisticsResponse(total_size=total, used_size=used,
                                     file_count=files)

    async def CollectionList(self, request, context):
        return pb.CollectionListResponse(
            collections=self.master.collection_names())

    async def CollectionDelete(self, request, context):
        if not request.name:
            # proto3 zero value must not match the default collection —
            # that would delete every unlabeled volume cluster-wide (the
            # HTTP twin rejects empty names the same way)
            return pb.CollectionDeleteResponse(
                ok=False, error="collection name required")
        out = await self.master.delete_collection(request.name)
        if out["errors"]:
            return pb.CollectionDeleteResponse(
                ok=False, error="; ".join(out["errors"]))
        return pb.CollectionDeleteResponse(ok=True)

    async def GetMasterConfiguration(self, request, context):
        m = self.master
        return pb.GetMasterConfigurationResponse(
            default_replication=m.default_replication,
            volume_size_limit_mb=m.topology.volume_size_limit
            // (1024 * 1024),
            garbage_threshold=m.garbage_threshold)

    async def LeaseAdminToken(self, request, context):
        resp, status = self.master.lease_admin_token(
            request.name, request.client, request.previous_token)
        if status != 200:
            return pb.LeaseAdminTokenResponse(error=resp["error"])
        return pb.LeaseAdminTokenResponse(token=resp["token"],
                                          expires_at=resp["expires_at"])

    async def ReleaseAdminToken(self, request, context):
        return pb.ReleaseAdminTokenResponse(
            ok=self.master.release_admin_token(request.name, request.token))


async def serve_master_grpc(master, host: str, port: int, tls=None):
    """Start the grpc.aio server; returns it (caller stops with
    .stop())."""
    server = grpc.aio.server()
    server.add_generic_rpc_handlers(
        (master_service_handler(MasterGrpcServicer(master),
                                guard=lambda: master.guard,
                                trace_instance=master.url),))
    creds = tls.grpc_server_credentials() if tls is not None else None
    # gRPC keeps the low 16 bits of a port, when it listens and when it
    # dials: beside an HTTP port above 55535 the +10000 convention lands
    # on (port + 10000) - 65536, which is what the log has to name
    if creds is not None:
        bound = server.add_secure_port(f"{host}:{port}", creds)
    else:
        bound = server.add_insecure_port(f"{host}:{port}")
    await server.start()
    log.info("master gRPC on %s:%d%s", host, bound,
             " (mtls)" if creds else "")
    return server
