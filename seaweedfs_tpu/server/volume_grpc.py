"""gRPC face of the volume server (role of the reference's
weed/server/volume_grpc_*.go family).

Serves the VolumeServer service from proto/volume_server.proto on
HTTP port + 10000. Handlers delegate to the same Store internals the
HTTP /admin/* surface uses; the bulk surfaces (CopyFile, VolumeTail,
VolumeIncrementalCopy, VolumeEcShardRead, Query) are real server
streams, replacing their chunked-HTTP analogs for cluster-internal
traffic (volume_server.proto:10-95 in the reference defines the same
streaming shapes).
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import shutil

import grpc

from ..pb import volume_server_pb2 as pb
from ..pb.rpc import volume_service_handler
from ..storage.store import safe_collection
from ..utils import durable

log = logging.getLogger("volume.grpc")

_CHUNK = 1 << 20
# the largest shard range the loop's thread copies out of a mapping
# itself (as `EcVolume.read_needle_nowait`'s needle): a degraded read asks
# for an interval of a needle, a rebuild or a copy for megabytes
_INLINE_MAX = 64 << 10
# labels of `ec_shard_read_served`: made once, a read only counts
_INLINE = {"how": "inline"}
_EXECUTOR = {"how": "executor"}


def _run(fn):
    return asyncio.get_event_loop().run_in_executor(None, fn)


def _ok() -> pb.Ok:
    return pb.Ok(ok=True)


def _err(e) -> pb.Ok:
    return pb.Ok(ok=False, error=str(e))


class VolumeGrpcServicer:
    def __init__(self, vs):
        self.vs = vs          # VolumeServer
        self.store = vs.store

    # --- data-plane helpers ---
    async def BatchDelete(self, request: pb.BatchDeleteRequest, context):
        from ..storage.file_id import FileId
        from ..storage.needle import Needle
        results = []
        for fid_str in request.fids:
            try:
                fid = FileId.parse(fid_str)
                n = Needle(cookie=fid.cookie, id=fid.key)
                size = await _run(
                    lambda f=fid, nn=n: self.store.delete_needle(
                        f.volume_id, nn))
                results.append(pb.DeleteResult(fid=fid_str, status=202,
                                               size=size))
            except Exception as e:
                results.append(pb.DeleteResult(fid=fid_str, status=404,
                                               error=str(e)))
        return pb.BatchDeleteResponse(results=results)

    async def VolumeNeedleStatus(self, request: pb.NeedleStatusRequest,
                                 context):
        try:
            n = await _run(lambda: self.store.read_needle(
                request.volume_id, request.needle_id))
            return pb.NeedleStatusResponse(
                cookie=n.cookie, size=len(n.data),
                last_modified=getattr(n, "last_modified", 0) or 0,
                crc=getattr(n, "checksum", 0) or 0,
                ttl=str(getattr(n, "ttl", "") or ""))
        except Exception as e:
            return pb.NeedleStatusResponse(error=str(e))

    # --- vacuum ---
    async def VacuumVolumeCheck(self, request: pb.VolumeRef, context):
        try:
            g = self.store.vacuum_check(request.volume_id)
            return pb.VacuumCheckResponse(garbage_ratio=g)
        except KeyError:
            return pb.VacuumCheckResponse(error="volume not found")

    async def VacuumVolumeCompact(self, request: pb.VacuumCompactRequest,
                                  context):
        try:
            await _run(lambda: self.store.vacuum_compact(
                request.volume_id, request.compaction_byte_per_second))
            return _ok()
        except (KeyError, RuntimeError) as e:
            return _err(e)

    async def VacuumVolumeCommit(self, request: pb.VolumeRef, context):
        try:
            await _run(lambda: self.store.vacuum_commit(request.volume_id))
            return _ok()
        except (KeyError, RuntimeError) as e:
            return _err(e)

    async def VacuumVolumeCleanup(self, request: pb.VolumeRef, context):
        try:
            self.store.vacuum_cleanup(request.volume_id)
            return _ok()
        except KeyError as e:
            return _err(e)

    # --- volume lifecycle ---
    async def AllocateVolume(self, request: pb.AllocateVolumeRequest,
                             context):
        try:
            self.store.add_volume(request.volume_id, request.collection,
                                  request.replication or "000",
                                  request.ttl)
        except (ValueError, RuntimeError) as e:
            return _err(e)
        await self._safe_heartbeat()
        return _ok()

    async def VolumeMount(self, request: pb.VolumeRef, context):
        try:
            self.store.mount_volume(request.volume_id, request.collection)
        except Exception as e:
            return _err(e)
        await self._safe_heartbeat()
        return _ok()

    async def VolumeUnmount(self, request: pb.VolumeRef, context):
        ok = self.store.unmount_volume(request.volume_id)
        await self._safe_heartbeat()
        return pb.Ok(ok=ok, error="" if ok else "volume not found")

    async def VolumeDelete(self, request: pb.VolumeRef, context):
        ok = self.store.delete_volume(request.volume_id)
        await self._safe_heartbeat()
        return pb.Ok(ok=ok, error="" if ok else "volume not found")

    async def VolumeMarkReadonly(self, request: pb.VolumeRef, context):
        ok = self.store.mark_readonly(request.volume_id, True)
        return pb.Ok(ok=ok, error="" if ok else "volume not found")

    async def VolumeMarkWritable(self, request: pb.VolumeRef, context):
        ok = self.store.mark_readonly(request.volume_id, False)
        return pb.Ok(ok=ok, error="" if ok else "volume not found")

    async def VolumeConfigure(self, request: pb.VolumeConfigureRequest,
                              context):
        try:
            self.store.configure_replication(request.volume_id,
                                             request.replication)
            return _ok()
        except Exception as e:
            return _err(e)

    async def VolumeStatus(self, request: pb.VolumeRef, context):
        v = self.store.find_volume(request.volume_id)
        if v is None:
            return pb.VolumeStatusResponse(error="volume not found")
        return pb.VolumeStatusResponse(
            is_read_only=v.read_only, volume_size=v.data_file_size(),
            file_count=v.file_count(),
            delete_count=v.nm.deleted_count)

    async def DeleteCollection(self, request: pb.DeleteCollectionRequest,
                               context):
        vids = [vid for loc in self.store.locations
                for vid, v in list(loc.volumes.items())
                if v.collection == request.collection]
        for vid in vids:
            self.store.delete_volume(vid)
        await self._safe_heartbeat()
        return _ok()

    # --- replication / move / sync ---
    async def VolumeCopy(self, request: pb.VolumeCopyRequest, context):
        """Pull a whole volume from the source server over its CopyFile
        gRPC stream and mount it (VolumeCopy pull model,
        weed/server/volume_grpc_copy.go:24-151)."""
        vid = request.volume_id
        collection = request.collection
        if not safe_collection(collection):
            return _err("bad collection")
        if self.store.find_volume(vid) is not None:
            return _err("volume exists")
        open_locs = [l for l in self.store.locations
                     if len(l.volumes) < l.max_volume_count]
        if not open_locs:
            return _err("no free slots")
        loc = min(open_locs, key=lambda l: len(l.volumes))
        prefix = f"{collection}_" if collection else ""
        base = os.path.join(loc.directory, f"{prefix}{vid}")
        try:
            for ext in (".dat", ".idx"):
                await pull_file_grpc(request.source_data_node, vid,
                                     collection, ext, base + ext)
            from ..storage.needle_map import remove_sidecars
            remove_sidecars(base + ".idx")  # never trust a leftover .sdx
            try:
                # a stale sync watermark from a prior same-id volume
                # would mis-anchor the pulled copy's recovery scan
                os.remove(base + ".swm")
            except FileNotFoundError:
                pass
            from ..storage.volume import Volume
            v = await _run(lambda: Volume(
                loc.directory, collection, vid,
                needle_map_kind=self.store.needle_map_kind))
            loc.volumes[vid] = v
        except Exception as e:
            for ext in (".dat", ".idx"):
                if os.path.exists(base + ext):
                    os.remove(base + ext)
            return _err(e)
        await self._safe_heartbeat()
        return _ok()

    async def ReadVolumeFileStatus(self, request: pb.VolumeRef, context):
        v = self.store.find_volume(request.volume_id)
        if v is None:
            return pb.VolumeFileStatusResponse(error="volume not found")
        idx_path = v.base_file_name() + ".idx"
        idx_size = os.path.getsize(idx_path) \
            if os.path.exists(idx_path) else 0
        return pb.VolumeFileStatusResponse(
            volume_id=request.volume_id,
            idx_file_size=idx_size, dat_file_size=v.data_file_size(),
            file_count=v.file_count(),
            compaction_revision=v.sb.compact_revision,
            collection=v.collection)

    async def CopyFile(self, request: pb.CopyFileRequest, context):
        """Stream one volume/shard file to a pulling peer."""
        ext = request.ext
        if not ext.startswith(".") or "/" in ext or ".." in ext \
                or not safe_collection(request.collection):
            yield pb.DataChunk(error="bad ext or collection", is_last=True)
            return
        prefix = (f"{request.collection}_" if request.collection else "")
        path = None
        for loc in self.store.locations:
            p = os.path.join(loc.directory,
                             f"{prefix}{request.volume_id}{ext}")
            if os.path.exists(p):
                path = p
                break
        if path is None:
            yield pb.DataChunk(error="file not found", is_last=True)
            return
        stop = request.stop_offset or os.path.getsize(path)
        with open(path, "rb") as f:
            sent = 0
            while sent < stop:
                chunk = await _run(
                    lambda: f.read(min(_CHUNK, stop - sent)))
                if not chunk:
                    break
                sent += len(chunk)
                yield pb.DataChunk(data=chunk)
        yield pb.DataChunk(is_last=True)

    async def VolumeTail(self, request: pb.TailRequest, context):
        """One needle record per chunk, appended after since_ns
        (VolumeTailSender, weed/server/volume_grpc_tail.go:16-79)."""
        from ..storage import volume_backup
        v = self.store.find_volume(request.volume_id)
        if v is None:
            yield pb.DataChunk(error="volume not found", is_last=True)
            return
        it = volume_backup.iter_needles_since(v, request.since_ns)

        def next_record():
            try:
                n = next(it)
            except StopIteration:
                return None
            return n.to_bytes(v.version)

        while True:
            rec = await _run(next_record)
            if rec is None:
                break
            yield pb.DataChunk(data=rec)
        yield pb.DataChunk(is_last=True)

    async def VolumeIncrementalCopy(self, request: pb.TailRequest,
                                    context):
        async for chunk in self.VolumeTail(request, context):
            yield chunk

    async def VolumeTailSender(self, request: pb.TailRequest, context):
        """Reference name for the tail stream (volume_grpc_tail.go
        VolumeTailSender); identical semantics to VolumeTail."""
        async for chunk in self.VolumeTail(request, context):
            yield chunk

    async def VolumeSyncStatus(self, request: pb.VolumeRef, context):
        """Tail offset + compaction revision for incremental sync
        (VolumeSyncStatus, volume_grpc_sync.go)."""
        v = self.store.find_volume(request.volume_id)
        if v is None:
            return pb.VolumeSyncStatusResponse(error="volume not found")
        idx_path = v.base_file_name() + ".idx"
        idx_size = (os.path.getsize(idx_path)
                    if os.path.exists(idx_path) else 0)
        return pb.VolumeSyncStatusResponse(
            volume_id=request.volume_id,
            collection=v.collection,
            tail_offset=v.data_file_size(),
            compact_revision=v.super_block.compaction_revision,
            idx_file_size=idx_size)

    async def VolumeTailReceiver(self, request: pb.TailReceiverRequest,
                                 context):
        """Pull new needle records from the source and append them
        locally (VolumeTailReceiver, volume_grpc_tail.go:81-126)."""
        from ..storage import volume_backup
        from ..storage.needle import Needle
        v = self.store.find_volume(request.volume_id)
        if v is None:
            return _err("volume not found")
        target = grpc_target(request.source_volume_server)
        n_applied = 0
        from ..pb.rpc import VolumeServerStub, aio_dial
        async with aio_dial(target) as channel:
            stub = VolumeServerStub(channel)
            async for chunk in stub.VolumeTail(pb.TailRequest(
                    volume_id=request.volume_id,
                    since_ns=request.since_ns)):
                if chunk.error:
                    return _err(chunk.error)
                if chunk.is_last:
                    break
                n = Needle.from_bytes(chunk.data, v.version)
                # empty body = tombstone -> delete, and the source's
                # append_at_ns is preserved so the replica's high-water
                # mark stays truthful for the next incremental tail
                await _run(lambda nn=n:
                           volume_backup.apply_tailed_needle(v, nn))
                n_applied += 1
        log.info("tail-receive applied %d records to %d",
                 n_applied, request.volume_id)
        return _ok()

    # --- erasure coding ---
    async def VolumeEcShardsGenerate(self, request: pb.EcGenerateRequest,
                                     context):
        try:
            await _run(lambda: self.store.ec_generate(request.volume_id))
            return _ok()
        except (KeyError, ValueError) as e:
            return _err(e)

    async def VolumeEcShardsRebuild(self, request: pb.EcRebuildRequest,
                                    context):
        try:
            rebuilt = await _run(lambda: self.store.ec_rebuild(
                request.volume_id, request.collection))
            return pb.EcRebuildResponse(rebuilt_shard_ids=rebuilt)
        except (KeyError, ValueError) as e:
            return pb.EcRebuildResponse(error=str(e))

    async def VolumeEcShardsCopy(self, request: pb.EcCopyRequest, context):
        """Pull shard files from the source server over gRPC CopyFile."""
        from .. import ec as ec_mod
        vid = request.volume_id
        collection = request.collection
        if not safe_collection(collection):
            return _err("bad collection")
        loc = self.store.locations[0]
        prefix = f"{collection}_" if collection else ""
        base = os.path.join(loc.directory, f"{prefix}{vid}")
        try:
            exts = [ec_mod.to_ext(sid) for sid in request.shard_ids]
            # a mounted volume keeps its own index (admin_ec_copy says
            # why); replacing the path would leave its descriptor on an
            # unlinked inode and lose its later tombstones
            if (request.copy_ecx_file
                    and self.store.find_ec_volume(vid) is None):
                exts += [".ecx", ".ecj"]
            for ext in exts:
                try:
                    await pull_file_grpc(request.source_data_node, vid,
                                         collection, ext, base + ext)
                except FileNotFoundError:
                    if ext == ".ecj":
                        continue  # delete journal is optional
                    raise
        except Exception as e:
            return _err(e)
        return _ok()

    async def VolumeEcShardsDelete(self, request: pb.EcShardsRequest,
                                   context):
        self.store.ec_delete_shards(request.volume_id, request.collection,
                                    list(request.shard_ids))
        await self._safe_heartbeat()
        return _ok()

    async def VolumeEcShardsMount(self, request: pb.EcShardsRequest,
                                  context):
        try:
            self.store.ec_mount(request.volume_id, request.collection,
                                list(request.shard_ids))
        except (KeyError, FileNotFoundError) as e:
            return _err(e)
        await self._safe_heartbeat()
        return _ok()

    async def VolumeEcShardsUnmount(self, request: pb.EcShardsRequest,
                                    context):
        self.store.ec_unmount(request.volume_id, list(request.shard_ids))
        await self._safe_heartbeat()
        return _ok()

    async def VolumeEcShardRead(self, request: pb.EcShardReadRequest,
                                context):
        """Stream a shard byte range (VolumeEcShardRead,
        volume_grpc_erasure_coding.go:270-328) — the degraded-read path's
        peer fetch rides this stream. A range of at most 64 KiB that lies
        in the mapped file of a shard mounted here is sliced on the
        loop's thread and is the whole answer, one message; anything
        else is read on an executor thread, 1 MiB a message, with a
        closing `is_last`."""
        if 0 < request.size <= _INLINE_MAX:
            data = self.store.ec_shard_slice(
                request.volume_id, request.shard_id, request.offset,
                request.size)
            if data is not None:
                self.vs.metrics.count("ec_shard_read_served",
                                      labels=_INLINE)
                yield pb.DataChunk(data=data, is_last=True)
                return
        self.vs.metrics.count("ec_shard_read_served", labels=_EXECUTOR)
        try:
            offset, remaining = request.offset, request.size
            while remaining > 0:
                n = min(_CHUNK, remaining)
                data = await _run(
                    lambda o=offset, s=n: self.store.ec_shard_read(
                        request.volume_id, request.shard_id, o, s))
                if data:
                    yield pb.DataChunk(data=data)
                if len(data) < n:
                    # short pread = range past shard EOF; a silent
                    # truncated stream would look complete to the caller
                    yield pb.DataChunk(
                        error=f"short read at {offset + len(data)}",
                        is_last=True)
                    return
                offset += n
                remaining -= n
            yield pb.DataChunk(is_last=True)
        except KeyError as e:
            yield pb.DataChunk(error=str(e), is_last=True)

    async def VolumeEcBlobDelete(self, request: pb.EcBlobDeleteRequest,
                                 context):
        try:
            self.store.ec_blob_delete(request.volume_id, request.file_key)
            return _ok()
        except KeyError as e:
            return _err(e)

    async def VolumeEcShardsToVolume(self, request: pb.VolumeRef, context):
        try:
            await _run(lambda: self.store.ec_to_volume(
                request.volume_id, request.collection))
        except (KeyError, FileNotFoundError) as e:
            return _err(e)
        await self._safe_heartbeat()
        return _ok()

    # --- tiered storage ---
    async def VolumeTierMoveDatToRemote(self, request: pb.TierMoveRequest,
                                        context):
        """destination_backend_name carries the JSON backend spec (the
        HTTP surface takes the same dict; named-backend config resolution
        is the shell's job)."""
        try:
            spec = json.loads(request.destination_backend_name)
        except ValueError:
            return _err("destination_backend_name must be a JSON "
                        "backend spec")
        try:
            await _run(lambda: self.store.tier_upload(
                request.volume_id, spec,
                keep_local=request.keep_local_dat_file))
        except Exception as e:
            return _err(e)
        await self._safe_heartbeat()
        return _ok()

    async def VolumeTierMoveDatFromRemote(self, request: pb.TierMoveRequest,
                                          context):
        try:
            await _run(lambda: self.store.tier_download(request.volume_id))
        except (KeyError, ValueError) as e:
            return _err(e)
        await self._safe_heartbeat()
        return _ok()

    # --- server-level ---
    async def VolumeServerStatus(self, request, context):
        disks = []
        vol_count = 0
        ec_count = 0
        for loc in self.store.locations:
            try:
                u = shutil.disk_usage(loc.directory)
                disks.append(pb.DiskStatus(dir=loc.directory, all=u.total,
                                           used=u.used, free=u.free))
            except OSError:
                pass
            vol_count += len(loc.volumes)
            ec_count += sum(len(ev.shards)
                            for ev in loc.ec_volumes.values())
        return pb.VolumeServerStatusResponse(
            disk_statuses=disks, volume_count=vol_count,
            ec_shard_count=ec_count, version="seaweedfs-tpu")

    async def VolumeServerLeave(self, request, context):
        """Stop heartbeating so the master prunes this node; the admin
        shell drains it first (command_volume_server_leave.go)."""
        if self.vs._hb_task is not None:
            self.vs._hb_task.cancel()
            self.vs._hb_task = None
        return _ok()

    # --- query pushdown ---
    async def Query(self, request: pb.QueryRequest, context):
        from ..query import QueryFilter, query_json_lines
        from ..storage.file_id import FileId
        flt = None
        if request.filter_json:
            try:
                f = json.loads(request.filter_json)
                flt = QueryFilter(f["field"], f.get("op", "="),
                                  f.get("value"))
            except (ValueError, KeyError) as e:
                yield pb.DataChunk(error=f"bad filter: {e}", is_last=True)
                return
        payloads = []
        for fid_str in request.file_ids:
            try:
                fid = FileId.parse(fid_str)
                n = await _run(lambda f=fid: self.store.read_needle(
                    f.volume_id, f.key, cookie=f.cookie))
                payloads.append(n.data)
            except Exception:
                continue
        selections = list(request.selections) or None
        for line in query_json_lines(payloads, flt, selections):
            yield pb.DataChunk(data=line.encode() + b"\n")
        yield pb.DataChunk(is_last=True)

    async def _safe_heartbeat(self):
        try:
            await self.vs.send_heartbeat()
        except Exception as e:
            log.warning("post-admin heartbeat failed: %s", e)


def grpc_target(http_url: str) -> str:
    from ..pb.rpc import grpc_address
    return grpc_address(http_url)


async def pull_file_grpc(source_http_url: str, vid: int, collection: str,
                         ext: str, dest_path: str) -> None:
    """Fetch one volume/shard file from a peer's CopyFile stream into
    dest_path. Raises FileNotFoundError when the peer lacks the file."""
    from ..pb.rpc import VolumeServerStub, aio_dial
    async with aio_dial(grpc_target(source_http_url)) as channel:
        stub = VolumeServerStub(channel)
        tmp = dest_path + ".tmp"
        try:
            with open(tmp, "wb") as f:
                async for chunk in stub.CopyFile(pb.CopyFileRequest(
                        volume_id=vid, collection=collection, ext=ext)):
                    if chunk.error:
                        if "not found" in chunk.error:
                            raise FileNotFoundError(chunk.error)
                        raise IOError(chunk.error)
                    if chunk.data:
                        f.write(chunk.data)
                    if chunk.is_last:
                        break
            # a pulled replica/shard becomes load-bearing the moment the
            # repair plan counts it — commit it durably, off the loop
            await asyncio.get_event_loop().run_in_executor(
                None, durable.replace_atomic, tmp, dest_path)
        finally:
            # transport errors (RpcError) land here too — never leave a
            # partial multi-GB .tmp in the data directory
            if os.path.exists(tmp):
                os.remove(tmp)


async def serve_volume_grpc(vs, host: str, port: int, tls=None):
    """Start the grpc.aio server for a VolumeServer; returns it."""
    server = grpc.aio.server()
    server.add_generic_rpc_handlers(
        (volume_service_handler(VolumeGrpcServicer(vs),
                                guard=lambda: vs.guard,
                                trace_instance=vs.url),))
    creds = tls.grpc_server_credentials() if tls is not None else None
    # the port bound, which is not `port` above 65535 (master_grpc.py)
    if creds is not None:
        bound = server.add_secure_port(f"{host}:{port}", creds)
    else:
        bound = server.add_insecure_port(f"{host}:{port}")
    await server.start()
    log.info("volume gRPC on %s:%d%s", host, bound,
             " (mtls)" if creds else "")
    return server
