"""Store: everything one volume server owns on disk.

Facade over one or more storage directories (DiskLocation), routing needle
operations to normal volumes and EC volumes — capability parity with the
reference Store (weed/storage/store.go:26-49, disk_location.go:18-30,
store_ec.go). Also produces the heartbeat payload the master consumes.
"""

from __future__ import annotations

import glob
import os
import threading
from typing import Optional

from .. import ec as ec_mod
from .. import observe
from ..ec import fused as ec_fused
from ..ec import pipeline as ec_pipeline
from ..utils import durable
from ..ec.coder import ErasureCoder
from ..ec.ec_volume import EcVolume
from . import types as t
from .needle import Needle
from .superblock import ReplicaPlacement, SuperBlock
from .volume import Volume


class DiskLocation:
    """One storage directory holding volumes and EC shards
    (weed/storage/disk_location.go)."""

    def __init__(self, directory: str, max_volume_count: int = 8,
                 needle_map_kind: str = "memory"):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.max_volume_count = max_volume_count
        self.needle_map_kind = needle_map_kind
        self.volumes: dict[int, Volume] = {}
        self.ec_volumes: dict[int, EcVolume] = {}
        self.low_space = False

    def load_existing(self, coder_factory,
                      geometry) -> None:
        """geometry: a Geometry (every EC volume assumed that shape) or
        a resolver callable (base_path, collection) -> Geometry — the
        store passes its marker-or-policy resolver so a mixed-geometry
        disk (RS(10,4) media next to RS(20,4) archive) loads right."""
        # tiered volumes have no local .dat — discover via .vif sidecars too
        names = {os.path.basename(p)[:-4]
                 for p in glob.glob(os.path.join(self.directory, "*.dat"))}
        names |= {os.path.basename(p)[:-4]
                  for p in glob.glob(os.path.join(self.directory, "*.vif"))}

        def load_one(name: str):
            collection, vid = _parse_volume_file_name(name)
            if vid is None:
                return None
            try:
                return vid, Volume(self.directory, collection, vid,
                                   needle_map_kind=self.needle_map_kind)
            except Exception:
                return None

        # 8-way concurrent load (disk_location.go:94-118): .idx replay is
        # the startup cost and parallelizes across volumes
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(max_workers=8) as pool:
            for res in pool.map(load_one, sorted(names)):
                if res is not None:
                    self.volumes[res[0]] = res[1]
        for ecx in glob.glob(os.path.join(self.directory, "*.ecx")):
            name = os.path.basename(ecx)[:-4]
            collection, vid = _parse_volume_file_name(name)
            if vid is None or vid in self.volumes:
                continue
            try:
                if callable(geometry):
                    g = geometry(os.path.join(self.directory, name),
                                 collection)
                else:
                    g = geometry
                ev = EcVolume(self.directory, collection, vid, g,
                              coder=coder_factory(g))
                for sid in range(ev.g.total_shards):
                    if os.path.exists(ev.base_file_name() + ec_mod.to_ext(sid)):
                        ev.add_shard(sid)
                if ev.shard_ids():
                    self.ec_volumes[vid] = ev
                else:
                    ev.close()
            except Exception:
                continue


def safe_collection(name: str) -> bool:
    """Collection names become file-name prefixes ("<collection>_<vid>.dat"),
    so anything that can traverse directories must be rejected before any
    path is built from caller input."""
    return ("/" not in name and "\\" not in name and ".." not in name
            and "\x00" not in name)


def _parse_volume_file_name(name: str) -> tuple[str, Optional[int]]:
    if "_" in name:
        collection, _, vid_str = name.rpartition("_")
    else:
        collection, vid_str = "", name
    try:
        return collection, int(vid_str)
    except ValueError:
        return "", None


class Store:
    def __init__(self, directories: list[str],
                 max_volume_counts: Optional[list[int]] = None,
                 coder_name: str = "auto",
                 geometry: ec_mod.Geometry = ec_mod.DEFAULT,
                 needle_map_kind: str = "memory",
                 min_free_space_percent: float = 1.0,
                 preallocate: int = 0,
                 geometry_policy: "ec_mod.GeometryPolicy | None" = None):
        # per-collection RS(k,m): explicit policy > WEED_EC_GEOMETRY env;
        # an explicit non-default `geometry` arg overrides the default
        # entry (back-compat for tests constructing shrunk geometries)
        policy = geometry_policy or ec_mod.GeometryPolicy.from_env()
        if geometry != ec_mod.DEFAULT:
            policy = ec_mod.GeometryPolicy(policy.per_collection, geometry)
        self.geometry_policy = policy
        self.geometry = policy.default
        self.coder_name = coder_name
        self.needle_map_kind = needle_map_kind
        self.min_free_space_percent = min_free_space_percent
        self.preallocate = preallocate
        self.low_disk_space = False
        self._coders: dict[tuple[int, int], ErasureCoder] = {}
        counts = max_volume_counts or [8] * len(directories)
        self.locations = [DiskLocation(d, c, needle_map_kind)
                          for d, c in zip(directories, counts)]
        self._lock = threading.RLock()
        for loc in self.locations:
            loc.load_existing(self.coder, self._resolve_geometry)
            for ev in loc.ec_volumes.values():
                ev.coder.warm_widths()

    def check_free_space(self) -> bool:
        """Min-free-space watchdog (disk_location.go:304 + statfs,
        weed/stats/disk_supported.go): when any location's disk drops
        below the threshold, every volume there goes readonly; space
        coming back lifts the seal for volumes we sealed ourselves."""
        low_any = False
        for loc in self.locations:
            st = os.statvfs(loc.directory)
            free_pct = st.f_bavail / max(st.f_blocks, 1) * 100.0
            low = free_pct < self.min_free_space_percent
            low_any = low_any or low
            if low and not loc.low_space:
                loc.low_space = True
                for v in loc.volumes.values():
                    if not v.read_only:
                        v.read_only = True
                        v.watchdog_sealed = True
            elif not low and loc.low_space:
                loc.low_space = False
                for v in loc.volumes.values():
                    # only lift seals the watchdog itself applied; an
                    # operator/readonly mark set in the interim clears
                    # watchdog_sealed and wins
                    if v.watchdog_sealed and not v.is_remote:
                        v.read_only = False
                    v.watchdog_sealed = False
        self.low_disk_space = low_any
        return low_any

    def coder(self, geometry: Optional[ec_mod.Geometry] = None
              ) -> ErasureCoder:
        g = geometry or self.geometry
        key = (g.data_shards, g.parity_shards)
        c = self._coders.get(key)
        if c is None:
            c = ec_mod.get_coder(
                self.coder_name, g.data_shards, g.parity_shards)
            c = self._coders[key] = self._maybe_mesh(c, g)
        return c

    def coder_status(self) -> dict:
        """The configured coder name and what it resolved to, per
        geometry, for the boot log and /admin/ec/mesh_status. Reads only
        coders that already exist: asking never initialises a JAX
        backend, so `resolved` is empty on a server that has not yet
        encoded, rebuilt or mounted an EC volume."""
        return {"name": self.coder_name,
                "resolved": [c.describe() for c in self._coders.values()]}

    def _maybe_mesh(self, c: ErasureCoder,
                    g: ec_mod.Geometry) -> ErasureCoder:
        """WEED_EC_MESH_DEVICES >= 2 lifts auto-selected device coders
        onto the jax.sharding mesh (parallel/mesh_coder.py), so every
        production encode/rebuild on this store shards its batch axis
        across the chips — an auto-picked PallasCoder keeps its
        hand-tiled kernel inside the shard_map step. Explicit backend
        names (numpy/cpp/pallas — byte-exact references, kernel pins)
        stay exactly what was asked for; "mesh" resolved through the
        registry already."""
        if self.coder_name not in ("auto", "jax"):
            return c
        try:
            from ..parallel import mesh_coder as mesh_mod
            n = mesh_mod.mesh_device_count()
            if n < 2:
                return c
            from ..ec.coder import PallasCoder
            method = "pallas" if isinstance(c, PallasCoder) else "bitplane"
            return mesh_mod.MeshCoder(g.data_shards, g.parity_shards,
                                      n_devices=n, method=method)
        except Exception as e:
            # a mesh that fails to build must never take encode offline
            # (the single-chip coder is always a correct fallback) — but
            # it must fail LOUDLY: the operator asked for a mesh, and a
            # silent fallback would leave them believing N chips are
            # encoding while one does
            from ..utils import glog
            glog.error("WEED_EC_MESH_DEVICES set but mesh coder "
                       "construction failed (%s: %s) — falling back to "
                       "the single-chip %s coder",
                       type(e).__name__, e, type(c).__name__)
            return c

    def geometry_for(self, collection: str = "") -> ec_mod.Geometry:
        """The policy geometry NEW encodes of this collection use."""
        return self.geometry_policy.for_collection(collection)

    def _resolve_geometry(self, base: str,
                          collection: str = "") -> ec_mod.Geometry:
        """The geometry an EXISTING shard set was encoded under: the
        .ecm sidecar's stamped record when present (authoritative — a
        policy change must never re-shape bytes already on disk),
        otherwise the collection policy."""
        from ..ec.striping import read_marker_geometry
        return (read_marker_geometry(base)
                or self.geometry_for(collection))

    # --- volume management ---
    def find_volume(self, vid: int) -> Optional[Volume]:
        for loc in self.locations:
            v = loc.volumes.get(vid)
            if v is not None:
                return v
        return None

    def find_ec_volume(self, vid: int) -> Optional[EcVolume]:
        for loc in self.locations:
            ev = loc.ec_volumes.get(vid)
            if ev is not None:
                return ev
        return None

    def has_volume(self, vid: int) -> bool:
        return self.find_volume(vid) is not None

    def add_volume(self, vid: int, collection: str = "",
                   replica_placement: str = "000", ttl: str = "",
                   version: int = t.CURRENT_VERSION) -> Volume:
        """AllocateVolume (weed/server/volume_grpc_admin.go)."""
        with self._lock:
            if self.find_volume(vid) is not None:
                raise ValueError(f"volume {vid} already exists")
            open_locs = [l for l in self.locations
                         if len(l.volumes) < l.max_volume_count]
            if not open_locs:
                raise RuntimeError("no free volume slots")
            loc = min(open_locs, key=lambda l: len(l.volumes))
            sb = SuperBlock(
                version=version,
                replica_placement=ReplicaPlacement.parse(replica_placement),
                ttl=t.TTL.parse(ttl))
            v = Volume(loc.directory, collection, vid, superblock=sb,
                       create=True,
                       needle_map_kind=self.needle_map_kind,
                       preallocate=self.preallocate)
            loc.volumes[vid] = v
            return v

    def delete_volume(self, vid: int) -> bool:
        with self._lock:
            for loc in self.locations:
                v = loc.volumes.pop(vid, None)
                if v is not None:
                    base = v.base_file_name()
                    v.close()
                    for ext in (".dat", ".idx", ".swm"):
                        if os.path.exists(base + ext):
                            os.remove(base + ext)
                    return True
        return False

    def mark_readonly(self, vid: int, read_only: bool = True) -> bool:
        v = self.find_volume(vid)
        if v is None:
            return False
        v.read_only = read_only
        # an explicit admin decision supersedes any watchdog seal
        v.watchdog_sealed = False
        return True

    def unmount_volume(self, vid: int) -> bool:
        """Close a volume and drop it from serving; files stay on disk
        (VolumeUnmount, weed/server/volume_grpc_admin.go)."""
        with self._lock:
            for loc in self.locations:
                v = loc.volumes.pop(vid, None)
                if v is not None:
                    v.close()
                    return True
        return False

    def mount_volume(self, vid: int, collection: str = "") -> Volume:
        """Load an on-disk volume back into serving (VolumeMount).
        Tiered volumes (no local .dat, a .vif sidecar) mount too."""
        with self._lock:
            if self.find_volume(vid) is not None:
                raise ValueError(f"volume {vid} already mounted")
            prefix = f"{collection}_" if collection else ""
            for loc in self.locations:
                base = os.path.join(loc.directory, f"{prefix}{vid}")
                if os.path.exists(base + ".dat") or \
                        os.path.exists(base + ".vif"):
                    v = Volume(loc.directory, collection, vid,
                               needle_map_kind=self.needle_map_kind)
                    loc.volumes[vid] = v
                    return v
        raise KeyError(f"volume {vid} not found on disk")

    def configure_replication(self, vid: int, replication: str) -> None:
        """Rewrite the superblock replica placement in place
        (VolumeConfigure, weed/server/volume_grpc_admin.go; superblock
        byte 1, super_block.go:12-31)."""
        v = self.find_volume(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        v.configure_replication(ReplicaPlacement.parse(replication))

    # --- cloud tier (volume_tier.go:15-50,
    #     volume_grpc_tier_upload/download.go) ---
    def tier_upload(self, vid: int, backend_spec: dict,
                    keep_local: bool = False) -> dict:
        """Move a sealed volume's .dat to an object store; the .idx stays
        local and reads proxy through the remote backend. Writes a `.vif`
        sidecar so the volume reloads tiered after restart."""
        from . import backend as backend_mod
        v = self.find_volume(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        if v.is_remote:
            raise ValueError(f"volume {vid} is already tiered")
        was_read_only = v.read_only
        v.read_only = True
        try:
            v.sync()
            base = v.base_file_name()
            store = backend_mod.open_store(backend_spec)
            key = f"{os.path.basename(base)}.dat"
            store.put(key, base + ".dat")
            size = os.path.getsize(base + ".dat")
            info = {"volume_id": vid, "version": v.version,
                    "files": [{"backend": store.spec(), "key": key,
                               "file_size": size,
                               "modified_at": int(os.path.getmtime(
                                   base + ".dat"))}]}
            backend_mod.save_volume_info(base, info)
        except Exception:
            # roll back the seal so the volume keeps taking writes
            v.read_only = was_read_only
            raise
        with v._lock:
            # swap the read handle; the OLD local file stays open (not
            # closed) so lock-free in-flight positioned reads that grabbed
            # the previous handle never hit a closed fd — the open fd also
            # keeps the unlinked file readable until volume close
            v._retired_dat = v._dat
            v._dat = backend_mod.RemoteFile(store, key, size)
        if not keep_local:
            os.remove(base + ".dat")
        return info

    def tier_download(self, vid: int) -> dict:
        """Bring a tiered volume's .dat back to local disk and drop the
        `.vif` (VolumeTierMoveDatFromRemote)."""
        from . import backend as backend_mod
        from .volume import Volume
        v = self.find_volume(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        if not v.is_remote:
            raise ValueError(f"volume {vid} is not tiered")
        base = v.base_file_name()
        info = backend_mod.load_volume_info(base)
        spec = info["files"][0]
        store = backend_mod.open_store(spec["backend"])
        store.get_to_file(spec["key"], base + ".dat")
        with self._lock:
            for loc in self.locations:
                if loc.volumes.get(vid) is v:
                    v.close()
                    os.remove(backend_mod.vif_path(base))
                    loc.volumes[vid] = Volume(
                        loc.directory, v.collection, vid,
                        needle_map_kind=self.needle_map_kind)
                    loc.volumes[vid].read_only = True
                    break
        return {"volume_id": vid, "bytes": spec["file_size"]}

    def needle_ids(self, vid: int) -> list[tuple[int, int]]:
        """Live (needle_id, size) pairs — the fsck inventory
        (weed/shell/command_volume_fsck.go collects the same via
        VolumeNeedleStatus/export)."""
        v = self.find_volume(vid)
        if v is not None:
            return v.nm.live_entries()
        ev = self.find_ec_volume(vid)
        if ev is not None:
            return ev.live_entries()
        raise KeyError(f"volume {vid} not found")

    # --- vacuum (VacuumVolume{Check,Compact,Commit,Cleanup},
    #     weed/server/volume_grpc_vacuum.go) ---
    def vacuum_check(self, vid: int) -> float:
        v = self.find_volume(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        return v.garbage_level()

    def vacuum_compact(self, vid: int,
                       compaction_bytes_per_second: int = 0) -> None:
        v = self.find_volume(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        v.begin_compact(compaction_bytes_per_second)

    def vacuum_commit(self, vid: int) -> None:
        v = self.find_volume(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        v.commit_compact()

    def vacuum_cleanup(self, vid: int) -> None:
        v = self.find_volume(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        v.cleanup_compact()

    def delete_expired_volumes(self, max_delay_minutes: int = 10) -> list[int]:
        """Drop TTL volumes whose grace period has passed
        (Store.DeleteExpiredVolumes semantics)."""
        expired = [vid for loc in self.locations
                   for vid, v in list(loc.volumes.items())
                   if v.is_expired() and
                   v.is_expired_long_enough(max_delay_minutes)]
        for vid in expired:
            self.delete_volume(vid)
        return expired

    # --- needle ops ---
    def write_needle(self, vid: int, n: Needle) -> tuple[int, int, bool]:
        v = self.find_volume(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        return v.write_needle(n)

    def read_needle(self, vid: int, needle_id: int,
                    cookie: Optional[int] = None, located=None) -> Needle:
        """`located`: what `read_ec_needle_nowait` found before it
        declined, so that the EC volume's index is searched once."""
        v = self.find_volume(vid)
        if v is not None:
            return v.read_needle(needle_id, cookie=cookie)
        ev = self.find_ec_volume(vid)
        if ev is not None:
            return ev.read_needle(needle_id, cookie=cookie,
                                  shard_reader=self._remote_shard_reader(ev),
                                  located=located)
        raise KeyError(f"volume {vid} not found")

    def read_ec_needle_nowait(self, vid: int, needle_id: int,
                              cookie: Optional[int] = None):
        """`EcVolume.read_needle_nowait` for an event loop's thread, at
        its own limit (`ec_volume.NOWAIT_MAX_SIZE`): (needle, None), or
        (None, located) for "use `read_needle`"."""
        ev = self.find_ec_volume(vid)
        if ev is None:
            return None, None
        return ev.read_needle_nowait(needle_id, cookie)

    def delete_needle(self, vid: int, n: Needle) -> int:
        v = self.find_volume(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        return v.delete_needle(n)

    # hook the server layer overrides to fetch shards from peers
    def _remote_shard_reader(self, ev: EcVolume):
        return None

    # --- EC lifecycle (VolumeEcShardsGenerate etc.,
    #     weed/server/volume_grpc_erasure_coding.go) ---
    def _ec_seal(self, vid: int):
        """Seal a volume for encoding; returns (volume, base, geometry)."""
        v = self.find_volume(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        with observe.stage("ec.seal"):
            v.read_only = True
            v.sync()
        g = self.geometry_for(v.collection)
        # a server that generates shards is about to mount them: the
        # widths a degraded read can meet compile beside the encode, on
        # the coder's own thread (`ec_mount` has the rest)
        self.coder(g).warm_widths()
        return v, v.base_file_name(), g

    def _ec_finish_generate(self, v, base: str,
                            g: ec_mod.Geometry) -> list[int]:
        with observe.stage("ec.ecx"):
            ec_mod.write_sorted_ecx_from_idx(base,
                                             offset_size=v.offset_size)
        # record per-shard digests into the .ecm while the bytes are
        # known-good — the EC scrubber's bit-rot reference
        with observe.stage("ec.stamp"):
            ec_pipeline.stamp_shard_digests(base, g)
        return list(range(g.total_shards))

    def ec_generate(self, vid: int) -> list[int]:
        # `ec.generate` encloses a warm-down from seal to stamp: its
        # seconds are the time one was in flight, its count the passes
        with observe.stage("ec.generate", observe.capture(),
                           enclosing=True):
            v, base, g = self._ec_seal(vid)
            # streaming pipeline: overlapped disk read / H2D / kernel /
            # shard write-back (ec/pipeline.py) — byte-identical to the
            # synchronous write_ec_files layout; geometry follows the
            # collection policy and is stamped into the .ecm for
            # rebuild/mount/decode
            ec_pipeline.stream_encode(base, self.coder(g), g)
            return self._ec_finish_generate(v, base, g)

    def ec_generate_many(self, vids: list[int]) -> dict[int, list[int]]:
        """Encode a WINDOW of volumes back-to-back: all volumes of one
        geometry stream through a single governed operating point (and
        therefore one compiled [k, B] executable — see
        pipeline.stream_encode_many), which is how the lifecycle
        daemon's encode queue amortizes program loads across a batch
        instead of paying one per volume."""
        # validate the whole window BEFORE sealing anything: one missing
        # vid must fail the batch cleanly, not leave the other volumes
        # sealed read-only with no shards to show for it
        absent = [vid for vid in vids if self.find_volume(vid) is None]
        if absent:
            raise KeyError(f"volume(s) {absent} not found")
        # one `ec.generate` a window: it seals all, encodes all and then
        # finishes all, so no one volume's seal-to-stamp stands apart
        with observe.stage("ec.generate", observe.capture(),
                           tags={"volumes": len(vids)}, enclosing=True):
            by_geometry: dict[ec_mod.Geometry, list] = {}
            sealed: list = []
            for vid in vids:
                was_read_only = self.find_volume(vid).read_only
                v, base, g = self._ec_seal(vid)
                by_geometry.setdefault(g, []).append((vid, v, base))
                sealed.append((v, base, was_read_only))
            out: dict[int, list[int]] = {}
            try:
                for g, items in by_geometry.items():
                    ec_pipeline.stream_encode_many(
                        [base for _, _, base in items], self.coder(g), g)
                    for vid, v, base in items:
                        out[vid] = self._ec_finish_generate(v, base, g)
            except BaseException:
                # a mid-window failure must not leave the REST of the
                # batch sealed with nothing to show for it: lift seals we
                # applied on volumes whose encode never completed
                # (stream_encode writes the .ecm marker only at the end
                # of each volume)
                for v, base, was_read_only in sealed:
                    if not was_read_only \
                            and not os.path.exists(base + ".ecm"):
                        v.read_only = False
                raise
            return out

    # --- fused warm-down: compact + gzip + RS + digest in one pass ---

    def _ec_fused_promote(self, base: str, staging: str,
                          g: ec_mod.Geometry) -> None:
        """Move a completed fused pass's shard set from its staging base
        to the volume's base. Every staged file is already fsynced (the
        fused pass orders its own durability), so promotion is renames:
        shards first, then .ecx, and the .ecm marker LAST — the marker
        rename is the commit point that makes the set mountable. The
        compacted .dat/.idx were only the encode vehicle (EC reads ride
        shards + .ecx; un-EC rebuilds a .dat from shards) and are
        dropped; the SOURCE volume files are untouched, so the PR 7
        verify-then-retire discipline still holds: until the lifecycle
        daemon verifies mounted shards and retires the original, both
        copies exist."""
        for i in range(g.total_shards):
            durable.replace_atomic(staging + ec_mod.to_ext(i),
                                   base + ec_mod.to_ext(i))
        durable.replace_atomic(staging + ".ecx", base + ".ecx")
        for ext in (".dat", ".idx"):
            try:
                os.remove(staging + ext)
            except OSError:
                pass
        durable.replace_atomic(staging + ".ecm", base + ".ecm")

    def _ec_fused_clean_staging(self, base: str,
                                g: ec_mod.Geometry) -> None:
        """Drop stale staging files a crashed prior pass left behind
        (they are uncommitted by construction — no .ecm at the volume
        base — so a re-run just starts over)."""
        staging = base + ".fusing"
        for ext in ([".dat", ".idx", ".ecx", ".ecm"]
                    + [ec_mod.to_ext(i) for i in range(g.total_shards)]):
            try:
                os.remove(staging + ext)
            except OSError:
                pass

    def ec_fused_generate(self, vid: int) -> list[int]:
        """One-pass warm-down (ec/fused.py): compaction, payload gzip,
        RS encode and shard digests in a single fused pass — the shard
        set encodes the COMPACTED volume, so tombstoned bytes never
        reach the archive tier and no separate vacuum precedes the
        encode. Output promotes to the volume base only after the whole
        pass is durable."""
        v, base, g = self._ec_seal(vid)
        self._ec_fused_clean_staging(base, g)
        staging = base + ".fusing"
        ec_fused.fused_vacuum_gzip_encode(v, staging, self.coder(g), g)
        self._ec_fused_promote(base, staging, g)
        return list(range(g.total_shards))

    def ec_fused_generate_many(self, vids: list[int]) -> dict[int,
                                                              list[int]]:
        """Fused warm-down for a WINDOW of volumes: one governed
        operating point (and one compiled [k, B] executable) per
        geometry group — the fused twin of ec_generate_many."""
        absent = [vid for vid in vids if self.find_volume(vid) is None]
        if absent:
            raise KeyError(f"volume(s) {absent} not found")
        by_geometry: dict[ec_mod.Geometry, list] = {}
        sealed: list = []
        for vid in vids:
            was_read_only = self.find_volume(vid).read_only
            v, base, g = self._ec_seal(vid)
            self._ec_fused_clean_staging(base, g)
            by_geometry.setdefault(g, []).append((vid, v, base))
            sealed.append((v, base, was_read_only))
        out: dict[int, list[int]] = {}
        try:
            for g, items in by_geometry.items():
                ec_fused.fused_vacuum_gzip_encode_many(
                    [v for _, v, _ in items],
                    [base + ".fusing" for _, _, base in items],
                    self.coder(g), g)
                for vid, v, base in items:
                    self._ec_fused_promote(base, base + ".fusing", g)
                    out[vid] = list(range(g.total_shards))
        except BaseException:
            # mirror ec_generate_many: volumes whose shard set never
            # committed get their seal lifted so the batch can retry
            for v, base, was_read_only in sealed:
                if not was_read_only and not os.path.exists(base + ".ecm"):
                    v.read_only = False
            raise
        return out

    def ec_mount(self, vid: int, collection: str,
                 shard_ids: list[int]) -> list[int]:
        with observe.stage("ec.mount"), self._lock:
            ev = self.find_ec_volume(vid)
            if ev is None:
                loc = self._location_with_ec_files(vid, collection)
                prefix = f"{collection}_" if collection else ""
                g = self._resolve_geometry(
                    os.path.join(loc.directory, f"{prefix}{vid}"),
                    collection)
                ev = EcVolume(loc.directory, collection, vid, g,
                              coder=self.coder(g))
                loc.ec_volumes[vid] = ev
            mounted = [sid for sid in shard_ids if ev.add_shard(sid)]
        # the widths a degraded read of this geometry can meet compile
        # from the store's first generate or mount of it on, on the
        # coder's own thread and not under the lock; every later call
        # finds that begun
        ev.coder.warm_widths()
        return mounted

    def _location_with_ec_files(self, vid: int, collection: str):
        prefix = f"{collection}_" if collection else ""
        for loc in self.locations:
            if os.path.exists(os.path.join(loc.directory,
                                           f"{prefix}{vid}.ecx")):
                return loc
        raise KeyError(f"no .ecx for volume {vid} in any location")

    def ec_unmount(self, vid: int, shard_ids: list[int]) -> list[int]:
        with self._lock:
            ev = self.find_ec_volume(vid)
            if ev is None:
                return []
            removed = [sid for sid in shard_ids if ev.delete_shard(sid)]
            if not ev.shard_ids():
                for loc in self.locations:
                    loc.ec_volumes.pop(vid, None)
                ev.close()
            return removed

    def ec_shard_read(self, vid: int, shard_id: int, offset: int,
                      size: int) -> bytes:
        ev = self.find_ec_volume(vid)
        if ev is None:
            raise KeyError(f"ec volume {vid} not found")
        shard = ev.shards.get(shard_id)
        if shard is None:
            raise KeyError(f"shard {vid}.{shard_id} not here")
        return shard.read_at(offset, size)

    def ec_shard_slice(self, vid: int, shard_id: int, offset: int,
                       size: int) -> Optional[bytes]:
        """`ec_shard_read` for an event loop's thread (`EcShard.slice_at`:
        never a system call), or None for "use `ec_shard_read`": no such
        volume or shard here, no mapping, a range not wholly in the
        file."""
        ev = self.find_ec_volume(vid)
        shard = ev.shards.get(shard_id) if ev is not None else None
        return shard.slice_at(offset, size) if shard is not None else None

    def ec_rebuild(self, vid: int, collection: str = "") -> list[int]:
        loc = self._location_with_ec_files(vid, collection)
        prefix = f"{collection}_" if collection else ""
        base = os.path.join(loc.directory, f"{prefix}{vid}")
        # geometry from the .ecm record, NOT the live policy: rebuilding
        # a RS(20,4) archive volume under a since-changed default would
        # reconstruct garbage
        g = self._resolve_geometry(base, collection)
        rebuilt = ec_pipeline.stream_rebuild(base, self.coder(g), g)
        ev = self.find_ec_volume(vid)
        ec_mod.rebuild_ecx_file(
            base, offset_size=(ev.offset_size if ev is not None
                               else t.OFFSET_SIZE))
        # merge-only stamp: freshly reconstructed shards get their digest
        # recorded; already-stamped ids keep the encode-time value
        ec_pipeline.stamp_shard_digests(base, g)
        return rebuilt

    def ec_blob_delete(self, vid: int, needle_id: int) -> None:
        ev = self.find_ec_volume(vid)
        if ev is None:
            raise KeyError(f"ec volume {vid} not found")
        ev.delete_needle(needle_id)

    def ec_delete_shards(self, vid: int, collection: str,
                         shard_ids: list[int]) -> None:
        self.ec_unmount(vid, shard_ids)
        prefix = f"{collection}_" if collection else ""
        for loc in self.locations:
            base = os.path.join(loc.directory, f"{prefix}{vid}")
            for sid in shard_ids:
                p = base + ec_mod.to_ext(sid)
                if os.path.exists(p):
                    os.remove(p)

    def ec_to_volume(self, vid: int, collection: str = "") -> None:
        """ShardsToVolume: decode local data shards back into a normal volume
        (weed/server/volume_grpc_erasure_coding.go:331-391)."""
        with self._lock:
            loc = self._location_with_ec_files(vid, collection)
            prefix = f"{collection}_" if collection else ""
            base = os.path.join(loc.directory, f"{prefix}{vid}")
            ev0 = loc.ec_volumes.get(vid)
            w = ev0.offset_size if ev0 is not None else t.OFFSET_SIZE
            dat_size = ec_mod.find_dat_file_size(base, t.CURRENT_VERSION,
                                                 offset_size=w)
            ec_mod.write_dat_file(base, dat_size,
                                  self._resolve_geometry(base, collection))
            ec_mod.write_idx_file_from_ec_index(base, offset_size=w)
            ev = loc.ec_volumes.pop(vid, None)
            if ev is not None:
                ev.close()
            loc.volumes[vid] = Volume(
                loc.directory, collection, vid,
                needle_map_kind=self.needle_map_kind)

    # --- heartbeat ---
    def heartbeat(self) -> dict:
        """The payload sent to the master (CollectHeartbeat,
        weed/storage/store.go:198)."""
        volumes = []
        ec_shards = []
        max_file_key = 0
        for loc in self.locations:
            for vid, v in loc.volumes.items():
                max_file_key = max(max_file_key, v.nm.maximum_key)
                volumes.append({
                    "id": vid,
                    "collection": v.collection,
                    "size": v.data_file_size(),
                    "file_count": v.file_count(),
                    "delete_count": v.nm.deleted_count,
                    "deleted_bytes": v.nm.deleted_byte_count,
                    "read_only": v.read_only,
                    "replica_placement": str(
                        v.super_block.replica_placement),
                    "ttl": str(v.super_block.ttl),
                    "version": v.version,
                    # newest write (unix s): the master lifecycle
                    # daemon's TTL expiry reference
                    "last_modified": v.last_modified_ts,
                })
            for vid, ev in loc.ec_volumes.items():
                ec_shards.append({
                    "id": vid,
                    "collection": ev.collection,
                    "shard_ids": ev.shard_ids(),
                    "shard_size": ev.shard_size(),
                })
        return {
            "volumes": volumes,
            "ec_shards": ec_shards,
            "max_file_key": max_file_key,
            "max_volume_count": sum(l.max_volume_count
                                    for l in self.locations),
        }

    def status(self) -> dict:
        hb = self.heartbeat()
        return {"volumes": hb["volumes"], "ec_shards": hb["ec_shards"]}

    def close(self) -> None:
        for loc in self.locations:
            for v in loc.volumes.values():
                v.close()
            for ev in loc.ec_volumes.values():
                ev.close()
            loc.volumes.clear()
            loc.ec_volumes.clear()
