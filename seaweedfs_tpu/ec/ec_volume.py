"""EC volume serving: read needles straight out of shard files.

Mirrors the reference serving path (weed/storage/erasure_coding/ec_volume.go,
ec_shard.go, ec_volume_delete.go and weed/storage/store_ec.go:122-376):

- .ecx (entries sorted by needle id) is binary-searched per lookup in a
  shared read-only mapping of the file: no syscall a probe, and no copy
  of the index on the Python side; the file stays the truth (a tombstone
  is a pwrite through the descriptor, seen through the mapping at once).
  One os.pread a probe where the file cannot be mapped or WEED_EC_MMAP=0
- a needle decomposes into intervals (locate.py); each interval is read from
  the local shard file when present, fetched from a peer when not, or
  reconstructed on line from any k shards as the last resort
- deletes tombstone the .ecx entry in place and append the id to .ecj

Remote access is abstracted as `shard_reader(shard_id, offset, size) ->
bytes | None`; the server layer plugs gRPC fetches in, tests plug files.
"""

from __future__ import annotations

import mmap
import os
import struct
import threading
import time
from concurrent.futures import ThreadPoolExecutor, as_completed
from typing import Callable, Optional

import numpy as np

from .. import observe
from ..cache import Singleflight
from ..storage import idx as idx_mod
from ..storage import types as t
from ..storage.needle import Needle
from ..storage.superblock import SuperBlock
from ..utils import metrics as metrics_mod
from .coder import ErasureCoder

# shared fan-out pool for parallel remote-survivor fetches; sized for one
# reconstruction's worth of peers, shared across volumes
_SURVIVOR_POOL = ThreadPoolExecutor(max_workers=14,
                                    thread_name_prefix="ec-survivor")
from .geometry import DEFAULT, Geometry, to_ext
from .locate import Interval, locate_data

ShardReader = Callable[[int, int, int], Optional[bytes]]
# what `EcVolume.locate` gives: (stored offset, size, intervals)
Located = tuple[int, int, list[Interval]]

# .ecx lookups by the way an entry was fetched, on the volume server's
# /metrics (seaweedfs_tpu_volume_ecx_lookups_total{via=}); the label
# dicts are made once, a lookup only counts
_LOOKUPS = metrics_mod.shared("volume")
_KEY = struct.Struct(">Q")  # an entry starts with its needle id
_VIA_MMAP = {"via": "mmap"}
_VIA_PREAD = {"via": "pread"}


def _map_shared(f, size: int) -> Optional[mmap.mmap]:
    """`f`'s first `size` bytes mapped shared and read-only (one
    page-cache copy, what the file's writers do is seen at once), or None:
    an empty file, WEED_EC_MMAP=0, a file that cannot be mapped."""
    from .feed import use_mmap_default
    if not size or not use_mmap_default():
        return None
    try:
        return mmap.mmap(f.fileno(), size, mmap.MAP_SHARED, mmap.PROT_READ)
    except (OSError, ValueError):
        return None


# The largest stored needle `read_needle_nowait` serves on the event
# loop's thread. It guards one thing: how long one GET holds that thread.
# Measured on the chip's host (`scripts/ec_loop_hold.py`, PR 35: one
# connection, an idle server, 2,020 GETs a size; the median of three
# runs, 1 KiB from one), ms a GET:
#
#   needle    held    of it the read    the same GET through the executor
#   1 KiB     0.138       0.016                   0.342
#   64 KiB    0.199       0.039                   0.404
#   128 KiB   0.243       0.062                   0.454
#   256 KiB   0.333       0.124  (0.123-0.124)    0.591
#   512 KiB   0.522       0.249  (0.229-0.263)    0.795
#   1 MiB     0.983       0.554                   1.245
#
# "held" is `ec.get.ecx` + `.shard_read` + `.parse` + `.resume` of a
# served GET. The search and `respond` (etag, head, body into the
# transport) run on the loop's thread whichever thread reads, and hold
# it for 0.12 ms at 1 KiB, so the limit decides only where "the read"
# runs (`.shard_read` + `.parse`: the slices, the join, the CRC).
# It is the largest power of two at which the read stays under 0.15 ms,
# a quarter of the 0.66 ms of `ec.get.queue` + `ec.get.resume` that the
# hand-off cost a 64 KB GET under load (PERF.md §5-§6, PR 35). Haystack's
# 65,536-byte photo is stored as 65,541; a filer's 1 MiB chunk keeps the
# executor.
NOWAIT_MAX_SIZE = 256 * 1024


class _Unmapped(Exception):
    """An interval `read_needle_nowait` cannot slice: it declines."""


def _unmap(mm: Optional[mmap.mmap]) -> None:
    if mm is not None:
        try:
            mm.close()
        except BufferError:
            pass


class EcShard:
    """One local .ecNN file (EcVolumeShard, ec_shard.go:16-95).

    Reads come off a shared read-only mmap when available (one page-cache
    copy, no syscall per interval — the serving-path twin of the encode
    feed in ec/feed.py, same WEED_EC_MMAP switch); os.pread is the
    fallback and the out-of-bounds path."""

    def __init__(self, base_file_name: str, shard_id: int):
        self.shard_id = shard_id
        self.path = base_file_name + to_ext(shard_id)
        self._f = open(self.path, "rb")
        self.size = os.path.getsize(self.path)
        self._mm = _map_shared(self._f, self.size)

    def slice_at(self, offset: int, size: int) -> Optional[bytes]:
        """The bytes out of the mapping, never a system call; None where
        there is no mapping, the range is not wholly inside the file, or
        the mapping is closed under the slice (the shard is being
        unmounted)."""
        mm = self._mm
        if mm is None or offset < 0 or offset + size > self.size:
            return None
        try:
            return mm[offset:offset + size]
        except ValueError:
            return None

    def read_at(self, offset: int, size: int) -> bytes:
        data = self.slice_at(offset, size)
        if data is not None:
            return data
        # positioned read: no shared seek state, safe under concurrency;
        # short reads past EOF keep the reference semantics
        return os.pread(self._f.fileno(), size, offset)

    def close(self) -> None:
        _unmap(self._mm)
        self._mm = None
        self._f.close()


class EcVolume:
    def __init__(self, directory: str, collection: str, vid: int,
                 geometry: Geometry = DEFAULT,
                 coder: Optional[ErasureCoder] = None):
        self.dir = directory
        self.collection = collection
        self.vid = vid
        self.g = geometry
        self.coder = coder
        self.shards: dict[int, EcShard] = {}
        # shard size learned from a peer, for volumes served with no local
        # shards (the reference assumes Shards[0] exists, ec_volume.go:198)
        self.remote_shard_size = 0
        self._layout_checked = False
        self._lock = threading.RLock()
        # concurrent cold reads of one missing interval collapse into a
        # single peer fetch / reconstruction (a reconstruct reads k
        # shards and runs the coder — the most expensive read this
        # server can serve)
        self.read_flight = Singleflight("ec.read")

        base = self.base_file_name()
        if not os.path.exists(base + ".ecx"):
            raise FileNotFoundError(base + ".ecx")
        self._ecx = open(base + ".ecx", "r+b")
        self.ecx_size = os.path.getsize(base + ".ecx")
        # lookups read the index where it lies in the page cache; writes
        # (tombstones, here and in rebuild_ecx_file) go through a
        # descriptor of the same inode and show in the mapping at once.
        # Whoever replaces the index of a mounted volume must swap the
        # path and never truncate the inode: a probe past the new end of
        # a mapped file is a SIGBUS (ec/copy leaves a mounted volume's
        # index alone for that)
        self._ecx_mm = _map_shared(self._ecx, self.ecx_size)
        self._ecj = open(base + ".ecj", "a+b")
        # volume version comes from the superblock at the head of .ec00
        # (readEcVolumeVersion, ec_decoder.go:73-90); default v3 if absent
        self.version = t.CURRENT_VERSION
        self.offset_size = t.OFFSET_SIZE
        ec00 = base + to_ext(0)
        if os.path.exists(ec00):
            with open(ec00, "rb") as f:
                head = f.read(8)
            if len(head) == 8:
                sb = SuperBlock.from_bytes(head)
                self.version = sb.version
                self.offset_size = sb.offset_size
        self._entry_size = t.needle_map_entry_size(self.offset_size)

    def base_file_name(self) -> str:
        prefix = f"{self.collection}_" if self.collection else ""
        return os.path.join(self.dir, f"{prefix}{self.vid}")

    # --- shard management ---
    def add_shard(self, shard_id: int) -> bool:
        with self._lock:
            if shard_id in self.shards:
                return False
            self.shards[shard_id] = EcShard(self.base_file_name(), shard_id)
            return True

    def delete_shard(self, shard_id: int) -> bool:
        with self._lock:
            shard = self.shards.pop(shard_id, None)
            if shard is None:
                return False
            shard.close()
            return True

    def shard_ids(self) -> list[int]:
        return sorted(self.shards)

    def shard_size(self) -> int:
        for s in self.shards.values():
            return s.size
        return self.remote_shard_size

    def live_entries(self) -> list[tuple[int, int]]:
        """Live (needle_id, size) pairs from the sorted .ecx, skipping
        tombstones (the fsck inventory for EC volumes)."""
        with self._lock:
            index = self._ecx_mm
            if index is None:
                index = os.pread(self._ecx.fileno(), self.ecx_size, 0)
            return [(key, size) for key, _, size
                    in idx_mod.iter_index_bytes(index, self.offset_size)
                    if not t.size_is_deleted(size)]

    # --- index lookup ---
    def find_needle(self, needle_id: int) -> tuple[int, int]:
        """(stored_offset, size) via binary search of the sorted index
        (SearchNeedleFromSortedIndex, ec_volume.go:210-235)."""
        return self._search(needle_id)

    def _search(self, needle_id: int,
                on_found: Optional[Callable[[int], None]] = None
                ) -> tuple[int, int]:
        """One bisect, two ways to fetch an entry: in place in the
        mapping, which never gives the GIL away, or 16-17 bytes by one
        os.pread a probe, which does every time. A probe reads the key;
        offset and size are read at the entry found, now, so a tombstone
        written a moment ago is what comes back."""
        mm, fd, width = self._ecx_mm, self._ecx.fileno(), self._entry_size
        _LOOKUPS.count("ecx_lookups",
                       labels=_VIA_PREAD if mm is None else _VIA_MMAP)
        key_at = _KEY.unpack_from
        buf, off = mm, 0
        lo, hi = 0, self.ecx_size // width
        while lo < hi:
            mid = (lo + hi) // 2
            at = mid * width
            if mm is None:
                buf = os.pread(fd, width, at)
            else:
                off = at
            key = key_at(buf, off)[0]
            if key == needle_id:
                _, offset, size = idx_mod.unpack_entry(
                    buf, off, self.offset_size)
                if on_found is not None:
                    on_found(at)
                return offset, size
            if key < needle_id:
                lo = mid + 1
            else:
                hi = mid
        raise KeyError(f"needle {needle_id:x} not in ec volume {self.vid}")

    def locate(self, needle_id: int) -> Located:
        """(offset, size, intervals) for a needle
        (LocateEcShardNeedle, ec_volume.go:190-204)."""
        offset, size = self.find_needle(needle_id)
        if t.size_is_deleted(size):
            return offset, size, []
        shard_size = self.shard_size()
        if shard_size == 0:
            raise IOError(
                f"ec volume {self.vid}: shard size unknown (no local shards; "
                f"set remote_shard_size before serving remote-only reads)")
        if not self._layout_checked:
            from .striping import check_layout_marker
            check_layout_marker(self.base_file_name(), shard_size, self.g)
            self._layout_checked = True
        dat_size = self.g.data_shards * shard_size
        intervals = locate_data(
            self.g, dat_size, t.stored_to_offset(offset),
            t.get_actual_size(size, self.version))
        return offset, size, intervals

    # --- read path ---
    def read_needle(self, needle_id: int, cookie: Optional[int] = None,
                    shard_reader: Optional[ShardReader] = None,
                    located: Optional[Located] = None) -> Needle:
        """One EC needle read. Its parts are observe stages (`ec.get.*`:
        PERF.md has the table), each exclusive of the others, under
        whatever request context is ambient. `located` is what a
        `read_needle_nowait` that declined had found: the index is then
        not searched again."""
        return self._read(
            needle_id, cookie, located or self._locate_live(needle_id),
            lambda iv: self._read_interval(iv, shard_reader))

    def read_needle_nowait(self, needle_id: int,
                           cookie: Optional[int] = None,
                           max_size: int = NOWAIT_MAX_SIZE
                           ) -> tuple[Optional[Needle], Optional[Located]]:
        """`read_needle` for a caller on the event loop's thread, the twin
        of `Volume.read_needle_nowait`: (needle, None) when everything
        the read needs is in this process's address space (the index
        mapped, a stored needle of at most `max_size`, which bounds how
        long the GET holds the loop: `NOWAIT_MAX_SIZE`, every interval
        inside the mapped file of a shard mounted here), and then no
        system call, no lock waited for, nothing that gives the GIL away.
        Otherwise it declines, (None, located): `read_needle`, on a
        thread that may block, takes `located` (None where the index was
        not searched). Raises what `read_needle` raises."""
        if self._ecx_mm is None or not self._layout_checked:
            return None, None  # a search of preads; the marker's first read
        located = self._locate_live(needle_id)
        if located[1] > max_size:
            return None, located
        try:
            return self._read(needle_id, cookie, located,
                              self._slice_interval), None
        except _Unmapped:
            return None, located

    def _locate_live(self, needle_id: int) -> Located:
        with observe.stage("ec.get.ecx"):
            located = self.locate(needle_id)
        if t.size_is_deleted(located[1]):
            raise KeyError(f"needle {needle_id:x} deleted")
        return located

    def _read(self, needle_id: int, cookie: Optional[int],
              located: Located,
              read_interval: Callable[[Interval], bytes]) -> Needle:
        parts = [read_interval(iv) for iv in located[2]]
        with observe.stage("ec.get.parse"):
            n = Needle.from_bytes(b"".join(parts), self.version)
            if cookie is not None and n.cookie != cookie:
                raise KeyError(f"needle {needle_id:x} cookie mismatch")
        return n

    def _slice_interval(self, iv: Interval) -> bytes:
        """A present interval out of its shard's mapping; `_Unmapped`
        where the shard is not mounted here or `slice_at` has nothing."""
        shard_id, offset = iv.to_shard_id_and_offset(self.g)
        shard = self.shards.get(shard_id)
        if shard is None:
            raise _Unmapped
        with observe.stage("ec.get.shard_read"):
            data = shard.slice_at(offset, iv.size)
        if data is None:
            raise _Unmapped
        return data

    def _read_interval(self, iv: Interval,
                       shard_reader: Optional[ShardReader]) -> bytes:
        shard_id, offset = iv.to_shard_id_and_offset(self.g)
        shard = self.shards.get(shard_id)
        if shard is not None:
            with observe.stage("ec.get.shard_read"):
                data = shard.read_at(offset, iv.size)
            if len(data) == iv.size:
                return data
        # non-local interval: peer fetch or (worst case) an on-line
        # reconstruction from k shards — N concurrent readers of the
        # same cold interval share one flight
        led = []

        def fetch() -> bytes:
            led.append(True)
            if shard_reader is not None:
                # a holder of the shard itself, its location looked up
                with observe.stage("ec.get.peer_fetch"):
                    data = shard_reader(shard_id, offset, iv.size)
                if data is not None and len(data) == iv.size:
                    return data
            return self._reconstruct_interval(shard_id, offset, iv.size,
                                              shard_reader)

        t0 = time.perf_counter()
        data = self.read_flight.do((shard_id, offset, iv.size), fetch)
        if not led:
            # another GET's flight brought the interval: all of do() was
            # the wait for it
            waited = time.perf_counter() - t0
            observe.record_span(
                "ec.get.flight_wait", None,
                int((time.time() - waited) * 1e6), int(waited * 1e6))
        return data

    def _reconstruct_interval(self, missing_shard: int, offset: int,
                              size: int,
                              shard_reader: Optional[ShardReader]) -> bytes:
        """Online reconstruction of one interval from any k other shards.
        Local shards are read inline; remote survivors are fetched in
        parallel, matching the reference's goroutine fan-out
        (recoverOneRemoteEcShardInterval, store_ec.go:322-376)."""
        if self.coder is None:
            raise IOError(
                f"shard {missing_shard} missing and no coder to reconstruct")
        shards: list[Optional[np.ndarray]] = [None] * self.g.total_shards
        with observe.stage("ec.get.survivors"):
            have = 0
            remote_candidates: list[int] = []
            for sid in range(self.g.total_shards):
                if sid == missing_shard:
                    continue
                local = self.shards.get(sid)
                if local is not None and have < self.g.data_shards:
                    b = local.read_at(offset, size)
                    if len(b) == size:
                        shards[sid] = np.frombuffer(b, dtype=np.uint8)
                        have += 1
                        continue
                remote_candidates.append(sid)
            need = self.g.data_shards - have
            if need > 0 and shard_reader is not None and remote_candidates:
                # each fetch under the request's trace; taken as they
                # complete, so that a shard nobody holds (its reader
                # asks the master before it answers None) never holds
                # up the survivors that are already in hand
                ctx = observe.capture()
                futs = {_SURVIVOR_POOL.submit(observe.run_with, ctx,
                                              shard_reader, sid, offset,
                                              size): sid
                        for sid in remote_candidates}
                for fut in as_completed(futs):
                    try:
                        b = fut.result()
                    except Exception:
                        continue
                    if b is not None and len(b) == size:
                        shards[futs[fut]] = np.frombuffer(b, dtype=np.uint8)
                        have += 1
                        if have >= self.g.data_shards:
                            break
                for fut in futs:
                    fut.cancel()
        if have < self.g.data_shards:
            raise IOError(
                f"cannot reconstruct shard {missing_shard}: "
                f"only {have} of {self.g.data_shards} shards reachable")
        rebuilt = self.coder.reconstruct(shards, targets=(missing_shard,),
                                         stage="ec.get")
        reg = metrics_mod.shared("ec")
        reg.count("reconstruct_intervals")
        reg.count("reconstruct_bytes", value=size)
        return np.asarray(rebuilt[missing_shard]).tobytes()

    # --- delete path ---
    def delete_needle(self, needle_id: int) -> None:
        """Tombstone in .ecx + journal to .ecj
        (DeleteNeedleFromEcx, ec_volume_delete.go:27-49)."""
        with self._lock:
            def mark(entry_offset: int) -> None:
                os.pwrite(self._ecx.fileno(),
                          t.put_u32(t.size_to_u32(t.TOMBSTONE_FILE_SIZE)),
                          entry_offset + t.NEEDLE_ID_SIZE
                          + self.offset_size)

            try:
                self._search(needle_id, on_found=mark)
            except KeyError:
                return
            self._ecj.seek(0, os.SEEK_END)
            self._ecj.write(t.put_u64(needle_id))
            self._ecj.flush()

    def close(self) -> None:
        with self._lock:
            for shard in self.shards.values():
                shard.close()
            self.shards.clear()
            _unmap(self._ecx_mm)
            self._ecx_mm = None
            self._ecx.close()
            self._ecj.close()


def rebuild_ecx_file(base_file_name: str,
                     offset_size: int = t.OFFSET_SIZE) -> None:
    """Re-apply .ecj tombstones into .ecx after a rebuild, then drop .ecj
    (RebuildEcxFile, ec_volume_delete.go:51-97)."""
    ecj_path = base_file_name + ".ecj"
    if not os.path.exists(ecj_path):
        return
    entry_size = t.needle_map_entry_size(offset_size)
    ecx_size = os.path.getsize(base_file_name + ".ecx")
    with open(base_file_name + ".ecx", "r+b") as ecx, \
            open(ecj_path, "rb") as ecj:
        while True:
            b = ecj.read(t.NEEDLE_ID_SIZE)
            if len(b) != t.NEEDLE_ID_SIZE:
                break
            needle_id = t.get_u64(b)
            lo, hi = 0, ecx_size // entry_size
            while lo < hi:
                mid = (lo + hi) // 2
                ecx.seek(mid * entry_size)
                key, _, _ = idx_mod.unpack_entry(
                    ecx.read(entry_size), offset_size=offset_size)
                if key == needle_id:
                    ecx.seek(mid * entry_size
                             + t.NEEDLE_ID_SIZE + offset_size)
                    ecx.write(t.put_u32(t.size_to_u32(t.TOMBSTONE_FILE_SIZE)))
                    break
                if key < needle_id:
                    lo = mid + 1
                else:
                    hi = mid
    os.remove(ecj_path)
