"""`EcVolume.read_needle_nowait`: the EC read an event loop's thread may
make itself, the twin of `Volume.read_needle_nowait`.

It serves a needle only when everything the read needs is in this
process's address space (the index mapped, every interval inside the
mapped file of a shard mounted here, a stored needle of at most
`max_size`, by default `NOWAIT_MAX_SIZE`), byte for byte as `read_needle`
does and raising what it raises; otherwise it declines, having called
nothing that can block, and hands on what it located so that
`read_needle` searches the index once.
"""

import os

import pytest

from seaweedfs_tpu import ec, observe
from seaweedfs_tpu.ec import ec_volume as ec_volume_mod
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.needle import CrcError, Needle
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.utils import metrics as metrics_mod

GEO = ec.Geometry(data_shards=10, parity_shards=4,
                  large_block_size=10000, small_block_size=100)
IDS = list(range(5, 5 + 3 * 40, 3))
DELETED = IDS[20]
BIG = IDS[-1] + 3  # a needle over any `max_size` a test passes
COOKIE = 0x4000


def _payload(i: int) -> bytes:
    return bytes([i % 251]) * (30 + i % 90)


@pytest.fixture(scope="module")
def built(tmp_path_factory) -> str:
    directory = str(tmp_path_factory.mktemp("nowait"))
    v = Volume(directory, "", 1, create=True)
    for i in IDS:
        v.write_needle(Needle(cookie=COOKIE + i, id=i, data=_payload(i)))
    v.write_needle(Needle(cookie=COOKIE + BIG, id=BIG, data=b"b" * 3000))
    base = v.base_file_name()
    v.close()
    ec.write_ec_files(base, ec.get_coder("numpy", 10, 4), GEO)
    ec.write_sorted_ecx_from_idx(base)
    ev = ec.EcVolume(directory, "", 1, GEO)
    ev.delete_needle(DELETED)
    ev.close()
    return directory


def _open(directory: str, warm: bool = True, geo: ec.Geometry = GEO,
          first: int = IDS[0]) -> ec.EcVolume:
    ev = ec.EcVolume(directory, "", 1, geo,
                     coder=ec.get_coder("numpy", 10, 4))
    for sid in range(geo.total_shards):
        ev.add_shard(sid)
    if warm:
        ev.locate(first)  # the layout marker's one read: not the loop's
    return ev


def _shards_of(ev: ec.EcVolume, needle_id: int) -> list[int]:
    return [iv.to_shard_id_and_offset(ev.g)[0]
            for iv in ev.locate(needle_id)[2]]


def _needle_on(ev: ec.EcVolume, n_intervals: int) -> int:
    return next(i for i in IDS if i != DELETED
                and len(set(_shards_of(ev, i))) == n_intervals)


def _lookups() -> float:
    return metrics_mod.shared("volume").value("ecx_lookups",
                                              {"via": "mmap"})


def _no_pread(monkeypatch) -> None:
    def refuse(*_):
        raise AssertionError("a system call on the loop's thread")
    monkeypatch.setattr(ec_volume_mod.os, "pread", refuse)


@pytest.mark.parametrize("n_intervals", [1, 2])
def test_served_byte_identical_to_read_needle(built, monkeypatch,
                                              n_intervals):
    """One interval, and two across a block boundary (on two shards)."""
    ev = _open(built)
    try:
        i = _needle_on(ev, n_intervals)
        want = ev.read_needle(i, cookie=COOKIE + i)
        flights = ev.read_flight.stats()
        before = _lookups()
        _no_pread(monkeypatch)
        observe.reset()
        with observe.bind(observe.TraceCtx("nowait1", "", "volume", "")):
            got, located = ev.read_needle_nowait(i, cookie=COOKIE + i)
        assert located is None
        assert got.data == want.data == _payload(i)
        assert got.to_bytes(ev.version) == want.to_bytes(ev.version)
        assert _lookups() == before + 1
        assert ev.read_flight.stats() == flights
        # the stages `read_needle` has, once each and one a slice
        names = sorted(s["name"] for s in observe.spans(trace_id="nowait1"))
        assert names == sorted(["ec.get.ecx", "ec.get.parse"]
                               + ["ec.get.shard_read"] * n_intervals)
    finally:
        ev.close()


def _lose_first(ev, monkeypatch, i):
    ev.delete_shard(_shards_of(ev, i)[0])


def _lose_second(ev, monkeypatch, i):
    ev.delete_shard(_shards_of(ev, i)[1])


def _unmap_shard(ev, monkeypatch, i):
    sid = _shards_of(ev, i)[0]
    ev.delete_shard(sid)
    monkeypatch.setenv("WEED_EC_MMAP", "0")
    ev.add_shard(sid)
    monkeypatch.delenv("WEED_EC_MMAP")
    assert ev.shards[sid]._mm is None


def _close_mapping_under_the_slice(ev, monkeypatch, i):
    # `close` has run as far as the mapping, `_mm` still names it
    ev.shards[_shards_of(ev, i)[0]]._mm.close()


def _unmount_between_the_slices(ev, monkeypatch, i):
    real = ec_volume_mod.EcShard.slice_at
    second = _shards_of(ev, i)[1]

    def slice_then_unmount(self, offset, size):
        data = real(self, offset, size)
        ev.delete_shard(second)
        return data

    monkeypatch.setattr(ec_volume_mod.EcShard, "slice_at",
                        slice_then_unmount)


@pytest.mark.parametrize("n_intervals,why", [
    (1, _lose_first), (2, _lose_second), (1, _unmap_shard),
    (1, _close_mapping_under_the_slice),
    (2, _unmount_between_the_slices),
], ids=["lost-shard", "one-local-one-lost", "shard-file-unmapped",
        "mapping-closed-under-the-slice", "unmounted-between-the-slices"])
def test_declines_where_an_interval_is_not_mapped_here(
        built, monkeypatch, n_intervals, why):
    ev = _open(built)
    try:
        i = _needle_on(ev, n_intervals)
        why(ev, monkeypatch, i)
        flights = ev.read_flight.stats()
        before = _lookups()
        _no_pread(monkeypatch)
        got, located = ev.read_needle_nowait(i, cookie=COOKIE + i)
        assert _lookups() == before + 1
        assert got is None and located == ev.locate(i)
        assert ev.read_flight.stats() == flights
        monkeypatch.undo()
        # ... and `read_needle`, which may block, takes it from there
        # without a second search
        before = _lookups()
        asked = []

        def no_peer(shard_id, offset, size):
            asked.append(shard_id)
            return None

        n = ev.read_needle(i, cookie=COOKIE + i, shard_reader=no_peer,
                           located=located)
        assert n.data == _payload(i)
        assert _lookups() == before
        assert bool(asked) == (why in (_lose_first, _lose_second,
                                       _unmount_between_the_slices))
    finally:
        ev.close()


def test_declines_a_needle_over_max_size(built, monkeypatch):
    ev = _open(built)
    try:
        _no_pread(monkeypatch)
        got, located = ev.read_needle_nowait(BIG, cookie=COOKIE + BIG,
                                             max_size=2048)
        assert got is None and located[1] > 2048
        # the default, `NOWAIT_MAX_SIZE`, admits it
        got, located = ev.read_needle_nowait(BIG, cookie=COOKIE + BIG)
        assert got.data == b"b" * 3000 and located is None
    finally:
        ev.close()


# --- the default limit, at sizes that meet it ---
LIMIT = ec_volume_mod.NOWAIT_MAX_SIZE
# blocks a photo crosses now and then and a needle at the limit always
PHOTO_GEO = ec.Geometry(data_shards=10, parity_shards=4,
                        large_block_size=16 << 20,
                        small_block_size=128 << 10)
PHOTO = 65536  # Haystack's needle (PERF.md: `haystack-photo-rs10-4`)
STORED_EXTRA = 5  # data size (4) and flags (1) beside the data
AT_LIMIT, OVER_LIMIT, FIRST_PHOTO, N_PHOTOS = 1, 2, 10, 12
STORED = {AT_LIMIT: LIMIT, OVER_LIMIT: LIMIT + 1,
          **{FIRST_PHOTO + j: PHOTO + STORED_EXTRA for j in range(N_PHOTOS)}}


def _photo_payload(i: int) -> bytes:
    size = STORED[i] - STORED_EXTRA
    return (bytes((i * 7 + j) % 256 for j in range(251))
            * (size // 251 + 1))[:size]


@pytest.fixture(scope="module")
def photos(tmp_path_factory) -> str:
    directory = str(tmp_path_factory.mktemp("nowait-photos"))
    v = Volume(directory, "", 1, create=True)
    for i in STORED:
        v.write_needle(Needle(cookie=COOKIE + i, id=i,
                              data=_photo_payload(i)))
    base = v.base_file_name()
    v.close()
    ec.write_ec_files(base, ec.get_coder("numpy", 10, 4), PHOTO_GEO)
    ec.write_sorted_ecx_from_idx(base)
    return directory


def _photo_in(ev: ec.EcVolume, n_parts: int) -> int:
    return next(i for i in range(FIRST_PHOTO, FIRST_PHOTO + N_PHOTOS)
                if len(set(_shards_of(ev, i))) == n_parts)


@pytest.mark.parametrize("case,served", [
    ("at-the-limit", True), ("one-byte-over", False),
    ("photo", True), ("photo-across-a-block", True),
    ("photo-across-a-block-one-part-lost", False)])
def test_default_limit_by_stored_size(photos, monkeypatch, case, served):
    """What `NOWAIT_MAX_SIZE` admits is decided by the stored size the
    index gives and by where the intervals lie, nothing else: a needle
    stored at exactly the limit, Haystack's 65,536-byte photo (over the
    64 KiB the limit once was) in one block and across two; one byte
    over, or with a part on a lost shard, declines with what it located
    handed on."""
    assert 64 * 1024 < PHOTO + STORED_EXTRA <= LIMIT
    ev = _open(photos, geo=PHOTO_GEO, first=AT_LIMIT)
    try:
        i = {"at-the-limit": AT_LIMIT, "one-byte-over": OVER_LIMIT}.get(
            case) or _photo_in(ev, 1 if case == "photo" else 2)
        assert ev.locate(i)[1] == STORED[i]
        want = ev.read_needle(i, cookie=COOKIE + i)
        assert want.data == _photo_payload(i)
        if case.endswith("one-part-lost"):
            ev.delete_shard(_shards_of(ev, i)[1])
        before = _lookups()
        _no_pread(monkeypatch)
        got, located = ev.read_needle_nowait(i, cookie=COOKIE + i)
        assert _lookups() == before + 1
        monkeypatch.undo()
        if served:
            assert located is None
            assert got.to_bytes(ev.version) == want.to_bytes(ev.version)
            return
        assert got is None and located == ev.locate(i)
        # `read_needle` takes it from there without a second search
        before = _lookups()
        n = ev.read_needle(i, cookie=COOKIE + i, located=located,
                           shard_reader=lambda *_: None)
        assert n.to_bytes(ev.version) == want.to_bytes(ev.version)
        assert _lookups() == before
    finally:
        ev.close()


def test_declines_unsearched_without_a_mapped_index(built, monkeypatch):
    """WEED_EC_MMAP=0: a search would be seventeen preads."""
    monkeypatch.setenv("WEED_EC_MMAP", "0")
    ev = _open(built)
    monkeypatch.delenv("WEED_EC_MMAP")
    try:
        assert ev._ecx_mm is None
        before = metrics_mod.shared("volume").value("ecx_lookups",
                                                    {"via": "pread"})
        _no_pread(monkeypatch)
        assert ev.read_needle_nowait(IDS[0]) == (None, None)
        monkeypatch.undo()
        assert metrics_mod.shared("volume").value(
            "ecx_lookups", {"via": "pread"}) == before
        assert ev.read_needle(IDS[0]).data == _payload(IDS[0])
    finally:
        ev.close()


def test_declines_until_the_layout_marker_has_been_read(built):
    """The first `locate` of a volume reads the `.ecm`: a file read,
    which is an executor thread's."""
    ev = _open(built, warm=False)
    try:
        assert ev.read_needle_nowait(IDS[0]) == (None, None)
        ev.read_needle(IDS[0])
        got, _ = ev.read_needle_nowait(IDS[0])
        assert got.data == _payload(IDS[0])
    finally:
        ev.close()


def _flip_a_byte(directory: str, ev: ec.EcVolume, needle_id: int) -> None:
    iv = ev.locate(needle_id)[2][0]
    sid, offset = iv.to_shard_id_and_offset(GEO)
    # inside the data, past the header
    at = offset + t.NEEDLE_HEADER_SIZE + 6
    path = ev.shards[sid].path
    with open(path, "r+b") as f:
        f.seek(at)
        b = f.read(1)
        f.seek(at)
        f.write(bytes([b[0] ^ 0xFF]))


@pytest.mark.parametrize("case", ["deleted", "unknown", "wrong-cookie",
                                  "flipped-byte"])
def test_raises_what_read_needle_raises(built, tmp_path, case):
    directory = built
    if case == "flipped-byte":
        # a volume of its own to spoil
        import shutil
        directory = str(tmp_path / "rot")
        shutil.copytree(built, directory)
    ev = _open(directory)
    try:
        i = _needle_on(ev, 1)
        key, cookie = {"deleted": (DELETED, COOKIE + DELETED),
                       "unknown": (IDS[3] + 1, COOKIE),
                       "wrong-cookie": (i, COOKIE + i + 1),
                       "flipped-byte": (i, COOKIE + i)}[case]
        if case == "flipped-byte":
            _flip_a_byte(directory, ev, i)
        kind = CrcError if case == "flipped-byte" else KeyError
        with pytest.raises(kind) as blocking:
            ev.read_needle(key, cookie=cookie)
        with pytest.raises(kind) as nowait:
            ev.read_needle_nowait(key, cookie=cookie)
        assert type(nowait.value) is type(blocking.value)
        assert str(nowait.value) == str(blocking.value)
    finally:
        ev.close()


def test_a_tombstone_written_a_moment_ago_is_what_comes_back(built,
                                                             tmp_path):
    import shutil
    directory = str(tmp_path / "del")
    shutil.copytree(built, directory)
    ev = _open(directory)
    try:
        i = _needle_on(ev, 1)
        assert ev.read_needle_nowait(i)[0].data == _payload(i)
        ev.delete_needle(i)
        with pytest.raises(KeyError, match="deleted"):
            ev.read_needle_nowait(i)
    finally:
        ev.close()


def test_read_at_still_preads_what_the_mapping_cannot_give(built):
    """`EcShard.read_at` shares the slice with the non-blocking read and
    keeps its fallback: a range past the end of the file is a short
    read, as the reference's."""
    ev = _open(built)
    try:
        shard = ev.shards[0]
        assert shard.slice_at(0, shard.size) is not None
        assert shard.slice_at(1, shard.size) is None
        assert shard.slice_at(shard.size - 4, 8) is None
        assert shard.read_at(shard.size - 4, 8) \
            == os.pread(shard._f.fileno(), 8, shard.size - 4)
        assert len(shard.read_at(shard.size - 4, 8)) == 4
        assert shard.read_at(8, 16) == shard.slice_at(8, 16)
    finally:
        ev.close()


def test_unmounts_under_the_read_are_declines_never_errors(built):
    """A shard unmounted and mounted again by other threads while a
    reader slices it: every read is the needle or a decline."""
    import sys
    import threading
    import time
    ev = _open(built)
    i = _needle_on(ev, 2)
    sids = _shards_of(ev, i)
    stop = threading.Event()
    errors: list = []

    def churn(sid: int) -> None:
        try:
            while not stop.is_set():
                ev.delete_shard(sid)
                ev.add_shard(sid)
        except Exception as e:  # reported below
            errors.append(e)

    served = declined = 0
    churners = [threading.Thread(target=churn, args=(sid,)) for sid in sids]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for th in churners:
            th.start()
        deadline = time.time() + 1.0
        while time.time() < deadline:
            got, located = ev.read_needle_nowait(i, cookie=COOKIE + i)
            if got is None:
                assert located is not None
                declined += 1
            else:
                assert got.data == _payload(i)
                served += 1
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        for th in churners:
            th.join(10)
        alive = [th for th in churners if th.is_alive()]
        ev.close()
    assert not alive and not errors, errors
    assert served and declined
