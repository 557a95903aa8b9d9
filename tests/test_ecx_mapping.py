"""The .ecx lookup from a shared mapping, and what keeps the mapping whole.

An EcVolume bisects a MAP_SHARED, PROT_READ mapping of its sorted index
(no os.pread a probe, so no hand-back of the GIL inside a lookup) and
keeps one os.pread a probe as its only other path (an index that cannot
be mapped, WEED_EC_MMAP=0): the reference these tests compare with. The
file stays the truth: tombstones go through a descriptor and show in the
mapping at once. A mapped file that is truncated kills its readers with
SIGBUS, so `ec/copy` leaves the index of a mounted volume alone.
"""

import os
import subprocess
import sys
import threading
import urllib.request

import pytest

from cluster_util import TEST_GEOMETRY, Cluster
from seaweedfs_tpu import ec
from seaweedfs_tpu.ec import ec_volume as ec_volume_mod
from seaweedfs_tpu.shell.ec_commands import EcCommands
from seaweedfs_tpu.storage import idx as idx_mod
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.superblock import SuperBlock
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.utils import metrics as metrics_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GEO = ec.Geometry(data_shards=10, parity_shards=4,
                  large_block_size=10000, small_block_size=100)
IDS = list(range(5, 5 + 3 * 70, 3))  # gaps, so that absent keys lie inside
TOMBSTONED = IDS[20]
WIDTHS = [t.OFFSET_SIZE, t.OFFSET_SIZE_LARGE]
KEYS = {"first": IDS[0], "last": IDS[-1], "middle": IDS[len(IDS) // 2],
        "absent-inside": IDS[30] + 1, "absent-below": 1,
        "absent-above": IDS[-1] + 9, "tombstoned": TOMBSTONED}


def _payload(i: int) -> bytes:
    return bytes([i % 251]) * (40 + i % 700)


def _build(directory: str, width: int) -> str:
    """One sealed volume of `IDS` at the offset width, encoded, its
    sorted index written, one needle tombstoned in the index; returns
    the base file name."""
    v = Volume(directory, "", 1, create=True,
               superblock=SuperBlock(offset_size=width))
    for i in IDS:
        v.write_needle(Needle(cookie=0x4000 + i, id=i, data=_payload(i)))
    base = v.base_file_name()
    v.close()
    ec.write_ec_files(base, ec.get_coder("numpy", 10, 4), GEO)
    ec.write_sorted_ecx_from_idx(base, offset_size=width)
    ev = ec.EcVolume(directory, "", 1, GEO)
    ev.delete_needle(TOMBSTONED)
    ev.close()
    return base


def _open(directory: str, monkeypatch=None, mapped: bool = True,
          shards: bool = False) -> ec.EcVolume:
    if not mapped:
        monkeypatch.setenv("WEED_EC_MMAP", "0")
    ev = ec.EcVolume(directory, "", 1, GEO)
    if not mapped:
        monkeypatch.delenv("WEED_EC_MMAP")
    assert (ev._ecx_mm is not None) == mapped
    if shards:
        for sid in range(GEO.total_shards):
            ev.add_shard(sid)
    return ev


def _lookup(ev: ec.EcVolume, key: int):
    try:
        return ev.find_needle(key)
    except KeyError:
        return KeyError


def _lookups() -> dict[str, float]:
    shared = metrics_mod.shared("volume")
    return {via: shared.value("ecx_lookups", {"via": via})
            for via in ("mmap", "pread")}


@pytest.fixture(scope="module", params=WIDTHS)
def built(request, tmp_path_factory):
    directory = str(tmp_path_factory.mktemp(f"ecx{request.param}"))
    return directory, _build(directory, request.param), request.param


@pytest.fixture()
def fresh(request, tmp_path):
    """A volume of its own, for the tests that write."""
    width = request.param
    return str(tmp_path), _build(str(tmp_path), width), width


@pytest.mark.parametrize("kind", sorted(KEYS))
def test_mapped_and_pread_searches_agree(built, monkeypatch, kind):
    directory, _, width = built
    mapped = _open(directory)
    by_pread = _open(directory, monkeypatch, mapped=False)
    try:
        assert mapped.offset_size == by_pread.offset_size == width
        got = _lookup(mapped, KEYS[kind])
        assert got == _lookup(by_pread, KEYS[kind])
        if kind.startswith("absent"):
            assert got is KeyError
        else:
            offset, size = got
            assert offset > 0
            assert t.size_is_deleted(size) == (kind == "tombstoned")
    finally:
        mapped.close()
        by_pread.close()


def test_every_key_agrees_and_reads_back(built, monkeypatch):
    directory, _, _ = built
    mapped = _open(directory, shards=True)
    by_pread = _open(directory, monkeypatch, mapped=False)
    try:
        for key in range(0, IDS[-1] + 4):
            assert _lookup(mapped, key) == _lookup(by_pread, key), key
        for i in IDS:
            if i != TOMBSTONED:
                assert mapped.read_needle(i, cookie=0x4000 + i).data \
                    == _payload(i)
        with pytest.raises(KeyError):
            mapped.read_needle(TOMBSTONED)
    finally:
        mapped.close()
        by_pread.close()


@pytest.mark.parametrize("width", WIDTHS)
def test_empty_ecx_mounts_and_answers_keyerror(tmp_path, width):
    """The fused warm-down's zero-live volume: nothing to map."""
    base = os.path.join(str(tmp_path), "1")
    open(base + ".ecx", "wb").close()
    with open(base + ec.to_ext(0), "wb") as f:
        f.write(SuperBlock(offset_size=width).to_bytes())
    ev = ec.EcVolume(str(tmp_path), "", 1, GEO)
    try:
        assert ev._ecx_mm is None and ev.offset_size == width
        with pytest.raises(KeyError):
            ev.find_needle(1)
        assert ev.live_entries() == []
        ev.delete_needle(1)  # nothing to tombstone, nothing journalled
        assert os.path.getsize(base + ".ecj") == 0
    finally:
        ev.close()


def test_mapped_lookup_and_read_make_no_pread(built, monkeypatch):
    directory, _, _ = built
    ev = _open(directory, shards=True)

    def no_pread(*_):
        raise AssertionError("os.pread under a mapped index")

    monkeypatch.setattr(os, "pread", no_pread)
    try:
        for i in IDS:
            ev.find_needle(i)
        assert ev.read_needle(IDS[3]).data == _payload(IDS[3])
        assert len(ev.live_entries()) == len(IDS) - 1
        with pytest.raises(KeyError):
            ev.find_needle(IDS[3] + 1)
    finally:
        monkeypatch.undo()
        ev.close()


@pytest.mark.parametrize("fresh", WIDTHS, indirect=True)
def test_delete_is_read_back_through_the_mapping(fresh):
    directory, base, width = fresh
    ev = _open(directory)
    victim = IDS[7]
    before = os.stat(base + ".ecx")
    try:
        offset, size = ev.find_needle(victim)
        assert not t.size_is_deleted(size)
        ev.delete_needle(victim)
        assert ev.find_needle(victim) == (offset, t.TOMBSTONE_FILE_SIZE)
        assert victim not in dict(ev.live_entries())
        # the file is the truth: a second volume on the same files, and
        # a plain read of the bytes
        other = _open(directory)
        try:
            assert other.find_needle(victim) == (offset,
                                                 t.TOMBSTONE_FILE_SIZE)
        finally:
            other.close()
        with open(base + ".ecx", "rb") as f:
            entries = {k: s for k, _, s in idx_mod.iter_index_bytes(
                f.read(), width)}
        assert t.size_is_deleted(entries[victim])
        assert sum(t.size_is_deleted(s) for s in entries.values()) == 2
        after = os.stat(base + ".ecx")
        assert (after.st_ino, after.st_size) == (before.st_ino,
                                                 before.st_size)
        with open(base + ".ecj", "rb") as f:
            assert f.read() == t.put_u64(TOMBSTONED) + t.put_u64(victim)
    finally:
        ev.close()


@pytest.mark.parametrize("fresh", WIDTHS, indirect=True)
def test_rebuild_ecx_file_beside_a_mounted_volume_is_seen(fresh):
    """`Store.ec_rebuild` replays the journal into the index through a
    descriptor of its own while the volume stays mounted."""
    directory, base, width = fresh
    ev = _open(directory)
    victims = [IDS[0], IDS[41], IDS[-1]]
    try:
        with open(base + ".ecj", "ab") as f:  # another holder's journal
            for key in victims:
                f.write(t.put_u64(key))
        ec_volume_mod.rebuild_ecx_file(base, offset_size=width)
        for key in victims:
            assert t.size_is_deleted(ev.find_needle(key)[1])
        assert not t.size_is_deleted(ev.find_needle(IDS[1])[1])
        assert not os.path.exists(base + ".ecj")
    finally:
        ev.close()


def test_live_entries_equals_the_pread_walk(built, monkeypatch):
    directory, base, width = built
    mapped = _open(directory)
    by_pread = _open(directory, monkeypatch, mapped=False)
    try:
        # one os.pread an entry, as the walk was before the mapping
        entry = t.needle_map_entry_size(width)
        walk = []
        with open(base + ".ecx", "rb") as f:
            for i in range(os.path.getsize(base + ".ecx") // entry):
                key, _, size = idx_mod.unpack_entry(
                    os.pread(f.fileno(), entry, i * entry),
                    offset_size=width)
                if not t.size_is_deleted(size):
                    walk.append((key, size))
        assert [k for k, _ in walk] == [i for i in IDS if i != TOMBSTONED]
        assert mapped.live_entries() == walk
        assert by_pread.live_entries() == walk
    finally:
        mapped.close()
        by_pread.close()


def test_close_releases_the_mapping(built):
    directory, _, _ = built
    ev = _open(directory, shards=True)
    index, shard = ev._ecx_mm, ev.shards[0]._mm
    ev.close()
    assert ev._ecx_mm is None and index.closed and shard.closed
    with pytest.raises(ValueError):
        ev.find_needle(IDS[0])


def test_unmappable_index_falls_back_to_pread(built, monkeypatch):
    directory, _, _ = built

    def refuse(*_, **__):
        raise OSError("no mapping on this filesystem")

    monkeypatch.setattr(ec_volume_mod.mmap, "mmap", refuse)
    ev = ec.EcVolume(directory, "", 1, GEO)
    try:
        assert ev._ecx_mm is None
        assert not t.size_is_deleted(ev.find_needle(IDS[2])[1])
    finally:
        ev.close()


@pytest.mark.parametrize("via", ["mmap", "pread"])
def test_lookups_are_counted_by_the_way_taken(built, monkeypatch, via):
    directory, _, _ = built
    ev = _open(directory, monkeypatch, mapped=via == "mmap")
    other = "pread" if via == "mmap" else "mmap"
    try:
        before = _lookups()
        ev.find_needle(IDS[0])
        ev.find_needle(IDS[1])
        assert _lookup(ev, IDS[1] + 1) is KeyError  # a miss is a lookup
        after = _lookups()
        assert after[via] - before[via] == 3
        assert after[other] == before[other]
    finally:
        ev.close()


def test_both_counters_are_born_at_zero(tmp_path):
    """A volume server that has looked nothing up yet says 0 for both
    ways, not nothing (a process of its own: the registry is shared)."""
    code = (
        "import sys\n"
        "from seaweedfs_tpu.storage.store import Store\n"
        "from seaweedfs_tpu.server.volume_server import VolumeServer\n"
        "from seaweedfs_tpu.utils import metrics\n"
        "VolumeServer(Store([sys.argv[1]]), '127.0.0.1:1',\n"
        "             url='127.0.0.1:2')\n"
        "print(metrics.render_shared())\n")
    out = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path)], cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    for via in ("mmap", "pread"):
        assert ('seaweedfs_tpu_volume_ecx_lookups_total{via="%s"} 0.0'
                % via) in out.stdout.splitlines()


# --- ec/copy leaves the index of a mounted volume alone -----------------

COLLECTION = "ecxmap"


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(n_volume_servers=0)
    c.master.repair_enabled = False
    for _ in range(3):
        c.add_volume_server(with_grpc=True)
    c.wait_for_nodes(3)
    yield c
    c.shutdown()


@pytest.fixture(scope="module")
def spread(cluster):
    """One EC volume spread over the three servers; returns its id, the
    fids with their bodies, and {url: shard ids}."""
    c = cluster
    fids = {}
    for i in range(24):
        body = bytes([i + 1]) * (500 + 137 * i)
        fids[c.client.upload(body, collection=COLLECTION)] = body
    c.wait_heartbeats()
    vid = int(next(iter(fids)).split(",")[0])
    EcCommands(c.client, TEST_GEOMETRY).encode(vid, COLLECTION)
    c.wait_heartbeats()
    return vid, fids


def _held(c: Cluster, vid: int) -> dict[str, list[int]]:
    return {vs.url: vs.store.find_ec_volume(vid).shard_ids()
            for vs in c.volume_servers
            if vs.store.find_ec_volume(vid) is not None}


def _base(vs, vid: int) -> str:
    return os.path.join(vs.store.locations[0].directory,
                        f"{COLLECTION}_{vid}")


def _read(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def _ec_copy(c: Cluster, plane: str, taker, giver, vid: int,
             shard_ids: list[int]) -> None:
    if plane == "http":
        c.client.volume_admin(taker.url, "ec/copy", {
            "volume_id": vid, "collection": COLLECTION,
            "shard_ids": shard_ids, "source": giver.url,
            "copy_ecx_file": True})
        return
    import grpc

    from seaweedfs_tpu.pb import volume_server_pb2 as vpb
    from seaweedfs_tpu.pb.rpc import VolumeServerStub

    async def go():
        async with grpc.aio.insecure_channel(
                f"127.0.0.1:{taker.grpc_port}") as ch:
            ok = await VolumeServerStub(ch).VolumeEcShardsCopy(
                vpb.EcCopyRequest(
                    volume_id=vid, collection=COLLECTION,
                    shard_ids=shard_ids, copy_ecx_file=True,
                    source_data_node=giver.url))
            assert ok.error == "", ok.error

    c.call(go())


@pytest.mark.parametrize("plane", ["http", "grpc"])
def test_ec_copy_leaves_a_mounted_takers_index_alone(cluster, spread,
                                                     plane):
    c = cluster
    vid, fids = spread
    held = _held(c, vid)
    taker, giver = c.volume_servers[0], c.volume_servers[1]
    sid = next(s for s in held[giver.url] if s not in held[taker.url])
    base = _base(taker, vid)
    # a delete that reached the taker alone: its own tombstone, its own
    # journal
    victim = int(sorted(fids)[{"http": 0, "grpc": 1}[plane]]
                 .split(",")[1][:-8], 16)
    taker.store.ec_blob_delete(vid, victim)
    before = os.stat(base + ".ecx")
    index, journal = _read(base + ".ecx"), _read(base + ".ecj")
    assert index != _read(_base(giver, vid) + ".ecx")
    assert t.put_u64(victim) in journal
    marker = _read(base + ".ecm")

    _ec_copy(c, plane, taker, giver, vid, [sid])

    after = os.stat(base + ".ecx")
    assert (after.st_ino, after.st_size) == (before.st_ino, before.st_size)
    assert _read(base + ".ecx") == index
    assert _read(base + ".ecj") == journal
    assert _read(base + ".ecm") == marker
    ev = taker.store.find_ec_volume(vid)
    assert t.size_is_deleted(ev.find_needle(victim)[1])
    assert os.stat(ev._ecx.fileno()).st_ino == after.st_ino
    # while the shard file arrived
    assert _read(base + ec.to_ext(sid)) \
        == _read(_base(giver, vid) + ec.to_ext(sid))
    os.remove(base + ec.to_ext(sid))


@pytest.mark.parametrize("plane", ["http", "grpc"])
def test_ec_copy_brings_the_index_to_a_server_without_the_volume(
        cluster, spread, plane):
    c = cluster
    vid, _ = spread
    giver = c.volume_servers[1]
    taker = c.add_volume_server(with_grpc=True)
    assert taker.store.find_ec_volume(vid) is None
    sid = _held(c, vid)[giver.url][0]
    _ec_copy(c, plane, taker, giver, vid, [sid])
    for ext in (".ecx", ec.to_ext(sid)):
        assert _read(_base(taker, vid) + ext) \
            == _read(_base(giver, vid) + ext), ext
    taker.store.ec_mount(vid, COLLECTION, [sid])
    try:
        assert taker.store.find_ec_volume(vid).live_entries() \
            == giver.store.find_ec_volume(vid).live_entries()
    finally:
        taker.store.ec_unmount(vid, [sid])


def test_reads_survive_shards_moving_onto_the_mounted_reader(cluster,
                                                             spread):
    """`EcCommands.balance`'s steps, a shard at a time, onto a server
    that answers GETs all the while: every reply whole, none lost."""
    c = cluster
    vid, fids = spread
    reader = c.volume_servers[2]
    ev = reader.store.find_ec_volume(vid)
    had = ev.shard_ids()
    live = {fid: body for fid, body in fids.items()
            if not t.size_is_deleted(
                ev.find_needle(int(fid.split(",")[1][:-8], 16))[1])}
    stop = threading.Event()
    wrong: list[str] = []
    done = [0]

    def read_all() -> None:
        while not stop.is_set():
            for fid, body in live.items():
                try:
                    with urllib.request.urlopen(
                            f"http://{reader.url}/{fid}", timeout=30) as r:
                        got = r.read()
                except OSError as e:
                    wrong.append(f"{fid}: {e}")
                    continue
                if got != body:
                    wrong.append(f"{fid}: {len(got)} bytes")
                done[0] += 1

    th = threading.Thread(target=read_all, daemon=True)
    th.start()
    try:
        moved = []
        for giver in c.volume_servers[:2]:
            for sid in _held(c, vid)[giver.url][:2]:
                body = {"volume_id": vid, "collection": COLLECTION,
                        "shard_ids": [sid]}
                c.client.volume_admin(reader.url, "ec/copy", {
                    **body, "source": giver.url, "copy_ecx_file": True})
                c.client.volume_admin(reader.url, "ec/mount", body)
                c.client.volume_admin(giver.url, "ec/delete_shards", body)
                moved.append(sid)
        floor = done[0] + 2 * len(live)
        while done[0] < floor and th.is_alive():
            stop.wait(0.02)
    finally:
        stop.set()
        th.join(60)
    assert not th.is_alive()
    assert len(moved) == 4 and ev.shard_ids() == sorted(had + moved)
    assert done[0] >= 2 * len(live)
    assert wrong == []
