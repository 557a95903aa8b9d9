"""A declined EC GET whose only missing intervals are on a live peer is
answered by the serving loop itself: `VolumeServer._read_ec_awaited`
awaits each such interval on the loop's grpc.aio call to the peer, with
no executor hop and no sync stream on a pool thread. Everything else
(a stale or empty location cache, a shard nobody holds, a needle over
`NOWAIT_MAX_SIZE`, a peer that fails or answers short) takes the
executor's read as before.

Two live volume servers on loopback, a reader and a peer, under one
master; one EC volume encoded here, its shards split between the two.
Every case waits on a condition, none sleeps for a fixed time.
"""

import json
import os
import shutil
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from cluster_util import TEST_GEOMETRY, Cluster
from seaweedfs_tpu import ec, faults, observe
from seaweedfs_tpu.ec.ec_volume import NOWAIT_MAX_SIZE
from seaweedfs_tpu.pb import volume_server_pb2 as vpb
from seaweedfs_tpu.storage.file_id import FileId
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.utils import metrics as metrics_mod

VID = 41
READER_SHARDS = (0, 1, 2, 3, 4, 10, 11)
PEER_SHARDS = (5, 6, 7, 8, 9, 12, 13)
LOST = 4  # unmounted from the reader where a test needs a shard nobody holds
AWAITED, HANDED_ON = {"then": "awaited"}, {"then": "executor"}
AIO, GRPC, HTTP = {"via": "grpc_aio"}, {"via": "grpc"}, {"via": "http"}
BUILT, REUSED = {"result": "built"}, {"result": "reused"}
SMALL = 1024
PHOTO = (64 << 10) + 5
LARGE = 300_000  # stored over NOWAIT_MAX_SIZE


def _wait(pred, what: str, limit: float = 30.0) -> None:
    deadline = time.time() + limit
    while not pred():
        assert time.time() < deadline, what
        time.sleep(0.02)


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """The volume's EC files and its needles: {id: (cookie, body)}."""
    directory = str(tmp_path_factory.mktemp("await"))
    rng = np.random.default_rng(41)
    v = Volume(directory, "", VID, create=True)
    needles = {}
    nid = 1
    for size in [SMALL] * 150 + [PHOTO, PHOTO, LARGE]:
        body = rng.bytes(size)
        cookie = int(rng.integers(0, 1 << 32))
        v.write_needle(Needle(cookie=cookie, id=nid, data=body))
        needles[nid] = (cookie, body)
        nid += 1
    base = v.base_file_name()
    v.close()
    ec.write_ec_files(base, ec.get_coder("numpy", 10, 4), TEST_GEOMETRY)
    ec.write_sorted_ecx_from_idx(base)
    return directory, needles


class Pair:
    """A reader and a peer under one master, the reader's location cache
    naming the holder of every shard (no lookup at the master)."""

    def __init__(self, built):
        directory, self.needles = built
        self.c = Cluster(n_volume_servers=0)
        self.c.master.repair_enabled = False
        self.reader = self.c.add_volume_server(with_grpc=True)
        self.peer = self.c.add_volume_server(with_grpc=True)
        for vs, shards in ((self.reader, READER_SHARDS),
                           (self.peer, PEER_SHARDS)):
            d = vs.store.locations[0].directory
            names = [f"{VID}.ecx"] + [f"{VID}.ec{s:02d}" for s in shards]
            for name in names + [f"{VID}.ecm"]:
                if os.path.exists(os.path.join(directory, name)):
                    shutil.copy(os.path.join(directory, name), d)
            vs.store.ec_mount(VID, "", list(shards))
        self.ev = self.reader.store.find_ec_volume(VID)
        self.ev.locate(1)  # the layout marker's one read: not the loop's
        self.name_holders()

    def name_holders(self, fetched: float | None = None,
                     holders: dict | None = None) -> None:
        shards = {str(s): [self.reader.url] for s in READER_SHARDS}
        shards.update({str(s): [self.peer.url] for s in PEER_SHARDS})
        shards.update(holders or {})
        self.reader._shard_loc_cache[VID] = (
            shards, time.monotonic() if fetched is None else fetched)

    def shards_of(self, nid: int) -> set[int]:
        return {iv.to_shard_id_and_offset(self.ev.g)[0]
                for iv in self.ev.locate(nid)[2]}

    def needle_on(self, size: int, shards) -> int:
        """A needle of `size` bytes whose intervals lie on `shards` alone,
        at least one of them on the peer."""
        return next(nid for nid, (_, body) in self.needles.items()
                    if len(body) == size
                    and self.shards_of(nid) <= set(shards)
                    and self.shards_of(nid) & set(PEER_SHARDS))

    def one_interval_on_the_peer(self) -> int:
        return next(nid for nid, (_, body) in self.needles.items()
                    if len(body) == SMALL and len(self.shards_of(nid)) == 1
                    and self.shards_of(nid) <= set(PEER_SHARDS))

    def get(self, nid: int, headers: dict | None = None) -> bytes:
        fid = FileId(volume_id=VID, key=nid, cookie=self.needles[nid][0])
        req = urllib.request.Request(f"http://{self.reader.url}/{fid}",
                                     headers=headers or {})
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.read()

    def count(self, name: str, labels: dict) -> float:
        return self.reader.metrics.value(name, labels)

    def declined(self) -> tuple[float, float]:
        return (self.count("ec_read_declined", AWAITED),
                self.count("ec_read_declined", HANDED_ON))

    def via(self) -> tuple[float, float, float]:
        return tuple(self.count("ec_remote_shard_reads", v)
                     for v in (AIO, GRPC, HTTP))

    def master_knows_the_peer(self) -> bool:
        try:
            with urllib.request.urlopen(
                    f"http://{self.c.master_url}/col/lookup/ec?volumeId="
                    f"{VID}", timeout=10) as r:
                shards = json.load(r).get("shards", {})
        except urllib.error.HTTPError:  # 404 before the first heartbeat
            return False
        return all(self.peer.url in shards.get(str(s), [])
                   for s in PEER_SHARDS)


@pytest.fixture()
def pair(built):
    faults.clear()
    p = Pair(built)
    yield p
    faults.clear()
    p.c.shutdown()


def _delta(before, after):
    return tuple(a - b for a, b in zip(after, before))


def test_the_counter_is_born_at_zero(pair):
    with urllib.request.urlopen(f"http://{pair.reader.url}/metrics",
                                timeout=10) as r:
        text = r.read().decode()
    fam = "seaweedfs_tpu_volume_ec_"
    for key in ('read_declined_total{then="awaited"}',
                'read_declined_total{then="executor"}',
                'remote_shard_reads_total{via="grpc_aio"}'):
        assert f"{fam}{key} 0" in text, key


def test_one_interval_on_the_peer_is_awaited(pair):
    nid = pair.one_interval_on_the_peer()
    observe.reset()
    trace = "feedc0de00000041"
    assert pair.get(nid, {observe.TRACE_HEADER: f"{trace}:parent41"}) \
        == pair.needles[nid][1]
    assert pair.declined() == (1, 0)
    assert pair.via() == (1, 0, 0)
    assert pair.count("ec_read_nowait", {"result": "declined"}) == 1
    assert (pair.count("ec_peer_call", BUILT),
            pair.count("ec_peer_call", REUSED)) == (1, 0)
    names = [s["name"] for s in observe.spans(trace_id=trace)]
    assert "ec.get.queue" not in names
    for stage in ("ec.get.ecx", "ec.get.peer_fetch", "ec.get.remote_read",
                  "ec.get.parse", "ec.get.resume"):
        assert names.count(stage) == 1, (stage, names)
    # the loop's own channel, and no sync one
    assert list(pair.reader._peer_aio_channels) == [pair.peer.url]
    assert not pair.reader._peer_grpc_channels


@pytest.mark.parametrize("size,then", [(SMALL, "awaited"),
                                       (PHOTO, "awaited"),
                                       (LARGE, "executor")])
def test_needles_up_to_the_limit_are_awaited(pair, size, then):
    nid = pair.needle_on(size, READER_SHARDS + PEER_SHARDS)
    assert (pair.ev.locate(nid)[1] <= NOWAIT_MAX_SIZE) == (then == "awaited")
    before = pair.declined()
    assert pair.get(nid) == pair.needles[nid][1]
    assert _delta(before, pair.declined()) == (
        (1, 0) if then == "awaited" else (0, 1))
    peer_intervals = sum(
        1 for iv in pair.ev.locate(nid)[2]
        if iv.to_shard_id_and_offset(pair.ev.g)[0] in PEER_SHARDS)
    aio, sync, http = pair.via()
    if then == "awaited":
        assert (aio, sync, http) == (peer_intervals, 0, 0)
    else:
        assert aio == 0 and sync >= 1 and http == 0


@pytest.mark.parametrize("cache", ["empty", "stale"])
def test_a_cache_not_fresh_takes_the_executor(pair, monkeypatch, cache):
    """No master call from the loop: the executor's read asks the master,
    and the next read finds the cache fresh and is awaited."""
    if cache == "empty":
        pair.reader._shard_loc_cache.clear()
    else:
        pair.name_holders(fetched=time.monotonic() - 8 * 60.0)
    asked_on: list[str] = []
    real = pair.reader._shard_locations

    def shard_locations(*args, **kwargs):
        asked_on.append(threading.current_thread().name)
        return real(*args, **kwargs)

    monkeypatch.setattr(pair.reader, "_shard_locations", shard_locations)
    _wait(pair.master_knows_the_peer, "the master never heard of the peer")
    nid = pair.needle_on(SMALL, READER_SHARDS + PEER_SHARDS)
    assert pair.get(nid) == pair.needles[nid][1]
    assert pair.declined() == (0, 1)
    assert asked_on and pair.c.thread.name not in asked_on
    assert pair.count("ec_shard_location_lookups", {"result": "holder"}) \
        >= 1
    assert pair.get(nid) == pair.needles[nid][1]
    assert pair.declined() == (1, 1)


def test_a_shard_nobody_holds_is_reconstructed_by_the_executor(pair):
    pair.reader.store.ec_unmount(VID, [LOST])
    pair.name_holders(holders={str(LOST): []})
    nid = next(n for n, (_, body) in pair.needles.items()
               if len(body) == SMALL and pair.shards_of(n) == {LOST})
    reconstructed = metrics_mod.shared("ec").value("reconstruct_intervals")
    assert pair.get(nid) == pair.needles[nid][1]
    assert pair.declined() == (0, 1)
    assert metrics_mod.shared("ec").value("reconstruct_intervals") \
        == reconstructed + 1
    assert pair.via()[0] == 0


def test_an_unmapped_local_shard_is_handed_on_before_any_peer_call(
        pair, monkeypatch):
    """A local interval the loop cannot slice (no mapping) hands the read
    on before a peer is asked: the executor reads both."""
    monkeypatch.setenv("WEED_EC_MMAP", "0")
    pair.reader.store.ec_unmount(VID, list(READER_SHARDS))
    pair.reader.store.ec_mount(VID, "", list(READER_SHARDS))
    pair.ev = pair.reader.store.find_ec_volume(VID)
    pair.ev.locate(1)
    nid = next(n for n, (_, body) in pair.needles.items()
               if len(body) == PHOTO
               and pair.shards_of(n) & set(READER_SHARDS))
    assert pair.get(nid) == pair.needles[nid][1]
    assert pair.declined() == (0, 1)
    assert pair.via()[0] == 0
    assert not pair.reader._peer_aio_channels
    assert all(s._mm is None for s in pair.ev.shards.values())


def test_a_closed_peer_port_falls_back_and_marks_the_peer_dead(pair):
    pair.c.call(pair.peer._grpc_server.stop(grace=0))
    pair.peer._grpc_server = None
    nid = pair.needle_on(SMALL, READER_SHARDS + PEER_SHARDS)
    t0 = time.time()
    assert pair.get(nid) == pair.needles[nid][1]
    assert pair.declined() == (0, 1)
    assert pair.via() == (0, 0, 1)  # the executor's read, over HTTP
    until = pair.reader._peer_grpc_dead[pair.peer.url]
    assert t0 + 59 < until <= time.time() + 60
    # while it is marked, the loop does not try it
    assert pair.get(nid) == pair.needles[nid][1]
    assert pair.declined() == (0, 2)
    assert pair.count("ec_peer_call", BUILT) == 1
    assert pair.count("ec_peer_call", REUSED) == 0


class _Told:
    """A grpc.aio call that answers what it was told to."""

    def __init__(self, chunks):
        self.chunks = list(chunks)

    async def read(self):
        import grpc
        return self.chunks.pop(0) if self.chunks else grpc.aio.EOF


class _ToldCall:
    def __init__(self, chunks):
        self.chunks, self.requests = chunks, []

    def __call__(self, request, timeout=None):
        self.requests.append((request, timeout))
        return _Told(self.chunks)


class _Channel:
    def __init__(self):
        self.closed = False

    async def close(self):
        self.closed = True


@pytest.mark.parametrize("reply", ["short", "long", "error"])
def test_a_wrong_reply_falls_back(pair, reply):
    nid = pair.one_interval_on_the_peer()
    (iv,) = pair.ev.locate(nid)[2]
    size = iv.size
    chunks = {"short": [vpb.DataChunk(data=b"x" * (size - 1), is_last=True)],
              "long": [vpb.DataChunk(data=b"x" * (size + 1), is_last=True)],
              "error": [vpb.DataChunk(error="not here", is_last=True)]}
    told = _ToldCall(chunks[reply])
    pair.reader._peer_aio_channels[pair.peer.url] = (_Channel(), told)
    assert pair.get(nid) == pair.needles[nid][1]
    ((request, timeout),) = told.requests
    shard_id, offset = iv.to_shard_id_and_offset(pair.ev.g)
    assert (request.volume_id, request.shard_id, request.offset,
            request.size, timeout) == (VID, shard_id, offset, size, 5)
    assert pair.declined() == (0, 1)
    assert pair.via() == (0, 1, 0)  # the executor's sync call
    assert not pair.reader._peer_grpc_dead


def test_the_requests_trace_reaches_the_peers_span(pair):
    nid = pair.needle_on(SMALL, READER_SHARDS + PEER_SHARDS)
    trace = "feedc0de00000042"
    observe.reset()
    assert pair.get(nid, {observe.TRACE_HEADER: f"{trace}:parent42"}) \
        == pair.needles[nid][1]
    assert pair.declined() == (1, 0)
    _wait(lambda: any("VolumeEcShardRead" in s["name"]
                      for s in observe.spans(trace_id=trace)),
          "the peer recorded no span under the request's trace", 5.0)
    span = next(s for s in observe.spans(trace_id=trace)
                if "VolumeEcShardRead" in s["name"])
    assert span["svc"] == "volume"


def test_sixteen_first_reads_at_once_leave_one_channel(pair):
    small = [n for n, (_, body) in pair.needles.items()
             if len(body) == SMALL
             and pair.shards_of(n) & set(PEER_SHARDS)][:16]
    assert len(small) == 16
    barrier = threading.Barrier(16)
    got: dict[int, bytes] = {}

    def one(nid):
        barrier.wait(30)
        got[nid] = pair.get(nid)

    threads = [threading.Thread(target=one, args=(n,)) for n in small]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    assert got == {n: pair.needles[n][1] for n in small}
    assert pair.declined() == (16, 0)
    assert list(pair.reader._peer_aio_channels) == [pair.peer.url]
    assert pair.count("ec_peer_call", BUILT) == 1
    assert pair.count("ec_peer_call", REUSED) == pair.via()[0] - 1


def test_cleanup_closes_the_channels(pair):
    nid = pair.needle_on(SMALL, READER_SHARDS + PEER_SHARDS)
    assert pair.get(nid) == pair.needles[nid][1]
    ch, call = pair.reader._peer_aio_channels[pair.peer.url]
    watched = _Watched(ch)
    pair.reader._peer_aio_channels[pair.peer.url] = (watched, call)
    pair.c.stop_volume_server(0)
    assert watched.closed
    assert not pair.reader._peer_aio_channels


class _Watched:
    """A grpc.aio channel that says whether it was closed."""

    def __init__(self, ch):
        self.ch, self.closed = ch, False

    async def close(self):
        self.closed = True
        await self.ch.close()
