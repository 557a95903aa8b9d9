"""One remote interval read is one prepared call (PR 33): the reader keeps
one gRPC channel a peer together with the one `VolumeEcShardRead` call
made from it, and the peer answers a range of at most 64 KiB out of a
mapped shard file on its loop's thread, in one message.

Two live volume servers on loopback, a reader and a peer; the peer holds
two hand-written shard files of 1 MiB + 4,099 random bytes. Every case
waits on a condition, none sleeps for a fixed time.
"""

import os
import threading
import time
import types
import urllib.request

import grpc
import numpy as np
import pytest

from cluster_util import Cluster
from seaweedfs_tpu import faults, observe
from seaweedfs_tpu.pb import volume_server_pb2 as vpb
from seaweedfs_tpu.pb.rpc import VolumeServerStub
from seaweedfs_tpu.server import volume_server as volume_server_mod
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.storage.store import Store

VID = 33
SHARDS = (3, 7)
SHARD_SIZE = (1 << 20) + 4099
KIB64 = 64 << 10
BUILT, REUSED = {"result": "built"}, {"result": "reused"}
INLINE, EXECUTOR = {"how": "inline"}, {"how": "executor"}
GRPC, HTTP = {"via": "grpc"}, {"via": "http"}


def _wait(pred, what: str, limit: float = 30.0) -> None:
    deadline = time.time() + limit
    while not pred():
        assert time.time() < deadline, what
        time.sleep(0.02)


class Pair:
    """A reader and a peer under one master; `read` is the reader's
    shard reader for the peer's volume, with the peer as the cached
    holder of both shards (no lookup at the master)."""

    def __init__(self):
        self.c = Cluster(n_volume_servers=0)
        self.reader = self.c.add_volume_server(with_grpc=True)
        self.peer = self.c.add_volume_server(with_grpc=True)
        self.dir = self.peer.store.locations[0].directory
        rng = np.random.default_rng(33)
        self.files = {}
        open(os.path.join(self.dir, f"{VID}.ecx"), "wb").close()
        for sid in SHARDS:
            self.files[sid] = rng.bytes(SHARD_SIZE)
            with open(os.path.join(self.dir, f"{VID}.ec{sid:02d}"),
                      "wb") as f:
                f.write(self.files[sid])
        self.peer.store.ec_mount(VID, "", list(SHARDS))
        self.name_holder(self.peer.url)
        self.read = self.reader._make_shard_reader(
            types.SimpleNamespace(vid=VID))

    def name_holder(self, url: str) -> None:
        self.reader._shard_loc_cache[VID] = (
            {str(s): [url] for s in SHARDS}, time.monotonic())

    def want(self, sid: int, offset: int, size: int) -> bytes:
        return self.files[sid][offset:offset + size]

    def calls(self) -> tuple[float, float]:
        m = self.reader.metrics
        return m.value("ec_peer_call", BUILT), m.value("ec_peer_call",
                                                       REUSED)

    def served(self) -> tuple[float, float]:
        m = self.peer.metrics
        return (m.value("ec_shard_read_served", INLINE),
                m.value("ec_shard_read_served", EXECUTOR))

    def via(self) -> tuple[float, float]:
        m = self.reader.metrics
        return (m.value("ec_remote_shard_reads", GRPC),
                m.value("ec_remote_shard_reads", HTTP))

    def stream(self, sid: int, offset: int, size: int) -> list:
        """The peer's answer, message by message, on a channel of the
        test's own."""
        with grpc.insecure_channel(
                f"127.0.0.1:{self.peer.grpc_port}") as ch:
            return list(VolumeServerStub(ch).VolumeEcShardRead(
                vpb.EcShardReadRequest(volume_id=VID, shard_id=sid,
                                       offset=offset, size=size),
                timeout=10))

    def restart_peer(self) -> None:
        """The peer, stopped, comes back on the same address over the
        same directory."""
        old = self.peer
        port = int(old.url.rsplit(":", 1)[1])
        store = Store([self.dir], max_volume_counts=[self.c.max_volumes],
                      coder_name=self.c.coder_name,
                      geometry=self.c.geometry)
        self.peer = VolumeServer(store, self.c.master_url, url=old.url,
                                 pulse_seconds=self.c.pulse,
                                 grpc_port=old.grpc_port)
        runner = self.c.serve(self.peer.app, port)
        self.c.runners.append(runner)
        self.c._vs_runners[1] = runner
        self.c.volume_servers[1] = self.peer
        if self.peer.store.find_ec_volume(VID) is None:
            self.peer.store.ec_mount(VID, "", list(SHARDS))


@pytest.fixture()
def pair():
    faults.clear()
    p = Pair()
    yield p
    faults.clear()
    p.c.shutdown()


def test_the_two_counters_are_born_at_zero(pair):
    with urllib.request.urlopen(f"http://{pair.reader.url}/metrics",
                                timeout=10) as r:
        text = r.read().decode()
    fam = "seaweedfs_tpu_volume_ec_"
    for key in ('peer_call_total{result="reused"}',
                'peer_call_total{result="built"}',
                'shard_read_served_total{how="inline"}',
                'shard_read_served_total{how="executor"}'):
        assert f"{fam}{key} 0" in text, key


def test_n_reads_from_one_peer_build_one_call(pair):
    for i in range(7):
        assert pair.read(3, 100 * i, 1048) == pair.want(3, 100 * i, 1048)
        if i == 0:
            first = pair.reader._peer_grpc_channels[pair.peer.url]
    assert pair.calls() == (1, 6)
    assert pair.via() == (7, 0)
    # one entry, the one the first read made: a channel and its call
    assert list(pair.reader._peer_grpc_channels) == [pair.peer.url]
    assert pair.reader._peer_grpc_channels[pair.peer.url] is first
    assert callable(first[1]) and hasattr(first[0], "unary_stream")


class _Watched:
    """A channel that says whether it was closed."""

    def __init__(self, ch):
        self.ch, self.closed = ch, False

    def __getattr__(self, name):
        return getattr(self.ch, name)

    def close(self):
        self.closed = True
        self.ch.close()


def test_sixteen_first_reads_at_once_keep_one_channel(pair, monkeypatch):
    """All sixteen find no entry and dial (the barrier inside `dial`
    holds each until every one has looked): one channel with its call
    survives, fifteen are closed, and every read has its bytes."""
    n = 16
    barrier = threading.Barrier(n)
    dialled: list[_Watched] = []
    real = volume_server_mod.dial

    def dial(target):
        ch = _Watched(real(target))
        dialled.append(ch)
        barrier.wait(30)
        return ch

    monkeypatch.setattr(volume_server_mod, "dial", dial)
    got: dict[int, bytes] = {}
    threads = [threading.Thread(
        target=lambda i=i: got.__setitem__(i, pair.read(7, 64 * i, 1048)))
        for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    assert got == {i: pair.want(7, 64 * i, 1048) for i in range(n)}
    assert len(dialled) == n
    survivor = pair.reader._peer_grpc_channels[pair.peer.url][0]
    assert [ch for ch in dialled if not ch.closed] == [survivor]
    assert pair.calls() == (1, n - 1)
    # and the survivor's call is the one every later read takes
    assert pair.read(7, 5, 1048) == pair.want(7, 5, 1048)
    assert pair.calls() == (1, n)
    assert len(dialled) == n


@pytest.mark.parametrize("size,how,data_messages,messages", [
    (1, "inline", 1, 1),
    (1048, "inline", 1, 1),
    (KIB64, "inline", 1, 1),
    (KIB64 + 1, "executor", 1, 2),      # today's path: data, then is_last
    ((1 << 20) + 1, "executor", 2, 3),  # in two chunks of at most 1 MiB
])
def test_bytes_equal_the_shard_file(pair, size, how, data_messages,
                                    messages):
    offset = 1234
    inline0, executor0 = pair.served()
    assert pair.read(3, offset, size) == pair.want(3, offset, size)
    inline, executor = pair.served()
    assert (inline - inline0, executor - executor0) == (
        (1, 0) if how == "inline" else (0, 1))
    assert pair.via() == (1, 0)
    chunks = pair.stream(3, offset, size)
    assert len(chunks) == messages
    assert [bool(c.data) for c in chunks].count(True) == data_messages
    assert chunks[-1].is_last and not any(c.is_last for c in chunks[:-1])
    assert not any(c.error for c in chunks)
    assert b"".join(c.data for c in chunks) == pair.want(3, offset, size)
    assert max(len(c.data) for c in chunks) <= 1 << 20


def test_the_last_bytes_of_a_shard_are_sliced(pair):
    offset = SHARD_SIZE - 1048
    assert pair.read(7, offset, 1048) == pair.files[7][-1048:]
    assert pair.served() == (1, 0)


def test_a_range_past_the_end_is_a_short_read(pair):
    """Not wholly in the mapped file: today's path, its error, None from
    gRPC, and then the HTTP answer, which is short too."""
    offset = SHARD_SIZE - 100
    chunks = pair.stream(3, offset, 1048)
    assert [c.data for c in chunks] == [pair.files[3][-100:], b""]
    assert chunks[-1].error == f"short read at {SHARD_SIZE}"
    assert chunks[-1].is_last
    assert pair.served() == (0, 1)
    with urllib.request.urlopen(
            f"http://{pair.peer.url}/admin/ec/shard_read?volume={VID}"
            f"&shard=3&offset={offset}&size=1048", timeout=10) as r:
        assert r.read() == pair.files[3][-100:]
    assert pair.read(3, offset, 1048) is None
    assert pair.served()[0] == 0 and pair.served()[1] >= 2
    assert pair.via() == (0, 0)
    # the peer is not held to be dead for an answer it gave
    assert not pair.reader._peer_grpc_dead


def test_a_shard_not_mounted_here_is_an_error_on_todays_path(pair):
    chunks = pair.stream(5, 0, 1048)
    assert len(chunks) == 1 and chunks[0].is_last
    assert "not here" in chunks[0].error
    assert pair.served() == (0, 1)


def test_an_unmapped_peer_serves_by_executor(pair, monkeypatch):
    monkeypatch.setenv("WEED_EC_MMAP", "0")
    pair.peer.store.ec_unmount(VID, list(SHARDS))
    pair.peer.store.ec_mount(VID, "", list(SHARDS))
    ev = pair.peer.store.find_ec_volume(VID)
    assert all(s._mm is None for s in ev.shards.values())
    assert pair.read(3, 77, 1048) == pair.want(3, 77, 1048)
    assert pair.served() == (0, 1)
    assert pair.via() == (1, 0)
    chunks = pair.stream(3, 77, 1048)
    assert [bool(c.data) for c in chunks] == [True, False]
    assert [c.is_last for c in chunks] == [False, True]


def test_a_closed_grpc_port_goes_to_http_and_is_marked_dead(pair):
    pair.c.call(pair.peer._grpc_server.stop(grace=0))
    pair.peer._grpc_server = None
    t0 = time.time()
    assert pair.read(3, 9, 1048) == pair.want(3, 9, 1048)
    assert pair.via() == (0, 1)
    until = pair.reader._peer_grpc_dead[pair.peer.url]
    assert t0 + 59 < until <= time.time() + 60
    built, reused = pair.calls()
    # while it is marked, a read does not try gRPC at all
    assert pair.read(3, 10, 1048) == pair.want(3, 10, 1048)
    assert pair.via() == (0, 2)
    assert pair.calls() == (built, reused)
    assert pair.reader._peer_grpc_dead[pair.peer.url] == until


def test_a_peer_started_again_on_its_address_is_read_on_the_old_channel(
        pair, monkeypatch):
    dials: list[str] = []
    real = volume_server_mod.dial
    monkeypatch.setattr(volume_server_mod, "dial",
                        lambda target: dials.append(target) or real(target))
    assert pair.read(3, 1, 1048) == pair.want(3, 1, 1048)
    entry = pair.reader._peer_grpc_channels[pair.peer.url]
    pair.c.stop_volume_server(1)
    assert pair.read(3, 2, 1048) is None  # nobody answers, either way
    assert pair.peer.url in pair.reader._peer_grpc_dead
    pair.restart_peer()

    def read_over_grpc() -> bool:
        # the test clears the mark instead of waiting 60 s; the channel
        # dials again by itself, after its own back-off
        pair.reader._peer_grpc_dead.clear()
        before = pair.via()[0]
        assert pair.read(3, 3, 1048) == pair.want(3, 3, 1048)
        return pair.via()[0] == before + 1

    _wait(read_over_grpc, "the old channel never reached the new peer")
    assert len(dials) == 1
    assert pair.reader._peer_grpc_channels[pair.peer.url] is entry
    assert pair.calls()[0] == 1
    assert pair.served()[0] >= 1  # the new peer's own count


class _Told:
    """A prepared call that answers what it was told to, as a peer of
    either age would."""

    def __init__(self, chunks):
        self.chunks, self.requests = chunks, []

    def __call__(self, request, timeout=None):
        self.requests.append((request, timeout))
        return iter(self.chunks)

    def close(self):
        pass


BODY = bytes(range(256)) * 4 + b"tail" * 6  # 1,048 bytes


@pytest.mark.parametrize("chunks", [
    [vpb.DataChunk(data=BODY), vpb.DataChunk(is_last=True)],
    [vpb.DataChunk(data=BODY, is_last=True)],
    [vpb.DataChunk(data=BODY[:500]), vpb.DataChunk(data=BODY[500:]),
     vpb.DataChunk(is_last=True)],
    [vpb.DataChunk(data=BODY[:500]),
     vpb.DataChunk(data=BODY[500:], is_last=True)],
], ids=["old-peer", "new-peer", "old-peer-chunked", "chunked-last-full"])
def test_the_callers_loop_reads_either_shape(pair, chunks):
    url = "127.0.0.1:9"
    told = _Told(chunks)
    pair.reader._peer_grpc_channels[url] = (told, told)
    pair.name_holder(url)
    assert pair.read(3, 4096, len(BODY)) == BODY
    (request, timeout), = told.requests
    assert (request.volume_id, request.shard_id, request.offset,
            request.size, timeout) == (VID, 3, 4096, len(BODY), 5)
    assert pair.calls() == (0, 1)
    assert pair.via() == (1, 0)


def test_a_reply_of_another_length_is_not_the_interval(pair):
    """`len(buf) == size` or None: gRPC's short answer is refused and
    the HTTP fallback is asked, which nobody answers at this address."""
    url = "127.0.0.1:9"
    told = _Told([vpb.DataChunk(data=BODY[:-1], is_last=True)])
    pair.reader._peer_grpc_channels[url] = (told, told)
    pair.name_holder(url)
    assert pair.read(3, 0, len(BODY)) is None
    assert pair.via() == (0, 0)


@pytest.mark.parametrize("action,marked_dead", [("drop", True),
                                                ("error", False)])
def test_the_fault_point_still_drops_and_errors(pair, action, marked_dead):
    """`rpc.VolumeEcShardRead`: a drop aborts UNAVAILABLE (a vanished
    peer: HTTP for 60 s), an error INTERNAL (HTTP for this read)."""
    faults.set_fault("rpc.VolumeEcShardRead", action, count=1)
    assert pair.read(3, 11, 1048) == pair.want(3, 11, 1048)
    assert pair.via() == (0, 1)
    assert pair.served() == (0, 0)  # the gate is in front of the servicer
    assert (pair.peer.url in pair.reader._peer_grpc_dead) == marked_dead
    pair.reader._peer_grpc_dead.clear()
    assert pair.read(3, 12, 1048) == pair.want(3, 12, 1048)
    assert pair.via() == (1, 1)
    assert pair.served() == (1, 0)


def test_a_read_carries_the_requests_trace_to_the_peers_span(pair):
    trace_id = "feedc0de00000033"
    ctx = observe.TraceCtx(trace_id, "parent33", "test", "")
    assert observe.run_with(ctx, pair.read, 3, 0, 1048) \
        == pair.want(3, 0, 1048)
    _wait(lambda: any("VolumeEcShardRead" in s["name"]
                      for s in observe.spans(trace_id=trace_id)),
          "the peer recorded no span under the request's trace", 5.0)
    span = next(s for s in observe.spans(trace_id=trace_id)
                if "VolumeEcShardRead" in s["name"])
    assert span["svc"] == "volume" and span["parent"] == "parent33"
    assert any(s["name"] == "ec.get.remote_read"
               for s in observe.spans(trace_id=trace_id))


def test_a_stub_builds_only_the_calls_it_is_asked_for():
    """What made a stub a read dear: 37 multicallables to use one."""
    made: list[str] = []

    class Channel:
        def unary_unary(self, path, **kw):
            made.append(path)
            return lambda request, **kwargs: ("uu", path)

        def unary_stream(self, path, **kw):
            made.append(path)
            return lambda request, **kwargs: ("us", path)

        stream_stream = unary_stream

    stub = VolumeServerStub(Channel())
    assert made == []
    call = stub.VolumeEcShardRead
    assert stub.VolumeEcShardRead is call  # kept, not made again
    assert made == ["/seaweedfs_tpu.volume.VolumeServer/VolumeEcShardRead"]
    assert call(vpb.EcShardReadRequest()) == ("us", made[0])
    assert stub.VolumeServerStatus(vpb.Empty())[0] == "uu"
    assert len(made) == 2
    with pytest.raises(AttributeError):
        stub.NoSuchRpc
    assert not hasattr(stub, "_no_such_private")
