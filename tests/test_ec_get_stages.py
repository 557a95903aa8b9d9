"""The stage clock inside an EC GET (observe.stage): one pair of clock
reads feeds the span ring, the seaweedfs_tpu_ec_stage_seconds family on
/metrics and a profiler annotation.

The server is `run_volume_server` with its fast path, as `cli volume`
runs it, holding one EC volume (served by the Pallas coder in interpret
mode, so that a degraded read takes the device path's stages) and one
plain volume.
"""

import asyncio
import collections
import re
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from seaweedfs_tpu import observe
from seaweedfs_tpu.ec import ec_volume as ec_volume_mod
from seaweedfs_tpu.ec.coder import PallasCoder
from seaweedfs_tpu.ec.geometry import Geometry
from seaweedfs_tpu.observe import profiler, wideevents
from seaweedfs_tpu.server.volume_server import (VolumeServer,
                                                run_volume_server)
from seaweedfs_tpu.storage.file_id import FileId
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.utils import metrics

GEOMETRY = Geometry(10, 4, large_block_size=64 * 1024,
                    small_block_size=4 * 1024)
COOKIE = 0x1234
N_NEEDLES = 40

# stage -> how often one GET records it: one that the loop's thread
# serves itself (every interval in a mapped shard file here) queues for
# no executor thread; one that declines (a lost shard) does, and still
# searches the index once
PER_SERVED_GET = {"ec.get": 1, "ec.get.handler": 1, "ec.get.ecx": 1,
                  "ec.get.parse": 1, "ec.get.resume": 1}
PER_DECLINED_GET = {**PER_SERVED_GET, "ec.get.queue": 1}
# stack, then pad to the bucket: two copies, two entries
PER_LOST_INTERVAL = {"ec.get.peer_fetch": 1, "ec.get.survivors": 1,
                     "ec.get.stack_pad": 2, "ec.get.dispatch": 1,
                     "ec.get.d2h_wait": 1}
ENCLOSING = {"ec.get", "ec.get.handler"}


def payload(i: int) -> bytes:
    return bytes([i % 251]) * 1000


# volume 3: no shard lost, and needles that meet the loop's limit. A
# 65,536-byte photo first, then needles over `NOWAIT_MAX_SIZE`, enough
# of them for one row of large blocks, so that the photo lies on two
# shards and not on all ten
BIG_VID, PHOTO, OVER_LIMIT = 3, 1, 2
BIG_SIZES = {PHOTO: 65536,
             **{OVER_LIMIT + j: ec_volume_mod.NOWAIT_MAX_SIZE
                for j in range(10 * GEOMETRY.large_block_size
                               // ec_volume_mod.NOWAIT_MAX_SIZE + 1)}}


BIG_NEEDLES = {"photo": PHOTO, "over-limit": OVER_LIMIT}


def big_payload(i: int) -> bytes:
    return (bytes((i + j) % 256 for j in range(253))
            * (BIG_SIZES[i] // 253 + 1))[:BIG_SIZES[i]]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Served:
    def __init__(self, tmpdir: str):
        self.store = Store([tmpdir], coder_name="numpy", geometry=GEOMETRY)
        self.store.add_volume(BIG_VID)
        for i in BIG_SIZES:
            self.store.write_needle(BIG_VID, Needle(
                id=i, cookie=COOKIE, data=big_payload(i)))
        self.store.ec_generate(BIG_VID)  # (0.85 MB: by the host coder)
        self.store.ec_mount(BIG_VID, "", list(range(14)))
        self.store.delete_volume(BIG_VID)
        # the layout marker's one read, which is not the loop's
        assert len(self.store.find_ec_volume(BIG_VID).locate(PHOTO)[2]) == 2
        self.store._coders[(10, 4)] = PallasCoder(10, 4, interpret=True)
        self.store.add_volume(1)
        self.store.add_volume(2)
        for i in range(N_NEEDLES):
            self.store.write_needle(1, Needle(id=i + 1, cookie=COOKIE,
                                              data=payload(i)))
        self.store.write_needle(2, Needle(id=1, cookie=COOKIE,
                                          data=b"plain" * 10))
        self.store.ec_generate(1)
        self.store.ec_mount(1, "", list(range(14)))
        self.store.delete_volume(1)
        self.ev = self.store.find_ec_volume(1)
        # lose the shard that holds needle 5; needle `present` lies on
        # another one
        self.lost_shard = self.shard_of(5)
        self.lost = 5
        self.present = next(i for i in range(1, N_NEEDLES + 1)
                            if self.shard_of(i) != self.lost_shard)
        self.ev.delete_shard(self.lost_shard)
        self.port = free_port()
        self.loop = asyncio.new_event_loop()
        self.runner = None
        ready = threading.Event()

        def run() -> None:
            asyncio.set_event_loop(self.loop)
            self.runner = self.loop.run_until_complete(run_volume_server(
                "127.0.0.1", self.port, self.store,
                master_url="127.0.0.1:1",  # no master: heartbeats warn
                pulse_seconds=3600))
            ready.set()
            self.loop.run_forever()

        self.thread = threading.Thread(target=run, daemon=True)
        self.thread.start()
        assert ready.wait(30), "the volume server did not start"
        # the first degraded read compiles (interpret mode: traces)
        assert self.get(self.fid(self.lost)) == payload(self.lost - 1)

    def shard_of(self, needle_id: int) -> int:
        _, _, intervals = self.ev.locate(needle_id)
        assert len(intervals) == 1
        return intervals[0].to_shard_id_and_offset(GEOMETRY)[0]

    @staticmethod
    def fid(needle_id: int, vid: int = 1) -> str:
        return str(FileId(vid, needle_id, COOKIE))

    def get(self, path: str, trace: str = "", headers=None) -> bytes:
        headers = dict(headers or {})
        if trace:
            headers["X-Seaweed-Trace"] = trace + ":"
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}/{path}", headers=headers)
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.read()

    def stage_counts(self) -> dict[str, int]:
        text = self.get("metrics").decode()
        return {m.group(1): int(float(m.group(2))) for m in re.finditer(
            r'^seaweedfs_tpu_ec_stage_seconds_count\{stage="([^"]+)"\} '
            r'(\S+)$', text, re.M)}

    def plane_counts(self) -> tuple[int, int]:
        """EC GETs answered by the fast path itself, and handed on."""
        text = self.get("metrics").decode()
        return tuple(int(float(m.group(1))) if m else 0 for m in (
            re.search(rf"^seaweedfs_tpu_volume_ec_read_{which}_total "
                      r"(\S+)$", text, re.M)
            for which in ("inline", "proxied")))

    def read_counts(self) -> dict[str, int]:
        """How the loop's own read went, the `read` histogram's count
        and the index lookups, off /metrics."""
        text = self.get("metrics").decode()
        return {name: int(float(m.group(1))) if m else 0 for name, m in (
            (name, re.search(rf"^seaweedfs_tpu_volume_{re.escape(key)} "
                             r"(\S+)$", text, re.M))
            for name, key in (
                ("served", 'ec_read_nowait_total{result="served"}'),
                ("declined", 'ec_read_nowait_total{result="declined"}'),
                ("timed", "read_seconds_count"),
                ("lookups", 'ecx_lookups_total{via="mmap"}')))}

    def stop(self) -> None:
        asyncio.run_coroutine_threadsafe(self.runner.cleanup(),
                                         self.loop).result(10)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(5)
        self.store.close()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    s = Served(str(tmp_path_factory.mktemp("ecget")))
    yield s
    s.stop()


def trace_of(trace_id: str) -> list[dict]:
    # the fast path records its root span after the client has its
    # answer: wait for it
    deadline = time.time() + 5
    while time.time() < deadline:
        spans = observe.spans(trace_id=trace_id)
        if any(s["name"].startswith("fast GET") for s in spans):
            return spans
        time.sleep(0.01)
    return observe.spans(trace_id=trace_id)


def names(spans: list[dict]) -> collections.Counter:
    return collections.Counter(s["name"] for s in spans)


def test_degraded_get_is_one_tree_with_every_stage(served):
    assert served.get(served.fid(served.lost), trace="tree1") \
        == payload(served.lost - 1)
    spans = trace_of("tree1")
    got = names(spans)
    for stage, count in {**PER_DECLINED_GET, **PER_LOST_INTERVAL}.items():
        assert got[stage] == count, (stage, got)
    # the interval was lost: nothing read it, nobody's flight was ridden
    assert got["ec.get.shard_read"] == 0
    assert got["ec.get.flight_wait"] == 0
    roots = [s for s in spans if not s["parent"]]
    assert [s["name"].split()[0:2] for s in roots] == [["fast", "GET"]]
    ids = {s["id"] for s in spans}
    assert all(s["parent"] in ids for s in spans if s["parent"])
    # every exclusive stage, those of the worker thread too, is a child
    # of `ec.get.handler`, which stands where `volume.read` does for a
    # plain volume
    by_id = {s["id"]: s for s in spans}
    assert got["volume.read"] == 0
    for s in spans:
        if s["name"].startswith("ec.get.") and s["name"] != "ec.get.handler":
            assert by_id[s["parent"]]["name"] == "ec.get.handler", s
    # one plane: the fast path answers, and nothing of this GET reached
    # the aiohttp listener, whose middleware would have left a `GET /..`
    handler = next(s for s in spans if s["name"] == "ec.get.handler")
    assert by_id[handler["parent"]]["name"] == "ec.get"
    whole = next(s for s in spans if s["name"] == "ec.get")
    assert by_id[whole["parent"]]["name"].startswith("fast GET /")
    assert not any(s["name"].startswith("GET /") for s in spans)


def test_inline_get_pays_for_no_second_plane(served):
    """`ec.get` less `ec.get.handler` is what a second plane costs a
    GET: where the fast path answers, next to nothing."""
    gaps = []
    for i in range(5):
        trace = f"gap{i}"
        served.get(served.fid(served.present), trace=trace)
        by_name = {s["name"]: s for s in trace_of(trace)}
        gaps.append(by_name["ec.get"]["dur_us"]
                    - by_name["ec.get.handler"]["dur_us"])
    assert all(g >= 0 for g in gaps)
    # (the least of five: a loaded machine may take the thread away
    # between the two exits once)
    assert min(gaps) < 300, gaps


def test_plane_counters_say_which_plane_answered(served, monkeypatch):
    from seaweedfs_tpu.server.fastpath import FastVolumeProtocol
    hops = []
    real = FastVolumeProtocol._proxy

    async def counted(self, raw, port=None):
        hops.append(raw.split(b"\r\n", 1)[0])
        return await real(self, raw, port=port)

    monkeypatch.setattr(FastVolumeProtocol, "_proxy", counted)
    inline0, proxied0 = served.plane_counts()
    served.get(served.fid(served.lost))
    served.get(served.fid(served.present))
    with pytest.raises(urllib.error.HTTPError) as miss:
        served.get(served.fid(N_NEEDLES + 7))
    assert miss.value.code == 404
    # (the error holds its connection, which the server's stop waits for)
    miss.value.close()
    # the three /metrics reads take the hop; no EC GET did
    assert [h for h in hops if b"/metrics" not in h] == []
    assert served.plane_counts() == (inline0 + 3, proxied0)
    assert served.get(served.fid(served.present),
                      headers={"Range": "bytes=10-19"}) \
        == payload(served.present - 1)[10:20]
    assert served.plane_counts() == (inline0 + 3, proxied0 + 1)
    assert sum(1 for h in hops if b"/metrics" not in h) == 1
    # a plain volume's GET is neither
    served.get(served.fid(1, vid=2))
    assert served.plane_counts() == (inline0 + 3, proxied0 + 1)


def test_range_get_takes_the_hop_and_records_both_sides(served):
    """The rare shapes stay with the aiohttp plane: `ec.get` is the
    residence on the fast path's side of the hop, `ec.get.handler` the
    part over there, as for every EC GET before the fast path answered
    any."""
    assert served.get(served.fid(served.lost), trace="range1",
                      headers={"Range": "bytes=0-99"}) \
        == payload(served.lost - 1)[:100]
    spans = trace_of("range1")
    got = names(spans)
    for stage, count in {**PER_DECLINED_GET, **PER_LOST_INTERVAL}.items():
        assert got[stage] == count, (stage, got)
    by_id = {s["id"]: s for s in spans}
    roots = [s for s in spans if not s["parent"]]
    assert [s["name"].split()[0:2] for s in roots] == [["fast", "GET"]]
    for s in spans:
        if s["name"].startswith("ec.get.") and s["name"] != "ec.get.handler":
            assert by_id[s["parent"]]["name"] == "ec.get.handler", s
    handler = next(s for s in spans if s["name"] == "ec.get.handler")
    assert by_id[handler["parent"]]["name"].startswith("GET /")
    whole = next(s for s in spans if s["name"] == "ec.get")
    assert by_id[whole["parent"]]["name"].startswith("fast GET /")
    assert whole["dur_us"] > handler["dur_us"]


def test_debug_trace_serves_the_same_tree(served):
    served.get(served.fid(served.lost), trace="tree2")
    trace_of("tree2")
    import json
    doc = json.loads(served.get("debug/trace?trace_id=tree2&format=spans"))
    got = names(doc["spans"])
    assert got["ec.get"] == 1 and got["ec.get.d2h_wait"] == 1
    assert sum(1 for s in doc["spans"] if not s["parent"]) == 1


def test_present_interval_is_read_not_reconstructed(served):
    assert served.get(served.fid(served.present), trace="present1") \
        == payload(served.present - 1)
    spans = trace_of("present1")
    got = names(spans)
    for stage, count in PER_SERVED_GET.items():
        assert got[stage] == count, (stage, got)
    assert got["ec.get.shard_read"] == 1
    assert got["ec.get.queue"] == 0
    assert not any(got[s] for s in PER_LOST_INTERVAL)
    # all of it on the loop's thread, under the handler's stage
    by_id = {s["id"]: s for s in spans}
    for s in spans:
        if s["name"] in ("ec.get.ecx", "ec.get.shard_read", "ec.get.parse",
                         "ec.get.resume"):
            assert by_id[s["parent"]]["name"] == "ec.get.handler", s


@pytest.mark.parametrize("needle,headers,plane", [
    ("present", {}, "fast"), ("lost", {}, "fast"),
    # a Range is the aiohttp plane's, which makes the same read
    ("present", {"Range": "bytes=10-19"}, "aiohttp"),
    ("lost", {"Range": "bytes=10-19"}, "aiohttp"),
    # a 64 KB needle, over two present shards, is the loop's too; one
    # over `NOWAIT_MAX_SIZE`, as present, is not
    ("photo", {}, "fast"), ("over-limit", {}, "fast"),
    ("photo", {"Range": "bytes=10-19"}, "aiohttp"),
    ("over-limit", {"Range": "bytes=10-19"}, "aiohttp"),
])
def test_loop_reads_what_is_mapped_here_and_hands_on_the_rest(
        served, monkeypatch, needle, headers, plane):
    """A GET whose intervals are in mapped shard files, of a needle that
    holds the loop no longer than `NOWAIT_MAX_SIZE` allows, is answered
    on the loop's thread: no executor submit, no `ec.get.queue`. One
    that meets the lost shard, or is over the limit, declines and is
    handed on with what the loop located: one search of the index, not
    two. Either way the `read` histogram and the heat tracker take it
    once."""
    from seaweedfs_tpu.lifecycle.heat import HeatTracker
    submits, heat = [], []
    real_submit = served.loop.run_in_executor
    real_heat = HeatTracker.record_read
    monkeypatch.setattr(
        served.loop, "run_in_executor",
        lambda *a: submits.append(a) or real_submit(*a))
    monkeypatch.setattr(
        HeatTracker, "record_read",
        lambda self, vid: heat.append(vid) or real_heat(self, vid))
    if needle in BIG_NEEDLES:
        vid, needle_id = BIG_VID, BIG_NEEDLES[needle]
        want = big_payload(needle_id)
    else:
        vid, needle_id = 1, getattr(served, needle)
        want = payload(needle_id - 1)
    trace = f"nowait-{needle}-{plane}"
    before = served.read_counts()
    del submits[:]  # (the /metrics read is none of this GET's)
    body = served.get(served.fid(needle_id, vid), trace=trace,
                      headers=headers)
    n_submits = len(submits)
    assert body == (want[10:20] if headers else want)
    got = names(trace_of(trace))
    after = served.read_counts()
    rose = {k: after[k] - before[k] for k in after}
    on_loop = needle in ("present", "photo")
    assert rose == {"served": int(on_loop), "declined": int(not on_loop),
                    "timed": 1, "lookups": 1}
    assert n_submits == got["ec.get.queue"] == int(not on_loop)
    assert got["ec.get.ecx"] == got["ec.get.resume"] == 1
    assert heat == [vid]
    assert bool(got["GET /" + served.fid(needle_id, vid)]) \
        == (plane == "aiohttp")


def test_an_error_of_the_search_is_the_loops_answer(served):
    """Unknown and deleted needles end on the loop's thread too."""
    before = served.read_counts()
    with pytest.raises(urllib.error.HTTPError) as miss:
        served.get(served.fid(N_NEEDLES + 7), trace="nowait-miss")
    assert miss.value.code == 404
    miss.value.close()
    got = names(trace_of("nowait-miss"))
    after = served.read_counts()
    assert after["served"] == before["served"] + 1
    assert after["declined"] == before["declined"]
    assert got["ec.get.ecx"] == 1 and got["ec.get.queue"] == 0


def test_counters_rise_by_the_spans_counts(served):
    before = served.stage_counts()
    served.get(served.fid(served.lost))
    served.get(served.fid(served.present))
    # `ec.get` closes after the client has its answer
    deadline = time.time() + 5
    while time.time() < deadline:
        after = served.stage_counts()
        if after.get("ec.get", 0) - before.get("ec.get", 0) == 2:
            break
        time.sleep(0.01)
    rose = {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}
    want = {k: 2 * v for k, v in PER_SERVED_GET.items()}
    want.update(PER_LOST_INTERVAL)
    want["ec.get.queue"] = 1  # the degraded GET alone was queued
    want["ec.get.shard_read"] = 1
    assert rose == want


def test_plain_volume_get_takes_no_stage(served):
    before = served.stage_counts()
    assert served.get(served.fid(1, vid=2), trace="plain1") == b"plain" * 10
    spans = trace_of("plain1")
    assert served.stage_counts() == before
    assert not any(s["name"].startswith("ec.") for s in spans)


def test_wide_event_carries_the_exclusive_stages(served):
    wideevents.reset()
    served.get(served.fid(served.lost), trace="wide1")
    trace_of("wide1")
    events = wideevents.events(trace="wide1")
    assert len(events) == 1
    stages = events[0]["stages"]
    for stage in {**PER_DECLINED_GET, **PER_LOST_INTERVAL}:
        assert (stage in stages) == (stage not in ENCLOSING), stage
    assert not ENCLOSING & set(stages)
    # so the tail is put down to what the GET waited for, not to the
    # span that wraps it all
    assert wideevents.dominant_stage(events[0])[0] not in ENCLOSING


def test_wrapped_ring_leaves_the_counters_whole(served, monkeypatch):
    # as under a small WEED_TRACE_RING: the ring keeps the last 8 spans
    monkeypatch.setattr(observe, "_ring", collections.deque(maxlen=8))
    before = served.stage_counts()
    for _ in range(5):
        served.get(served.fid(served.lost))
    deadline = time.time() + 5
    while time.time() < deadline:
        after = served.stage_counts()
        if after["ec.get"] - before["ec.get"] == 5:
            break
        time.sleep(0.01)
    assert len(observe.spans()) == 8
    for stage, count in {**PER_DECLINED_GET, **PER_LOST_INTERVAL}.items():
        assert after[stage] - before[stage] == 5 * count, stage


def test_follower_of_a_flight_waits_under_its_own_stage(served):
    """Two reads of one lost interval at once: one reconstructs, the
    other rides its flight and says so."""
    release = threading.Event()
    asked = threading.Event()

    def slow_peer(shard_id, offset, size):
        asked.set()
        release.wait(10)
        return None  # no peer has it: reconstruct

    ctx = observe.TraceCtx("flight1", "", "volume", "")
    out = []

    def read():
        out.append(observe.run_with(
            ctx, served.ev.read_needle, served.lost, COOKIE, slow_peer))

    leader = threading.Thread(target=read)
    leader.start()
    assert asked.wait(10)
    follower = threading.Thread(target=read)
    follower.start()
    deadline = time.time() + 10
    while served.ev.read_flight.stats()["shared"] == 0 \
            and time.time() < deadline:
        time.sleep(0.005)
    release.set()
    leader.join(30)
    follower.join(30)
    assert not leader.is_alive() and not follower.is_alive()
    assert [n.data for n in out] == [payload(served.lost - 1)] * 2
    got = names(observe.spans(trace_id="flight1"))
    assert got["ec.get.flight_wait"] == 1
    assert got["ec.get.peer_fetch"] == 1 and got["ec.get.dispatch"] == 1
    assert got["ec.get.ecx"] == 2 and got["ec.get.parse"] == 2


def stage_totals() -> dict[str, list]:
    """stage -> [count, seconds] as the shared `ec` registry holds them."""
    family = metrics.shared("ec")._seconds.get("stage")
    return {k: list(v) for k, v in family[1].items()} if family else {}


def test_only_a_caller_that_names_its_steps_has_them_timed():
    """A reconstruction outside a GET (no `stage` prefix) and an encode
    take the same host apply and leave no stage behind."""
    before = stage_totals()
    observe.reset()
    coder = PallasCoder(10, 4, interpret=True)
    data = np.arange(10 * 1000, dtype=np.uint8).reshape(10, 1000)
    parity = coder.encode(data)
    shards = [*data, *parity]
    shards[3] = None
    assert (coder.reconstruct(shards, targets=(3,))[3] == data[3]).all()
    assert stage_totals() == before
    assert not observe.spans()
    ctx = observe.TraceCtx("named1", "", "volume", "")
    with observe.bind(ctx):
        coder.reconstruct(shards, targets=(3,), stage="ec.get")
    assert names(observe.spans(trace_id="named1")) == {
        "ec.get.stack_pad": 2, "ec.get.dispatch": 1, "ec.get.d2h_wait": 1}


def test_record_form_feeds_ring_and_counters_for_ec_names_only():
    ctx = observe.TraceCtx("rec1", "", "volume", "")
    observe.record_span("ec.test.recorded", ctx, 0, 2500)
    observe.record_span("volume.read", ctx, 0, 2500)
    assert names(observe.spans(trace_id="rec1")) == {
        "ec.test.recorded": 1, "volume.read": 1}
    totals = stage_totals()
    assert totals["ec.test.recorded"] == [1, pytest.approx(0.0025)]
    assert "volume.read" not in totals
    text = metrics.shared("ec").render()
    assert "# TYPE seaweedfs_tpu_ec_stage_seconds summary" in text
    assert 'seaweedfs_tpu_ec_stage_seconds_count{stage="ec.test.recorded"}' \
        ' 1' in text
    # a count and a sum alone: nothing reads a bucket of this family
    assert "seaweedfs_tpu_ec_stage_seconds_bucket" not in text


def test_stages_under_an_enclosing_one_are_paid_for_at_its_exit():
    """Sinks 1 and 2 once a request: nothing of a stage that ends under
    an open enclosing stage is in the ring or the counters until that
    one closes; then all of it is, and the wide event has the exclusive
    ones."""
    ctx = observe.TraceCtx("fold1", "rootspan", "volume", "")
    before = stage_totals().get("ec.test.under", [0, 0.0])[0]
    with observe.bind(ctx):
        tok = wideevents.begin("rootspan")
        try:
            with observe.stage("ec.test.whole", enclosing=True):
                with observe.stage("ec.test.under"):
                    pass
                observe.record_span("ec.test.under", None, 5, 1000)
                with observe.stage("ec.test.inner", enclosing=True):
                    pass  # an enclosing stage under another: at once
                assert names(observe.spans(trace_id="fold1")) == {
                    "ec.test.inner": 1}
                assert stage_totals().get(
                    "ec.test.under", [0, 0.0])[0] == before
            acc = wideevents.current()
        finally:
            wideevents.end(tok)
    spans = observe.spans(trace_id="fold1")
    assert names(spans) == {"ec.test.under": 2, "ec.test.inner": 1,
                            "ec.test.whole": 1}
    by_name = {s["name"]: s for s in spans}
    assert len({s["id"] for s in spans}) == 4
    # the gathered ones are the gatherer's children
    assert [s["parent"] for s in spans if s["name"] == "ec.test.under"] \
        == [by_name["ec.test.whole"]["id"]] * 2
    assert by_name["ec.test.whole"]["parent"] == "rootspan"
    # an enclosing stage under another is that one's child
    assert by_name["ec.test.inner"]["parent"] == by_name["ec.test.whole"]["id"]
    assert observe.spans(trace_id="fold1") == spans  # ids read the same
    assert stage_totals()["ec.test.under"][0] == before + 2
    assert set(acc["stages"]) == {"ec.test.under"}
    assert acc["stages"]["ec.test.under"] >= 1000


def test_stage_reaches_a_profiler_session_on_its_own_clock(tmp_path):
    """Sink 3: while a session is open the with-form's block is a host
    event of the trace, which the operator's summary reads back."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        ctx = observe.TraceCtx("note1", "", "volume", "")
        for _ in range(3):
            with observe.stage("ec.test.noted", ctx):
                time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    found = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    summary = profiler.summarize_xplane(str(found[-1]))
    count, seconds = summary["stages"]["ec.test.noted"]
    assert count == 3 and 0.03 <= seconds < 0.3
    assert summary["window_s"] > seconds
    assert summary["devices_traced"] == 0 and summary["device_busy_s"] == 0
    ring = sum(s["dur_us"] for s in observe.spans(trace_id="note1")) / 1e6
    assert seconds == pytest.approx(ring, rel=0.05)


def xprof(served, query: str = "") -> tuple[int, dict]:
    import json
    try:
        return 200, json.loads(served.get("debug/xprof" + query))
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_xprof_answers_501_without_an_accelerator(served):
    status, body = xprof(served, "?seconds=0.1")
    assert status == 501 and "accelerator" in body["error"]


def test_xprof_one_window_at_a_time(served, monkeypatch):
    """With the server told it is on a device the window runs here too
    (the CPU has a profiler): a second request meanwhile is refused, a
    GET served meanwhile is in the answer."""
    monkeypatch.setattr(VolumeServer, "_ec_on_device", lambda self: True)
    first: list = []
    th = threading.Thread(
        target=lambda: first.append(xprof(served, "?seconds=1.5")))
    th.start()
    time.sleep(0.5)
    status, body = xprof(served, "?seconds=0.1")
    assert status == 409 and "window is open" in body["error"]
    served.get(served.fid(served.lost))
    th.join(60)
    assert not th.is_alive()
    status, body = first[0]
    assert status == 200, body
    assert 1.5 <= body["window_s"] < 10
    assert body["stages"]["ec.get.d2h_wait"][0] == 1
    assert body["stages"]["ec.get"][0] == 1
    assert "trace_dir" not in body


def test_xprof_refuses_while_another_owner_holds_the_profiler(
        served, monkeypatch, tmp_path):
    import jax
    monkeypatch.setattr(VolumeServer, "_ec_on_device", lambda self: True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        status, body = xprof(served, "?seconds=0.1")
    finally:
        jax.profiler.stop_trace()
    assert status == 409 and "held elsewhere" in body["error"]


def test_xprof_keep_leaves_the_trace(served, monkeypatch):
    import os
    import shutil
    monkeypatch.setattr(VolumeServer, "_ec_on_device", lambda self: True)
    status, body = xprof(served, "?seconds=0.1&keep=1")
    assert status == 200
    try:
        assert any(name.endswith(".xplane.pb")
                   for _, _, files in os.walk(body["trace_dir"])
                   for name in files)
    finally:
        shutil.rmtree(body["trace_dir"], ignore_errors=True)


@pytest.mark.parametrize("stage,bucket", [
    ("ec.get.ecx", "disk"), ("ec.get.shard_read", "disk"),
    ("ec.get.survivors", "disk"), ("ec.get.parse", "disk"),
    ("ec.get.peer_fetch", "remote-hop"),
    ("ec.get.remote_read", "remote-hop"),
    ("ec.get.queue", "admission-queue"),
    ("ec.get.resume", "admission-queue"),
    ("ec.get.flight_wait", "lock"),
    ("ec.get.stack_pad", "kernel"), ("ec.get.dispatch", "kernel"),
    ("ec.get.d2h_wait", "kernel"), ("ec.kernel", "kernel"),
    ("ec.dispatch", "kernel"),
    ("ec.get.respond", "handler"), ("ec.get.handler", "handler"),
    ("ec.read", "disk"), ("ec.write", "disk"), ("ec.fsync", "disk"),
    ("ec.seal", "disk"), ("ec.ecx", "disk"), ("ec.stamp", "disk"),
    # no catch-all: a host-side ec.* stage without a row is the
    # handler's, never the kernel's
    ("ec.mount", "handler"), ("ec.digest", "handler"),
    ("ec.some_new_host_stage", "handler"),
])
def test_stage_buckets(stage, bucket):
    assert wideevents.stage_bucket(stage) == bucket


def test_warm_down_control_path_is_staged(tmp_path):
    """seal, .ecx, stamp, fsync and mount each a stage with a counter:
    instrumented now, read when warm-down is a cell."""
    def count(stage: str) -> int:
        return stage_totals().get(stage, [0, 0.0])[0]

    stages = ("ec.seal", "ec.ecx", "ec.stamp", "ec.fsync", "ec.mount",
              "ec.read", "ec.write", "ec.kernel", "ec.dispatch")
    before = {s: count(s) for s in stages}
    store = Store([str(tmp_path)], coder_name="numpy", geometry=GEOMETRY)
    try:
        store.add_volume(7)
        store.write_needle(7, Needle(id=1, cookie=COOKIE, data=b"x" * 5000))
        store.ec_generate(7)
        store.ec_mount(7, "", list(range(14)))
    finally:
        store.close()
    for s in stages:
        assert count(s) > before[s], s
    for s in ("ec.seal", "ec.ecx", "ec.stamp", "ec.fsync", "ec.mount"):
        assert count(s) == before[s] + 1, s
