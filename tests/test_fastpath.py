"""The hand-rolled data-plane listener (server/fastpath.py).

run_volume_server's public port speaks the minimal HTTP/1.1 protocol and
proxies the non-data surface to the internal aiohttp app; these tests
exercise exactly that wiring (the in-process Cluster used by other suites
serves aiohttp directly, so this file is the fastpath's coverage).
"""

import asyncio
import json
import socket
import threading

import pytest

from seaweedfs_tpu.server.volume_server import run_volume_server
from seaweedfs_tpu.storage.store import Store


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class _Srv:
    """run_volume_server in a background loop thread."""

    def __init__(self, tmpdir: str, whitelist=None, store=None, **kwargs):
        self.port = _free_port()
        if store is None:
            store = Store([tmpdir])
            store.add_volume(1)
        self.store = store
        self.loop = asyncio.new_event_loop()
        if whitelist is not None:
            from seaweedfs_tpu.security.guard import Guard
            kwargs["guard"] = Guard(whitelist=whitelist)
        self.runner = None

        def run():
            asyncio.set_event_loop(self.loop)
            self.runner = self.loop.run_until_complete(run_volume_server(
                "127.0.0.1", self.port, self.store,
                master_url="127.0.0.1:1",  # no master: heartbeats warn only
                pulse_seconds=3600, **kwargs))
            self.loop.run_forever()

        self.th = threading.Thread(target=run, daemon=True)
        self.th.start()
        import time
        deadline = time.time() + 10
        while time.time() < deadline:
            try:
                socket.create_connection(("127.0.0.1", self.port),
                                         timeout=0.2).close()
                return
            except OSError:
                time.sleep(0.05)
        raise TimeoutError("fastpath server did not listen")

    def stop(self):
        async def halt():
            await self.runner.cleanup()
        asyncio.run_coroutine_threadsafe(halt(), self.loop).result(10)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.th.join(5)


def _req(port, method, path, body=b"", headers=None):
    """One raw HTTP/1.1 request on a fresh connection."""
    s = socket.create_connection(("127.0.0.1", port), timeout=10)
    hs = "".join(f"{k}: {v}\r\n" for k, v in (headers or {}).items())
    s.sendall(f"{method} {path} HTTP/1.1\r\nHost: x\r\n"
              f"Content-Length: {len(body)}\r\n{hs}\r\n".encode() + body)
    buf = b""
    while b"\r\n\r\n" not in buf:
        chunk = s.recv(1 << 16)
        if not chunk:
            break
        buf += chunk
    head, _, rest = buf.partition(b"\r\n\r\n")
    status = int(head.split(b" ", 2)[1])
    length = None
    for line in head.split(b"\r\n")[1:]:
        if line.lower().startswith(b"content-length"):
            length = int(line.split(b":")[1])
    if length is not None and method != "HEAD":
        while len(rest) < length:
            chunk = s.recv(1 << 16)
            if not chunk:
                break
            rest += chunk
        rest = rest[:length]
    s.close()
    return status, dict(
        (line.split(b":", 1)[0].decode().lower(),
         line.split(b":", 1)[1].strip().decode())
        for line in head.split(b"\r\n")[1:] if b":" in line), rest


def _multipart(data: bytes, filename="f.bin",
               ctype="application/octet-stream"):
    b = "fastb0undary"
    body = (f'--{b}\r\nContent-Disposition: form-data; name="file"; '
            f'filename="{filename}"\r\nContent-Type: {ctype}\r\n\r\n'
            ).encode() + data + f"\r\n--{b}--\r\n".encode()
    return body, f"multipart/form-data; boundary={b}"


@pytest.fixture()
def srv(tmp_path):
    s = _Srv(str(tmp_path))
    yield s
    s.stop()


FID = "1,42deadbeef"


def test_write_read_head_delete(srv):
    payload = b"\x01\x02fastpath payload" * 40
    body, ct = _multipart(payload)
    status, _, resp = _req(srv.port, "POST", f"/{FID}", body,
                           {"Content-Type": ct})
    assert status == 201
    meta = json.loads(resp)
    # size is the STORED length (post write-path gzip), matching the
    # aiohttp handler's semantics
    assert 0 < meta["size"] <= len(payload)

    status, hdrs, got = _req(srv.port, "GET", f"/{FID}")
    assert status == 200 and got == payload
    assert hdrs.get("etag")

    # HEAD reports the real size with no body
    status, hdrs, got = _req(srv.port, "HEAD", f"/{FID}")
    assert status == 200 and got == b""
    assert int(hdrs["content-length"]) == len(payload)

    # conditional read
    status, _, _ = _req(srv.port, "GET", f"/{FID}",
                        headers={"If-None-Match": hdrs["etag"]})
    assert status == 304

    # range requests proxy to aiohttp and still work
    status, _, got = _req(srv.port, "GET", f"/{FID}",
                          headers={"Range": "bytes=2-5"})
    assert status == 206 and got == payload[2:6]

    status, _, resp = _req(srv.port, "DELETE", f"/{FID}")
    assert status == 200 and json.loads(resp)["size"] > 0
    status, _, _ = _req(srv.port, "GET", f"/{FID}")
    assert status == 404


def test_proxied_surface_and_errors(srv):
    # /status is served by the aiohttp app through the loopback proxy
    status, _, resp = _req(srv.port, "GET", "/status")
    assert status == 200
    assert "volumes" in json.loads(resp)
    # unknown fid forms
    status, _, _ = _req(srv.port, "GET", "/nofid")
    assert status == 400
    # missing needle 404s via the proxied repair path
    status, _, _ = _req(srv.port, "GET", "/1,99aaaaaaaa")
    assert status == 404
    # oversize declared body is rejected before buffering
    s = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
    s.sendall(b"POST /" + FID.encode() + b" HTTP/1.1\r\nHost: x\r\n"
              b"Content-Length: 999999999999\r\n\r\n")
    buf = b""
    while b"\r\n\r\n" not in buf:
        chunk = s.recv(1 << 16)
        if not chunk:
            break
        buf += chunk
    assert b" 413 " in buf.split(b"\r\n", 1)[0]
    s.close()


def test_proxied_head_no_hang(srv):
    """HEAD responses on the proxied path carry Content-Length but no
    body; the relay must not wait for body bytes (it used to stall until
    aiohttp's keep-alive timeout, ~75s)."""
    import time
    t0 = time.time()
    # missing needle -> proxied repair path -> 404 with a JSON error body
    # advertised in Content-Length but never sent for HEAD
    status, _, got = _req(srv.port, "HEAD", "/1,99aaaaaaaa")
    assert status == 404 and got == b""
    # proxied admin surface
    status, _, got = _req(srv.port, "HEAD", "/status")
    assert status == 200 and got == b""
    assert time.time() - t0 < 10

    # the per-connection loop is serial: a request pipelined after a
    # proxied HEAD must still be answered promptly
    s = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
    s.sendall(b"HEAD /1,99aaaaaaaa HTTP/1.1\r\nHost: x\r\n"
              b"Content-Length: 0\r\n\r\n"
              b"GET /status HTTP/1.1\r\nHost: x\r\n"
              b"Content-Length: 0\r\n\r\n")
    buf = b""
    deadline = time.time() + 10
    while buf.count(b"HTTP/1.1") < 2 and time.time() < deadline:
        chunk = s.recv(1 << 16)
        if not chunk:
            break
        buf += chunk
    s.close()
    assert buf.count(b"HTTP/1.1") >= 2 and b" 200 " in buf


def test_malformed_content_length_400(srv):
    s = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
    s.sendall(b"POST /" + FID.encode() + b" HTTP/1.1\r\nHost: x\r\n"
              b"Content-Length: banana\r\n\r\n")
    buf = b""
    while b"\r\n\r\n" not in buf:
        chunk = s.recv(1 << 16)
        if not chunk:
            break
        buf += chunk
    assert b" 400 " in buf.split(b"\r\n", 1)[0]
    s.close()
    # negative declared length is equally malformed
    s = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
    s.sendall(b"POST /" + FID.encode() + b" HTTP/1.1\r\nHost: x\r\n"
              b"Content-Length: -5\r\n\r\n")
    buf = b""
    while b"\r\n\r\n" not in buf:
        chunk = s.recv(1 << 16)
        if not chunk:
            break
        buf += chunk
    assert b" 400 " in buf.split(b"\r\n", 1)[0]
    s.close()


def test_keepalive_many_requests(srv):
    payload = b"ka" * 100
    body, ct = _multipart(payload)
    s = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
    for i in range(20):
        s.sendall(f"POST /1,{i+1:x}00000011 HTTP/1.1\r\nHost: x\r\n"
                  f"Content-Type: {ct}\r\n"
                  f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        buf = b""
        while b"\r\n\r\n" not in buf:
            buf += s.recv(1 << 16)
        head, _, rest = buf.partition(b"\r\n\r\n")
        assert b" 201 " in head.split(b"\r\n", 1)[0]
        ln = int([l for l in head.split(b"\r\n")
                  if l.lower().startswith(b"content-length")][0]
                 .split(b":")[1])
        while len(rest) < ln:
            rest += s.recv(1 << 16)
    s.close()


def test_whitelist_passes_through_proxy(tmp_path):
    # a whitelist that includes the client must admit BOTH inline and
    # proxied requests (the internal listener sees 127.0.0.1; the token
    # header carries the original verification through)
    s = _Srv(str(tmp_path), whitelist=["127.0.0.1"])
    try:
        status, _, _ = _req(s.port, "GET", "/status")
        assert status == 200
        payload = b"wl" * 10
        body, ct = _multipart(payload)
        status, _, _ = _req(s.port, "POST", f"/{FID}", body,
                            {"Content-Type": ct})
        assert status == 201
    finally:
        s.stop()


def test_fastpath_admission_hook_sheds(tmp_path, monkeypatch):
    """The raw-socket listener bypasses aiohttp middleware, so the
    overload plane hooks it explicitly: with a 1-slot foreground pipe
    and no queue, a second concurrent read sheds 503 with the shed
    marker + Retry-After, bg-tagged reads shed under that pressure, and
    the inline fast read fires the volume.read fault point (which is
    what makes this test's service time controllable at all)."""
    import time

    from seaweedfs_tpu import faults

    monkeypatch.setenv("WEED_ADMISSION_FG_CONCURRENCY", "1")
    monkeypatch.setenv("WEED_ADMISSION_FG_QUEUE", "0")
    monkeypatch.setenv("WEED_ADMISSION_LAG_SAMPLE_MS", "100")
    monkeypatch.setenv("WEED_ADMISSION_RETRY_AFTER_S", "1")
    srv = _Srv(str(tmp_path))
    try:
        payload = b"shed me" * 10
        body, ct = _multipart(payload)
        status, _, _ = _req(srv.port, "POST", f"/{FID}", body,
                            {"Content-Type": ct})
        assert status == 201

        # unfaulted read works and is admitted
        status, _, got = _req(srv.port, "GET", f"/{FID}")
        assert status == 200 and got == payload

        # make the inline fast read slow via the fault plane (the hook
        # added alongside admission: fastpath fires volume.read too)
        faults.set_fault("volume.read", "delay", ms=600)
        t = threading.Thread(target=_req,
                             args=(srv.port, "GET", f"/{FID}"))
        t.start()
        time.sleep(0.2)  # the slow read owns the single fg slot
        status, hdrs, _ = _req(srv.port, "GET", f"/{FID}")
        assert status == 503
        assert hdrs.get("x-seaweed-shed") == "1"
        assert int(hdrs.get("retry-after", "0")) >= 1
        # background is locked out while fg is under pressure
        status, hdrs, _ = _req(srv.port, "GET", f"/{FID}",
                               headers={"X-Seaweed-Priority": "bg"})
        assert status == 503 and hdrs.get("x-seaweed-shed") == "1"
        t.join(10)
        faults.clear()
        # pressure gone (one sampler window): everything flows again
        time.sleep(0.15)
        status, _, got = _req(srv.port, "GET", f"/{FID}",
                              headers={"X-Seaweed-Priority": "bg"})
        assert status == 200 and got == payload
    finally:
        faults.clear()
        srv.stop()


def test_fastpath_sheds_before_buffering_body(tmp_path, monkeypatch):
    """Admission runs from the HEADERS, before the body is buffered: a
    write that will be shed must be refused while its body is still on
    the wire, or a storm of declared-large POSTs buffers gigabytes of
    bodies that were never going to be admitted (the memory-collapse
    mode the overload plane exists to stop).  The shed answer arrives
    with none of the body sent, and the connection closes (an unread
    body makes the framing unrecoverable)."""
    import time

    from seaweedfs_tpu import faults

    monkeypatch.setenv("WEED_ADMISSION_FG_CONCURRENCY", "1")
    monkeypatch.setenv("WEED_ADMISSION_FG_QUEUE", "0")
    monkeypatch.setenv("WEED_ADMISSION_LAG_SAMPLE_MS", "2000")
    srv = _Srv(str(tmp_path))
    try:
        payload = b"hold the slot"
        body, ct = _multipart(payload)
        status, _, _ = _req(srv.port, "POST", f"/{FID}", body,
                            {"Content-Type": ct})
        assert status == 201
        faults.set_fault("volume.read", "delay", ms=800)
        t = threading.Thread(target=_req,
                             args=(srv.port, "GET", f"/{FID}"))
        t.start()
        time.sleep(0.2)  # the slow read owns the single fg slot
        s = socket.create_connection(("127.0.0.1", srv.port), timeout=5)
        try:
            s.sendall(f"POST /{FID} HTTP/1.1\r\nHost: x\r\n"
                      f"Content-Length: 10000000\r\n"
                      f"Content-Type: multipart/form-data; boundary=q"
                      f"\r\n\r\n".encode())  # headers only — no body
            s.settimeout(3.0)
            t0 = time.monotonic()
            data = s.recv(65536)
            took = time.monotonic() - t0
            line = data.split(b"\r\n", 1)[0]
            assert b"503" in line, data
            assert b"x-seaweed-shed: 1" in data.lower(), data
            # answered from the headers alone, not after a body wait
            assert took < 1.0, took
            # unread body in flight -> server closes the connection
            assert s.recv(4096) == b""
        finally:
            s.close()
        t.join(10)
    finally:
        faults.clear()
        srv.stop()


def test_mark_internal_strips_spoofed_swfs_headers():
    """A client-sent X-Swfs-Tunnel on a proxied (already-admitted)
    request would make the aiohttp middleware meter it a SECOND time —
    with fg slots held at the listener that deadlocks the class into
    queue-timeout sheds. All client copies of the internal headers are
    stripped before the listener injects its own."""
    import types
    from seaweedfs_tpu.server.fastpath import FastVolumeProtocol

    p = FastVolumeProtocol.__new__(FastVolumeProtocol)
    p.server = types.SimpleNamespace(_internal_token="tok123")
    p.peer_ip = "10.0.0.9"
    raw = (b"GET /1,abc HTTP/1.1\r\n"
           b"Host: x\r\n"
           b"X-Swfs-Tunnel: 1\r\n"
           b"X-Swfs-Internal: guessed\r\n"
           b"X-Swfs-Peer: 8.8.8.8\r\n"
           b"Accept: */*\r\n"
           b"\r\nBODY")
    parts = p._mark_internal(raw)
    # the body rides as an uncopied view into the original buffer (a
    # proxied 256 MB PUT must not pay full-buffer copies here)
    assert isinstance(parts[-1], memoryview)
    assert parts[-1].obj is raw
    marked = b"".join(bytes(x) for x in parts)
    head = marked.split(b"\r\n\r\n", 1)[0]
    # exactly one copy of each injected header, ours
    assert head.count(b"X-Swfs-Internal:") == 1
    assert b"X-Swfs-Internal: tok123" in head
    assert head.count(b"X-Swfs-Peer:") == 1
    assert b"X-Swfs-Peer: 10.0.0.9" in head
    assert b"X-Swfs-Tunnel" not in head       # spoofed marker gone
    assert b"guessed" not in head and b"8.8.8.8" not in head
    assert b"Host: x" in head and b"Accept: */*" in head
    assert marked.endswith(b"\r\n\r\nBODY")
    # the real tunnel path still marks itself
    marked = b"".join(bytes(x)
                      for x in p._mark_internal(raw, tunnel=True))
    head = marked.split(b"\r\n\r\n", 1)[0]
    assert head.count(b"X-Swfs-Tunnel:") == 1
    assert b"X-Swfs-Tunnel: 1" in head


def test_fastpath_emits_wide_events(srv):
    """The raw-socket listener bypasses aiohttp middleware, so it emits
    its own wide events: one canonical record per fast-served request,
    carrying the propagated trace id, priority class, and byte counts —
    and no duplicate record for the proxied surface (the aiohttp
    middleware owns those)."""
    import time

    from seaweedfs_tpu.observe import wideevents

    def _wait_events(trace, n=1, deadline_s=5.0):
        # the record lands in the listener's finally block AFTER the
        # response bytes hit the wire — poll rather than race it
        deadline = time.time() + deadline_s
        while time.time() < deadline:
            evs = wideevents.events(trace=trace)
            if len(evs) >= n:
                return evs
            time.sleep(0.02)
        return wideevents.events(trace=trace)

    wideevents.reset()
    payload = b"wide event payload" * 30
    body, ct = _multipart(payload)
    status, _, _ = _req(srv.port, "POST", f"/{FID}", body,
                        {"Content-Type": ct})
    assert status == 201

    tid = "feedfacefastwide"
    status, _, got = _req(srv.port, "GET", f"/{FID}",
                          headers={"X-Seaweed-Trace": f"{tid}:",
                                   "X-Seaweed-Priority": "bg"})
    assert status == 200 and got == payload

    evs = _wait_events(tid)
    assert len(evs) == 1, evs
    ev = evs[0]
    assert ev["svc"] == "volume"
    assert ev["name"].startswith("fast GET /")
    assert ev["cls"] == "bg"
    assert ev["status"] == 200
    assert ev["bytes_out"] == len(payload)
    assert ev["shed"] is False
    assert ev["dur_us"] > 0

    # the proxied surface (/status goes through the loopback tunnel to
    # aiohttp) produces exactly ONE event — the middleware's, not a
    # second one from the fastpath listener
    tid2 = "feedfaceproxied0"
    status, _, _ = _req(srv.port, "GET", "/status",
                        headers={"X-Seaweed-Trace": f"{tid2}:"})
    assert status == 200
    evs = _wait_events(tid2)
    time.sleep(0.2)  # give a would-be duplicate emitter time to land
    evs = wideevents.events(trace=tid2)
    assert len(evs) == 1, evs
    assert not evs[0]["name"].startswith("fast ")
    wideevents.reset()


def test_fastpath_shed_emits_wide_event(tmp_path, monkeypatch):
    """A request refused at the fastpath admission gate still leaves a
    wide event (shed=True, 503) — sheds are exactly the traffic a tail
    investigation must be able to see."""
    import time

    from seaweedfs_tpu import faults
    from seaweedfs_tpu.observe import wideevents

    monkeypatch.setenv("WEED_ADMISSION_FG_CONCURRENCY", "1")
    monkeypatch.setenv("WEED_ADMISSION_FG_QUEUE", "0")
    monkeypatch.setenv("WEED_ADMISSION_LAG_SAMPLE_MS", "100")
    srv = _Srv(str(tmp_path))
    try:
        payload = b"shed and observe" * 8
        body, ct = _multipart(payload)
        status, _, _ = _req(srv.port, "POST", f"/{FID}", body,
                            {"Content-Type": ct})
        assert status == 201

        wideevents.reset()
        faults.set_fault("volume.read", "delay", ms=600)
        t = threading.Thread(target=_req,
                             args=(srv.port, "GET", f"/{FID}"))
        t.start()
        time.sleep(0.2)  # the slow read owns the single fg slot
        tid = "feedfaceshedwide"
        status, hdrs, _ = _req(srv.port, "GET", f"/{FID}",
                               headers={"X-Seaweed-Trace": f"{tid}:"})
        assert status == 503 and hdrs.get("x-seaweed-shed") == "1"
        t.join(10)
        faults.clear()

        deadline = time.time() + 5
        while time.time() < deadline:
            evs = wideevents.events(trace=tid)
            if evs:
                break
            time.sleep(0.02)
        assert len(evs) == 1, evs
        assert evs[0]["shed"] is True
        assert evs[0]["status"] == 503
        # the shed tail is queryable the way cluster.tail reads it
        assert any(e["trace"] == tid
                   for e in wideevents.events(shed=True))
        wideevents.reset()
    finally:
        faults.clear()
        srv.stop()


def test_hot_parse_allocations_pinned():
    """The per-request parse path must not allocate on the benchmark
    shapes: the no-query GET shares ONE dict (_EMPTY_QUERY) and
    _HeaderView is slotted so token extraction costs one fixed-size
    object, not a dict copy. Regressions here (an f-string, a
    per-request dict, a dropped __slots__) show up as net block
    growth across iterations."""
    import gc
    import sys

    from seaweedfs_tpu.server import fastpath

    # the no-query fast shape returns the module-level shared dict
    assert fastpath._parse_query("") is fastpath._EMPTY_QUERY
    assert fastpath._parse_query("") is fastpath._parse_query("")
    # escaped and plain pairs still decode like aiohttp would
    assert fastpath._parse_query("a=1&b=x%20y") == {"a": "1", "b": "x y"}
    # _HeaderView carries no per-instance __dict__
    view = fastpath._HeaderView({b"authorization": b"Bearer t"})
    assert not hasattr(view, "__dict__")
    assert view.get("Authorization") == "Bearer t"

    headers = {b"content-length": b"0", b"authorization": b""}

    def hot() -> None:
        q = fastpath._parse_query("")
        assert not q
        fastpath._HeaderView(headers).get("Authorization")

    for _ in range(200):  # warm caches/interning before measuring
        hot()
    gc.collect()
    before = sys.getallocatedblocks()
    for _ in range(5000):
        hot()
    gc.collect()
    grown = sys.getallocatedblocks() - before
    # transient objects are freed each iteration; anything that sticks
    # (a cache keyed per call, a leaked view) grows net blocks linearly
    assert grown < 500, f"hot parse path leaked {grown} blocks"
    # callers treat query dicts as read-only; the shared empty dict
    # must never pick up keys from a request
    assert len(fastpath._EMPTY_QUERY) == 0


# --- EC GETs: the fast path answers the plain shape itself ---------------
# One EC volume, built alike under two servers: the public listener of one
# is the fast path, the other serves aiohttp alone (fastpath=False), which
# is the answer the fast path's has to equal.

EC_COOKIE = 0x1234
EC_PRESENT, EC_LOST, EC_DELETED, EC_GZIP, EC_UNKNOWN = 0, 5, 7, 41, 999
EC_TEXT = b"a needle stored gzipped\n" * 200


def _ec_store(tmpdir: str) -> tuple[Store, dict]:
    import gzip

    from seaweedfs_tpu.ec.geometry import Geometry
    from seaweedfs_tpu.storage.needle import (FLAG_HAS_LAST_MODIFIED,
                                              FLAG_HAS_MIME, FLAG_HAS_NAME,
                                              FLAG_IS_COMPRESSED, Needle)
    geometry = Geometry(10, 4, large_block_size=64 * 1024,
                        small_block_size=4 * 1024)
    store = Store([tmpdir], coder_name="numpy", geometry=geometry)
    store.add_volume(1)

    def put(i: int, data: bytes, name: bytes, mime: bytes, gz=False):
        n = Needle(id=i, cookie=EC_COOKIE, data=data)
        for flag in (FLAG_HAS_NAME, FLAG_HAS_MIME, FLAG_HAS_LAST_MODIFIED):
            n.set_flag(flag)
        if gz:
            n.set_flag(FLAG_IS_COMPRESSED)
        n.name, n.mime, n.last_modified = name, mime, 1_700_000_000 + i
        store.write_needle(1, n)

    for i in range(1, 41):
        put(i, bytes([i]) * 1000, b"n%d.bin" % i, b"application/x-test")
    put(EC_GZIP, gzip.compress(EC_TEXT, mtime=0), b"t.txt", b"text/plain",
        gz=True)
    store.ec_generate(1)
    store.ec_mount(1, "", list(range(14)))
    store.delete_volume(1)
    ev = store.find_ec_volume(1)

    def shard_of(i: int) -> int:
        return ev.locate(i)[2][0].to_shard_id_and_offset(geometry)[0]

    lost = shard_of(EC_LOST)
    present = next(i for i in range(1, 41) if shard_of(i) != lost
                   and i != EC_DELETED)
    ev.delete_shard(lost)
    store.ec_blob_delete(1, EC_DELETED)
    return store, {EC_PRESENT: present}


@pytest.fixture(scope="module")
def ec_planes(tmp_path_factory):
    fast_store, ids = _ec_store(str(tmp_path_factory.mktemp("ecfast")))
    slow_store, _ = _ec_store(str(tmp_path_factory.mktemp("ecslow")))
    fast = _Srv("", store=fast_store)
    slow = _Srv("", store=slow_store, fastpath=False)
    yield fast, slow, ids
    fast.stop()
    slow.stop()
    fast_store.close()
    slow_store.close()


def _ec_fid(needle: int, cookie: int = EC_COOKIE) -> str:
    return f"/1,{needle:x}{cookie:08x}"


def _plane_counts(port: int) -> dict:
    import re
    text = _req(port, "GET", "/metrics")[2].decode()
    return {name: int(float(m.group(1))) if m else 0 for name, m in (
        (name, re.search(rf"^seaweedfs_tpu_volume_{name}_total (\S+)$",
                         text, re.M))
        for name in ("ec_read_inline", "ec_read_proxied", "read"))}


def _nowait_counts(port: int) -> tuple[int, int]:
    """EC reads the loop's thread made itself, and those it handed on."""
    import re
    text = _req(port, "GET", "/metrics")[2].decode()
    return tuple(int(float(re.search(
        rf'^seaweedfs_tpu_volume_ec_read_nowait_total{{result="{result}"}} '
        r"(\S+)$", text, re.M).group(1)))
        for result in ("served", "declined"))


_EC_HEADERS = ("etag", "x-last-modified", "content-type", "content-length",
               "content-disposition", "content-encoding")


@pytest.mark.parametrize("case,method,needle,cookie,headers,status", [
    ("present", "GET", EC_PRESENT, EC_COOKIE, {}, 200),
    ("lost", "GET", EC_LOST, EC_COOKIE, {}, 200),
    ("deleted", "GET", EC_DELETED, EC_COOKIE, {}, 404),
    ("unknown", "GET", EC_UNKNOWN, EC_COOKIE, {}, 404),
    ("wrong-cookie", "GET", EC_LOST, EC_COOKIE + 1, {}, 404),
    ("head", "HEAD", EC_PRESENT, EC_COOKIE, {}, 200),
    ("head-lost", "HEAD", EC_LOST, EC_COOKIE, {}, 200),
    ("if-none-match", "GET", EC_PRESENT, EC_COOKIE,
     {"If-None-Match": None}, 304),
    ("gzip-accepted", "GET", EC_GZIP, EC_COOKIE,
     {"Accept-Encoding": "gzip, deflate"}, 200),
    ("gzip-not-accepted", "GET", EC_GZIP, EC_COOKIE, {}, 200),
    ("head-gzip-not-accepted", "HEAD", EC_GZIP, EC_COOKIE, {}, 200),
])
def test_ec_get_answers_alike_on_both_planes(ec_planes, case, method,
                                             needle, cookie, headers,
                                             status):
    fast, slow, ids = ec_planes
    path = _ec_fid(ids.get(needle, needle), cookie)
    if "If-None-Match" in headers:
        headers = {"If-None-Match": _req(fast.port, "GET", path)[1]["etag"]}
    before = _plane_counts(fast.port)
    nowait = _nowait_counts(fast.port)
    got = _req(fast.port, method, path, headers=headers)
    after = _plane_counts(fast.port)
    served, declined = _nowait_counts(fast.port)
    want = _req(slow.port, method, path, headers=headers)
    assert got[0] == want[0] == status
    assert got[2] == want[2]
    # the loop's thread read it, unless it lies on the lost shard
    on_loop = needle != EC_LOST
    assert (served - nowait[0], declined - nowait[1]) \
        == (int(on_loop), int(not on_loop))
    # the fast path answered, and no EC GET went to the aiohttp listener
    assert after["ec_read_inline"] == before["ec_read_inline"] + 1
    assert after["ec_read_proxied"] == before["ec_read_proxied"]
    assert after["read"] == before["read"] + 1
    if status == 304:
        # no representation, so no headers of one (the fast path frames
        # every bodiless answer with `_send`'s Content-Type and a zero
        # Content-Length, for a plain volume too)
        assert "etag" not in got[1] and "etag" not in want[1]
        return
    for name in _EC_HEADERS:
        a, b = got[1].get(name), want[1].get(name)
        if name == "content-type":
            # aiohttp's JSON errors add "; charset=utf-8"
            a, b = a.split(";")[0], b.split(";")[0]
        assert a == b, (name, a, b)
    if status == 200:
        assert got[1]["etag"] and got[1]["content-disposition"]
        assert int(got[1]["x-last-modified"]) > 1_700_000_000
        if method == "GET":
            assert int(got[1]["content-length"]) == len(got[2])
    if case == "gzip-accepted":
        import gzip
        assert got[1]["content-encoding"] == "gzip"
        assert gzip.decompress(got[2]) == EC_TEXT
    if case == "gzip-not-accepted":
        assert "content-encoding" not in got[1] and got[2] == EC_TEXT
    if status == 404:
        # (a needle deleted from an EC volume reads as absent: its
        # `.ecx` entry is a tombstone, which `find_needle` takes for none)
        assert json.loads(got[2]) == {"error": "not found"}


@pytest.mark.parametrize("action,status,body", [
    ("drop", 404, {"error": "injected drop"}),
    ("error", 500, {"error": "injected fault at volume.read"}),
    ("delay", 200, None),
])
def test_volume_read_fault_acts_once_on_inline_ec_get(ec_planes, action,
                                                      status, body):
    import time

    from seaweedfs_tpu import faults
    fast, _, ids = ec_planes
    before = _plane_counts(fast.port)
    nowait = _nowait_counts(fast.port)
    faults.clear()
    faults.set_fault("volume.read", action, ms=200.0)
    try:
        t0 = time.time()
        got = _req(fast.port, "GET", _ec_fid(ids[EC_PRESENT]))
        took = time.time() - t0
        fired = [f["fired"] for f in faults.active()]
    finally:
        faults.clear()
    assert got[0] == status
    # the point fired on the fast path, once, and not again behind a hop
    assert fired == [1]
    if body is None:
        assert got[2] == bytes([ids[EC_PRESENT]]) * 1000
        assert took >= 0.2
    else:
        assert json.loads(got[2]) == body
    after = _plane_counts(fast.port)
    assert after["ec_read_inline"] == before["ec_read_inline"] + 1
    assert after["ec_read_proxied"] == before["ec_read_proxied"]
    assert after["read"] == before["read"] + 1
    # the point stands before the read, the loop's own included: a delay
    # is followed by it, a drop or an error is all there is
    served, declined = _nowait_counts(fast.port)
    assert (served - nowait[0], declined - nowait[1]) \
        == (int(action == "delay"), 0)


@pytest.mark.parametrize("where", ["loop", "executor"])
@pytest.mark.parametrize("raised,status,body,inline,proxied", [
    ("NeedleDeleted", 404, {"error": "deleted"}, 1, 0),
    ("NeedleExpired", 404, {"error": "not found"}, 1, 0),
    # the repair logic is the aiohttp side's: the GET goes on to it
    ("CrcError", 500, {"error": "data corruption"}, 0, 1),
])
def test_what_the_ec_read_raises_maps_as_on_the_aiohttp_plane(
        ec_planes, monkeypatch, raised, status, body, inline, proxied,
        where):
    """Whichever thread the read ends on: the loop's own (a needle in a
    mapped shard file) or an executor's (one on the lost shard)."""
    from seaweedfs_tpu.storage import needle, volume
    exc = getattr(volume, raised, None) or getattr(needle, raised)
    fast, slow, ids = ec_planes

    def read_needle(self, vid, needle_id, cookie=None, located=None):
        raise exc("as the store would")

    if where == "loop":
        monkeypatch.setattr(Store, "read_ec_needle_nowait", read_needle)
        path = _ec_fid(ids[EC_PRESENT])
    else:
        monkeypatch.setattr(Store, "read_needle", read_needle)
        path = _ec_fid(EC_LOST)
    before = _plane_counts(fast.port)
    got = _req(fast.port, "GET", path)
    want = _req(slow.port, "GET", path)
    after = _plane_counts(fast.port)
    assert got[0] == want[0] == status
    assert json.loads(got[2]) == json.loads(want[2]) == body
    assert after["ec_read_inline"] - before["ec_read_inline"] == inline
    assert after["ec_read_proxied"] - before["ec_read_proxied"] == proxied


def test_read_jwt_is_enforced_on_inline_ec_get(tmp_path):
    from seaweedfs_tpu.security.guard import Guard
    store, ids = _ec_store(str(tmp_path))
    guard = Guard(read_signing_key="read-secret")
    s = _Srv("", store=store, guard=guard)
    try:
        path = _ec_fid(EC_LOST)
        status, _, resp = _req(s.port, "GET", path)
        assert status == 401 and json.loads(resp) == {
            "error": "missing read jwt"}
        status, _, _ = _req(s.port, "GET", path, headers={
            "Authorization": "BEARER " + guard.sign_read("1,99aaaaaaaa")})
        assert status == 401
        assert _plane_counts(s.port)["ec_read_inline"] == 0
        status, _, got = _req(s.port, "GET", path, headers={
            "Authorization": "BEARER " + guard.sign_read(path[1:])})
        assert status == 200 and got == bytes([EC_LOST]) * 1000
        counts = _plane_counts(s.port)
        assert counts["ec_read_inline"] == 1
        assert counts["ec_read_proxied"] == 0
    finally:
        s.stop()
        store.close()
