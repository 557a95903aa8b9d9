"""Hand-rolled asyncio HTTP data plane for the volume server.

The reference's Go server frames requests in the runtime at negligible
cost; CPython + aiohttp charge every request single-core CPU that a
minimal asyncio.Protocol HTTP loop does not (PERF.md section 6, PR 26: an
EC GET's p50 9.66 -> 3.58 ms once it stopped crossing to aiohttp). Since
the volume data plane (GET/POST/DELETE /fid —
volume_server_handlers_read.go:28, volume_server_handlers_write.go:19) is
the server's req/s-bound surface, it is served here by a minimal HTTP/1.1
protocol sharing the SAME store/batcher/guard objects as the aiohttp app.

Everything that is not the hot common case transparently proxies over a
loopback connection to the unchanged aiohttp app: the admin/EC/status
surface, and rare data-path shapes (Range requests, image resize,
chunked/Expect bodies, replicated-volume writes, EC writes and deletes,
read repair/redirect on miss). Correctness stays in exactly one place;
the fast path only re-implements the straight-line read and write.

An EC GET of the plain shape (GET or HEAD, no Range, no resize) is
answered here as well: `VolumeServer.read_ec_needle`, the one EC read
both planes call, and the response code of a plain needle. A Range or a
resize of an EC needle, and one whose CRC fails (the repair logic is the
aiohttp side's), take the hop; `ec_read_inline` / `ec_read_proxied` on
/metrics count the two.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import time
from typing import Optional
from urllib.parse import unquote_plus

from .. import faults, observe, overload
from ..observe import profiler, wideevents
from ..security.guard import token_from_request
from ..storage.file_id import FileId
from ..storage.needle import (FLAG_HAS_LAST_MODIFIED, FLAG_HAS_MIME,
                              FLAG_HAS_NAME, FLAG_HAS_TTL,
                              FLAG_IS_COMPRESSED, CrcError, Needle)
from ..storage.volume import NeedleDeleted, NeedleExpired, NeedleNotFound
from ..storage import types as t
from ..utils import compression, fast_multipart

log = logging.getLogger("fastpath")

# non-data-path routes served by the aiohttp app (volume_server.py
# _build_app): exact paths + prefixes
_PROXY_EXACT = {"/status", "/metrics", "/healthz", "/ui", "", "/"}
_PROXY_PREFIX = ("/admin/", "/debug/")

_E404 = json.dumps({"error": "not found"}).encode()
_E404_DELETED = json.dumps({"error": "deleted"}).encode()
_E404_DROP = json.dumps({"error": "injected drop"}).encode()
_E400 = json.dumps({"error": "missing file id"}).encode()

# _admission_gate answered a shed response itself; no ticket to release
_SHED = object()


def server_sendfile_min(server) -> int:
    """Resolve (once per server object) the sendfile eligibility floor:
    -1 = sendfile disabled (WEED_VOLUME_SENDFILE=0/false/off), else the
    minimum body size in bytes (WEED_SENDFILE_MIN, default 4096 — below
    that the extra validation preads cost more than the copy saves)."""
    m = getattr(server, "_sendfile_min", None)
    if m is None:
        env = os.environ
        if env.get("WEED_VOLUME_SENDFILE", "").lower() in (
                "0", "false", "off", "no"):
            m = -1
        else:
            try:
                m = int(env.get("WEED_SENDFILE_MIN", "") or 4096)
            except ValueError:
                m = 4096
        try:
            server._sendfile_min = m
        except AttributeError:
            pass
    return m
# _read_request answered the request inline (403/shed on a body-less
# request): nothing to dispatch, keep serving the connection
_HANDLED = object()


# the no-query fast shape (every benchmark GET) shares ONE dict: the
# hot path must not allocate per request.  Callers treat query dicts as
# read-only — anything mutating this would poison every later request,
# which the allocation-pinning test in test_fastpath guards against.
_EMPTY_QUERY: dict = {}


def _parse_query(q: str) -> dict:
    if not q:
        return _EMPTY_QUERY
    out = {}
    for pair in q.split("&"):
        k, _, v = pair.partition("=")
        if "%" in pair or "+" in pair:
            # decode like the aiohttp handlers do, or the same request
            # means different things on the two code paths — but only
            # pay for it when an escape is actually present
            out[unquote_plus(k)] = unquote_plus(v)
        else:
            out[k] = v
    return out


class FastVolumeProtocol(asyncio.Protocol):
    """One client connection: parse minimal HTTP/1.1, serve the volume
    data plane inline, proxy the rest to the in-process aiohttp listener.
    Also the base for FastMasterProtocol (framing/_send/_proxy shared;
    only _dispatch differs). `server` must expose `.guard` and
    `._internal_token`."""

    def __init__(self, server, internal_port: int):
        self.server = server
        self.internal_port = internal_port
        self.buf = b""
        self.transport = None
        self.peer_ip = ""
        self._task: Optional[asyncio.Task] = None
        self._queue: asyncio.Queue = asyncio.Queue()
        self._closed = False
        self._paused = False
        self._proxied = False
        # last response written by _send, for the request's wide event
        self._status = 0
        self._sent = 0

    # --- connection lifecycle ---
    def connection_made(self, transport) -> None:
        self.transport = transport
        peer = transport.get_extra_info("peername")
        self.peer_ip = peer[0] if peer else ""
        sock = transport.get_extra_info("socket")
        if sock is not None:
            try:
                import socket as _s
                sock.setsockopt(_s.IPPROTO_TCP, _s.TCP_NODELAY, 1)
            except OSError:
                pass
        self._task = asyncio.get_event_loop().create_task(self._run())

    def connection_lost(self, exc) -> None:
        self._closed = True
        self._queue.put_nowait(None)
        if self._task is not None:
            self._task.cancel()

    def data_received(self, data: bytes) -> None:
        self._queue.put_nowait(data)
        # backpressure: a sender outpacing the handler must not grow the
        # queue without bound (the aiohttp path gets this from its stream)
        if self._queue.qsize() > 64 and not self._paused:
            self._paused = True
            try:
                self.transport.pause_reading()
            except Exception:
                pass

    async def _recv(self) -> bytes:
        data = await self._queue.get()
        if self._paused and self._queue.qsize() < 16:
            self._paused = False
            try:
                self.transport.resume_reading()
            except Exception:
                pass
        if data is None:
            raise ConnectionResetError
        return data

    # the span/service label for this listener's root spans (the master
    # subclass overrides it)
    TRACE_SERVICE = "volume"

    # --- main loop ---
    async def _run(self) -> None:
        try:
            while not self._closed:
                req = await self._read_request()
                if req is None:
                    return
                if req is _HANDLED:
                    continue
                await self._dispatch_traced(*req)
        except (ConnectionResetError, asyncio.CancelledError):
            pass
        except Exception:
            log.exception("fastpath connection error")
            if self.transport is not None:
                self.transport.close()

    async def _dispatch_traced(self, method: str, path: str, query: str,
                               headers: dict, body: bytes, raw: bytes,
                               ticket=None, ptok=None) -> None:
        """Root span for the raw-socket data plane: join the trace from
        the X-Seaweed-Trace header when present, mint one otherwise.
        Proxied requests re-enter the aiohttp app whose middleware span
        parents under this one (the header is rewritten in
        _mark_internal to point at the ambient span).

        Whitelist + admission already ran in _read_request (BEFORE the
        body was buffered); this owns releasing the admission ticket and
        the bg ambient-priority binding when the request completes."""
        tid, parent = observe.parse_header(
            headers.get(b"x-seaweed-trace", b"").decode("latin-1"))
        ctx = observe.TraceCtx(tid or observe.new_id(), parent,
                               self.TRACE_SERVICE,
                               getattr(self.server, "url", ""))
        sp = observe.Span(f"fast {method} {path}", ctx=ctx)
        ctl = getattr(self.server, "admission", None)
        cls = overload.classify(
            headers.get(b"x-seaweed-priority", b"").decode("latin-1"),
            path, ctl.system_paths, ctl.system_prefixes) \
            if ctl is not None else overload.classify(
                headers.get(b"x-seaweed-priority", b"").decode("latin-1"),
                path)
        self._proxied = False
        self._status = 0
        self._sent = 0
        wide = wideevents.enabled()
        acc = None
        error = ""
        try:
            with sp:
                acc_tok = wideevents.begin(sp.span_id) if wide else None
                try:
                    with profiler.request_tag(cls, sp.trace_id):
                        await self._dispatch(method, path, query, headers,
                                             body, raw)
                except Exception as e:
                    error = type(e).__name__
                    raise
                finally:
                    if acc_tok is not None:
                        acc = wideevents.current()
                        wideevents.end(acc_tok)
                    if ptok is not None:
                        overload.reset_priority(ptok)
                    if ticket is not None:
                        ticket.release()
        finally:
            # proxied requests re-enter the aiohttp app, whose middleware
            # applies the proper slow-log rules (streams exempt) and
            # emits the request's wide event; doing either here too would
            # double-count — and charge stream lifetime (/cluster/watch,
            # tails) as latency
            if not self._proxied:
                observe.maybe_log_slow(sp)
                if wide:
                    tenant = ""
                    if cls != overload.CLASS_SYSTEM and "collection" in query:
                        tenant = _parse_query(query).get("collection", "")
                    wideevents.finish(
                        acc, name=sp.name, trace=sp.trace_id,
                        svc=self.TRACE_SERVICE,
                        inst=getattr(self.server, "url", ""), cls=cls,
                        dur_us=getattr(sp, "dur_us", 0),
                        status=self._status, tenant=tenant,
                        bytes_in=len(body), bytes_out=self._sent,
                        shed=False, error=error)

    async def _admission_gate(self, path: str, query: str, headers: dict):
        """Admission hook for the raw-socket listener: classify, meter,
        and bound exactly like the aiohttp admission middleware does.
        Returns (ticket, priority_token) once admitted — ticket may be
        None when the server has no controller — or (_SHED, None) after
        answering a shed response on the wire."""
        ctl = getattr(self.server, "admission", None)
        if ctl is None:
            return None, None
        cls = overload.classify(
            headers.get(b"x-seaweed-priority", b"").decode("latin-1"),
            path, ctl.system_paths, ctl.system_prefixes)
        tenant = ""
        if ctl.tenant_buckets is not None and "collection" in query:
            tenant = _parse_query(query).get("collection", "")
        try:
            ticket = await ctl.admit(cls, tenant)
        except overload.ShedError as e:
            self._send(e.status,
                       json.dumps({"error":
                                   f"overloaded: {e.reason}"}).encode(),
                       extra=e.raw_headers())
            if wideevents.enabled():
                # shed before dispatch: no accumulator ever opened, emit
                # the minimal record so the tail sees its own backpressure
                tid, _ = observe.parse_header(
                    headers.get(b"x-seaweed-trace", b"").decode("latin-1"))
                wideevents.finish(
                    None, name=f"fast {path}",
                    trace=tid or observe.new_id(),
                    svc=self.TRACE_SERVICE,
                    inst=getattr(self.server, "url", ""), cls=cls,
                    dur_us=0, status=e.status, shed=True)
            return _SHED, None
        ptok = (overload.set_priority(overload.CLASS_BG)
                if cls == overload.CLASS_BG else None)
        return ticket, ptok

    # matches the aiohttp app's client_max_size in volume_server.py
    MAX_BODY = 256 * 1024 * 1024

    async def _read_request(self):
        """Returns (method, path, query, headers, body, raw), None on a
        clean close between requests, or TUNNELED after handing a
        non-Content-Length-framed request off to the aiohttp listener."""
        while b"\r\n\r\n" not in self.buf:
            try:
                self.buf += await self._recv()
            except ConnectionResetError:
                return None
        head, _, rest = self.buf.partition(b"\r\n\r\n")
        lines = head.split(b"\r\n")
        try:
            method, target, _ = lines[0].split(b" ", 2)
        except ValueError:
            self.transport.close()
            return None
        headers = {}
        for line in lines[1:]:
            k, _, v = line.partition(b":")
            headers[k.strip().lower()] = v.strip()
        if b"transfer-encoding" in headers or b"expect" in headers:
            # framing we don't speak (chunked bodies, 100-continue
            # handshakes): hand the whole connection to aiohttp BEFORE
            # trying to frame the body, or both sides deadlock waiting.
            # Admission runs FIRST — the proxied request carries the
            # whitelist-bypassing internal token, so an unchecked tunnel
            # would let any client evade a configured IP whitelist.
            target_s = target.decode("latin-1")
            path, _, query = target_s.partition("?")
            if not await self._admit(path):
                self._send(403, json.dumps({"error": "ip not allowed"}
                                           ).encode())
                self.transport.close()
                return None
            # tunneled requests never come back through _dispatch_traced;
            # admission happens in the aiohttp middleware instead: the
            # X-Swfs-Tunnel marker tells it to meter despite the internal
            # token (which only bypasses the whitelist re-check).  That
            # keeps the bounding REQUEST-scoped — admitting here would
            # either pin a concurrency slot for the whole connection
            # (idle keep-alive chunked clients wedge the class) or
            # release it immediately (any client dodges the caps by
            # adding Transfer-Encoding: chunked).
            self.buf = b""
            rport = None
            route = getattr(self.server, "shard_route", None)
            if route is not None:
                fid_str = path.lstrip("/").split("/", 1)[0]
                if "," in fid_str:
                    try:
                        rport = route(FileId.parse(fid_str).volume_id)
                    except ValueError:
                        rport = None
            await self._proxy_tunnel(head + b"\r\n\r\n" + rest,
                                     port=rport)
            return None
        # strict HTTP grammar: digits only (int() would also accept
        # '+5' / '5_0', a framing-desync risk behind stricter proxies)
        cl = headers.get(b"content-length", b"0") or b"0"
        length = int(cl) if cl.isdigit() else -1
        if length < 0:
            self._send(400, json.dumps({"error": "invalid content-length"}
                                       ).encode())
            self.transport.close()
            return None
        if length > self.MAX_BODY:
            self._send(413, json.dumps({"error": "entry too large"}
                                       ).encode())
            self.transport.close()
            return None
        target_s = target.decode("latin-1")
        path, _, query = target_s.partition("?")

        def answered():
            # request refused inline: with an unread body still on the
            # wire the framing is unrecoverable — close (under overload
            # that is also the cheapest outcome); a body-less request
            # keeps the connection, preserving pipelined bytes
            if length:
                self.transport.close()
                return None
            self.buf = rest
            return _HANDLED

        # whitelist + admission run BEFORE the body is buffered: the
        # overload plane exists to stop the buffer-then-collapse mode,
        # so a request that will be shed must be refused while its body
        # is still on the wire — a storm of concurrent 100MB POSTs must
        # cost ~0 bytes of heap, not buffer every body and shed after.
        # Whitelist first (an off-whitelist flood burns a cheap 403, not
        # admission tokens/queue slots — mirrors the aiohttp middleware
        # order guard_mw -> admission on master/volume).
        if not await self._admit(path):
            self._send(403, json.dumps({"error": "ip not allowed"}
                                       ).encode())
            return answered()
        ticket, ptok = await self._admission_gate(path, query, headers)
        if ticket is _SHED:
            return answered()
        try:
            parts = [rest]
            got = len(rest)
            while got < length:
                chunk = await self._recv()
                parts.append(chunk)
                got += len(chunk)
        except (ConnectionResetError, asyncio.CancelledError):
            # client vanished mid-body while holding an admission slot:
            # the ticket must not leak or the class bleeds capacity
            if ptok is not None:
                overload.reset_priority(ptok)
            if ticket is not None:
                ticket.release()
            raise
        rest = b"".join(parts)
        body, self.buf = rest[:length], rest[length:]
        raw = head + b"\r\n\r\n" + body
        return (method.decode("latin-1"), path, query, headers, body,
                raw, ticket, ptok)

    # --- response helpers ---
    def _send(self, status: int, body: bytes, ctype: str = "application/json",
              extra: str = "") -> None:
        reason = {200: "OK", 201: "Created", 304: "Not Modified",
                  400: "Bad Request", 401: "Unauthorized", 403: "Forbidden",
                  404: "Not Found", 405: "Method Not Allowed",
                  409: "Conflict", 413: "Payload Too Large",
                  429: "Too Many Requests",
                  500: "Internal Server Error",
                  503: "Service Unavailable"}.get(status, "X")
        head = (f"HTTP/1.1 {status} {reason}\r\n"
                f"Content-Type: {ctype}\r\n"
                f"Content-Length: {len(body)}\r\n{extra}\r\n")
        self._status = status
        self._sent = len(body)
        self.transport.write(head.encode("latin-1") + body)

    # --- admission (matches the aiohttp guard middleware; runs BEFORE
    # any proxying because proxied requests carry the internal token) ---
    async def _admit(self, path: str) -> bool:
        if path == "/healthz":
            return True
        return self.server.guard.check_whitelist(self.peer_ip)

    # --- dispatch ---
    async def _dispatch(self, method: str, path: str, query: str,
                        headers: dict, body: bytes, raw: bytes) -> None:
        # whitelist already checked in _read_request (before admission)
        guard = self.server.guard
        if path in _PROXY_EXACT or path.startswith(_PROXY_PREFIX):
            await self._proxy(raw)
            return
        fid_str = path.lstrip("/")
        if "," not in fid_str:
            self._send(400, _E400)
            return
        try:
            fid = FileId.parse(fid_str.split("/", 1)[0])
        except ValueError as e:
            self._send(400, json.dumps({"error": str(e)}).encode())
            return
        # shard fleet: a volume owned by a sibling shard is served by
        # proxying the whole request to that shard's aiohttp listener
        # over loopback (auth/EC/replica logic all run there)
        route = getattr(self.server, "shard_route", None)
        if route is not None:
            rport = route(fid.volume_id)
            if rport:
                await self._proxy(raw, port=rport)
                return
        q = _parse_query(query)
        token = token_from_request(_HeaderView(headers), q)
        if method in ("GET", "HEAD"):
            err = guard.verify_read(token, str(fid))
            if err:
                self._send(401, json.dumps({"error": err}).encode())
                return
            await self._read(method, fid, q, headers, raw)
        elif method in ("POST", "PUT"):
            err = guard.verify_write(token, str(fid))
            if err:
                self._send(401, json.dumps({"error": err}).encode())
                return
            await self._write(fid, q, headers, body, raw)
        elif method == "DELETE":
            err = guard.verify_write(token, str(fid))
            if err:
                self._send(401, json.dumps({"error": err}).encode())
                return
            await self._delete(fid, q, raw)
        else:
            self._send(405, json.dumps({"error": "method not allowed"}
                                       ).encode())

    # --- data plane: read (volume_server_handlers_read.go:28 fast shape) ---
    async def _read(self, method: str, fid: FileId, q: dict,
                    headers: dict, raw: bytes) -> None:
        server = self.server
        vol = server.store.find_volume(fid.volume_id)
        rare = b"range" in headers or q.get("width") or q.get("height")
        if (vol is None
                and server.store.find_ec_volume(fid.volume_id) is not None):
            # an EC GET: `ec.get` is its whole residence here. The plain
            # shape is answered in place; the rare ones, and a needle
            # whose CRC failed, by the aiohttp side, whose
            # `ec.get.handler` is then the part over there
            with observe.stage("ec.get", enclosing=True):
                if rare or not await self._read_ec(method, fid, headers):
                    server.metrics.count("ec_read_proxied")
                    await self._proxy(raw)
            return
        if rare:
            await self._proxy(raw)  # rare shapes: aiohttp path
            return
        if vol is None:
            await self._proxy(raw)  # redirect logic
            return
        # zero-copy GET: whole plain-shape needle bodies go straight
        # from the .dat fd to the socket via the kernel (os.sendfile).
        # Eligibility is decided conservatively; anything else falls
        # through to the existing pread path below, byte-identically.
        if (method == "GET" and server_sendfile_min(server) >= 0
                and self.transport.get_extra_info("sslcontext") is None):
            try:
                ext = vol.needle_sendfile_extent(fid.key, fid.cookie)
            except NeedleExpired:
                server.metrics.count("read")
                self._send(404, _E404)
                return
            except NeedleDeleted:
                server.metrics.count("read")
                self._send(404, _E404_DELETED)
                return
            except (NeedleNotFound, KeyError):
                await self._proxy(raw)  # read-repair / replica logic
                return
            if (ext is not None
                    and ext[2] >= server_sendfile_min(server)):
                await self._sendfile_read(fid, ext, headers)
                return
        start_us = int(time.time() * 1e6)
        t0 = time.perf_counter()
        try:
            n = vol.read_needle_nowait(fid.key, fid.cookie)
            read_s = time.perf_counter() - t0
        except NeedleExpired:
            server.metrics.count("read")
            self._send(404, _E404)
            return
        except NeedleDeleted:
            server.metrics.count("read")
            self._send(404, _E404_DELETED)
            return
        except (NeedleNotFound, KeyError):
            await self._proxy(raw)  # read-repair / replica logic counts
            return                  # the read on the aiohttp side
        if n is None:  # big needle, contended lock, or remote backend
            await self._proxy(raw)
            return
        # same named fault point as the aiohttp read handler — chaos and
        # overload drills against deployed clusters must reach the
        # inline fast path too (delay faults here are how the overload
        # bench makes service time, and so capacity, deterministic).
        # Fired only once the read is committed to be served INLINE:
        # every proxy fallback above reaches the aiohttp handler, which
        # fires the point itself — firing before the proxy decision
        # would double-charge delays and compound drop probabilities on
        # exactly the shapes that traverse both paths.
        try:
            if await faults.fire_async("volume.read"):
                server.metrics.count("read")
                self._send(404, _E404_DROP)
                return
        except faults.FaultError as e:
            server.metrics.count("read")
            self._send(500, json.dumps({"error": str(e)}).encode())
            return
        server.metrics.count("read")
        # the inline fast shape must feed the same read-latency histogram
        # as the aiohttp handler's timed("read") — fast GETs are the hot
        # data plane, and skipping them leaves /metrics (and its trace
        # exemplars) describing only the slow shapes. The observation
        # covers the needle read itself, not the injected fault delay:
        # faults charge their own fault.<point> span, same as aiohttp.
        server.metrics.observe("read", read_s)
        observe.record_span("volume.read", observe.capture(), start_us,
                            int(read_s * 1e6), tags={"fid": str(fid)})
        # lifecycle heat: the inline fast shape must feed the same
        # tracker as the aiohttp handler or hot volumes look cold
        server.heat.record_read(fid.volume_id)
        self._send_needle(method, n, headers)

    async def _read_ec(self, method: str, fid: FileId,
                       headers: dict) -> bool:
        """An EC GET of the plain shape, answered in place under
        `ec.get.handler` (which here closes within `ec.get`, so what the
        two differ by is what a second plane would cost). False, with
        nothing sent, for a needle whose CRC failed: the caller proxies
        it to the repair logic of the aiohttp side, which reads it again
        (that rare GET meets the fault point and the counters twice)."""
        server = self.server
        with observe.stage("ec.get.handler", enclosing=True):
            try:
                if await server.read_ec_needle(
                        fid, lambda n: self._send_needle(
                            method, n, headers) or True) is None:
                    self._send(404, _E404_DROP)
            except CrcError:
                return False
            except faults.FaultError as e:
                self._send(500, json.dumps({"error": str(e)}).encode())
            except NeedleDeleted:
                self._send(404, _E404_DELETED)
            except (NeedleExpired, NeedleNotFound, KeyError):
                self._send(404, _E404)
        server.metrics.count("ec_read_inline")
        return True

    def _send_needle(self, method: str, n: Needle, headers: dict) -> None:
        """A read needle as the response (the aiohttp side's `_respond`
        less Range and resize, which never come here): etag / 304,
        headers, gzip verbatim or decompressed, HEAD."""
        etag = f'"{n.etag()}"'
        if headers.get(b"if-none-match", b"").decode("latin-1") == etag:
            self._send(304, b"")
            return
        extra = [f"ETag: {etag}\r\n", "Accept-Ranges: bytes\r\n"]
        if n.has(FLAG_HAS_LAST_MODIFIED):
            extra.append(f"X-Last-Modified: {n.last_modified}\r\n")
        mime = (n.mime.decode("utf-8", "replace")
                if n.has(FLAG_HAS_MIME) else "application/octet-stream")
        if n.has(FLAG_HAS_NAME) and n.name:
            fname = n.name.decode("utf-8", "replace")
            extra.append(f'Content-Disposition: inline; '
                         f'filename="{fname}"\r\n')
        body = n.data
        if n.is_compressed:
            if b"gzip" in headers.get(b"accept-encoding", b""):
                extra.append("Content-Encoding: gzip\r\n")
            else:
                body = compression.decompress(body)
        if method == "HEAD":
            # headers only, but Content-Length must be the body size
            head = (f"HTTP/1.1 200 OK\r\nContent-Type: {mime}\r\n"
                    f"Content-Length: {len(body)}\r\n"
                    f"{''.join(extra)}\r\n")
            self._status = 200
            self.transport.write(head.encode("latin-1"))
            return
        self._send(200, body, ctype=mime, extra="".join(extra))

    async def _sendfile_read(self, fid: FileId, ext: tuple,
                             headers: dict) -> None:
        """Serve a whole-needle GET body via the kernel: HTTP head from
        userspace, body straight from the .dat fd with ``sendfile``.
        The extent was validated by Volume.needle_sendfile_extent; the
        ETag is the stored CRC so conditional requests behave exactly
        like the parsed path.  If the native syscall is unavailable the
        response head is already on the wire, so the body is delivered
        with a positioned pread instead — never a seek on the shared
        file object (concurrent requests share the .dat handle)."""
        server = self.server
        (fobj, data_off, data_size, etag_hex, last_modified,
         name, mime) = ext
        start_us = int(time.time() * 1e6)
        t0 = time.perf_counter()
        # same named fault point as the pread fast shape: fired once
        # the read is committed to be served inline
        try:
            if await faults.fire_async("volume.read"):
                server.metrics.count("read")
                self._send(404, _E404_DROP)
                return
        except faults.FaultError as e:
            server.metrics.count("read")
            self._send(500, json.dumps({"error": str(e)}).encode())
            return
        server.metrics.count("read")
        server.heat.record_read(fid.volume_id)
        etag = f'"{etag_hex}"'
        if headers.get(b"if-none-match", b"").decode("latin-1") == etag:
            self._send(304, b"")
            return
        extra = [f"ETag: {etag}\r\n", "Accept-Ranges: bytes\r\n"]
        if last_modified:
            extra.append(f"X-Last-Modified: {last_modified}\r\n")
        # identical decoration to the parsed pread path: stored mime
        # wins, a stored name becomes the inline disposition
        ctype = (mime.decode("utf-8", "replace") if mime
                 else "application/octet-stream")
        if name:
            fname = name.decode("utf-8", "replace")
            extra.append(f'Content-Disposition: inline; '
                         f'filename="{fname}"\r\n')
        head = ("HTTP/1.1 200 OK\r\n"
                f"Content-Type: {ctype}\r\n"
                f"Content-Length: {data_size}\r\n{''.join(extra)}\r\n")
        self._status = 200
        self._sent = data_size
        self.transport.write(head.encode("latin-1"))
        loop = asyncio.get_event_loop()
        try:
            await loop.sendfile(self.transport, fobj, data_off,
                                data_size, fallback=False)
        except (asyncio.SendfileNotAvailableError, NotImplementedError,
                AttributeError):
            data = await loop.run_in_executor(
                None, os.pread, fobj.fileno(), data_size, data_off)
            self.transport.write(data)
        read_s = time.perf_counter() - t0
        # the read-latency histogram covers the kernel send too — that
        # IS the disk+copy work this stage replaces
        server.metrics.observe("read", read_s)
        # distinct stage name so cluster.tail attributes sendfile time
        # separately from parsed reads (wideevents buckets it under
        # "disk")
        observe.record_span("disk.sendfile", observe.capture(), start_us,
                            int(read_s * 1e6), tags={"fid": str(fid)})

    # --- data plane: write (volume_server_handlers_write.go:19 fast shape) ---
    async def _write(self, fid: FileId, q: dict, headers: dict,
                     body: bytes, raw: bytes) -> None:
        server = self.server
        # same named fault point as the aiohttp handler: the fastpath
        # serves the common unreplicated write inline, and chaos drills
        # against deployed (subprocess) clusters must still reach it
        try:
            if await faults.fire_async("volume.write"):
                self._send(503, json.dumps({"error": "injected drop"}
                                           ).encode())
                return
        except faults.FaultError as e:
            self._send(500, json.dumps({"error": str(e)}).encode())
            return
        vol = server.store.find_volume(fid.volume_id)
        if vol is None:
            await self._proxy(raw)  # 404 / EC semantics
            return
        rp = vol.super_block.replica_placement
        if getattr(rp, "to_byte", lambda: 0)() != 0:
            await self._proxy(raw)  # replicated write fan-out
            return
        n = Needle(cookie=fid.cookie, id=fid.key)
        raw_ct = headers.get(b"content-type", b"").decode("latin-1")
        filename, ctype = "", ""
        already_gzipped = False
        if raw_ct[:10].lower().startswith("multipart/"):
            part = fast_multipart.parse_single_part(body, raw_ct)
            if part is None:
                await self._proxy(raw)  # irregular multipart (counts there)
                return
            server.metrics.count("write")
            n.data = part.data
            filename = part.filename
            if filename:
                n.set_flag(FLAG_HAS_NAME)
                n.name = filename.encode()[:255]
            ctype = part.content_type
            if ctype and ctype != "application/octet-stream":
                n.set_flag(FLAG_HAS_MIME)
                n.mime = ctype.encode()[:255]
            already_gzipped = part.content_encoding == "gzip"
        else:
            server.metrics.count("write")
            n.data = body
            already_gzipped = headers.get(
                b"content-encoding", b"") == b"gzip"
        if already_gzipped and compression.is_gzipped(n.data):
            n.set_flag(FLAG_IS_COMPRESSED)
        elif q.get("compress") != "false":
            ext = os.path.splitext(filename)[1] if filename else ""
            payload, compressed = compression.maybe_compress(
                n.data, ext, ctype)
            if compressed:
                n.data = payload
                n.set_flag(FLAG_IS_COMPRESSED)
        if len(n.data) > 32 * 1024 * 1024:
            self._send(413, json.dumps({"error": "entry too large"}).encode())
            return
        ttl_s = q.get("ttl", "")
        if ttl_s:
            n.set_flag(FLAG_HAS_TTL)
            n.ttl = t.TTL.parse(ttl_s)
        n.set_flag(FLAG_HAS_LAST_MODIFIED)
        n.last_modified = int(time.time())
        with server.metrics.timed("write"):
            try:
                _, size, unchanged = await server._batcher.write(
                    fid.volume_id, n)
            except KeyError:
                self._send(404, json.dumps({"error": "volume not found"}
                                           ).encode())
                return
            except Exception as e:
                self._send(409, json.dumps({"error": str(e)}).encode())
                return
        server.heat.record_write(fid.volume_id)
        self._send(201, json.dumps({
            "name": (n.name or b"").decode("utf-8", "replace"),
            "size": len(n.data), "eTag": n.etag(),
            "unchanged": unchanged}).encode())

    # --- data plane: delete ---
    async def _delete(self, fid: FileId, q: dict, raw: bytes) -> None:
        server = self.server
        vol = server.store.find_volume(fid.volume_id)
        if vol is None:
            await self._proxy(raw)  # EC delete / 404 semantics
            return
        rp = vol.super_block.replica_placement
        if getattr(rp, "to_byte", lambda: 0)() != 0:
            await self._proxy(raw)
            return
        server.metrics.count("delete")
        n = Needle(cookie=fid.cookie, id=fid.key)
        try:
            size = await asyncio.get_event_loop().run_in_executor(
                None, lambda: server.store.delete_needle(fid.volume_id, n))
        except KeyError:
            self._send(404, json.dumps({"error": "volume not found"}
                                       ).encode())
            return
        server.heat.record_write(fid.volume_id)
        self._send(200, json.dumps({"size": size}).encode())

    def _mark_internal(self, raw: bytes, tunnel: bool = False) -> list:
        """Insert the per-process internal token + the real peer IP after
        the request line so the aiohttp app can (a) skip its IP-whitelist
        re-check — it would otherwise see 127.0.0.1 and 403 every proxied
        request under a whitelist — and (b) log the true client.
        ``tunnel`` adds X-Swfs-Tunnel: the request was NOT admitted at
        this listener and the admission middleware must meter it.

        Client-supplied copies of the X-Swfs-* headers are stripped
        first: a spoofed X-Swfs-Tunnel on a proxied (already-admitted)
        request would make the middleware meter it a second time —
        with fg slots held at the listener, a handful of such requests
        deadlock the class into queue-timeout sheds — and a spoofed
        X-Swfs-Peer would forge the logged client identity.

        Returns buffers to write in order: the rebuilt head, then the
        body region untouched (as a memoryview — a proxied 256 MB PUT
        must not pay full-buffer copies just to rewrite headers)."""
        hdr_end = raw.find(b"\r\n\r\n")
        if hdr_end < 0:
            hdr_end = len(raw)
        line_end = raw.find(b"\r\n")
        line = raw[:line_end]
        head = raw[line_end + 2:hdr_end]
        kept = [ln for ln in head.split(b"\r\n")
                if ln and not ln.lower().startswith(
                    (b"x-swfs-internal:", b"x-swfs-tunnel:",
                     b"x-swfs-peer:"))]
        tok = self.server._internal_token.encode()
        extra = b"X-Swfs-Tunnel: 1\r\n" if tunnel else b""
        hv = observe.header_value()
        if hv:
            # parent the aiohttp-side span under the fastpath span; the
            # injected header is first so it wins over the client's copy
            # further down the head (headers.get returns the first)
            extra += (b"X-Seaweed-Trace: " + hv.encode("latin-1")
                      + b"\r\n")
        new_head = (line + b"\r\nX-Swfs-Internal: " + tok
                    + b"\r\nX-Swfs-Peer: "
                    + self.peer_ip.encode("latin-1") + b"\r\n" + extra
                    + b"".join(h + b"\r\n" for h in kept) + b"\r\n")
        body = memoryview(raw)[hdr_end + 4:] \
            if hdr_end + 4 <= len(raw) else b""
        return [new_head, body]

    async def _proxy_tunnel(self, initial: bytes,
                            port: Optional[int] = None) -> None:
        """Bidirectional relay for requests we cannot frame (chunked,
        Expect: 100-continue): everything from here on belongs to the
        aiohttp listener; the client connection closes when either side
        does.  ``port`` overrides the loopback target — cross-shard
        routing sends the tunnel straight to the owning shard's aiohttp
        listener."""
        self._proxied = True
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", port or self.internal_port)
        for part in self._mark_internal(initial, tunnel=True):
            writer.write(part)
        await writer.drain()

        async def pump_up() -> None:
            try:
                while True:
                    data = await self._recv()
                    writer.write(data)
                    await writer.drain()
            except (ConnectionResetError, ConnectionError):
                pass
            finally:
                try:
                    writer.close()
                except Exception:
                    pass

        up = asyncio.get_event_loop().create_task(pump_up())
        try:
            while True:
                chunk = await reader.read(1 << 16)
                if not chunk:
                    break
                self.transport.write(chunk)
        finally:
            up.cancel()
            try:
                writer.close()
            except Exception:
                pass
            self.transport.close()

    # --- loopback proxy to the aiohttp app ---
    async def _proxy(self, raw: bytes, port: Optional[int] = None) -> None:
        """Relay one framed request/response over loopback.  ``port``
        overrides the target: None = this process's own aiohttp
        listener; a shard-fleet peer's internal port when the volume
        lives on another shard (the request carries the fleet-shared
        internal token, so the peer's guard and admission treat it as
        pre-admitted exactly like a same-process proxy)."""
        self._proxied = True
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", port or self.internal_port)
        try:
            for part in self._mark_internal(raw):
                writer.write(part)
            await writer.drain()
            head = b""
            while b"\r\n\r\n" not in head:
                chunk = await reader.read(1 << 16)
                if not chunk:
                    raise ConnectionError("internal server closed")
                head += chunk
            hdr, _, rest = head.partition(b"\r\n\r\n")
            length = None
            chunked = False
            for line in hdr.split(b"\r\n")[1:]:
                k, _, v = line.partition(b":")
                lk = k.strip().lower()
                if lk == b"content-length":
                    try:
                        length = int(v)
                    except ValueError:
                        length = None
                elif lk == b"transfer-encoding" and b"chunked" in v.lower():
                    chunked = True
            self.transport.write(hdr + b"\r\n\r\n" + rest)
            # HEAD answers and 204/304 statuses carry headers (often incl.
            # Content-Length) but NO body — waiting for body bytes here
            # stalls the serial per-connection loop until aiohttp's
            # keep-alive timeout (~75s)
            method = raw[:raw.find(b" ")]
            status_line = hdr.split(b"\r\n", 1)[0].split(b" ")
            try:
                status = int(status_line[1])
            except (IndexError, ValueError):
                status = 200
            if method == b"HEAD" or status in (204, 304):
                return
            if length is not None and not chunked:
                got = len(rest)
                while got < length:
                    chunk = await reader.read(1 << 16)
                    if not chunk:
                        break
                    got += len(chunk)
                    self.transport.write(chunk)
            else:
                # chunked or close-delimited: relay until EOF, then close
                # the client side too (framing unknown to us)
                if chunked:
                    last = rest
                    while not last.endswith(b"0\r\n\r\n"):
                        chunk = await reader.read(1 << 16)
                        if not chunk:
                            break
                        last = (last + chunk)[-8:]
                        self.transport.write(chunk)
                else:
                    while True:
                        chunk = await reader.read(1 << 16)
                        if not chunk:
                            break
                        self.transport.write(chunk)
                    self.transport.close()
        finally:
            writer.close()


class FastMasterProtocol(FastVolumeProtocol):
    """Master hot path: /dir/assign and /dir/lookup served inline (they
    are one HTTP round trip per benchmark write — dirAssignHandler,
    weed/server/master_server_handlers.go:96-150), the rest proxied to
    the aiohttp app. Inherits framing/proxy from FastVolumeProtocol;
    only the route dispatch differs."""

    TRACE_SERVICE = "master"

    async def _admit(self, path: str) -> bool:
        # same admission as the master's guard_mw: peers, whitelist, or a
        # one-shot peer refresh — for EVERY route, proxied ones included
        if path == "/healthz":
            return True
        server = self.server
        return (self.peer_ip in server._peer_ips
                or server.guard.check_whitelist(self.peer_ip)
                or await server._refresh_peer_ips(self.peer_ip))

    async def _dispatch(self, method: str, path: str, query: str,
                        headers: dict, body: bytes, raw: bytes) -> None:
        # whitelist already checked in _read_request (before admission)
        server = self.server
        if path not in ("/dir/assign", "/dir/lookup"):
            await self._proxy(raw)
            return
        # followers proxy API traffic to the leader via the aiohttp app's
        # leader_proxy_mw
        if not server.raft.is_leader:
            await self._proxy(raw)
            return
        q = _parse_query(query)
        if path == "/dir/assign":
            server.metrics.count("assign")
            try:
                if await faults.fire_async("master.assign"):
                    self._send(503, json.dumps({"error": "injected drop"}
                                               ).encode())
                    return
            except faults.FaultError as e:
                self._send(500, json.dumps({"error": str(e)}).encode())
                return
            if not await server.ensure_assign_ready():
                self._send(503, json.dumps(
                    {"error": "not the leader / not ready"}).encode())
                return
            try:
                count = int(q.get("count", 1))
            except ValueError:
                self._send(400, json.dumps({"error": "invalid count"}
                                           ).encode())
                return
            resp, status = await server.assign_api(
                count=count,
                collection=q.get("collection", ""),
                replication=q.get("replication",
                                  server.default_replication),
                ttl=q.get("ttl", ""),
                data_center=q.get("dataCenter", ""))
            self._send(status, json.dumps(resp).encode())
            return
        await self._proxy(raw)  # /dir/lookup: clients cache it, keep one impl


class _HeaderView:
    """dict-of-bytes -> .get(str) view for token_from_request."""

    __slots__ = ("_h",)

    def __init__(self, headers: dict):
        self._h = headers

    def get(self, key: str, default: str = "") -> str:
        v = self._h.get(key.lower().encode("latin-1"))
        return v.decode("latin-1") if v is not None else default


async def start_fastpath(server, host: str, port: int, internal_port: int,
                         ssl_context=None, protocol=FastVolumeProtocol,
                         reuse_port: bool = False):
    """Listen on the public (host, port) with the fast protocol, proxying
    non-hot-path requests to the aiohttp listener at internal_port.
    ``reuse_port`` sets SO_REUSEPORT so every process of a shard fleet
    binds the same port and the kernel spreads accepted connections."""
    loop = asyncio.get_event_loop()
    kwargs = {"ssl": ssl_context}
    if reuse_port:
        kwargs["reuse_port"] = True
    return await loop.create_server(
        lambda: protocol(server, internal_port), host, port, **kwargs)
