"""Cluster-wide request tracing: spans, propagation, Chrome-trace export.

Every server process keeps a bounded ring buffer of completed spans.  A
request entering any HTTP surface (master, volume, filer, webdav, S3 — and
the raw-socket fastpath) gets a per-request trace ID, carried downstream
over HTTP via the ``X-Seaweed-Trace: <trace_id>:<parent_span_id>`` header
and over gRPC via ``x-seaweed-trace`` metadata (pb/rpc.py), so one S3 GET
that fans out s3 -> filer -> volume -> EC-reconstruct yields one mergeable
span timeline.

``/debug/trace`` serves the ring as Chrome trace-event JSON (open in
Perfetto / chrome://tracing); ``?format=spans`` returns the raw span dicts
the ``cluster.trace`` shell command fetches from every node and merges into
one document.  A root span slower than WEED_TRACE_SLOW_MS (default 1000)
emits a slow-request glog line.

Spans are contextvars-based so they nest naturally across awaits within a
task; worker threads don't inherit context — capture() the ambient context
on the event loop and re-enter it in the thread with bind()/run_with()
(the EC pipeline stages do exactly this, ec/pipeline.py).
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import os
import random
import threading
import time
from collections import deque
from typing import Iterable, NamedTuple, Optional

TRACE_HEADER = "X-Seaweed-Trace"
GRPC_TRACE_KEY = "x-seaweed-trace"

from ..utils import metrics as _metrics  # noqa: E402
from . import profiler, wideevents  # noqa: E402  (no circular import:
# neither submodule imports this package's namespace back)


def _ring_size() -> int:
    """A config typo must not stop every server from importing —
    malformed/negative values fall back like slow_threshold_ms does."""
    try:
        size = int(os.environ.get("WEED_TRACE_RING", "4096"))
    except ValueError:
        return 4096
    return size if size > 0 else 4096


RING_SIZE = _ring_size()

_trace_id: contextvars.ContextVar[str] = contextvars.ContextVar(
    "sw_trace_id", default="")
_span_id: contextvars.ContextVar[str] = contextvars.ContextVar(
    "sw_span_id", default="")
_service: contextvars.ContextVar[str] = contextvars.ContextVar(
    "sw_service", default="")
_instance: contextvars.ContextVar[str] = contextvars.ContextVar(
    "sw_instance", default="")

_ring: deque = deque(maxlen=RING_SIZE)


def slow_threshold_ms() -> float:
    """Root spans slower than this log a glog warning (env-tunable so a
    busy cluster can raise it without a restart-and-redeploy of code)."""
    try:
        return float(os.environ.get("WEED_TRACE_SLOW_MS", "1000"))
    except ValueError:
        return 1000.0


# ids only need uniqueness, not unpredictability: SystemRandom-seeded
# PRNG hex is ~60x cheaper than os.urandom per id on this host class,
# which matters on the fastpath (one trace id + one span id per request)
_id_rng = random.Random(random.SystemRandom().getrandbits(64))


def new_id() -> str:
    # one C call under the GIL: there is nothing for a lock to guard
    return "%016x" % _id_rng.getrandbits(64)


class TraceCtx(NamedTuple):
    """A captured trace position, safe to hand across threads."""
    trace_id: str
    span_id: str
    service: str
    instance: str


def capture() -> TraceCtx:
    """Snapshot the ambient trace context (for worker threads)."""
    return TraceCtx(_trace_id.get(), _span_id.get(),
                    _service.get(), _instance.get())


@contextlib.contextmanager
def bind(ctx: TraceCtx):
    """Re-enter a captured context (typically inside a worker thread)."""
    tokens = (_trace_id.set(ctx.trace_id), _span_id.set(ctx.span_id),
              _service.set(ctx.service), _instance.set(ctx.instance))
    try:
        yield
    finally:
        for var, tok in zip((_trace_id, _span_id, _service, _instance),
                            tokens):
            var.reset(tok)


def run_with(ctx: TraceCtx, fn, *args, **kwargs):
    """Run fn under a captured context — the run_in_executor bridge
    (run_in_executor does NOT copy contextvars, unlike call_soon)."""
    with bind(ctx):
        return fn(*args, **kwargs)


def parse_header(value: str) -> tuple[str, str]:
    """'<trace_id>:<parent_span_id>' -> (trace_id, parent_id); either part
    may be empty. Bounded so a hostile header can't bloat the ring."""
    if not value:
        return "", ""
    tid, _, parent = value.partition(":")
    return tid.strip()[:64], parent.strip()[:64]


def header_value() -> str:
    """Outbound header for the ambient trace ('' when not tracing)."""
    tid = _trace_id.get()
    if not tid:
        return ""
    return f"{tid}:{_span_id.get()}"


def inject(headers: dict) -> dict:
    """Add the trace header to an outbound-request header dict."""
    hv = header_value()
    if hv:
        headers[TRACE_HEADER] = hv
    return headers


def grpc_metadata(existing=None):
    """Outbound gRPC metadata with the trace pair appended (pb/rpc.py
    client stubs call this on every RPC)."""
    hv = header_value()
    if not hv:
        return existing
    meta = list(existing) if existing else []
    meta.append((GRPC_TRACE_KEY, hv))
    return meta


class Span:
    """Context manager measuring one operation; records into the ring on
    exit. Usable in async code (contextvars are task-local) and — with an
    explicit ctx= — in plain threads."""

    __slots__ = ("name", "tags", "_ctx", "_root", "trace_id", "span_id",
                 "parent_id", "_service", "_instance", "_t0", "_start",
                 "_tokens", "dur_us")

    def __init__(self, name: str, tags: Optional[dict] = None,
                 ctx: Optional[TraceCtx] = None,
                 service: str = "", root: bool = False):
        self.name = name
        self.tags = dict(tags) if tags else {}
        self._ctx = ctx
        self._root = root
        self._service = service
        self._tokens = None

    def __enter__(self) -> "Span":
        ctx = self._ctx
        if ctx is None:
            trace, parent, svc, inst = (_trace_id.get(), _span_id.get(),
                                        _service.get(), _instance.get())
        else:
            trace, parent, svc, inst = ctx
        self.trace_id = trace = trace or new_id()
        self.parent_id = "" if self._root else parent
        self.span_id = new_id()
        self._service = svc = self._service or svc
        self._instance = inst
        self._tokens = (_trace_id.set(trace), _span_id.set(self.span_id),
                        _service.set(svc), _instance.set(inst))
        self._start = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        seconds = time.perf_counter() - self._t0
        self.dur_us = dur_us = int(seconds * 1e6)
        t_trace, t_span, t_svc, t_inst = self._tokens
        _trace_id.reset(t_trace)
        _span_id.reset(t_span)
        _service.reset(t_svc)
        _instance.reset(t_inst)
        if exc_type is not None:
            self.tags.setdefault("error", exc_type.__name__)
        wideevents.absorb(self.name, self.span_id, dur_us)
        _ring.append((self.name, seconds, self._start, self.trace_id,
                      self.span_id, self.parent_id, self._service,
                      self._instance, threading.get_ident(), self.tags))

    @property
    def dur_ms(self) -> float:
        return (time.perf_counter() - self._t0) * 1e3


def span(name: str, tags: Optional[dict] = None,
         ctx: Optional[TraceCtx] = None, service: str = "") -> Span:
    return Span(name, tags=tags, ctx=ctx, service=service)


# --- the ring's rows ---------------------------------------------------
# One completed span is one tuple in the ring, name and seconds first:
#   (name, seconds, start_s, trace, id, parent, svc, inst, thread, tags)
# which spans() unfolds into the span dict every reader knows. A hot
# path pays for a tuple and an append (atomic: no lock); whoever reads
# pays for the dict, the microseconds and the id's sixteen hex digits.
# A Span's id is a string made when it opens, since its children name
# it; a stage's id stays the integer it took from this process's
# sequence (_id_seq) until somebody reads it, also where it is the
# parent of the stages that ended under it.

_id_base = _id_rng.getrandbits(64)
_id_seq = itertools.count(1)


def _hex(sid) -> str:
    return sid if type(sid) is str else "%016x" % (
        (_id_base + sid) & 0xFFFFFFFFFFFFFFFF)


def _unfold(row: tuple) -> dict:
    name, seconds, start_s, trace, sid, parent, svc, inst, tid, tags = row
    return {"trace": trace, "id": _hex(sid), "parent": _hex(parent),
            "name": name, "svc": svc, "inst": inst,
            "start_us": int(start_s * 1e6), "dur_us": int(seconds * 1e6),
            "tid": tid & 0x7FFFFFFF, "tags": dict(tags) if tags else {}}


# --- stages: one pair of clock reads, three sinks ----------------------
# 1. the ring (above); 2. the shared `ec` registry, one family
# seaweedfs_tpu_ec_stage_seconds{stage="<name>"} whose _sum and _count
# never truncate as the ring does; 3. (with-form only) a profiler
# TraceAnnotation, so that a device trace shows the stage on its own
# clock. The family is the EC tier's: only `ec.*` names accumulate, and
# the plain-volume data plane's record_span calls pay one prefix test.
#
# A served GET takes a dozen of these on one interpreter that runs at
# four fifths of what it sustains, where a microsecond of a request's
# path costs some twenty at the median (PERF.md, PR 25). So a request's
# stages pay sinks 1 and 2 once a request, not once a stage: while an
# enclosing stage of the request is open, a stage that ends under it
# (in the request's task, or in a worker that runs under a copy of its
# context) is one row appended to that stage's list. The enclosing
# stage's exit folds the list into the request's wide event, adds it to
# the registry under one lock and to the ring in one call.

_EC_STAGES = _metrics.shared("ec")
# the enclosing stage that is open in this context: its id, the rows of
# the stages that have ended under it, and what every row of its request
# repeats (trace id, service, instance)
_open: contextvars.ContextVar[Optional[tuple]] = contextvars.ContextVar(
    "sw_stages", default=None)


def _emit(name: str, ctx: Optional[TraceCtx], start_s: float,
          seconds: float, tags: Optional[dict],
          enclosing: bool = False) -> None:
    """One ended stage to the ring and the counters: by way of the
    enclosing stage open in the ambient context, whose child it then is,
    if the stage is the ambient context's (`ctx` None) and wraps nothing
    itself; else at once."""
    if ctx is None:
        over = _open.get()
        if over is not None and not enclosing:
            sid, under, trace, svc, inst = over
            under.append((name, seconds, start_s, trace, next(_id_seq), sid,
                          svc, inst, threading.get_ident(), tags))
            return
        ctx = (_trace_id.get(), _span_id.get(), _service.get(),
               _instance.get())
    row = (name, seconds, start_s, ctx[0], next(_id_seq), ctx[1], ctx[2],
           ctx[3], threading.get_ident(), tags)
    if not enclosing:
        wideevents.absorb(name, "", int(seconds * 1e6))
    _ring.append(row)
    if name.startswith("ec."):
        _EC_STAGES.add_seconds("stage", "stage", (row,))


def record_span(name: str, ctx: Optional[TraceCtx], start_us: int,
                dur_us: int, tags: Optional[dict] = None) -> None:
    """Record a completed span — the record form of a stage, for a block
    the caller timed itself (one that ends on another thread than it
    began, or whose last pull must not count). Against an explicit
    context, or the ambient one (`ctx` None). Feeds the ring and the
    stage counters; no trace annotation, which needs the with-form."""
    _emit(name, ctx, start_us / 1e6, dur_us / 1e6, tags)


class stage:
    """Time a block as one stage: a span in the ring under ``ctx`` (the
    ambient context when None: a request's own thread, or a worker that
    runs under a copy of it), a count and its seconds in
    seaweedfs_tpu_ec_stage_seconds{stage=name}, and a profiler
    annotation of the same block. Sets no span id: stages are siblings
    under the span that is ambient. ``enclosing`` marks a stage
    that only wraps stages named on their own: it is kept out of the
    request's wide event, where the largest entry is taken for the
    dominant one and an enclosing one always is; and it gathers the
    stages that end under it as its children, and pays for them all at
    its exit. One opened under another (`ec.get.handler` in `ec.get`)
    is that one's child and gathers in its place until it closes."""

    __slots__ = ("_name", "_ctx", "_tags", "_enclosing", "_note",
                 "_start", "_t0", "_over")

    def __init__(self, name: str, ctx: Optional[TraceCtx] = None,
                 tags: Optional[dict] = None, enclosing: bool = False):
        self._name = name
        self._ctx = ctx
        self._tags = tags
        self._enclosing = enclosing

    def __enter__(self) -> "stage":
        if self._enclosing and self._ctx is None:
            self._over = _open.set((next(_id_seq), [], _trace_id.get(),
                                    _service.get(), _instance.get()))
        else:
            self._over = None
        self._note = profiler.trace_annotation(self._name)
        self._start = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        seconds = time.perf_counter() - self._t0
        if self._note is not None:
            self._note.__exit__(exc_type, exc, tb)
        if self._over is None:
            _emit(self._name, self._ctx, self._start, seconds, self._tags,
                  self._enclosing)
            return
        sid, under, trace, svc, inst = _open.get()
        _open.reset(self._over)
        outer = _open.get()
        acc = wideevents.current()
        if acc is not None:
            stages = acc["stages"]
            for row in under:
                stages[row[0]] = stages.get(row[0], 0) + int(row[1] * 1e6)
        under.append((self._name, seconds, self._start, trace, sid,
                      _span_id.get() if outer is None else outer[0], svc,
                      inst, threading.get_ident(), self._tags))
        _ring.extend(under)
        if self._name.startswith("ec."):
            _EC_STAGES.add_seconds("stage", "stage", under)


def ensure_ctx(service: str = "") -> TraceCtx:
    """The ambient context, or a fresh root one (trace id minted) when no
    trace is active — lets background operations (EC encode from the CLI)
    still produce one coherent trace."""
    ctx = capture()
    if ctx.trace_id:
        return ctx
    return TraceCtx(new_id(), "", ctx.service or service, ctx.instance)


def _rows() -> list[tuple]:
    while True:
        try:
            return list(_ring)
        except RuntimeError:
            # an append (which takes no lock) fell between two steps of
            # the copy; all but impossible, since the copy is one call
            continue


def spans(trace_id: str = "", limit: int = 0) -> list[dict]:
    """Completed spans, oldest first, optionally filtered by trace id."""
    rows = _rows()
    if trace_id:
        rows = [row for row in rows if row[3] == trace_id]
    if limit and len(rows) > limit:
        rows = rows[-limit:]
    return [_unfold(row) for row in rows]


def reset() -> None:
    """Drop all recorded spans (tests)."""
    _ring.clear()


def stage_totals(trace_id: str = "",
                 prefix: str = "") -> dict[str, tuple[int, int]]:
    """Aggregate completed spans by name -> (count, total_us), optionally
    filtered by trace id and name prefix.  The EC feed governor derives
    its per-stage time model from these — the same spans /debug/trace
    serves, so the numbers driving auto-tuning are the ones an operator
    can inspect."""
    out: dict[str, tuple[int, int]] = {}
    for s in spans(trace_id=trace_id):
        name = s.get("name", "")
        if prefix and not name.startswith(prefix):
            continue
        c, t = out.get(name, (0, 0))
        out[name] = (c + 1, t + int(s.get("dur_us", 0)))
    return out


def maybe_log_slow(span_obj: Span) -> None:
    """Slow-request glog line for a request-level span (the per-process
    root); threshold WEED_TRACE_SLOW_MS."""
    dur = span_obj.dur_ms
    if dur >= slow_threshold_ms():
        from ..utils import glog
        glog.warning("slow request trace=%s svc=%s %s took %.1fms",
                     span_obj.trace_id, span_obj._service or "?",
                     span_obj.name, dur)


# histogram exemplars: every metrics.observe() made under a traced
# request stamps its bucket with the ambient trace id, so a p99 bucket
# on /metrics?exemplars=1 links straight to its /debug/trace span
_metrics.set_exemplar_source(lambda: _trace_id.get(""))


# --- Chrome trace-event export (Perfetto / chrome://tracing) ---

def to_chrome_trace(span_dicts: Iterable[dict]) -> dict:
    """Span dicts -> one Chrome trace-event JSON document. Each distinct
    (service, instance) pair becomes a synthetic pid with a process_name
    metadata record, so a merged multi-node trace renders as one process
    lane per server."""
    span_dicts = list(span_dicts)
    procs: dict[tuple[str, str], int] = {}
    for s in span_dicts:
        key = (s.get("svc") or "unknown", s.get("inst") or "")
        procs.setdefault(key, len(procs) + 1)
    events = []
    for (svc, inst), pid in procs.items():
        events.append({
            "ph": "M", "pid": pid, "tid": 0, "name": "process_name",
            "args": {"name": f"{svc}@{inst}" if inst else svc}})
    for s in span_dicts:
        pid = procs[(s.get("svc") or "unknown", s.get("inst") or "")]
        args = {"trace_id": s.get("trace", ""),
                "span_id": s.get("id", "")}
        if s.get("parent"):
            args["parent_id"] = s["parent"]
        for k, v in (s.get("tags") or {}).items():
            args[str(k)] = v
        events.append({
            "name": s.get("name", "?"),
            "cat": s.get("svc") or "unknown",
            "ph": "X",
            "ts": s.get("start_us", 0),
            "dur": max(int(s.get("dur_us", 0)), 1),
            "pid": pid,
            "tid": s.get("tid", 0),
            "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# --- aiohttp server middleware + /debug/trace handler ---

def trace_middleware(service: str, instance: str = ""):
    """Per-request root span: extract/mint the trace id, bind context for
    the handler (so nested spans and outbound calls ride along), record,
    log slow requests, tag the serving thread for the continuous
    profiler, and emit the request's wide event."""
    from aiohttp import web

    from .. import overload as _ov

    # telemetry classification uses THIS surface's system set (the same
    # one its admission controller carries), so a user file named
    # /heartbeat on a catch-all surface isn't mislabeled system
    surface_paths = {"master": _ov.MASTER_SYSTEM_PATHS,
                     "volume": _ov.VOLUME_SYSTEM_PATHS,
                     "filer": _ov.FILER_SYSTEM_PATHS,
                     }.get(service, _ov.GATEWAY_SYSTEM_PATHS)

    @web.middleware
    async def trace_mw(request: web.Request, handler):
        tid, parent = parse_header(request.headers.get(TRACE_HEADER, ""))
        ctx = TraceCtx(tid or new_id(), parent, service, instance)
        sp = Span(f"{request.method} {request.path}", ctx=ctx)
        cls = _ov.classify(request.headers.get(_ov.PRIORITY_HEADER, ""),
                           request.path, surface_paths)
        # bind the caller's deadline budget (X-Seaweed-Deadline) so the
        # handler's own outbound requests inherit what's LEFT of it —
        # piggybacked here because this is the one middleware every
        # server installs (utils/retry.py owns the semantics)
        from ..utils import retry as _retry
        _dl_token = _retry.bind_deadline(request.headers)
        wide = wideevents.enabled()
        streamed = False
        acc = None
        status = 0
        bytes_out = 0
        shed = False
        error = ""
        try:
            with sp:
                acc_tok = wideevents.begin(sp.span_id) if wide else None
                try:
                    with profiler.request_tag(cls, sp.trace_id):
                        resp = await handler(request)
                except Exception as e:
                    status = getattr(e, "status", 500)
                    error = type(e).__name__
                    raise
                finally:
                    if acc_tok is not None:
                        acc = wideevents.current()
                        wideevents.end(acc_tok)
                sp.tags["status"] = resp.status
                status = resp.status
                bytes_out = resp.content_length or 0
                shed = resp.headers.get(_ov.SHED_HEADER) == "1"
                # a bare StreamResponse is a long-lived stream
                # (/cluster/watch, meta subscribe, tail): its lifetime is
                # not latency — same exemption the gRPC stream wrapper
                # makes. /debug/profile and /debug/xprof block for their
                # sample window by design.
                streamed = (not isinstance(resp, web.Response)
                            or request.path in ("/debug/profile",
                                                "/debug/xprof"))
                return resp
        finally:
            _retry.reset_deadline(_dl_token)
            if not streamed:
                maybe_log_slow(sp)
                if wide:
                    tenant = ""
                    if cls != _ov.CLASS_SYSTEM:
                        try:
                            tenant = _ov.tenant_from_request(request)
                        except Exception:
                            tenant = ""
                    wideevents.finish(
                        acc, name=sp.name, trace=sp.trace_id,
                        svc=service, inst=instance, cls=cls,
                        dur_us=getattr(sp, "dur_us", 0), status=status,
                        tenant=tenant,
                        bytes_in=request.content_length or 0,
                        bytes_out=bytes_out, shed=shed, error=error)

    return trace_mw


def trace_handler():
    """aiohttp handler for GET /debug/trace[?trace_id=&limit=&format=].

    Default: Chrome trace-event JSON of this process's span ring.
    format=spans: the raw span dicts (what cluster.trace merges)."""
    from aiohttp import web

    async def handler(request: web.Request) -> web.Response:
        trace_id = request.query.get("trace_id", "")
        try:
            limit = int(request.query.get("limit", "0"))
        except ValueError:
            limit = 0
        out = spans(trace_id=trace_id, limit=limit)
        if request.query.get("format") == "spans":
            return web.json_response({"spans": out})
        return web.json_response(to_chrome_trace(out))

    return handler


def client_trace_config():
    """aiohttp TraceConfig injecting the trace header into every outbound
    request of a session created with it — one hook instead of touching
    each call site (params.headers is the live request header dict)."""
    import aiohttp

    tc = aiohttp.TraceConfig()

    async def on_request_start(session, trace_ctx, params) -> None:
        hv = header_value()
        if hv and TRACE_HEADER not in params.headers:
            params.headers[TRACE_HEADER] = hv
        # the deadline budget and the priority class ride every outbound
        # aiohttp request the same way the trace id does (the repair
        # daemon/scrubber bind bg priority; receivers shed it first)
        from ..utils import retry as _retry
        _retry.inject_deadline(params.headers)
        from .. import overload as _overload
        _overload.inject(params.headers)

    tc.on_request_start.append(on_request_start)
    return tc
