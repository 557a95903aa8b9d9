"""Per-request wide events: ONE canonical structured record per request.

Spans answer "what happened inside this trace"; metrics answer "how much
of everything"; neither answers "show me every slow request last minute
and what each one was doing".  That is the wide event's job (the
Dapper/Honeycomb posture): the trace middleware and the fastpath
listeners emit exactly one record per request — trace id, priority
class, tenant, status, bytes in/out, retries, cache hit/miss, shed
marker, admission queue wait, and per-stage timings accumulated from the
request's own spans — into a bounded per-process ring (snapshot-under-
lock reads, the corrected span-ring pattern) plus an optional ndjson
sink.  ``/debug/events`` serves the ring with filters; ``cluster.tail``
merges the slow tail cluster-wide and ranks where p99 actually goes.

The per-request stage accumulator is a contextvar: observe feeds
every completed span's duration into the ambient request's
accumulator (worker-thread spans recorded against an explicit ctx don't
cross — the EC pipeline emits its own records via ``emit_stages``; an
EC GET's worker runs under a copy of the request's context, so its
stages do).
Code anywhere under the request can attach fields with ``annotate()`` /
``annotate_add()`` (utils/retry counts retries, the chunk cache counts
hits/misses) without plumbing a context object through every layer.

Knobs: ``WEED_WIDE_EVENTS`` (default on; 0 disables emission),
``WEED_WIDE_RING`` (default 4096), ``WEED_WIDE_EVENTS_SINK`` (ndjson
file path, appended one object per line).
"""

from __future__ import annotations

import contextvars
import json
import os
import threading
import time
from collections import deque
from typing import Optional


def _ring_size() -> int:
    try:
        size = int(os.environ.get("WEED_WIDE_RING", "4096"))
    except ValueError:
        return 4096
    return size if size > 0 else 4096


def enabled() -> bool:
    return os.environ.get("WEED_WIDE_EVENTS", "1") not in ("0", "false")


def sink_path() -> str:
    return os.environ.get("WEED_WIDE_EVENTS_SINK", "")


_ring: deque = deque(maxlen=_ring_size())
_ring_lock = threading.Lock()

# the per-request accumulator: {"root": span_id, "stages": {}, "notes": {}}
_acc: contextvars.ContextVar[Optional[dict]] = contextvars.ContextVar(
    "sw_wide_acc", default=None)


def configure(ring: int = 0) -> None:
    """Re-size the ring (tests); drops current contents."""
    global _ring
    with _ring_lock:
        _ring = deque(maxlen=ring or _ring_size())


# --- per-request accumulation -----------------------------------------


def begin(root_span_id: str) -> contextvars.Token:
    """Open a request accumulator; the root span's own duration is the
    event's dur, so its id is excluded from the stage breakdown."""
    return _acc.set({"root": root_span_id, "stages": {}, "notes": {}})


def end(token: contextvars.Token) -> None:
    _acc.reset(token)


def current() -> Optional[dict]:
    return _acc.get()


def absorb(name: str, span_id: str, dur_us: int) -> None:
    """Fold a completed span into the ambient request accumulator —
    called by observe for every span that closes, so stage timings cost
    nothing extra at the span call sites."""
    acc = _acc.get()
    if acc is None or span_id == acc["root"]:
        return
    stages = acc["stages"]
    stages[name] = stages.get(name, 0) + dur_us


def annotate(key: str, value) -> None:
    """Attach a field to the ambient request's wide event (no-op outside
    a request)."""
    acc = _acc.get()
    if acc is not None:
        acc["notes"][key] = value


def annotate_add(key: str, delta: float = 1) -> None:
    """Increment a numeric field on the ambient request's wide event
    (retry counts, cache hits) — no-op outside a request."""
    acc = _acc.get()
    if acc is not None:
        notes = acc["notes"]
        notes[key] = notes.get(key, 0) + delta


# --- emission ----------------------------------------------------------


def emit(event: dict) -> None:
    """Append one event to the ring (+ ndjson sink when configured)."""
    with _ring_lock:
        _ring.append(event)
    path = sink_path()
    if path:
        try:
            line = json.dumps(event, default=str)
            with open(path, "a") as f:
                f.write(line + "\n")
        except OSError:
            pass  # a full/missing sink disk must never fail a request


def finish(acc: Optional[dict], *, name: str, trace: str, svc: str,
           inst: str, cls: str, dur_us: int, status: int = 0,
           tenant: str = "", bytes_in: int = 0, bytes_out: int = 0,
           shed: bool = False, error: str = "") -> dict:
    """Build + emit the canonical per-request record from an accumulator
    (None for paths that never opened one, e.g. sheds)."""
    stages = dict(acc["stages"]) if acc else {}
    ev = {
        "ts": round(time.time(), 3),
        "name": name,
        "trace": trace,
        "svc": svc,
        "inst": inst,
        "cls": cls,
        "status": status,
        "dur_us": dur_us,
        "bytes_in": bytes_in,
        "bytes_out": bytes_out,
        "shed": shed,
        # admission queue wait gets its own top-level field: it is THE
        # "was this latency our own backpressure" discriminator
        "queue_us": stages.get("admission.wait", 0),
        "stages": stages,
    }
    if tenant:
        ev["tenant"] = tenant
    if error:
        ev["error"] = error
    if acc:
        for k, v in acc["notes"].items():
            ev.setdefault(k, v)
    emit(ev)
    return ev


def emit_stages(svc: str, name: str, trace: str, dur_us: int,
                totals: dict, cls: str = "bg", inst: str = "") -> dict:
    """Emit a record from pre-aggregated stage totals (observe.
    stage_totals form: name -> (count, total_us)) — the EC pipeline's
    feed/governor stages report through here so chip-side runs are
    attributed identically to serving requests."""
    stages = {k: int(v[1]) for k, v in totals.items()}
    ev = {
        "ts": round(time.time(), 3),
        "name": name,
        "trace": trace,
        "svc": svc,
        "inst": inst,
        "cls": cls,
        "status": 0,
        "dur_us": dur_us,
        "bytes_in": 0,
        "bytes_out": 0,
        "shed": False,
        "queue_us": stages.get("admission.wait", 0),
        "stages": stages,
    }
    emit(ev)
    return ev


# --- queries -----------------------------------------------------------


def events(trace: str = "", cls: str = "", status: int = 0,
           min_ms: float = 0.0, stage: str = "", svc: str = "",
           shed: Optional[bool] = None, limit: int = 0) -> list[dict]:
    """Filtered events, oldest first.  All filters AND together;
    ``stage`` matches events whose breakdown contains that stage name
    (prefix match), ``status`` an exact HTTP status."""
    with _ring_lock:
        out = list(_ring)
    if trace:
        out = [e for e in out if e.get("trace") == trace]
    if cls:
        out = [e for e in out if e.get("cls") == cls]
    if svc:
        out = [e for e in out if e.get("svc") == svc]
    if status:
        out = [e for e in out if e.get("status") == status]
    if min_ms > 0:
        min_us = min_ms * 1000.0
        out = [e for e in out if e.get("dur_us", 0) >= min_us]
    if stage:
        out = [e for e in out
               if any(s.startswith(stage) for s in e.get("stages", {}))]
    if shed is not None:
        out = [e for e in out if bool(e.get("shed")) == shed]
    if limit and len(out) > limit:
        out = out[-limit:]
    return out


def reset() -> None:
    """Drop all recorded events (tests)."""
    with _ring_lock:
        _ring.clear()


# --- tail attribution helpers (cluster.tail + /debug/events) ----------

# stage-name prefix -> attribution bucket. Ordered: first match wins.
# "fault.<point>" spans (injected delays, faults plane) attribute as the
# point they delay, so a chaos drill's p99 names the faulted stage.
_STAGE_BUCKETS: tuple[tuple[str, str], ...] = (
    ("admission.", "admission-queue"),
    ("singleflight.", "lock"),
    ("lock", "lock"),
    ("volume.read_repair", "remote-hop"),
    ("volume.replicate", "remote-hop"),
    ("disk.sendfile", "disk"),
    ("volume.read", "disk"),
    ("volume.write", "disk"),
    ("volume.scrub", "disk"),
    # the EC GET's stages (observe.stage; PERF.md has the table), each
    # named: a host-side ec.* stage is not the kernel's for having no row
    ("ec.get.ecx", "disk"),
    ("ec.get.shard_read", "disk"),
    ("ec.get.survivors", "disk"),
    ("ec.get.parse", "disk"),
    ("ec.get.peer_fetch", "remote-hop"),
    ("ec.get.remote_read", "remote-hop"),
    ("ec.get.queue", "admission-queue"),
    ("ec.get.resume", "admission-queue"),
    ("ec.get.flight_wait", "lock"),
    ("ec.get.stack_pad", "kernel"),
    ("ec.get.dispatch", "kernel"),
    ("ec.get.d2h_wait", "kernel"),
    ("ec.get.handler", "handler"),
    ("ec.read", "disk"),
    ("ec.write", "disk"),
    ("ec.fsync", "disk"),
    ("ec.seal", "disk"),
    ("ec.ecx", "disk"),
    ("ec.stamp", "disk"),
    ("ec.kernel", "kernel"),
    ("ec.dispatch", "kernel"),
    ("ec.stage.chip", "kernel"),
    ("filer.fetch_chunk", "remote-hop"),
    ("filer.upload_chunk", "remote-hop"),
    ("filer.upload", "remote-hop"),
    ("geo.", "remote-hop"),
    ("assign.", "remote-hop"),
    ("cache.", "cache"),
)


def stage_bucket(name: str) -> str:
    """Attribution bucket for a stage name (fault.X buckets as X)."""
    if name.startswith("fault."):
        name = name[len("fault."):]
    for prefix, bucket in _STAGE_BUCKETS:
        if name.startswith(prefix):
            return bucket
    return "handler"


def dominant_stage(event: dict) -> tuple[str, int]:
    """(stage name, us) of the single largest stage in the event; the
    un-attributed remainder competes as '(handler)' so a request slow in
    its own handler code isn't pinned on an incidental 1µs stage.  Stage
    spans nest (a cache.lookup inside a filer.fetch_chunk), so the
    remainder is floored at zero rather than trusted as exact."""
    stages = event.get("stages", {})
    best, best_us = "", 0
    for name, us in stages.items():
        if us > best_us:
            best, best_us = name, us
    rem = event.get("dur_us", 0) - sum(stages.values())
    if rem > best_us:
        return "(handler)", rem
    return (best or "(handler)"), best_us or max(rem, 0)


def events_handler():
    """aiohttp handler for GET /debug/events[?trace_id=&class=&status=
    &min_ms=&stage=&shed=&limit=] — the raw records cluster.tail merges."""
    from aiohttp import web

    async def handler(request: web.Request) -> web.Response:
        q = request.query

        def _f(key, cast, default):
            try:
                return cast(q.get(key, default))
            except (TypeError, ValueError):
                return default

        shed = q.get("shed", "")
        out = events(trace=q.get("trace_id", ""),
                     cls=q.get("class", ""),
                     svc=q.get("svc", ""),
                     status=_f("status", int, 0),
                     min_ms=_f("min_ms", float, 0.0),
                     stage=q.get("stage", ""),
                     shed=(shed == "1") if shed in ("0", "1") else None,
                     limit=_f("limit", int, 0))
        return web.json_response({"events": out, "count": len(out),
                                  "enabled": enabled()})

    return handler
