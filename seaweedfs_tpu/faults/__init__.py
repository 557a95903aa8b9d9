"""Declarative fault-injection plane: named points, env/admin control.

The chaos and degraded-read suites used to monkeypatch one method per
test; operators had nothing at all.  This registry gives every process a
set of *named fault points* compiled into the hot paths (volume
read/write/replicate, EC shard reads, the gRPC planes, the pooled HTTP
client).  A point does nothing until a fault is armed against it — the
disarmed check is one dict lookup on an almost-always-empty dict.

Faults are armed three ways:

  * ``WEED_FAULTS`` env at process start, e.g.::

        WEED_FAULTS="volume.read:error:p=0.5:count=3,ec.shard_read:delay:ms=200"

  * ``POST /admin/faults`` on any server (body
    ``{"set": [{"point": ..., "action": ...}]}`` / ``{"clear": "*"}``) —
    process-local, never proxied, so a test or operator targets exactly
    one node;
  * programmatically via :func:`set_fault` (in-process tests).

Actions:

  ``delay``    sleep ``ms`` milliseconds before the operation
  ``error``    raise :class:`FaultError` (surfaces as a 5xx / RPC error)
  ``drop``     the call site silently discards the operation (replicate
               fan-out skips a peer, a shard read reports "not here")
  ``corrupt``  flip one deterministic byte of the payload (bit-rot)

Every fault carries a probability ``p`` (rolled on a per-fault
``random.Random(seed)`` so chaos runs replay deterministically) and an
optional ``count`` budget — after ``count`` firings the fault disarms
itself, which is how tests express "fail the first N, then recover".
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

import random


class FaultError(RuntimeError):
    """An injected failure (action=error)."""


_ACTIONS = ("delay", "error", "drop", "corrupt")

# fire() consumes these; corrupt() consumes only "corrupt" — a corrupt
# fault armed at a point whose code path calls both must not be burned
# by the control-flow check before the payload ever reaches corrupt()
_FLOW_ACTIONS = ("delay", "error", "drop")


@dataclass
class Fault:
    point: str              # exact name, or prefix ending in '*'
    action: str
    p: float = 1.0          # firing probability per arrival
    count: Optional[int] = None   # remaining budget; None = unlimited
    ms: float = 0.0         # delay duration (action=delay)
    seed: int = 0
    fired: int = 0
    _rng: random.Random = field(default=None, repr=False)  # type: ignore

    def __post_init__(self):
        if self.action not in _ACTIONS:
            raise ValueError(f"unknown fault action {self.action!r}")
        self._rng = random.Random(self.seed)

    def matches(self, point: str) -> bool:
        if self.point.endswith("*"):
            return point.startswith(self.point[:-1])
        return self.point == point

    def to_dict(self) -> dict:
        d = {"point": self.point, "action": self.action, "p": self.p,
             "ms": self.ms, "seed": self.seed, "fired": self.fired}
        if self.count is not None:
            d["count"] = self.count
        return d


# the declared fault-point registry: every point compiled into a hot
# path is named here, and weedlint's fault-point-registry rule holds
# the two sides together — a point fired in code but missing here is a
# typo waiting to no-op a chaos drill (PR 5's silently no-oping fast
# paths), and a point declared here that nothing fires is dead chaos
# surface that tests believe in but nothing honors
KNOWN_POINTS = frozenset({
    "volume.read",          # volume server read path (incl. fastpath)
    "volume.write",         # volume server write path (incl. fastpath)
    "volume.replicate",     # replica fan-out
    "master.assign",        # fid assignment (incl. fastpath listener)
    "ec.shard_read",        # EC shard interval reads
    "ec.feed.read",         # EC feed stripe/survivor reads (ec/feed.py)
    "ec.feed.stall",        # EC feed staging-buffer waits (ec/feed.py)
    "http_pool.request",    # pooled intra-cluster HTTP request
    "http_pool.response",   # pooled response payload (corrupt target)
    "lifecycle.warm",       # hot->warm transition
    "lifecycle.unec",       # warm->hot un-EC transition
    "lifecycle.expire",     # TTL whole-volume expiry
    "lifecycle.encode",     # lifecycle-driven ec encode step
    "geo.apply",            # cross-cluster event apply (geo/ + sync
                            # replicator) — error = sink failure,
                            # drop = event lost mid-flight
    "geo.stream",           # the /__meta__/subscribe tail a replicator
                            # rides — error/drop = stream torn down
    "ring.proxy",           # metaring owner-proxy/mirror hop between
                            # filer peers — drop = peer vanished
                            # mid-request (read fallback / mirror
                            # degradation paths)
    "ring.handoff",         # metaring partition handoff walker —
                            # error/drop = coordinator died mid-move
                            # (resume-from-watermark path)
    "master.log.apply",     # master metadata-log apply (assign
                            # batches, volume create/retire, geometry
                            # stamps riding the raft plane)
    "disk.write",           # DiskFile.write_at — corrupt = bit-rot on
                            # the way to the platter (CRC read-repair
                            # drills), error = EIO, delay = slow disk
    "disk.sync",            # DiskFile.sync fsync barrier — error =
                            # fsync failure (crash-consistency drills
                            # crash "at" a named barrier by erroring it)
    "ec.fused.read",        # fused warm-down compaction-chunk reads
                            # (ec/fused.py) — drop FAILS the chunk
                            # (skipping live extents would compact
                            # acked needles away)
    "ec.fused.gzip",        # fused warm-down payload transform — drop
                            # fails the gzip/splice stage
    "ec.fused.commit",      # fused warm-down commit barrier, fired
                            # after shards/.dat/.idx/.ecx are durable
                            # and BEFORE the .ecm marker — the crash
                            # window the crashsim workload walks
    "master.balance.plan",  # balancer planning pass — drop = pass
                            # skipped, error = planner crash drills
    "master.balance.move",  # balancer volume move, fired BEFORE the
                            # copy — error/drop here is the worst-case
                            # kill window the chaos suite proves leaves
                            # a complete copy on exactly one side
    "sim.heartbeat",        # clustersim virtual-node heartbeat — drop
                            # = that node's beat lost this tick (flap /
                            # dead-node drills at 1000 nodes)
})

_lock = threading.Lock()
_faults: list[Fault] = []
_env_loaded = False


def _parse_spec(spec: str) -> Fault:
    """'point:action[:k=v]*' -> Fault."""
    parts = [p for p in spec.strip().split(":") if p]
    if len(parts) < 2:
        raise ValueError(f"bad fault spec {spec!r} "
                         "(want point:action[:k=v]...)")
    kwargs: dict = {}
    for kv in parts[2:]:
        k, _, v = kv.partition("=")
        if k == "count":
            kwargs["count"] = int(v)
        elif k == "p":
            kwargs["p"] = float(v)
        elif k == "ms":
            kwargs["ms"] = float(v)
        elif k == "seed":
            kwargs["seed"] = int(v)
        else:
            raise ValueError(f"unknown fault param {k!r} in {spec!r}")
    return Fault(point=parts[0], action=parts[1], **kwargs)


def _ensure_env() -> None:
    global _env_loaded
    if _env_loaded:
        return
    with _lock:
        if _env_loaded:
            return
        _env_loaded = True
        env = os.environ.get("WEED_FAULTS", "")
        for spec in env.split(","):
            if spec.strip():
                _faults.append(_parse_spec(spec))


def set_fault(point: str, action: str, p: float = 1.0,
              count: Optional[int] = None, ms: float = 0.0,
              seed: int = 0) -> dict:
    """Arm a fault; returns its dict form."""
    _ensure_env()
    f = Fault(point=point, action=action, p=p, count=count, ms=ms,
              seed=seed)
    with _lock:
        _faults.append(f)
    return f.to_dict()


def clear(point: Optional[str] = None) -> int:
    """Disarm faults at `point` (exact registration string), or all."""
    global _faults
    _ensure_env()
    with _lock:
        before = len(_faults)
        if point is None or point == "*":
            _faults = []
        else:
            _faults = [f for f in _faults if f.point != point]
        return before - len(_faults)


def active() -> list[dict]:
    _ensure_env()
    with _lock:
        return [f.to_dict() for f in _faults]


def _arm(point: str, kinds: tuple) -> Optional[Fault]:
    """Roll the dice for `point`; returns the fault to apply (budget
    already consumed) or None. The disarmed fast path (every production
    request) is one unlocked emptiness check — stale reads are benign
    (one extra lock round at worst)."""
    if _env_loaded and not _faults:
        return None
    _ensure_env()
    with _lock:
        if not _faults:
            return None
        for f in _faults:
            if f.action not in kinds or not f.matches(point):
                continue
            if f.count is not None and f.count <= 0:
                continue
            if f.p < 1.0 and f._rng.random() >= f.p:
                continue
            f.fired += 1
            if f.count is not None:
                f.count -= 1
            return f
    return None


def fire(point: str) -> bool:
    """Hook for sync call sites. Applies any armed delay/error fault;
    returns True when the operation should be silently DROPPED."""
    f = _arm(point, _FLOW_ACTIONS)
    if f is None:
        return False
    if f.action == "delay":
        # record the injected delay as a fault.<point> span: chaos-drill
        # latency must show up in the wide event's stage breakdown
        # attributed to the faulted point, not vanish into the handler
        # remainder (observe.stage_bucket strips the fault. prefix)
        from .. import observe
        with observe.span(f"fault.{point}"):
            time.sleep(f.ms / 1000.0)
        return False
    if f.action == "error":
        raise FaultError(f"injected fault at {point}")
    return True  # drop


async def fire_async(point: str) -> bool:
    """fire() for coroutine call sites — delays park on the loop instead
    of blocking it."""
    f = _arm(point, _FLOW_ACTIONS)
    if f is None:
        return False
    if f.action == "delay":
        import asyncio

        from .. import observe
        with observe.span(f"fault.{point}"):
            await asyncio.sleep(f.ms / 1000.0)
        return False
    if f.action == "error":
        raise FaultError(f"injected fault at {point}")
    return True


def corrupt(point: str, data: bytes) -> bytes:
    """Apply an armed corrupt fault to a payload: one byte, chosen by the
    fault's deterministic rng, is bit-flipped. No fault -> data verbatim."""
    if not data:
        return data
    f = _arm(point, ("corrupt",))
    if f is None:
        return data
    pos = f._rng.randrange(len(data))
    out = bytearray(data)
    out[pos] ^= 0xFF
    return bytes(out)


def admin_enabled() -> bool:
    """Whether UNGUARDED servers (the s3/webdav gateways, the filer —
    surfaces with no IP-whitelist middleware) may expose /admin/faults.
    Off by default: an open fault endpoint is a one-request DoS. The
    master and volume servers always register it — their guard
    middleware already fences the admin surface."""
    return os.environ.get("WEED_FAULTS_ADMIN", "") not in ("", "0")


def admin_handler():
    """aiohttp handler for GET/POST /admin/faults — the declarative knob
    chaos tests and operators flip instead of monkeypatching.

    GET  -> {"faults": [...]}
    POST {"set": [{"point":..,"action":..,...} | "point:action:k=v"]}
         {"clear": "point" | "*"}
    """
    from aiohttp import web

    async def handler(request: web.Request) -> web.Response:
        if request.method == "GET":
            return web.json_response({"faults": active()})
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": "bad json"}, status=400)
        try:
            cleared = 0
            if "clear" in body:
                cleared = clear(None if body["clear"] in ("*", None)
                                else body["clear"])
            for spec in body.get("set", []):
                if isinstance(spec, str):
                    f = _parse_spec(spec)
                    with _lock:
                        _faults.append(f)
                else:
                    set_fault(spec["point"], spec["action"],
                              p=float(spec.get("p", 1.0)),
                              count=(int(spec["count"])
                                     if spec.get("count") is not None
                                     else None),
                              ms=float(spec.get("ms", 0.0)),
                              seed=int(spec.get("seed", 0)))
        except (KeyError, ValueError) as e:
            return web.json_response({"error": str(e)}, status=400)
        return web.json_response({"ok": True, "cleared": cleared,
                                  "faults": active()})

    return handler
