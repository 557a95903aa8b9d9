"""Share-nothing per-core serving tier: the SO_REUSEPORT shard fleet.

One serving surface (volume/filer/S3) forks into ``WEED_SERVE_SHARDS``
worker processes, each binding the SAME public port via ``SO_REUSEPORT``
with its own event loop, fastpath listener and admission controller —
the kernel's reuseport hash spreads accepted connections across shards,
so the req/s ceiling moves from "one core" to "the host" without any
userspace accept lock.

The fork happens BEFORE any event loop exists (``run_sharded`` is
called from the CLI, ahead of ``asyncio.new_event_loop``): an epoll fd
created pre-fork would be shared by every child and they would steal
each other's readiness events.  weedlint's fork-then-asyncio rule pins
this ordering.

What little the shards share lives in one anonymous ``mmap`` segment
created pre-fork and inherited through the fork:

* a fixed-layout **meta slot** per shard (alive flag, pid, loopback
  aiohttp port, heartbeat timestamp, demand/shed/inversion tallies,
  current stripe share) — single writer per slot (the shard itself),
  racy lock-free readers everywhere else;
* a length-prefixed **JSON blob** per shard (its volume list for the
  master heartbeat union, its ``/healthz`` summary) — written with a
  generation guard so a torn read is detected and skipped, never
  half-parsed.

Striped admission: each shard starts at ``1/N`` of the node's
configured global/tenant rate and a periodic rebalance tick re-divides
the budget demand-proportionally (an idle shard's unspent budget flows
to the hot ones) while the SUM across shards stays at the whole-node
rate.  ``/healthz`` and ``/metrics`` answered by ANY shard aggregate
the segment so load balancers and the telemetry shell keep seeing one
node.
"""

from __future__ import annotations

import asyncio
import json
import logging
import mmap
import os
import signal
import struct
import time
from typing import Callable, List, Optional

log = logging.getLogger("sharded")

# -- knobs --------------------------------------------------------------

SHARDS_ENV = "WEED_SERVE_SHARDS"
REBALANCE_ENV = "WEED_SHARD_REBALANCE_S"

#: rebalance/publish tick; also the heartbeat granularity of the
#: liveness view, so keep it well under STALE_AFTER_S
DEFAULT_REBALANCE_S = 0.5

#: a slot whose heartbeat timestamp is older than this is reported dead
#: even if its alive flag is still set (covers SIGKILL, where the shard
#: never gets to clear the flag itself)
STALE_AFTER_S = 5.0

#: additive smoothing (in requests-per-tick) for the demand-
#: proportional split: keeps a zero-demand shard at a small floor so a
#: traffic flip doesn't have to wait a full tick to get budget back
DEMAND_SMOOTHING = 4.0

MAX_SHARDS = 64


def shards_from_env(env=os.environ) -> int:
    """Resolve WEED_SERVE_SHARDS: 1 (today's proven single-process
    path) unless explicitly raised; clamped to [1, MAX_SHARDS]."""
    try:
        n = int(env.get(SHARDS_ENV, "") or 1)
    except (TypeError, ValueError):
        return 1
    return max(1, min(MAX_SHARDS, n))


# -- the shared stats segment ------------------------------------------

# alive u32 | pid u32 | internal_port u32 | reserved u32
# | hb_ts f64 | demand u64 | shed u64 | inversions u64 | requests u64
# | stripe_share f64
_META = struct.Struct("<IIIIdQQQQd")
_BLOB_HDR = struct.Struct("<II")          # generation u32 | length u32
_HEADER = struct.Struct("<4sHH8x")        # magic | version | nshards
_MAGIC = b"SWSH"
_VERSION = 1

_SLOT_SIZE = 64 * 1024
_BLOB_OFF = 256                           # blob area within a slot
_BLOB_MAX = _SLOT_SIZE - _BLOB_OFF - _BLOB_HDR.size


class ShardContext:
    """One shard's handle on the fleet: its index, the shared segment,
    and the pre-fork loopback secret.

    Everything here is safe to call from any shard at any time: writes
    touch only this shard's slot; reads of other slots are lock-free
    and defensive (a torn blob is skipped, a stale slot reads as dead).
    """

    def __init__(self, shards: int, mm: mmap.mmap, token: str,
                 index: int = 0):
        self.shards = shards
        self.index = index
        self.token = token
        self._mm = mm
        self.child_pids: List[int] = []
        # per-context demand snapshot for delta-based rebalancing
        self._prev_demand: dict = {}
        self._blob_gen = 0
        # vid -> owning shard index, rebuilt each stripe tick from the
        # fleet's published volume lists.  Essential for LEGACY volumes:
        # everything that existed before sharding lives in shard 0's
        # base dir regardless of what vid % N says.
        self._vol_owner: dict = {}

    # -- construction --

    @classmethod
    def create(cls, shards: int, token: str) -> "ShardContext":
        """Build the segment PRE-FORK so every shard inherits the same
        anonymous mapping."""
        shards = max(1, min(MAX_SHARDS, int(shards)))
        size = _HEADER.size + shards * _SLOT_SIZE
        mm = mmap.mmap(-1, size)
        mm[0:_HEADER.size] = _HEADER.pack(_MAGIC, _VERSION, shards)
        return cls(shards, mm, token)

    # -- slot addressing --

    def _slot_off(self, i: int) -> int:
        if not (0 <= i < self.shards):
            raise IndexError(f"shard {i} out of range 0..{self.shards - 1}")
        return _HEADER.size + i * _SLOT_SIZE

    # -- my slot (single writer) --

    def publish_meta(self, *, alive: int = 1, pid: Optional[int] = None,
                     internal_port: Optional[int] = None,
                     demand: int = 0, shed: int = 0, inversions: int = 0,
                     requests: int = 0, stripe_share: float = 1.0) -> None:
        off = self._slot_off(self.index)
        self._mm[off:off + _META.size] = _META.pack(
            int(alive), int(pid if pid is not None else os.getpid()),
            int(internal_port or 0), 0, time.time(),
            int(demand), int(shed), int(inversions), int(requests),
            float(stripe_share))

    def touch(self, *, demand: int, shed: int, inversions: int,
              requests: int, stripe_share: float) -> None:
        """Refresh my heartbeat timestamp + counters, preserving the
        alive/pid/port words already published."""
        off = self._slot_off(self.index)
        alive, pid, port, _, _, _, _, _, _, _ = _META.unpack(
            self._mm[off:off + _META.size])
        self._mm[off:off + _META.size] = _META.pack(
            alive, pid, port, 0, time.time(),
            int(demand), int(shed), int(inversions), int(requests),
            float(stripe_share))

    def mark_dead(self, i: Optional[int] = None) -> None:
        """Clear a slot's alive flag (own graceful shutdown, or the
        supervisor reaping a dead child's slot)."""
        off = self._slot_off(self.index if i is None else i)
        self._mm[off:off + 4] = struct.pack("<I", 0)

    def write_blob(self, obj: dict) -> None:
        """Publish my JSON blob with a torn-read guard: generation is
        bumped to an ODD value before the body write and back to the
        next EVEN value after, so a reader that catches the write in
        flight sees an odd/duplicate generation and skips the slot."""
        data = json.dumps(obj, separators=(",", ":")).encode()
        if len(data) > _BLOB_MAX:
            # oversized payloads (a shard with thousands of volumes)
            # degrade to meta-only: aggregation still sees the shard
            # alive, the heartbeat union just misses its volume list
            # until it shrinks — log once per size change
            log.warning("shard %d blob %dB exceeds %dB slot, skipping",
                        self.index, len(data), _BLOB_MAX)
            data = b"{}"
        off = self._slot_off(self.index) + _BLOB_OFF
        self._blob_gen += 2
        gen = self._blob_gen
        self._mm[off:off + _BLOB_HDR.size] = _BLOB_HDR.pack(gen - 1,
                                                            len(data))
        self._mm[off + _BLOB_HDR.size:off + _BLOB_HDR.size + len(data)] = data
        self._mm[off:off + _BLOB_HDR.size] = _BLOB_HDR.pack(gen, len(data))

    # -- any slot (lock-free reads) --

    def read_meta(self, i: int) -> dict:
        off = self._slot_off(i)
        (alive, pid, port, _, hb_ts, demand, shed, inversions,
         requests, share) = _META.unpack(self._mm[off:off + _META.size])
        fresh = (time.time() - hb_ts) <= STALE_AFTER_S
        return {"shard": i, "alive": bool(alive) and fresh, "pid": pid,
                "internal_port": port, "hb_ts": hb_ts, "demand": demand,
                "shed": shed, "inversions": inversions,
                "requests": requests, "stripe_share": share,
                "stale": bool(alive) and not fresh}

    def read_blob(self, i: int) -> Optional[dict]:
        off = self._slot_off(i) + _BLOB_OFF
        for _ in range(3):
            gen1, length = _BLOB_HDR.unpack(
                self._mm[off:off + _BLOB_HDR.size])
            if gen1 == 0 or gen1 % 2 or length > _BLOB_MAX:
                return None
            raw = bytes(self._mm[off + _BLOB_HDR.size:
                                 off + _BLOB_HDR.size + length])
            gen2, _ = _BLOB_HDR.unpack(self._mm[off:off + _BLOB_HDR.size])
            if gen1 != gen2:
                continue      # writer raced us: retry
            try:
                return json.loads(raw.decode())
            except (ValueError, UnicodeDecodeError):
                return None   # torn despite guard — treat as absent
        return None

    # -- fleet views --

    def alive_shards(self) -> List[int]:
        return [i for i in range(self.shards)
                if self.read_meta(i)["alive"]]

    def aggregate_health(self) -> dict:
        """The whole-node view for /healthz: every shard's meta slot
        plus its self-reported admission summary (from its blob)."""
        rows = []
        shedding = False
        for i in range(self.shards):
            m = self.read_meta(i)
            blob = self.read_blob(i) or {}
            h = blob.get("health") or {}
            row = {"shard": i, "alive": m["alive"], "pid": m["pid"],
                   "demand": m["demand"], "shed": m["shed"],
                   "inversions": m["inversions"],
                   "requests": m["requests"],
                   "stripe_share": round(m["stripe_share"], 4),
                   "shedding": bool(h.get("shedding", False)),
                   "loop_lag_ms": h.get("loop_lag_ms", 0.0)}
            shedding = shedding or row["shedding"]
            rows.append(row)
        return {"count": self.shards,
                "alive": sum(1 for r in rows if r["alive"]),
                "shedding": shedding, "per_shard": rows}

    def metrics_lines(self) -> str:
        """Prometheus text lines aggregating the segment, appended to
        any shard's /metrics answer.  Hand-rendered (not via the
        metrics Registry) because the values belong to OTHER processes
        — routing them through this process's registry would fold
        per-shard series into its own labels and break the label-
        registry invariants weedlint pins."""
        out = ["# HELP swfs_shard_alive shard liveness from the shared"
               " stats segment",
               "# TYPE swfs_shard_alive gauge"]
        metas = [self.read_meta(i) for i in range(self.shards)]
        for m in metas:
            out.append(f'swfs_shard_alive{{shard="{m["shard"]}"}} '
                       f'{1 if m["alive"] else 0}')
        for name, key, kind in (
                ("swfs_shard_demand_total", "demand", "counter"),
                ("swfs_shard_shed_total", "shed", "counter"),
                ("swfs_shard_inversions_total", "inversions", "counter"),
                ("swfs_shard_requests_total", "requests", "counter"),
                ("swfs_shard_stripe_share", "stripe_share", "gauge")):
            out.append(f"# TYPE {name} {kind}")
            for m in metas:
                v = m[key]
                v = round(v, 6) if isinstance(v, float) else v
                out.append(f'{name}{{shard="{m["shard"]}"}} {v}')
        return "\n".join(out) + "\n"

    # -- volume-id routing (volume surface only) --

    def owner(self, vid: int) -> int:
        """NEW volumes land on shard ``vid % N`` — a static map every
        shard computes identically with no coordination."""
        return int(vid) % self.shards

    def route_port(self, vid: int) -> Optional[int]:
        """Loopback aiohttp port of the shard owning ``vid``, or None
        when the volume is (or must be handled) locally: we own it, the
        owner is dead (let the local slow path answer authoritatively),
        or the owner hasn't published its port yet."""
        o = self.owner(vid)
        if o == self.index:
            return None
        m = self.read_meta(o)
        if m["alive"] and m["internal_port"]:
            return m["internal_port"]
        return None

    def rebuild_routes(self) -> None:
        """Refresh the vid -> owning-shard map from every live shard's
        published heartbeat blob (driven from stripe_tick).  Volumes
        published by a dead shard keep their last known owner: routing
        to it fails closed (lookup returns None → local authoritative
        404/answer) rather than misrouting to the modulo owner."""
        routes: dict = {}
        for i in range(self.shards):
            m = self.read_meta(i)
            if not m["alive"] and i != self.index:
                continue
            blob = self.read_blob(i) or {}
            p = blob.get("heartbeat") or {}
            for v in p.get("volumes", ()):
                vid = v.get("id")
                if isinstance(vid, int):
                    routes[vid] = i
        if routes or not self._vol_owner:
            self._vol_owner = routes
        else:
            # blobs not published yet — keep the previous map rather
            # than flushing known routes into the modulo fallback
            self._vol_owner.update(routes)

    def lookup_volume_port(self, vid: int) -> Optional[int]:
        """Loopback port of the shard that actually HOLDS ``vid`` per
        the published volume lists; falls back to the static modulo map
        for volumes nobody has published yet (assign in flight)."""
        o = self._vol_owner.get(int(vid))
        if o is None:
            return self.route_port(vid)
        if o == self.index:
            return None
        m = self.read_meta(o)
        if m["alive"] and m["internal_port"]:
            return m["internal_port"]
        return None

    def merged_heartbeat(self, my_payload: dict) -> dict:
        """Shard 0's master heartbeat: the union of every live shard's
        published volume list, so the master keeps seeing ONE node.
        My own payload is authoritative for my volumes; other shards
        contribute their latest blob (at most one tick stale)."""
        volumes = list(my_payload.get("volumes", ()))
        ec_shards = list(my_payload.get("ec_shards", ()))
        seen = {v["id"] for v in volumes}
        seen_ec = {e["id"] for e in ec_shards}
        max_file_key = my_payload.get("max_file_key", 0)
        max_volume_count = my_payload.get("max_volume_count", 0)
        for i in range(self.shards):
            if i == self.index:
                continue
            m = self.read_meta(i)
            if not m["alive"]:
                continue
            blob = self.read_blob(i) or {}
            p = blob.get("heartbeat") or {}
            for v in p.get("volumes", ()):
                if v.get("id") not in seen:
                    seen.add(v.get("id"))
                    volumes.append(v)
            for e in p.get("ec_shards", ()):
                if e.get("id") not in seen_ec:
                    seen_ec.add(e.get("id"))
                    ec_shards.append(e)
            max_file_key = max(max_file_key, p.get("max_file_key", 0))
            max_volume_count += p.get("max_volume_count", 0)
        merged = dict(my_payload)
        merged.update(volumes=volumes, ec_shards=ec_shards,
                      max_file_key=max_file_key,
                      max_volume_count=max_volume_count)
        return merged

    # -- demand-proportional striping --

    def compute_share(self) -> float:
        """My next stripe share: demand-proportional over the deltas
        since my previous tick, with additive smoothing so idle shards
        keep a floor and the shares of the LIVE shards sum to ~1.  Dead
        shards drop out of the denominator — a survivor inherits the
        dead shard's budget on the next tick (the kill-one-shard test
        pins this)."""
        deltas = {}
        for i in range(self.shards):
            m = self.read_meta(i)
            if not m["alive"] and i != self.index:
                self._prev_demand.pop(i, None)
                continue
            prev = self._prev_demand.get(i, m["demand"])
            deltas[i] = max(0.0, float(m["demand"] - prev))
            self._prev_demand[i] = m["demand"]
        if len(deltas) <= 1:
            return 1.0
        total = sum(deltas.values()) + DEMAND_SMOOTHING * len(deltas)
        return (deltas.get(self.index, 0.0) + DEMAND_SMOOTHING) / total

    # -- shard-0 supervision --

    def reap_children(self) -> List[int]:
        """Non-blocking reap; marks reaped children's slots dead.
        Returns the shard indexes that died (for logging/tests)."""
        died = []
        while True:
            try:
                pid, _status = os.waitpid(-1, os.WNOHANG)
            except ChildProcessError:
                break
            if pid == 0:
                break
            for i in range(self.shards):
                off = self._slot_off(i)
                meta = _META.unpack(self._mm[off:off + _META.size])
                if meta[1] == pid and meta[0]:
                    self.mark_dead(i)
                    died.append(i)
        return died

    def close(self) -> None:
        try:
            self._mm.close()
        except (BufferError, ValueError):
            pass


# -- the stripe/publish loop (runs inside each shard's event loop) -----


async def run_stripe_loop(ctx: ShardContext, controller, *,
                          blob_fn: Optional[Callable[[], dict]] = None,
                          interval: Optional[float] = None) -> None:
    """Periodic tick per shard: publish my counters + blob into the
    segment, then re-tune my admission stripe from the fleet's demand.
    Cancelled at shutdown; marks the slot dead on the way out."""
    if interval is None:
        try:
            interval = float(os.environ.get(REBALANCE_ENV, "")
                             or DEFAULT_REBALANCE_S)
        except (TypeError, ValueError):
            interval = DEFAULT_REBALANCE_S
        interval = max(0.05, interval)
    try:
        while True:
            stripe_tick(ctx, controller, blob_fn=blob_fn)
            await asyncio.sleep(interval)
    except asyncio.CancelledError:
        ctx.mark_dead()
        raise


def stripe_tick(ctx: ShardContext, controller, *,
                blob_fn: Optional[Callable[[], dict]] = None) -> None:
    """One synchronous publish+rebalance step (separated from the loop
    so tests can drive ticks deterministically)."""
    blob = {"health": controller.health()}
    if blob_fn is not None:
        try:
            blob.update(blob_fn() or {})
        except Exception:
            log.exception("shard %d blob_fn failed", ctx.index)
    ctx.touch(demand=controller.demand, shed=controller.sheds,
              inversions=controller.inversions,
              requests=controller.demand,
              stripe_share=controller.stripe_share)
    ctx.write_blob(blob)
    if ctx.shards > 1:
        ctx.rebuild_routes()
        controller.apply_stripe(ctx.compute_share())


# -- the fork runner ----------------------------------------------------


def run_sharded(ctx: ShardContext,
                child_main: Callable[[ShardContext], None]) -> None:
    """Fork the fleet and run ``child_main(ctx)`` in every shard.

    MUST be called before any event loop exists in this process (the
    children inherit the parent's fds; a pre-fork epoll fd would be
    shared — weedlint's fork-then-asyncio rule enforces the ordering).
    The parent IS shard 0: it serves traffic like any other shard and
    doubles as the supervisor (reap_children is driven from its stripe
    loop caller).  When shard 0 exits, the children are terminated —
    systemd/k8s restart semantics stay one-process-shaped.
    """
    pids: List[int] = []
    for i in range(1, ctx.shards):
        pid = os.fork()
        if pid == 0:
            ctx.index = i
            ctx.child_pids = []
            code = 0
            try:
                child_main(ctx)
            except KeyboardInterrupt:
                pass
            except BaseException:
                # os._exit below skips the interpreter's own traceback:
                # a shard that cannot boot must say why
                log.exception("shard %d died", i)
                code = 1
            finally:
                ctx.mark_dead()
                os._exit(code)
        pids.append(pid)
    ctx.index = 0
    ctx.child_pids = pids
    if pids:
        log.info("sharded fleet: %d shards (children %s)",
                 ctx.shards, pids)
        # default SIGTERM disposition would kill shard 0 without
        # unwinding — the children would outlive the fleet.  Raise
        # instead so the finally below terminates them (one-process
        # shutdown semantics for systemd/k8s).
        signal.signal(signal.SIGTERM,
                      lambda *_: (_ for _ in ()).throw(SystemExit(0)))
    try:
        child_main(ctx)
    finally:
        ctx.mark_dead()
        for pid in pids:
            try:
                os.kill(pid, signal.SIGTERM)
            except ProcessLookupError:
                pass
        deadline = time.time() + 5.0
        for pid in pids:
            while time.time() < deadline:
                try:
                    done, _ = os.waitpid(pid, os.WNOHANG)
                except ChildProcessError:
                    break
                if done:
                    break
                time.sleep(0.05)
            else:
                try:
                    os.kill(pid, signal.SIGKILL)
                    os.waitpid(pid, 0)
                except (ProcessLookupError, ChildProcessError):
                    pass
