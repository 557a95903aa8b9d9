"""Always-on continuous profiler + the on-demand cProfile surface.

Two complementary profiling modes, one module (the orphaned
``utils/profiling.py`` is consolidated here — one profiling surface, no
duplicate entry points):

* **Continuous sampling profiler** (Google-Wide-Profiling posture): a
  daemon thread walks ``sys._current_frames()`` at ``WEED_PROFILE_HZ``
  (default 19 — a prime, so the sampler can't phase-lock with periodic
  work) and folds every thread's stack into a bounded per-process
  aggregate.  Samples landing on a thread that is executing a request
  are tagged with that request's priority class and trace id (the trace
  middleware and the fastpath listeners tag the serving thread for the
  request's lifetime — attribution is approximate under asyncio
  interleaving: a sample is credited to the most recently entered
  in-flight request of the thread, which is exactly the request whose
  handler code is on-CPU unless it awaited).  Served at ``/debug/pprof``
  as collapsed-stack text (``format=collapsed``, flamegraph.pl/speedscope
  ingestible) or flamegraph JSON (``format=flame``); the
  ``cluster.profile`` shell command fetches and merges across nodes.

* **Windowed cProfile** (the net/http/pprof analog the reference routes
  through grace.SetupProfiling): ``setup_cpu_profile(path)`` for the
  ``-cpuprofile`` server flag, and ``profile_handler()`` serving
  ``/debug/profile?seconds=N`` as pstats text.

The sampler is cheap by construction: at 19Hz it acquires the GIL ~19
times a second to snapshot frames — measured well under 1% of one core —
so it runs always-on in every server (disable with ``WEED_PROFILE=0``).
"""

from __future__ import annotations

import atexit
import contextlib
import cProfile
import io
import os
import pstats
import re
import sys
import threading
import time
from typing import Optional

# --- knobs -------------------------------------------------------------


def _hz() -> float:
    """WEED_PROFILE_HZ, malformed/absurd values fall back (a config typo
    must not stop every server from importing)."""
    try:
        hz = float(os.environ.get("WEED_PROFILE_HZ", "19"))
    except ValueError:
        return 19.0
    return hz if 0 < hz <= 1000 else 19.0


def _max_stacks() -> int:
    try:
        n = int(os.environ.get("WEED_PROFILE_MAX_STACKS", "20000"))
    except ValueError:
        return 20000
    return n if n > 0 else 20000


def enabled_by_env() -> bool:
    return os.environ.get("WEED_PROFILE", "1") not in ("0", "false", "")


# stack depth cap: deep recursion must not make one sample unbounded
_MAX_DEPTH = 64

# --- request tagging ---------------------------------------------------
# thread id -> (priority class, trace id) for the request currently
# executing on that thread.  Written by the trace middleware / fastpath
# listeners (one dict write per request), read by the sampler thread.
_request_tags: dict[int, tuple[str, str]] = {}


@contextlib.contextmanager
def request_tag(cls: str, trace_id: str):
    """Tag the current thread's samples with (class, trace) for the
    duration of the block.  Exit only clears the tag if it is still ours
    — under asyncio interleaving a newer request may have re-tagged the
    thread, and popping its tag would mis-attribute ITS samples."""
    if _profiler is None:
        yield
        return
    tid = threading.get_ident()
    tag = (cls, trace_id)
    _request_tags[tid] = tag
    try:
        yield
    finally:
        if _request_tags.get(tid) is tag:
            _request_tags.pop(tid, None)


# --- the sampling profiler --------------------------------------------


class SamplingProfiler:
    """Fold sys._current_frames() snapshots into per-(class, stack)
    counts.  All mutation happens under one lock; readers snapshot under
    the same lock (the span-ring discipline — a concurrent sample during
    /debug/pprof serialization must not interleave)."""

    def __init__(self, hz: Optional[float] = None,
                 max_stacks: Optional[int] = None):
        self.hz = hz if hz else _hz()
        self.max_stacks = max_stacks if max_stacks else _max_stacks()
        self._lock = threading.Lock()
        # (cls, stack tuple) -> [count, last trace id seen]
        self._stacks: dict[tuple, list] = {}
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.samples = 0
        self.dropped = 0          # distinct-stack cap overflow
        self.started_at = 0.0

    # -- lifecycle --

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self.started_at = time.time()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="weed-profiler")
        self._thread.start()

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout=2.0)
        self._thread = None

    @property
    def running(self) -> bool:
        return self._thread is not None

    def _run(self) -> None:
        period = 1.0 / self.hz
        me = threading.get_ident()
        while not self._stop.wait(period):
            try:
                self._sample(me)
            except Exception:
                # the profiler must never take a server down
                pass

    # -- sampling --

    def _sample(self, own_tid: int) -> None:
        frames = sys._current_frames()
        now = self.samples
        folded = []
        for tid, frame in frames.items():
            if tid == own_tid:
                continue
            stack = []
            f = frame
            while f is not None and len(stack) < _MAX_DEPTH:
                code = f.f_code
                stack.append(getattr(code, "co_qualname", code.co_name))
                f = f.f_back
            stack.reverse()     # root-first, collapsed-stack order
            cls, trace = _request_tags.get(tid, ("idle", ""))
            folded.append(((cls, tuple(stack)), trace))
        del frames  # drop frame refs before taking the lock
        with self._lock:
            self.samples = now + 1
            for key, trace in folded:
                ent = self._stacks.get(key)
                if ent is not None:
                    ent[0] += 1
                    if trace:
                        ent[1] = trace
                elif len(self._stacks) < self.max_stacks:
                    self._stacks[key] = [1, trace]
                else:
                    self.dropped += 1

    # -- reads (snapshot under the lock, format outside it) --

    def _snapshot_stacks(self) -> list[tuple[str, tuple, int, str]]:
        with self._lock:
            return [(cls, stack, ent[0], ent[1])
                    for (cls, stack), ent in self._stacks.items()]

    def collapsed(self, cls_filter: str = "") -> str:
        """Collapsed-stack text: ``class;frame;frame... count`` per line,
        hottest first (flamegraph.pl / speedscope / inferno input)."""
        rows = self._snapshot_stacks()
        if cls_filter:
            rows = [r for r in rows if r[0] == cls_filter]
        rows.sort(key=lambda r: -r[2])
        return "\n".join(f"{cls};{';'.join(stack)} {count}"
                         for cls, stack, count, _ in rows) + \
            ("\n" if rows else "")

    def flame(self, cls_filter: str = "") -> dict:
        """Fold the aggregate into d3-flame-graph JSON: nested
        {name, value, children}, each class a top-level child so one
        graph separates fg/bg/system/idle time."""
        root = {"name": "all", "value": 0, "children": {}}
        for cls, stack, count, trace in self._snapshot_stacks():
            if cls_filter and cls != cls_filter:
                continue
            root["value"] += count
            node = root
            for frame in (cls,) + stack:
                child = node["children"].get(frame)
                if child is None:
                    child = {"name": frame, "value": 0, "children": {}}
                    node["children"][frame] = child
                child["value"] += count
                node = child
            if trace:
                node["trace"] = trace    # leaf: last trace seen here

        def _freeze(node: dict) -> dict:
            out = {"name": node["name"], "value": node["value"]}
            if "trace" in node:
                out["trace"] = node["trace"]
            kids = sorted(node["children"].values(),
                          key=lambda n: -n["value"])
            if kids:
                out["children"] = [_freeze(k) for k in kids]
            return out

        return _freeze(root)

    def stats(self) -> dict:
        with self._lock:
            by_cls: dict[str, int] = {}
            for (cls, _), ent in self._stacks.items():
                by_cls[cls] = by_cls.get(cls, 0) + ent[0]
            return {"hz": self.hz, "samples": self.samples,
                    "distinct_stacks": len(self._stacks),
                    "dropped_stacks": self.dropped,
                    "samples_by_class": by_cls,
                    "uptime_s": round(time.time() - self.started_at, 1)
                    if self.started_at else 0.0}

    def reset(self) -> None:
        with self._lock:
            self._stacks.clear()
            self.samples = 0
            self.dropped = 0


# --- process-wide singleton -------------------------------------------

_profiler: Optional[SamplingProfiler] = None
_profiler_lock = threading.Lock()


def ensure_started() -> Optional[SamplingProfiler]:
    """Start (once) and return the process profiler; None when disabled
    via WEED_PROFILE=0.  Every server calls this at startup — combined
    servers and in-process test clusters share one sampler."""
    global _profiler
    if not enabled_by_env():
        return None
    with _profiler_lock:
        if _profiler is None:
            _profiler = SamplingProfiler()
            _profiler.start()
        elif not _profiler.running:
            _profiler.start()
        return _profiler


def active() -> Optional[SamplingProfiler]:
    return _profiler


def shutdown() -> None:
    """Stop and drop the process profiler (tests)."""
    global _profiler
    with _profiler_lock:
        if _profiler is not None:
            _profiler.stop()
            _profiler = None
    _request_tags.clear()


def pprof_handler():
    """aiohttp handler for GET /debug/pprof[?format=&class=].

    Default: collapsed-stack text of the always-on aggregate.
    ``format=flame``: d3-flame-graph JSON.  ``format=stats``: sampler
    meta (rate, sample counts per class).  ``class=fg|bg|system|idle``
    filters to one priority class."""
    from aiohttp import web

    async def handler(request: web.Request) -> web.Response:
        prof = active() or ensure_started()
        if prof is None:
            return web.json_response(
                {"error": "profiler disabled (WEED_PROFILE=0)"},
                status=503)
        fmt = request.query.get("format", "collapsed")
        cls = request.query.get("class", "")
        if fmt == "flame":
            return web.json_response(prof.flame(cls))
        if fmt == "stats":
            return web.json_response(prof.stats())
        return web.Response(text=prof.collapsed(cls),
                            content_type="text/plain")

    return handler


# --- windowed cProfile (role of weed/util/grace/pprof.go +
# net/http/pprof; formerly utils/profiling.py) -------------------------

_active: Optional[cProfile.Profile] = None


def setup_cpu_profile(path: str) -> None:
    """Start profiling the whole process; write pstats to `path` at exit
    (grace.SetupProfiling, weed/util/grace/pprof.go:11)."""
    global _active
    if not path or _active is not None:
        return
    prof = cProfile.Profile()
    prof.enable()
    _active = prof

    def dump() -> None:
        prof.disable()
        prof.dump_stats(path)

    atexit.register(dump)


def profile_handler():
    """aiohttp handler: GET /debug/profile?seconds=5 returns pstats text
    for that window (net/http/pprof's /debug/pprof/profile analog).
    cProfile allows one active profiler per process, so the endpoint
    answers 409 while -cpuprofile or another window is running."""
    import asyncio

    from aiohttp import web

    busy = threading.Lock()

    async def handler(request: web.Request) -> web.Response:
        if _active is not None:
            return web.Response(
                status=409,
                text="process-wide -cpuprofile is active; "
                     "only one profiler can run at a time\n")
        if not busy.acquire(blocking=False):
            return web.Response(status=409,
                                text="another profile window is running\n")
        try:
            seconds = min(float(request.query.get("seconds", 5)), 60.0)
            prof = cProfile.Profile()
            prof.enable()
            await asyncio.sleep(seconds)
            prof.disable()
        finally:
            busy.release()
        out = io.StringIO()
        stats = pstats.Stats(prof, stream=out)
        stats.sort_stats("cumulative").print_stats(60)
        return web.Response(text=out.getvalue(),
                            content_type="text/plain")

    return handler


# --- the device profiler (jax.profiler): stage annotations and the
# operator's windowed device trace. The one place that imports it. -----

_annotation_cls = None


def trace_annotation(name: str):
    """While a device profiler session is open: an entered
    jax.profiler.TraceAnnotation, a host event named `name` on the
    trace's own clock that lasts until the caller's `__exit__` of it
    (observe.stage holds one per stage). With no session open, None,
    for the price of one flag test in the profiler; in a process that
    never imported JAX no session can be open, and JAX is not imported
    for it."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        if "jax" not in sys.modules:
            return None
        try:
            import jax.profiler
        except ImportError:
            return None
        cls = _annotation_cls = jax.profiler.TraceAnnotation
    if not cls.is_enabled():
        return None
    note = cls(name)
    note.__enter__()
    return note


XPROF_MAX_SECONDS = 60.0
_DEVICE_PLANE = "/device:"
_OPS_LINE = "XLA Ops"
_HOST_PLANE = "/host:CPU"
_HLO_OP = re.compile(r"%?([\w.\-]+) = ([^{ ]+)")


def _merged_seconds(spans: list[tuple[float, float]]) -> float:
    """Nanosecond intervals -> seconds covered by any of them."""
    total, end = 0.0, float("-inf")
    for lo, hi in sorted(spans):
        if hi > end:
            total += hi - max(lo, end)
            end = hi
    return total / 1e9


def summarize_xplane(path: str) -> dict:
    """What one recorded window says, from the trace alone: the
    session's length, the seconds in which any operation ran on a device
    (the union over its `XLA Ops` line, averaged over the devices that
    ran any), each device operation and each `ec.*` stage with its count
    and seconds (a name's overlapping repeats on one thread counted
    once)."""
    from jax.profiler import ProfileData
    start = stop = None
    busy: list[float] = []
    ops: dict[str, list] = {}
    stages: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                start = float(value)
            elif key == "profile_stop_time":
                stop = float(value)
        if plane.name.startswith(_DEVICE_PLANE):
            spans = []
            for line in plane.lines:
                if line.name != _OPS_LINE:
                    continue
                for e in line.events:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns))
                    # `%gf_apply.1 = u8[1,16384]{..} custom-call(..)` ->
                    # `gf_apply.1 u8[1,16384]`: the op and its result
                    m = _HLO_OP.match(e.name)
                    rec = ops.setdefault(
                        f"{m.group(1)} {m.group(2)}" if m else e.name[:80],
                        [0, 0.0])
                    rec[0] += 1
                    rec[1] += e.duration_ns / 1e9
            if spans:
                busy.append(_merged_seconds(spans))
        elif plane.name == _HOST_PLANE:
            for line in plane.lines:
                by_name: dict[str, list] = {}
                for e in line.events:
                    if e.name.startswith("ec."):
                        by_name.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
                for name, spans in by_name.items():
                    rec = stages.setdefault(name, [0, 0.0])
                    rec[0] += len(spans)
                    rec[1] += _merged_seconds(spans)
    window = (stop - start) / 1e9 if start is not None \
        and stop is not None else 0.0
    return {"window_s": window,
            "device_busy_s": sum(busy) / len(busy) if busy else 0.0,
            "devices_traced": len(busy),
            "device_ops": dict(sorted(ops.items(),
                                      key=lambda kv: -kv[1][1])),
            "stages": dict(sorted(stages.items()))}


def xprof_handler(on_device):
    """aiohttp handler: GET /debug/xprof?seconds=N[&keep=1] puts a
    jax.profiler session (python tracer off, host tracer level 2: what
    benchmark/run.py traces with) around the next N seconds of the live
    server and answers what summarize_xplane reads from it; keep=1
    leaves the trace directory and returns its path. `on_device()` says
    whether this server computes on an accelerator: where it does not
    there is no device to trace, 501. One session a process: 409 while
    a window is open here or another owner holds the profiler."""
    import asyncio
    import glob
    import shutil
    import tempfile

    from aiohttp import web

    busy = threading.Lock()

    async def handler(request: web.Request) -> web.Response:
        if not on_device():
            return web.json_response(
                {"error": "this server computes on no accelerator: "
                          "there is no device to trace"}, status=501)
        if not busy.acquire(blocking=False):
            return web.json_response(
                {"error": "another device trace window is open"},
                status=409)
        trace_dir = ""
        kept = False
        try:
            try:
                seconds = min(max(float(request.query.get("seconds", 5)),
                                  0.0), XPROF_MAX_SECONDS)
            except ValueError:
                return web.json_response({"error": "seconds: a number"},
                                         status=400)
            import jax.profiler
            trace_dir = tempfile.mkdtemp(prefix="weed-xprof.")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            loop = asyncio.get_event_loop()
            try:
                # opening a session takes a while too: off the loop
                await loop.run_in_executor(
                    None, lambda: jax.profiler.start_trace(
                        trace_dir, profiler_options=opts))
            except RuntimeError as e:
                # a session somebody else opened (a benchmark harness
                # that hosts this server, a debugger)
                return web.json_response(
                    {"error": f"the profiler is held elsewhere: {e}"},
                    status=409)
            try:
                await asyncio.sleep(seconds)
            finally:
                # so does writing the trace out
                await loop.run_in_executor(None, jax.profiler.stop_trace)
            found = sorted(glob.glob(os.path.join(
                trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
            if not found:
                return web.json_response(
                    {"error": "the profiler wrote no trace"}, status=500)
            out = await loop.run_in_executor(
                None, summarize_xplane, found[-1])
            if request.query.get("keep", "") in ("1", "true"):
                out["trace_dir"] = trace_dir
                kept = True
            return web.json_response(out)
        finally:
            busy.release()
            if trace_dir and not kept:
                shutil.rmtree(trace_dir, ignore_errors=True)

    return handler
