"""Streaming EC pipeline: disk -> host buffer -> HBM -> kernel -> shard files.

The naive encode loop (striping.write_ec_files) is the reference shape —
synchronous 256KB batches (weed/storage/erasure_coding/ec_encoder.go:162-231).
It leaves the chip idle while the host reads and writes. This module is the
production path: multi-MB batches with disk read, host->HBM transfer, kernel,
and shard write-back all overlapped.

Stages (bounded queues between them; every file gets its own writer thread so
shard write-back parallelizes across the 14 files):

  reader thread   -- assemble [k, B] uint8 batches through the zero-copy
                     host feed (ec/feed.py): mmap'd page-cache views where
                     the stripe allows, pooled double-buffered staging
                     otherwise (preadv fallback when mmap is unavailable),
                     push to a depth-bounded queue
  main thread     -- pop a batch, dispatch coder.encode_async (device_put +
                     jitted kernel; JAX dispatch is asynchronous so this
                     returns immediately with computation in flight)
  materializer    -- block on the parity handle (only this thread waits on
                     the device), then fan rows out to the per-file queues;
                     data rows go straight from the host buffer — data shards
                     never round-trip through the device
  k+m writers     -- one thread per shard file, coalescing queued rows into
                     single writev appends

Batch size and queue depths default to the adaptive governor's operating
point (ec/governor.py), tuned from the per-stage observe spans this module
emits. Explicit arguments pin the plan. Only parity bytes (m/k of the
input) cross device->host. Layout semantics are identical to
striping.write_ec_files: row-major two-tier striping, final batch
zero-padded and written full-length (tests assert byte-identical output
between the two paths).
"""

from __future__ import annotations

import contextlib
import os
import queue
import threading
import time
from typing import Iterator, Optional, Sequence

import numpy as np

from .. import observe
from ..utils import durable
from ..utils import metrics as metrics_mod
from . import feed as feed_mod
from . import governor
from .coder import ErasureCoder
from .geometry import DEFAULT, Geometry, to_ext
from .striping import stripe_segments

# fallback operating point when the governor is bypassed (explicit args):
# 8MB per shard-row batch = 80MB host buffer per in-flight batch at RS(10,4)
DEFAULT_BATCH_SIZE = 8 * 1024 * 1024
DEFAULT_DEPTH = 4

_SENTINEL = None


def _resolve_op(batch_size: Optional[int], depth: Optional[int],
                nbytes: int, k: int,
                chips: int = 1) -> tuple["governor.OperatingPoint",
                                         bool]:
    """(operating point, governed?) — explicit args pin the plan and opt
    the run out of the governor entirely: no retuning from this run's
    shapes AND no export of a plan the run isn't using (tests and
    benches must neither steer nor misreport the process-global
    operating point). `chips` is the coder's mesh width — the governor
    scales the batch with it before deepening queues."""
    if batch_size is None and depth is None:
        return governor.get().plan(nbytes, k, chips=chips), True
    b = batch_size if batch_size is not None else DEFAULT_BATCH_SIZE
    d = depth if depth is not None else DEFAULT_DEPTH
    return governor.OperatingPoint(b, d, d,
                                   feed_mod.reader_count_default(),
                                   max(chips, 1)), False


def coder_chips(coder: ErasureCoder) -> int:
    """The device-mesh width a coder spreads each batch over (1 for
    every single-chip backend; parallel/mesh_coder.MeshCoder exports
    mesh_devices)."""
    return int(getattr(coder, "mesh_devices", 1) or 1)


class _FanOut:
    """One writer thread per output file, each with a bounded row queue;
    writers drain their queue greedily and append every waiting row in
    ONE os.writev call (straight from the row memory — no userspace
    write buffer, no per-row syscall)."""

    MAX_COALESCE = 16  # rows per writev: bounds latency and iov count

    def __init__(self, paths: Sequence[str], depth: int):
        self.queues = [queue.Queue(maxsize=depth) for _ in paths]
        self.errors: list[BaseException] = []
        self.threads = []
        for q, path in zip(self.queues, paths):
            th = threading.Thread(target=self._writer, args=(q, path),
                                  daemon=True)
            th.start()
            self.threads.append(th)

    @staticmethod
    def _writev_all(fd: int, rows: list) -> None:
        bufs = [memoryview(r) for r in rows]
        while bufs:
            n = os.writev(fd, bufs)
            if n <= 0:
                raise IOError("writev wrote nothing")
            while bufs and n >= bufs[0].nbytes:
                n -= bufs[0].nbytes
                bufs.pop(0)
            if n:
                bufs[0] = bufs[0][n:]

    def _writer(self, q: queue.Queue, path: str) -> None:
        batch: list = []
        stop = False  # close()'s sentinel already consumed
        try:
            fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
            try:
                while True:
                    item = q.get()
                    if item is _SENTINEL:
                        # sync before the .ecm marker commits the set:
                        # shards a power loss can drop must not be
                        # reachable from a durable marker
                        os.fsync(fd)
                        return
                    batch = [item]
                    while len(batch) < self.MAX_COALESCE and not q.empty():
                        nxt = q.get_nowait()
                        if nxt is _SENTINEL:
                            stop = True
                            break
                        batch.append(nxt)
                    self._writev_all(fd, [row for row, _ in batch])
                    for _, cb in batch:
                        if cb is not None:
                            cb()
                    batch = []
                    if stop:
                        os.fsync(fd)
                        return
            finally:
                os.close(fd)
        except BaseException as e:
            self.errors.append(e)
            # the coalesced rows already popped when the write failed
            # still need their callbacks: each belongs to a different
            # put_rows batch, and a skipped callback strands that
            # batch's pooled staging buffer for the rest of the run
            for _, cb in batch:
                if cb is not None:
                    cb()
            while not stop:  # drain (unless the sentinel was already
                item = q.get()  # swallowed mid-coalesce); never
                if item is _SENTINEL:  # deadlock the producer
                    return
                _, cb = item
                if cb is not None:
                    cb()  # keep buffers recycling on the error path

    def put_rows(self, rows: Iterator[np.ndarray],
                 on_done=None) -> None:
        """Enqueue one batch's rows; on_done fires once after EVERY row
        of this call has been handed to the kernel (the host batch may be
        a pooled staging buffer that must not be reused earlier)."""
        rows = [np.ascontiguousarray(r) for r in rows]
        cb = None
        if on_done is not None:
            state = {"left": len(rows)}
            lock = threading.Lock()

            def cb() -> None:
                with lock:
                    state["left"] -= 1
                    done = state["left"] == 0
                if done:
                    on_done()

        for q, row in zip(self.queues, rows):
            q.put((row, cb))

    def close(self) -> None:
        for q in self.queues:
            q.put(_SENTINEL)
        for th in self.threads:
            th.join()


def _traced_batches(batches: Iterator[np.ndarray],
                    ctx: "observe.TraceCtx") -> Iterator[np.ndarray]:
    """Wrap the read stage with one ec.read span per batch (runs in the
    reader thread, so spans use the explicit captured context). Manual
    record_span rather than observe.stage: the final next() pull only
    learns it was the sentinel after the timing window closes, and that
    empty pull must not record a span."""
    import time as time_mod
    it = iter(batches)
    i = 0
    while True:
        start_us = int(time_mod.time() * 1e6)
        t0 = time_mod.perf_counter()
        item = next(it, None)
        if item is None:
            return
        observe.record_span(
            "ec.read", ctx, start_us,
            int((time_mod.perf_counter() - t0) * 1e6),
            tags={"batch": i, "bytes": int(item.nbytes)})
        yield item
        i += 1


def _run_pipeline(batches: Iterator[np.ndarray], dispatch, consume,
                  depth: int,
                  trace_ctx: "observe.TraceCtx | None" = None,
                  recycle=None) -> None:
    """reader thread -> main dispatch -> materializer thread.

    recycle (optional) is called on batches drained without being
    consumed on error paths, so pooled feed buffers keep circulating."""
    read_q: queue.Queue = queue.Queue(maxsize=depth)
    mat_q: queue.Queue = queue.Queue(maxsize=depth)
    errors: list[BaseException] = []

    def _recycle(batch) -> None:
        if recycle is not None:
            try:
                recycle(batch)
            except Exception:
                pass

    def reader_main() -> None:
        try:
            for item in batches:
                read_q.put(item)
        except BaseException as e:
            errors.append(e)
        finally:
            read_q.put(_SENTINEL)

    def mat_main() -> None:
        try:
            while True:
                item = mat_q.get()
                if item is _SENTINEL:
                    return
                consume(*item)
        except BaseException as e:
            errors.append(e)
            while True:
                item = mat_q.get()
                if item is _SENTINEL:
                    return
                _recycle(item[0])

    reader = threading.Thread(target=reader_main, daemon=True)
    mat = threading.Thread(target=mat_main, daemon=True)
    mat.start()
    reader.start()
    drained = False
    batch_i = 0
    try:
        while True:
            batch = read_q.get()
            if batch is _SENTINEL:
                drained = True
                break
            with (observe.stage("ec.dispatch", trace_ctx,
                                tags={"batch": batch_i})
                  if trace_ctx is not None else contextlib.nullcontext()):
                try:
                    handle = dispatch(batch)
                except BaseException:
                    # the in-flight batch is nobody else's to recycle:
                    # the drain below only sees batches still QUEUED, so
                    # a dispatch that dies here would strand this one's
                    # pooled staging buffer lent forever
                    _recycle(batch)
                    raise
            batch_i += 1
            # kick the device->host copy off immediately so it overlaps the
            # next batch's H2D + kernel instead of starting at materialize
            # time (matters most when the transfer link is the bottleneck)
            start_async = getattr(handle, "copy_to_host_async", None)
            if start_async is not None:
                try:
                    start_async()
                except Exception:
                    pass
            mat_q.put((batch, handle))
    finally:
        mat_q.put(_SENTINEL)
        # drain read_q so a reader blocked on a full queue can finish
        # (otherwise a dispatch() exception would deadlock reader.join())
        while not drained:
            item = read_q.get()
            if item is _SENTINEL:
                break
            _recycle(item)
        reader.join()
        mat.join()
    if errors:
        raise errors[0]


def _stream_encode_core(batches: Iterator[np.ndarray], coder: ErasureCoder,
                        shard_paths: Sequence[str],
                        op: "governor.OperatingPoint",
                        tctx: "observe.TraceCtx",
                        recycle=None,
                        digests: "np.ndarray | None" = None) -> None:
    """The encode engine shared by stream_encode and the fused warm-down
    (ec/fused.py): host batches -> async dispatch -> materialize -> one
    writer thread per shard file. Returns with every shard file written
    AND fsynced; writes NO .ecm marker — committing the set is the
    caller's decision (the fused path orders the marker after its own
    .dat/.idx/.ecx finalization).

    `digests` (uint64[total_shards]) accumulates each shard row's
    wrapping byte-sum inline while the rows stream through — the
    scrubber's reference digest comes out of the encode pass itself and
    the host never re-reads the fresh shards to compute it."""
    fan = _FanOut(list(shard_paths), op.write_depth)
    counters = metrics_mod.shared("ec")

    def dispatch(batch: np.ndarray):
        # inside `ec.dispatch`, whose clock this rides: what went to the
        # coder, the last row's zero padding included
        handle = coder.encode_async(batch)
        counters.count("encode_input_bytes", batch.nbytes)
        counters.count("encode_batches")
        return handle

    def consume(data: np.ndarray, handle) -> None:
        with observe.stage("ec.kernel", tctx):
            parity = coder.materialize(handle)
        rows = [*data, *parity]
        if digests is not None:
            with observe.stage("ec.digest", tctx):
                for i, row in enumerate(rows):
                    digests[i] += np.sum(row, dtype=np.uint64)
        with observe.stage("ec.write", tctx):
            # data rows are written straight from the host batch (a
            # page-cache view or a pooled staging buffer); the buffer
            # recycles only after every row has been handed off
            cb = None
            if recycle is not None:
                cb = (lambda b=data: recycle(b))
            fan.put_rows(iter(rows), on_done=cb)

    try:
        _run_pipeline(
            _traced_batches(batches, tctx),
            dispatch, consume, op.depth, trace_ctx=tctx,
            recycle=recycle)
    finally:
        # what is still queued, then each shard file's fsync and close
        with observe.stage("ec.fsync", tctx):
            fan.close()
    if fan.errors:
        raise fan.errors[0]


def stream_encode(base_file_name: str, coder: ErasureCoder,
                  geometry: Geometry = DEFAULT,
                  batch_size: Optional[int] = None,
                  depth: Optional[int] = None,
                  _op: "governor.OperatingPoint | None" = None) -> None:
    """Encode <base>.dat into shard files with the overlapped pipeline.

    Byte-identical output to striping.write_ec_files (WriteEcFiles,
    ec_encoder.go:57) — only the schedule differs. batch_size/depth
    default to the adaptive governor's operating point; passing them
    explicitly pins the schedule and skips retuning. _op pins a full
    operating point (stream_encode_many shares one across a window and
    does the window-level finish_run itself).
    """
    g = geometry
    assert coder.k == g.data_shards and coder.m == g.parity_shards
    run_t0 = time.perf_counter()
    dat_size = os.path.getsize(base_file_name + ".dat")
    if _op is not None:
        op, governed = _op, False
    else:
        op, governed = _resolve_op(batch_size, depth, dat_size,
                                   g.data_shards, coder_chips(coder))
    src = feed_mod.open_feed(base_file_name + ".dat", g.data_shards,
                             op.batch_size, pool_buffers=op.depth + 2,
                             readers=op.readers)
    # per-stage spans share the caller's trace (volume server passes its
    # request context into this thread via observe.run_with); a fresh
    # root is minted when none is active (CLI/bench encodes)
    tctx = observe.ensure_ctx("ec")
    digests = np.zeros(g.total_shards, dtype=np.uint64)
    try:
        _stream_encode_core(
            src.batches(stripe_segments(dat_size, g, op.batch_size)),
            coder, [base_file_name + to_ext(i)
                    for i in range(g.total_shards)],
            op, tctx, recycle=src.recycle, digests=digests)
    finally:
        src.close()
    from .striping import write_layout_marker
    write_layout_marker(base_file_name, dat_size, g,
                        shard_digests={i: int(digests[i]) & 0xFFFFFFFF
                                       for i in range(g.total_shards)})
    if governed:
        governor.get().finish_run(tctx.trace_id, op, dat_size,
                                  g.data_shards)
    # chip-side runs report through the same wide-event plane as serving
    # requests, so cluster.tail attributes encode time by stage too
    from ..observe import wideevents
    wideevents.emit_stages(
        "ec", f"ec.encode {os.path.basename(base_file_name)}",
        tctx.trace_id, int((time.perf_counter() - run_t0) * 1e6),
        observe.stage_totals(tctx.trace_id, prefix="ec."))


def stream_encode_many(base_file_names: Sequence[str], coder: ErasureCoder,
                       geometry: Geometry = DEFAULT,
                       batch_size: Optional[int] = None,
                       depth: Optional[int] = None) -> int:
    """Encode N volumes back-to-back through ONE governed operating
    point — the encode-queue regime (lifecycle daemon batches, `ec.encode`
    multi-volume plans). The operating point is planned once for the
    whole window, so every volume feeds the same [k, B] batch shape and
    the coder's jit cache serves ONE executable for all of them (no
    per-volume recompiles, no per-volume program loads); the governor
    retunes once from the window's aggregate read/h2d/kernel/write
    spans. Returns the number of volumes encoded."""
    g = geometry
    bases = [b for b in base_file_names]
    if not bases:
        return 0
    total = sum(os.path.getsize(b + ".dat") for b in bases)
    op, governed = _resolve_op(batch_size, depth, total, g.data_shards,
                               coder_chips(coder))
    tctx = observe.ensure_ctx("ec")
    for base in bases:
        with observe.stage("ec.volume", tctx, tags={"base": base}):
            observe.run_with(tctx, stream_encode, base, coder, g,
                             _op=op)
    if governed:
        governor.get().finish_run(tctx.trace_id, op, total, g.data_shards)
    return len(bases)


def shard_file_digest(base_file_name: str,
                      shard_ids: Sequence[int]) -> np.ndarray:
    """[len(ids)] uint32 wrapping byte-sum of each shard file — what the
    .ecm sidecar stamps and the EC scrubber re-computes. Accumulates in
    uint64 and masks once at the end: explicit wrapping arithmetic, no
    overflow warnings (a full uint64 holds > 2^56 bytes of sum)."""
    out = []
    for i in shard_ids:
        total = np.uint64(0)
        with open(base_file_name + to_ext(i), "rb") as f:
            while True:
                chunk = f.read(1 << 24)
                if not chunk:
                    break
                total += np.sum(np.frombuffer(chunk, dtype=np.uint8),
                                dtype=np.uint64)
        out.append(int(total) & 0xFFFFFFFF)
    return np.asarray(out, dtype=np.uint32)


def read_stamped_digests(base_file_name: str) -> dict[int, int]:
    """shard id -> stamped uint32 byte-sum digest from the .ecm sidecar
    ({} when the marker is absent or carries no digests)."""
    import json as json_mod
    try:
        with open(base_file_name + ".ecm") as f:
            meta = json_mod.load(f)
    except (OSError, ValueError):
        return {}
    return {int(k): int(v)
            for k, v in (meta.get("shard_digests") or {}).items()}


def stamp_shard_digests(base_file_name: str,
                        geometry: Geometry = DEFAULT) -> dict[int, int]:
    """Record each local shard file's digest into the .ecm sidecar — the
    reference the EC scrubber verifies against. Merge-only: a shard id
    already stamped keeps its original value (recomputing over a shard
    that has since rotted would launder the corruption into the record),
    so the truth is established exactly once, at encode/rebuild time
    when the bytes are known-good. No-op without an existing marker: a
    digests-only .ecm would fail the layout-version check at mount."""
    import json as json_mod
    path = base_file_name + ".ecm"
    try:
        with open(path) as f:
            meta = json_mod.load(f)
    except (OSError, ValueError):
        return {}
    digests = {int(k): int(v)
               for k, v in (meta.get("shard_digests") or {}).items()}
    recomputed = 0
    for sid in range(geometry.total_shards):
        if sid in digests or not os.path.exists(
                base_file_name + to_ext(sid)):
            continue
        digests[sid] = int(shard_file_digest(base_file_name, [sid])[0])
        recomputed += 1
    if recomputed:
        # encode passes that stamp digests inline (stream_encode, the
        # fused warm-down) leave nothing to recompute; this counter is
        # how the bench proves "scrubber re-digest count 0"
        metrics_mod.shared("ec").count("ec_digest_host_recompute",
                                       recomputed)
    meta["shard_digests"] = {str(k): v
                             for k, v in sorted(digests.items())}
    durable.write_json_atomic(path, meta)
    return digests


def stream_rebuild(base_file_name: str, coder: ErasureCoder,
                   geometry: Geometry = DEFAULT,
                   batch_size: Optional[int] = None,
                   depth: Optional[int] = None) -> list[int]:
    """Regenerate missing shard files from k survivors, overlapped
    (RebuildEcFiles, ec_encoder.go:233-287 — but with multi-MB strides and
    read/compute/write overlap instead of synchronous 1MB loops).
    Returns the rebuilt shard ids. Runs on the same zero-copy feed and
    governed operating point as stream_encode.
    """
    g = geometry
    run_t0 = time.perf_counter()
    present = [i for i in range(g.total_shards)
               if os.path.exists(base_file_name + to_ext(i))]
    missing = [i for i in range(g.total_shards) if i not in present]
    if not missing:
        return []
    if len(present) < g.data_shards:
        raise ValueError(
            f"need {g.data_shards} shards to rebuild, have {len(present)}")
    survivors_ids = tuple(present[:g.data_shards])
    shard_size = os.path.getsize(base_file_name + to_ext(survivors_ids[0]))
    op, governed = _resolve_op(batch_size, depth,
                               g.data_shards * shard_size, g.data_shards,
                               coder_chips(coder))
    fn = coder.rec_apply_async(survivors_ids, tuple(missing))
    src = feed_mod.ShardFeed(
        [base_file_name + to_ext(i) for i in survivors_ids],
        op.batch_size, pool_buffers=op.depth + 2, readers=op.readers)
    fan = _FanOut([base_file_name + to_ext(i) for i in missing],
                  op.write_depth)
    tctx = observe.ensure_ctx("ec")

    def consume(survivors: np.ndarray, handle) -> None:
        with observe.stage("ec.kernel", tctx):
            rebuilt = coder.materialize(handle)
        # the kernel has consumed the survivor batch: recycle it now —
        # the rebuilt rows fanned out below are device-materialized
        # arrays, not views of the staging buffer
        src.recycle(survivors)
        with observe.stage("ec.write", tctx):
            fan.put_rows(iter(rebuilt))

    try:
        _run_pipeline(
            _traced_batches(src.batches(op.batch_size), tctx), fn,
            consume, op.depth, trace_ctx=tctx, recycle=src.recycle)
    finally:
        with observe.stage("ec.fsync", tctx):
            fan.close()
        src.close()
    if fan.errors:
        raise fan.errors[0]
    if governed:
        governor.get().finish_run(tctx.trace_id, op,
                                  g.data_shards * shard_size,
                                  g.data_shards)
    from ..observe import wideevents
    wideevents.emit_stages(
        "ec", f"ec.rebuild {os.path.basename(base_file_name)}",
        tctx.trace_id, int((time.perf_counter() - run_t0) * 1e6),
        observe.stage_totals(tctx.trace_id, prefix="ec."))
    return missing
