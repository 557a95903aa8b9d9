"""Zero-copy feed (ec/feed.py) — equivalence and mechanics.

The mmap and preadv feeds replace the pread-into-buffer host assembly;
the only acceptable difference is speed. These tests pin that: encoding
the SAME odd-sized (non-divisible) .dat through striping.write_ec_files
and through the pipeline on each feed must produce byte-identical
.ec00-.ec13, the two feeds must agree batch-for-batch, and pooled
buffers must actually recycle (bounded memory) without corrupting
batches still in flight.
"""

import hashlib
import os

import numpy as np
import pytest

from seaweedfs_tpu import ec
from seaweedfs_tpu.ec import feed as feed_mod
from seaweedfs_tpu.ec import pipeline
from seaweedfs_tpu.ec.striping import stripe_segments

GEO = ec.Geometry(data_shards=10, parity_shards=4,
                  large_block_size=10000, small_block_size=100)

# odd: not divisible by batch widths, small blocks, rows, or each other —
# exercises mid-stream flushes, the strided zero-copy path, EOF zero-fill
# and the padded final large row
ODD_SIZES = [99_001, 30_553, 100_001, 7]


def _write_dat(tmp_path, name: str, size: int, seed: int) -> str:
    rng = np.random.default_rng(seed)
    base = os.path.join(str(tmp_path), name)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
    return base


def _sha(path: str) -> str:
    return hashlib.sha256(open(path, "rb").read()).hexdigest()


@pytest.mark.parametrize("size", ODD_SIZES)
@pytest.mark.parametrize("use_mmap", [True, False])
def test_pipeline_feed_matches_striping(tmp_path, size, use_mmap,
                                        monkeypatch):
    """Golden equivalence at an odd size: new feed vs the synchronous
    reference-shaped writer, byte-identical .ec00-.ec13."""
    monkeypatch.setenv("WEED_EC_MMAP", "1" if use_mmap else "0")
    coder = ec.get_coder("numpy", 10, 4)
    base_a = _write_dat(tmp_path, "a_1", size, seed=size % 97)
    ec.write_ec_files(base_a, coder, GEO, buffer_size=100)
    base_b = _write_dat(tmp_path, "b_1", size, seed=size % 97)
    pipeline.stream_encode(base_b, coder, GEO, batch_size=1000)
    for i in range(14):
        assert _sha(base_a + ec.to_ext(i)) == _sha(base_b + ec.to_ext(i)), \
            (size, use_mmap, i)


def test_mmap_and_preadv_agree_batchwise(tmp_path):
    size = 123_457
    base = _write_dat(tmp_path, "1", size, seed=5)
    for batch in (64, 1000, 1 << 16):
        feeds = [cls(base + ".dat", GEO.data_shards, batch, pool_buffers=3)
                 for cls in (feed_mod.MmapFeed, feed_mod.PreadvFeed)]
        got = []
        for f in feeds:
            out = []
            for b in f.batches(stripe_segments(size, GEO, batch)):
                out.append(b.copy())
                f.recycle(b)
            f.close()
            got.append(out)
        assert len(got[0]) == len(got[1])
        for a, b in zip(*got):
            assert a.shape == b.shape and np.array_equal(a, b)


def test_mmap_zero_copy_views_for_strided_batches(tmp_path):
    """When a batch is one uniformly-strided in-bounds segment the mmap
    feed must yield a VIEW of the map — no host copy at all."""
    g = ec.Geometry(10, 4, large_block_size=4096, small_block_size=256)
    size = g.large_row_size * 2  # exactly 2 large rows, no tail
    base = _write_dat(tmp_path, "1", size, seed=9)
    f = feed_mod.MmapFeed(base + ".dat", 10, 4096, pool_buffers=2)
    batches = list(f.batches(stripe_segments(size, g, 4096)))
    assert len(batches) == 2
    for b in batches:
        assert not b.flags.owndata and b.base is not None
        assert b.strides == (g.large_block_size, 1)
    # and the bytes are right
    dat = np.fromfile(base + ".dat", dtype=np.uint8)
    row0 = dat[:g.large_row_size].reshape(10, g.large_block_size)
    assert np.array_equal(batches[0], row0)
    f.close()


def test_buffer_pool_bounded_and_recycled(tmp_path):
    """A pooled feed over many batches must never allocate beyond its
    pool: withholding recycle() stalls acquire (bounded memory), and
    recycling returns the SAME buffers."""
    size = 64 * 1024
    base = _write_dat(tmp_path, "1", size, seed=11)
    f = feed_mod.PreadvFeed(base + ".dat", 10, 1024, pool_buffers=2)
    seen_ids = set()
    it = f.batches(stripe_segments(size, GEO, 1024))
    held = [next(it), next(it)]
    seen_ids = {id(b.base if b.base is not None else b) for b in held}
    # pool of 2 exhausted: the feed must block rather than allocate
    import threading
    got_third = threading.Event()
    result = {}

    def puller():
        try:
            result["b"] = next(it)
            got_third.set()
        except RuntimeError:
            got_third.set()

    th = threading.Thread(target=puller, daemon=True)
    th.start()
    assert not got_third.wait(0.3), "feed allocated beyond its pool"
    expect = held[0].copy()
    f.recycle(held.pop(0))
    assert got_third.wait(2.0), "recycle did not unblock the feed"
    assert "b" in result
    b3 = result["b"]
    assert id(b3.base if b3.base is not None else b3) in seen_ids
    # the batch still held was not corrupted by the third assembly
    assert np.array_equal(held[0], np.asarray(held[0]))
    assert not np.array_equal(expect, b3.copy()) or size <= 2048
    f.close()
    th.join(2.0)


def test_feed_close_unblocks_starved_reader(tmp_path):
    """close() must wake a reader stuck waiting for a buffer (error-path
    wedge guard)."""
    import threading
    size = 64 * 1024
    base = _write_dat(tmp_path, "1", size, seed=13)
    f = feed_mod.PreadvFeed(base + ".dat", 10, 1024, pool_buffers=2)
    it = f.batches(stripe_segments(size, GEO, 1024))
    _ = [next(it), next(it)]  # drain the pool, never recycle
    raised = threading.Event()

    def puller():
        try:
            next(it)
        except RuntimeError:
            raised.set()

    th = threading.Thread(target=puller, daemon=True)
    th.start()
    th.join(0.2)
    f.close()
    assert raised.wait(2.0), "close() left the reader wedged"
    th.join(2.0)


def test_fanout_writer_error_still_fires_callbacks(tmp_path):
    """A writer that dies mid-batch (ENOSPC) must still fire every row's
    completion callback — a skipped callback strands a pooled staging
    buffer and can wedge the reader (regression: review finding)."""
    import threading

    from seaweedfs_tpu.ec.pipeline import _FanOut

    if not os.path.exists("/dev/full"):
        pytest.skip("no /dev/full on this platform")
    fan = _FanOut([str(tmp_path / "ok.bin"), "/dev/full"], depth=2)
    fired = threading.Event()
    fan.put_rows(iter([np.zeros(64, np.uint8), np.ones(64, np.uint8)]),
                 on_done=fired.set)
    fan.close()
    assert fired.wait(2.0), "writer error path dropped a row callback"
    assert fan.errors  # the ENOSPC surfaced


def test_stream_rebuild_uses_feed_and_matches(tmp_path, monkeypatch):
    """Rebuild through the ShardFeed (both modes) reproduces the original
    shards exactly."""
    size = 77_803
    base = _write_dat(tmp_path, "1", size, seed=17)
    coder = ec.get_coder("numpy", 10, 4)
    pipeline.stream_encode(base, coder, GEO, batch_size=1000)
    golden = {i: _sha(base + ec.to_ext(i)) for i in range(14)}
    for use_mmap in ("1", "0"):
        monkeypatch.setenv("WEED_EC_MMAP", use_mmap)
        victims = [1, 4, 10, 13]
        for v in victims:
            os.remove(base + ec.to_ext(v))
        rebuilt = pipeline.stream_rebuild(base, coder, GEO, batch_size=512)
        assert sorted(rebuilt) == victims
        for i in range(14):
            assert _sha(base + ec.to_ext(i)) == golden[i], (use_mmap, i)


# ----------------------------------------------------- reader pool / O_DIRECT

@pytest.mark.parametrize("size", [99_001, 30_553, 7])
@pytest.mark.parametrize("mode", ["readers", "odirect", "odirect+readers"])
def test_pipeline_parallel_feed_matches_striping(tmp_path, size, mode,
                                                 monkeypatch):
    """The reader pool (WEED_EC_READERS > 1) and the O_DIRECT path must
    be byte-identical to the synchronous reference-shaped writer at odd
    sizes (unaligned tails, EOF zero-fill, padded final rows) — the only
    acceptable difference is speed."""
    if "readers" in mode:
        monkeypatch.setenv("WEED_EC_READERS", "3")
    if "odirect" in mode:
        monkeypatch.setenv("WEED_EC_ODIRECT", "1")
    coder = ec.get_coder("numpy", 10, 4)
    base_a = _write_dat(tmp_path, "a_1", size, seed=size % 89)
    ec.write_ec_files(base_a, coder, GEO, buffer_size=100)
    base_b = _write_dat(tmp_path, "b_1", size, seed=size % 89)
    pipeline.stream_encode(base_b, coder, GEO, batch_size=1000)
    for i in range(14):
        assert _sha(base_a + ec.to_ext(i)) == _sha(base_b + ec.to_ext(i)), \
            (size, mode, i)


@pytest.mark.parametrize("feed_cls", [feed_mod.MmapFeed,
                                      feed_mod.PreadvFeed])
def test_reader_pool_agrees_with_serial(tmp_path, feed_cls):
    """readers=1 (serial path) and readers=N (pool) must produce the
    SAME ordered batch sequence for the same segments."""
    size = 123_457
    base = _write_dat(tmp_path, "1", size, seed=21)
    for batch in (64, 1000, 1 << 16):
        got = []
        for readers in (1, 4):
            f = feed_cls(base + ".dat", GEO.data_shards, batch,
                         pool_buffers=3, readers=readers)
            out = []
            for b in f.batches(stripe_segments(size, GEO, batch)):
                out.append(b.copy())
                f.recycle(b)
            f.close()
            got.append(out)
        assert len(got[0]) == len(got[1]), batch
        for a, b in zip(*got):
            assert a.shape == b.shape and np.array_equal(a, b), batch


def test_shard_feed_reader_pool_agrees_with_serial(tmp_path):
    size = 77_803
    base = _write_dat(tmp_path, "1", size, seed=23)
    coder = ec.get_coder("numpy", 10, 4)
    pipeline.stream_encode(base, coder, GEO, batch_size=1000)
    paths = [base + ec.to_ext(i) for i in range(10)]
    got = []
    for readers in (1, 3):
        f = feed_mod.ShardFeed(paths, 512, pool_buffers=3,
                               readers=readers)
        out = []
        for b in f.batches(512):
            out.append(b.copy())
            f.recycle(b)
        f.close()
        got.append(out)
    assert len(got[0]) == len(got[1])
    for a, b in zip(*got):
        assert np.array_equal(a, b)


def test_odirect_falls_back_gracefully(tmp_path, monkeypatch):
    """On filesystems that refuse O_DIRECT (EINVAL at open) the feed
    must degrade to buffered reads with identical bytes, not fail."""
    monkeypatch.setenv("WEED_EC_ODIRECT", "1")
    size = 50_001
    base = _write_dat(tmp_path, "1", size, seed=29)
    f = feed_mod.open_feed(base + ".dat", GEO.data_shards, 1000,
                           readers=2)
    assert isinstance(f, feed_mod.PreadvFeed)  # odirect forces pread path
    ref = feed_mod.MmapFeed(base + ".dat", GEO.data_shards, 1000,
                            pool_buffers=3, readers=1)
    got_a, got_b = [], []
    for b in f.batches(stripe_segments(size, GEO, 1000)):
        got_a.append(b.copy())
        f.recycle(b)
    for b in ref.batches(stripe_segments(size, GEO, 1000)):
        got_b.append(b.copy())
        ref.recycle(b)
    f.close()
    ref.close()
    assert len(got_a) == len(got_b)
    for a, b in zip(got_a, got_b):
        assert np.array_equal(a, b)


def test_odirect_staging_buffers_are_page_aligned():
    pool = feed_mod.BufferPool(10, 8192, count=2, aligned=True)
    for _ in range(2):
        buf = pool.acquire()
        assert buf.ctypes.data % feed_mod._ALIGN == 0
        assert buf.shape == (10, 8192)


def test_mid_read_close_unblocks_pool_threads_without_leaks(tmp_path):
    """close() mid-iteration must wake a consumer starved for staging
    buffers, terminate every reader-pool thread, and leave no staging
    buffer lent beyond the batches the consumer still legitimately
    holds (in-flight lookahead buffers recycle on the way out)."""
    import threading
    size = 256 * 1024
    base = _write_dat(tmp_path, "1", size, seed=31)
    f = feed_mod.PreadvFeed(base + ".dat", 10, 1024, pool_buffers=2,
                            readers=3)
    it = f.batches(stripe_segments(size, GEO, 1024))
    held = [next(it), next(it)]  # drain the pool, never recycle
    threads = list(f._rpool._threads)
    assert threads and all(th.is_alive() for th in threads)

    raised = threading.Event()

    def puller():
        try:
            next(it)
        except RuntimeError:
            raised.set()

    th = threading.Thread(target=puller, daemon=True)
    th.start()
    th.join(0.3)
    assert th.is_alive(), "puller should be blocked awaiting a buffer"
    f.close()
    assert raised.wait(2.0), "close() left the consumer wedged"
    th.join(2.0)
    for worker in threads:
        worker.join(2.0)
        assert not worker.is_alive(), "close() leaked a pool thread"
    # the only buffers still lent are the two the consumer holds
    assert len(f._lent) <= len(held), "close() leaked staging buffers"
