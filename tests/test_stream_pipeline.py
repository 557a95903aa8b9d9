"""Streaming EC pipeline (ec/pipeline.py) — identity vs the synchronous path.

The pipeline must produce byte-identical shard files to striping.write_ec_files
for every geometry/batch-size combination (the schedule is the only thing that
changes), and stream_rebuild must reproduce the original shards exactly.
"""

import hashlib
import os
import random

import pytest

from seaweedfs_tpu import ec
from seaweedfs_tpu.ec import pipeline
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume

GEO = ec.Geometry(data_shards=10, parity_shards=4,
                  large_block_size=10000, small_block_size=100)


def build_volume(tmp_path, n_needles=60, seed=3):
    os.makedirs(str(tmp_path), exist_ok=True)
    rng = random.Random(seed)
    v = Volume(str(tmp_path), "", 1, create=True)
    for i in range(1, n_needles + 1):
        data = bytes(rng.getrandbits(8) for _ in range(rng.randint(1, 1200)))
        v.write_needle(Needle(cookie=0x9000 + i, id=i, data=data))
    v.close()


def _sha(path):
    return hashlib.sha256(open(path, "rb").read()).hexdigest()


@pytest.mark.parametrize("batch_size", [64, 4096, 1 << 20])
def test_stream_encode_matches_sync(tmp_path, batch_size):
    # same .dat encoded through both paths (needle v3 timestamps make two
    # separately-built volumes differ)
    build_volume(tmp_path / "a")
    os.makedirs(str(tmp_path / "b"))
    base_a = os.path.join(str(tmp_path / "a"), "1")
    base_b = os.path.join(str(tmp_path / "b"), "1")
    with open(base_a + ".dat", "rb") as src, \
            open(base_b + ".dat", "wb") as dst:
        dst.write(src.read())
    coder = ec.get_coder("jax", 10, 4)
    ec.write_ec_files(base_a, coder, GEO, buffer_size=50)
    pipeline.stream_encode(base_b, coder, GEO, batch_size=batch_size)
    for i in range(14):
        assert _sha(base_a + ec.to_ext(i)) == _sha(base_b + ec.to_ext(i)), i


def test_stream_rebuild_roundtrip(tmp_path):
    build_volume(tmp_path)
    coder = ec.get_coder("jax", 10, 4)
    base = os.path.join(str(tmp_path), "1")
    pipeline.stream_encode(base, coder, GEO, batch_size=4096)
    golden = {i: _sha(base + ec.to_ext(i)) for i in range(14)}
    victims = [0, 5, 11, 13]
    for i in victims:
        os.remove(base + ec.to_ext(i))
    rebuilt = pipeline.stream_rebuild(base, coder, GEO, batch_size=512)
    assert sorted(rebuilt) == victims
    for i in range(14):
        assert _sha(base + ec.to_ext(i)) == golden[i], i


@pytest.mark.parametrize("dat_blocks", [
    # mixed-tier sizes in units of GEO.small_block (100B), chosen around the
    # large-row (10000B -> 1000 small units... here large=10000, small=100,
    # ratio=100, large_row=100000, small_row=1000) ambiguity window: a tail
    # needing a full large_block of small rows used to make k*shard_size
    # decode to the wrong large-row count (the reference's own layout has
    # this inconsistency, ec_locate.go:19-20 vs ec_encoder.go:57)
    99_000, 99_001, 100_000, 100_001, 152_000, 199_999, 200_000])
def test_mixed_tier_layout_consistency(tmp_path, dat_blocks):
    import numpy as np

    from seaweedfs_tpu.ec.locate import locate_data
    size = dat_blocks  # bytes
    rng = np.random.default_rng(size % 89)
    dat = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
    base = os.path.join(str(tmp_path), "1")
    with open(base + ".dat", "wb") as f:
        f.write(dat)
    coder = ec.get_coder("numpy", 10, 4)
    ec.write_ec_files(base, coder, GEO, buffer_size=100)
    base2 = os.path.join(str(tmp_path), "2")
    with open(base2 + ".dat", "wb") as f:
        f.write(dat)
    pipeline.stream_encode(base2, coder, GEO, batch_size=1000)
    for i in range(14):
        assert _sha(base + ec.to_ext(i)) == _sha(base2 + ec.to_ext(i)), i
    # locate() addressing must read back the true bytes through shards
    shard_bytes = [open(base + ec.to_ext(i), "rb").read()
                   for i in range(10)]
    padded = 10 * os.path.getsize(base + ec.to_ext(0))
    for start, ln in ((0, min(size, 777)), (size // 2, 555),
                      (max(0, size - 999), 999)):
        ln = min(ln, size - start)
        got = b""
        for iv in locate_data(GEO, padded, start, ln):
            sid, o = iv.to_shard_id_and_offset(GEO)
            got += shard_bytes[sid][o:o + iv.size]
        assert got == dat[start:start + ln], (start, ln)
    # decode inverts encode
    os.remove(base + ".dat")
    ec.write_dat_file(base, size, GEO)
    assert open(base + ".dat", "rb").read() == dat


CODERS = ["numpy", "jax", "pallas"]
GEOMETRIES = {"rs10+4": GEO,
              "rs20+4": ec.Geometry(20, 4, large_block_size=10000,
                                    small_block_size=100),
              "rs6+3": ec.Geometry(6, 3, large_block_size=10000,
                                   small_block_size=100)}


def _coder(name: str, g=GEO):
    if name == "pallas":  # interpret mode is the test's explicit request
        from seaweedfs_tpu.ec.coder import PallasCoder
        return PallasCoder(g.data_shards, g.parity_shards, interpret=True)
    return ec.get_coder(name, g.data_shards, g.parity_shards)


def _write_dat(tmp_path, size: int, seed: int) -> str:
    import numpy as np
    os.makedirs(str(tmp_path), exist_ok=True)
    base = os.path.join(str(tmp_path), "1")
    with open(base + ".dat", "wb") as f:
        f.write(np.random.default_rng(seed).integers(
            0, 256, size, dtype=np.uint8).tobytes())
    return base


def _host_recomputes() -> float:
    from seaweedfs_tpu.utils import metrics as metrics_mod
    return metrics_mod.shared("ec").value("ec_digest_host_recompute")


@pytest.mark.parametrize("geometry", list(GEOMETRIES))
@pytest.mark.parametrize("coder_name", CODERS)
def test_stream_encode_stamps_digests_of_its_files(tmp_path, coder_name,
                                                   geometry):
    # the scrubber's reference comes out of the encode pass itself: the
    # .ecm digests equal the byte sums of all k+m files as written, and
    # a following stamp finds nothing left to read back
    g = GEOMETRIES[geometry]
    base = _write_dat(tmp_path, 123_457, seed=g.total_shards)
    pipeline.stream_encode(base, _coder(coder_name, g), g, batch_size=4096)
    stamped = pipeline.read_stamped_digests(base)
    true = pipeline.shard_file_digest(base, range(g.total_shards))
    assert stamped == {i: int(true[i]) for i in range(g.total_shards)}
    before = _host_recomputes()
    assert pipeline.stamp_shard_digests(base, g) == stamped
    assert _host_recomputes() == before


@pytest.mark.parametrize("lost", [[4], [12], [0, 3, 7, 12], [1, 2, 5, 8]],
                         ids=["one_data", "one_parity", "mixed4", "four_data"])
@pytest.mark.parametrize("coder_name", CODERS)
def test_stream_rebuild_restores_bytes_and_digests(tmp_path, coder_name,
                                                   lost):
    # encode under the host reference, rebuild under the coder in test:
    # the files come back byte for byte and still answer to the digests
    # the encode stamped
    base = _write_dat(tmp_path, 98_765, seed=len(lost))
    pipeline.stream_encode(base, ec.get_coder("numpy", 10, 4), GEO,
                           batch_size=4096)
    golden = {i: _sha(base + ec.to_ext(i)) for i in range(14)}
    stamped = pipeline.read_stamped_digests(base)
    for i in lost:
        os.remove(base + ec.to_ext(i))
    rebuilt = pipeline.stream_rebuild(base, _coder(coder_name), GEO,
                                      batch_size=1000)
    assert sorted(rebuilt) == lost
    for i in range(14):
        assert _sha(base + ec.to_ext(i)) == golden[i], i
    true = pipeline.shard_file_digest(base, lost)
    assert [stamped[i] for i in lost] == [int(d) for d in true]


@pytest.mark.parametrize("coder_name", CODERS)
def test_governed_equals_pinned(tmp_path, coder_name):
    # the governor's operating point changes the schedule, never a byte
    from seaweedfs_tpu.ec import governor
    governor.reset()
    try:
        coder = _coder(coder_name)
        a = _write_dat(tmp_path / "a", 210_011, seed=5)
        b = _write_dat(tmp_path / "b", 210_011, seed=5)
        pipeline.stream_encode(a, coder, GEO)  # governed
        pipeline.stream_encode(b, coder, GEO, batch_size=777, depth=2)
        assert governor.get().runs == 1
        for i in range(14):
            assert _sha(a + ec.to_ext(i)) == _sha(b + ec.to_ext(i)), i
        os.remove(a + ec.to_ext(2))
        os.remove(b + ec.to_ext(2))
        assert pipeline.stream_rebuild(a, coder, GEO) == [2]  # governed
        assert pipeline.stream_rebuild(b, coder, GEO, batch_size=333,
                                       depth=2) == [2]
        assert governor.get().runs == 2
        assert _sha(a + ec.to_ext(2)) == _sha(b + ec.to_ext(2))
    finally:
        governor.reset()


def test_ec_layout_marker(tmp_path, caplog):
    """Both encode paths stamp .ecm; a marker with a stale version is
    refused; an unmarked set in the ambiguity window (shard size a whole
    number of large blocks) warns loudly but keeps serving — sidecars
    legitimately go missing (remote serving, copies), and every healthy
    L-large-row volume has that size too."""
    import json
    import logging

    build_volume(tmp_path)
    coder = ec.get_coder("numpy", 10, 4)
    base = os.path.join(str(tmp_path), "1")
    pipeline.stream_encode(base, coder, GEO, batch_size=4096)
    ec.write_sorted_ecx_from_idx(base)
    meta = json.load(open(base + ".ecm"))
    assert meta["layout_version"] == 2

    ev = ec.EcVolume(str(tmp_path), "", 1, GEO, coder=coder)
    for sid in range(14):
        ev.add_shard(sid)
    ev.read_needle(1)  # marked: serves fine
    ev.close()

    # stale layout version: hard refusal
    json.dump({"layout_version": 1}, open(base + ".ecm", "w"))
    ev = ec.EcVolume(str(tmp_path), "", 1, GEO, coder=coder)
    for sid in range(14):
        ev.add_shard(sid)
    with pytest.raises(IOError, match="layout version"):
        ev.read_needle(1)
    ev.close()

    # unmarked + ambiguous size: warning, not refusal
    os.remove(base + ".ecm")
    sz = os.path.getsize(base + ec.to_ext(0))
    pad = (-sz) % GEO.large_block_size or GEO.large_block_size
    for i in range(14):
        with open(base + ec.to_ext(i), "ab") as f:
            f.write(bytes(pad))
    ev = ec.EcVolume(str(tmp_path), "", 1, GEO, coder=coder)
    for sid in range(14):
        ev.add_shard(sid)
    with caplog.at_level(logging.WARNING, logger="ec"):
        try:
            ev.read_needle(1)
        except Exception:
            pass  # the padded layout really is misaddressed — the point
            # here is that the warning fired before any read was served
    assert any("unmarked EC shard set" in r.message for r in caplog.records)
    ev.close()


def test_stream_rebuild_too_few_shards(tmp_path):
    build_volume(tmp_path)
    coder = ec.get_coder("numpy", 10, 4)
    base = os.path.join(str(tmp_path), "1")
    pipeline.stream_encode(base, coder, GEO, batch_size=4096)
    for i in range(5):
        os.remove(base + ec.to_ext(i))
    with pytest.raises(ValueError):
        pipeline.stream_rebuild(base, coder, GEO)


def test_reader_error_propagates(tmp_path):
    # a truncated survivor shard must raise, not hang the pipeline
    build_volume(tmp_path)
    coder = ec.get_coder("numpy", 10, 4)
    base = os.path.join(str(tmp_path), "1")
    pipeline.stream_encode(base, coder, GEO)
    os.remove(base + ec.to_ext(2))
    with open(base + ec.to_ext(3), "r+b") as f:
        f.truncate(os.path.getsize(base + ec.to_ext(3)) - 37)
    with pytest.raises(IOError):
        pipeline.stream_rebuild(base, coder, GEO, batch_size=4096)
