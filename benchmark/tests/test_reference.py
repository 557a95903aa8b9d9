"""The plain reference against upstream's own output (the golden SHA-256s
that tests/test_reference_fixture.py pins for tests/fixtures/ec/1.dat)
and against the program's NumpyCoder at both geometries."""

import hashlib
import os

import numpy as np
import pytest

import reference
from conftest import ROOT
from tests.test_reference_fixture import (GOLDEN_ECX, GOLDEN_REAL,
                                          GOLDEN_SHRUNK,
                                          PARITY_MATRIX_10_4)

FIXTURE = os.path.join(ROOT, "tests", "fixtures", "ec", "1")


def test_matrix_is_klauspost_default():
    assert reference.encoding_matrix(10, 4)[10:] == PARITY_MATRIX_10_4
    top = reference.encoding_matrix(6, 3)[:6]
    assert top == [[int(i == j) for j in range(6)] for i in range(6)]


@pytest.mark.parametrize("blocks,golden", [
    ((10000, 100), GOLDEN_SHRUNK), ((1 << 30, 1 << 20), GOLDEN_REAL)])
def test_shards_match_upstream_goldens(blocks, golden):
    hashes = [hashlib.sha256() for _ in range(14)]
    for _, chunk in reference.iter_shard_chunks(FIXTURE + ".dat", 10, 4,
                                                *blocks, threads=2):
        for h, row in zip(hashes, chunk):
            h.update(row.tobytes())
    assert [h.hexdigest() for h in hashes] == golden


def test_ecx_matches_upstream_golden():
    with open(FIXTURE + ".idx", "rb") as f:
        ecx = reference.sorted_ecx(f.read())
    assert hashlib.sha256(ecx).hexdigest() == GOLDEN_ECX


@pytest.mark.parametrize("k,m", [(10, 4), (6, 3)])
def test_parity_equals_numpy_coder_with_ragged_tail(k, m, tmp_path):
    from seaweedfs_tpu.ec.coder import NumpyCoder
    from seaweedfs_tpu.ec.geometry import Geometry
    from seaweedfs_tpu.ec import striping
    g = Geometry(k, m, large_block_size=4096, small_block_size=256)
    base = str(tmp_path / "7")
    size = 3 * g.large_row_size + 5 * g.small_row_size + 1234
    with open(base + ".dat", "wb") as f:
        f.write(np.random.default_rng(k).bytes(size))
    striping.write_ec_files(base, NumpyCoder(k, m), g, buffer_size=256)
    got = bytearray(), [bytearray() for _ in range(k + m)]
    for _, chunk in reference.iter_shard_chunks(base + ".dat", k, m, 4096,
                                                256, rows_per_chunk=3):
        for buf, row in zip(got[1], chunk):
            buf += row.tobytes()
    for s, buf in enumerate(got[1]):
        with open(f"{base}.ec{s:02d}", "rb") as f:
            assert f.read() == bytes(buf), s


@pytest.mark.parametrize("k,m", [(10, 4), (6, 3), (20, 4)])
def test_pair_tables_equal_the_bytewise_definition(k, m):
    data = np.random.default_rng(m).integers(0, 256, (k, 100001),
                                             dtype=np.uint8)
    rows = reference.encoding_matrix(k, m)[k:]
    assert np.array_equal(reference.apply_rows(rows, data),
                          reference.apply_rows_bytewise(rows, data))
    assert np.array_equal(reference.apply_rows_threaded(rows, data, 3),
                          reference.apply_rows_bytewise(rows, data))


def test_fold_idx_last_entry_holds_and_tombstones_delete():
    entry = reference.IDX_DTYPE
    idx = np.array([(7, 1, 10), (3, 5, 20), (7, 9, 30), (5, 11, 40),
                    (3, 13, reference.TOMBSTONE), (9, 0, 50)], dtype=entry)
    keys, offsets, sizes = reference.fold_idx(idx.tobytes())
    assert (keys.tolist(), offsets.tolist(), sizes.tolist()) == (
        [5, 7], [11, 9], [40, 30])
    assert reference.sorted_ecx(idx.tobytes()) == np.array(
        [(5, 11, 40), (7, 9, 30)], dtype=entry).tobytes()


def test_locate_agrees_with_the_programs_locate():
    from seaweedfs_tpu.ec import locate as prog
    from seaweedfs_tpu.ec.geometry import Geometry
    g = Geometry(10, 4, large_block_size=10000, small_block_size=100)
    dat_size = 2590912
    shard = reference.shard_size(dat_size, 10, 10000, 100)
    for off, length in ((0, 50), (99990, 25000), (2500000, 90000),
                        (2590000, 912)):
        want = [(iv.to_shard_id_and_offset(g)[0],
                 iv.to_shard_id_and_offset(g)[1], iv.size)
                for iv in prog.locate_data(g, shard, off, length)]
        assert reference.locate(off, length, dat_size, 10, 10000,
                                100) == want
