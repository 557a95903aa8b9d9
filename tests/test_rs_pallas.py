"""Pallas kernel tests — every call here ASKS for interpret mode (the CPU
test mesh); the same kernel compiles via Mosaic on the chip, where
chip_smoke.py and scripts/chip_kernels.py drive it."""

import numpy as np

from seaweedfs_tpu.ops import gf256, rs_pallas


def test_pallas_encode_matches_numpy():
    rng = np.random.default_rng(20)
    data = rng.integers(0, 256, (10, 4096), dtype=np.uint8)
    want = gf256.encode_parity(data, 4)
    got = np.asarray(rs_pallas.encode_parity(data, 4, tile=1024,
                                            interpret=True))
    assert np.array_equal(got, want)


def test_pallas_unaligned_width():
    rng = np.random.default_rng(21)
    for n in [1, 100, 1023, 1025]:
        data = rng.integers(0, 256, (10, n), dtype=np.uint8)
        want = gf256.encode_parity(data, 4)
        got = np.asarray(rs_pallas.encode_parity(data, 4, tile=1024,
                                                interpret=True))
        assert np.array_equal(got, want), n


def test_pallas_arbitrary_matrix():
    rng = np.random.default_rng(22)
    mat = rng.integers(0, 256, (6, 12)).astype(np.uint8)
    x = rng.integers(0, 256, (12, 2048), dtype=np.uint8)
    mul = gf256.mul_table()
    want = np.zeros((6, 2048), dtype=np.uint8)
    for r in range(6):
        for c in range(12):
            want[r] ^= mul[mat[r, c]][x[c]]
    got = np.asarray(rs_pallas.gf_apply_pallas(mat, tile=512,
                                               interpret=True)(x))
    assert np.array_equal(got, want)


def test_pallas_coder_roundtrip():
    from seaweedfs_tpu.ec.coder import PallasCoder
    coder = PallasCoder(10, 4, interpret=True)
    rng = np.random.default_rng(23)
    data = rng.integers(0, 256, (10, 3000), dtype=np.uint8)
    parity = coder.encode(data)
    assert np.array_equal(parity, gf256.encode_parity(data, 4))
    shards = [data[i] for i in range(10)] + [parity[j] for j in range(4)]
    holed = [None if i in (0, 5, 11, 13) else s
             for i, s in enumerate(shards)]
    out = coder.reconstruct(holed)
    for i in range(14):
        assert np.array_equal(np.asarray(out[i]), shards[i]), i
    assert coder.verify(shards)
