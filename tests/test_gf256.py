import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256


def test_field_axioms():
    # spot-check associativity/distributivity on random triples
    rng = np.random.default_rng(0)
    for a, b, c in rng.integers(0, 256, size=(200, 3)):
        a, b, c = int(a), int(b), int(c)
        assert gf256.gf_mul(a, gf256.gf_mul(b, c)) == \
            gf256.gf_mul(gf256.gf_mul(a, b), c)
        assert gf256.gf_mul(a, b ^ c) == \
            gf256.gf_mul(a, b) ^ gf256.gf_mul(a, c)
    for a in range(1, 256):
        assert gf256.gf_mul(a, gf256.gf_inv(a)) == 1


def test_known_products():
    # 2*2=4, and the wraparound product 0x80*2 = 0x11D & 0xFF = 0x1D
    assert gf256.gf_mul(2, 2) == 4
    assert gf256.gf_mul(0x80, 2) == 0x1D
    assert gf256.gf_mul(0, 123) == 0
    assert gf256.gf_exp(2, 8) == 0x1D


def test_mul_table_matches_scalar():
    tbl = gf256.mul_table()
    rng = np.random.default_rng(1)
    for a, b in rng.integers(0, 256, size=(500, 2)):
        assert tbl[a, b] == gf256.gf_mul(int(a), int(b))


def test_matrix_inverse_roundtrip():
    rng = np.random.default_rng(2)
    for _ in range(20):
        while True:
            m = rng.integers(0, 256, size=(6, 6)).astype(np.uint8)
            try:
                inv = gf256.gf_mat_inv(m)
                break
            except np.linalg.LinAlgError:
                continue
        assert np.array_equal(gf256.gf_matmul(m, inv),
                              np.eye(6, dtype=np.uint8))


def test_rs_matrix_systematic_and_mds():
    for k, m in [(10, 4), (6, 3), (12, 4), (20, 4), (3, 2)]:
        mat = gf256.rs_matrix(k, m)
        assert mat.shape == (k + m, k)
        assert np.array_equal(mat[:k], np.eye(k, dtype=np.uint8))
        # MDS property: every k-row subset must be invertible. Exhaustive is
        # combinatorial; check all subsets that drop <=2 rows plus random ones.
        import itertools
        rows = list(range(k + m))
        subsets = list(itertools.combinations(rows, k))
        rng = np.random.default_rng(3)
        if len(subsets) > 80:
            idx = rng.choice(len(subsets), size=80, replace=False)
            subsets = [subsets[i] for i in idx]
        for sub in subsets:
            gf256.gf_mat_inv(mat[list(sub)])  # must not raise


def test_rs_10_4_parity_matrix_pinned():
    """Pin the RS(10,4) parity coefficients.

    These values are a property of (field 0x11D, Vandermonde-systematic
    construction) and therefore of the reference coder's default geometry;
    any change here breaks on-disk shard compatibility.
    """
    pm = gf256.parity_matrix(10, 4)
    assert pm.shape == (4, 10)
    # every coefficient nonzero (MDS systematic matrices have dense parity)
    assert (pm != 0).all()
    # literal pin — recomputing the construction here could not catch a
    # drift in the construction itself; these are the bytes the reference
    # coder (klauspost reedsolomon.New(10,4) default) multiplies by, also
    # asserted against the reference fixture in test_reference_fixture.py
    assert pm.tolist() == [
        [129, 150, 175, 184, 210, 196, 254, 232, 3, 2],
        [150, 129, 184, 175, 196, 210, 232, 254, 2, 3],
        [191, 214, 98, 10, 6, 111, 223, 183, 5, 4],
        [214, 191, 10, 98, 111, 6, 183, 223, 4, 5],
    ]


def test_encode_reconstruct_roundtrip():
    rng = np.random.default_rng(4)
    for k, m in [(10, 4), (6, 3), (12, 4)]:
        n = 1000
        data = rng.integers(0, 256, size=(k, n)).astype(np.uint8)
        parity = gf256.encode_parity(data, m)
        shards = [data[i] for i in range(k)] + [parity[i] for i in range(m)]
        # drop m random shards
        drop = rng.choice(k + m, size=m, replace=False)
        holed: list = [None if i in drop else s.copy()
                       for i, s in enumerate(shards)]
        rebuilt = gf256.reconstruct(holed, k, m)
        for i in range(k + m):
            assert np.array_equal(rebuilt[i], shards[i]), f"shard {i}"


def test_reconstruct_data_only():
    rng = np.random.default_rng(5)
    k, m = 10, 4
    data = rng.integers(0, 256, size=(k, 64)).astype(np.uint8)
    parity = gf256.encode_parity(data, m)
    shards = [data[i] for i in range(k)] + [parity[i] for i in range(m)]
    holed: list = list(shards)
    holed[0] = None
    holed[13] = None
    out = gf256.reconstruct(holed, k, m, data_only=True)
    assert np.array_equal(out[0], shards[0])
    assert out[13] is None  # parity left unfilled in data-only mode


def test_too_few_shards_raises():
    k, m = 4, 2
    data = np.zeros((k, 8), dtype=np.uint8)
    parity = gf256.encode_parity(data, m)
    shards: list = [data[i] for i in range(k)] + [parity[i] for i in range(m)]
    for i in range(m + 1):
        shards[i] = None
    with pytest.raises(ValueError):
        gf256.reconstruct(shards, k, m)
