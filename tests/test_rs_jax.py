import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256, rs_jax


@pytest.mark.parametrize("method", ["lut", "bitplane"])
@pytest.mark.parametrize("k,m", [(10, 4), (6, 3), (12, 4), (20, 4)])
def test_encode_matches_numpy(method, k, m):
    rng = np.random.default_rng(10)
    data = rng.integers(0, 256, size=(k, 4096)).astype(np.uint8)
    want = gf256.encode_parity(data, m)
    got = np.asarray(rs_jax.encode_parity(data, m, method=method))
    assert got.dtype == np.uint8
    assert np.array_equal(got, want)


@pytest.mark.parametrize("method", ["lut", "bitplane"])
def test_encode_odd_width(method):
    # widths that don't align to TPU lanes must still be exact
    rng = np.random.default_rng(11)
    for n in [1, 7, 127, 129, 1000]:
        data = rng.integers(0, 256, size=(10, n)).astype(np.uint8)
        want = gf256.encode_parity(data, 4)
        got = np.asarray(rs_jax.encode_parity(data, 4, method=method))
        assert np.array_equal(got, want), n


@pytest.mark.parametrize("method", ["lut", "bitplane"])
def test_reconstruct_matches_numpy(method):
    rng = np.random.default_rng(12)
    k, m = 10, 4
    data = rng.integers(0, 256, size=(k, 2048)).astype(np.uint8)
    parity = gf256.encode_parity(data, m)
    shards = [data[i] for i in range(k)] + [parity[j] for j in range(m)]
    for trial in range(5):
        drop = rng.choice(k + m, size=m, replace=False)
        holed = [None if i in drop else s for i, s in enumerate(shards)]
        out = rs_jax.reconstruct(holed, k, m, method=method)
        for i in range(k + m):
            assert np.array_equal(np.asarray(out[i]), shards[i]), (trial, i)


def test_reconstruct_data_only():
    rng = np.random.default_rng(13)
    k, m = 10, 4
    data = rng.integers(0, 256, size=(k, 256)).astype(np.uint8)
    parity = gf256.encode_parity(data, m)
    shards = [data[i] for i in range(k)] + [parity[j] for j in range(m)]
    holed = list(shards)
    holed[3] = None
    holed[12] = None
    out = rs_jax.reconstruct(holed, k, m, data_only=True)
    assert np.array_equal(np.asarray(out[3]), shards[3])
    assert out[12] is None


def test_bitplane_matrix_roundtrip_property():
    # random GF matrix applied via bitplanes == table-based numpy product
    rng = np.random.default_rng(14)
    mat = rng.integers(0, 256, size=(5, 7)).astype(np.uint8)
    x = rng.integers(0, 256, size=(7, 333)).astype(np.uint8)
    mul = gf256.mul_table()
    want = np.zeros((5, 333), dtype=np.uint8)
    for r in range(5):
        for c in range(7):
            want[r] ^= mul[mat[r, c]][x[c]]
    import jax
    got = np.asarray(jax.jit(rs_jax.gf_apply_bitplane(mat))(x))
    assert np.array_equal(got, want)
    got_lut = np.asarray(jax.jit(rs_jax.gf_apply_lut(mat))(x))
    assert np.array_equal(got_lut, want)
