"""Multi-chip sharded EC on the 8-device virtual CPU mesh."""

import jax
import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.parallel import sharded


@pytest.fixture(scope="module")
def mesh():
    return sharded.make_mesh(8)


def test_sharded_encode_matches_single(mesh):
    rng = np.random.default_rng(30)
    data = rng.integers(0, 256, (16, 10, 1024), dtype=np.uint8)
    parity = np.asarray(sharded.sharded_encode(mesh, data, use_pallas=False))
    assert parity.shape == (16, 4, 1024)
    for b in range(16):
        want = gf256.encode_parity(data[b], 4)
        assert np.array_equal(parity[b], want), b


def test_sharded_encode_pallas_interpret(mesh):
    rng = np.random.default_rng(31)
    data = rng.integers(0, 256, (8, 10, 512), dtype=np.uint8)
    parity = np.asarray(sharded.sharded_encode(mesh, data, use_pallas=True,
                                               interpret=True))
    for b in range(8):
        assert np.array_equal(parity[b], gf256.encode_parity(data[b], 4)), b


def test_sharded_rebuild_all_gather(mesh):
    rng = np.random.default_rng(32)
    k, m, n = 10, 4, 2048  # n divisible by 8 devices
    data = rng.integers(0, 256, (k, n), dtype=np.uint8)
    parity = gf256.encode_parity(data, m)
    shards = [data[i] for i in range(k)] + [parity[j] for j in range(m)]
    holed = [None if i in (2, 7, 10, 13) else s
             for i, s in enumerate(shards)]
    out = sharded.sharded_rebuild(mesh, holed, k, m, use_pallas=False)
    for i in range(k + m):
        assert np.array_equal(np.asarray(out[i]), shards[i]), i
