"""The ErasureCoder interface (ec/coder.py), held by every backend.

What the product calls is small: `encode`, `encode_async` +
`materialize` (the streaming pipeline), `rec_apply_async` (rebuild),
`reconstruct` (a degraded read), `verify`, `describe`. Every coder that
constructs on a CPU host must answer all of them with the reference's
bytes, in flight or not, and the interface must not grow back the hooks
and knobs that only a second pipeline needed.
"""

import inspect
import os

import numpy as np
import pytest

from seaweedfs_tpu.ec import coder as coder_mod
from seaweedfs_tpu.ec.coder import ErasureCoder, NumpyCoder, get_coder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GEOMETRIES = [(10, 4), (20, 4), (6, 3)]
WIDTH = 1013  # odd: no lane, tile or mesh width divides it


def _build(name: str, k: int, m: int) -> ErasureCoder:
    if name == "pallas":  # interpret mode is the test's explicit request
        return coder_mod.PallasCoder(k, m, interpret=True)
    if name == "mesh":  # conftest.py pins eight virtual CPU devices
        from seaweedfs_tpu.parallel import MeshCoder
        return MeshCoder(k, m, n_devices=8)
    if name == "cpp":
        from seaweedfs_tpu.ops import native
        if not native.available():
            pytest.skip("the native library does not build here")
    return get_coder(name, k, m)


@pytest.mark.parametrize("k,m", GEOMETRIES)
@pytest.mark.parametrize("name", ["numpy", "cpp", "jax", "pallas", "mesh"])
def test_in_flight_equals_blocking_equals_reference(name, k, m):
    coder = _build(name, k, m)
    rng = np.random.default_rng(100 * k + m)
    data = rng.integers(0, 256, (k, WIDTH), dtype=np.uint8)
    want = NumpyCoder(k, m).encode(data)
    assert np.array_equal(coder.encode(data), want)
    got = coder.materialize(coder.encode_async(data))
    assert got.shape == (m, WIDTH) and got.dtype == np.uint8
    assert np.array_equal(got, want)

    shards = [*data, *want]
    assert coder.verify(shards)
    # lose as many as the code allows, data and parity both
    missing = tuple(sorted({1, k - 1, k, k + m - 1}))[:m]
    present = tuple(i for i in range(k + m) if i not in missing)[:k]
    survivors = np.stack([shards[i] for i in present])
    rebuilt = coder.materialize(
        coder.rec_apply_async(present, missing)(survivors))
    assert rebuilt.shape == (len(missing), WIDTH)
    holed = [None if i in missing else s for i, s in enumerate(shards)]
    filled = coder.reconstruct(holed)
    for row, sid in enumerate(missing):
        assert np.array_equal(rebuilt[row], shards[sid]), sid
        assert np.array_equal(filled[sid], shards[sid]), sid


def test_interface_is_what_the_product_calls():
    public = {n for n, v in vars(ErasureCoder).items()
              if callable(v) and not n.startswith("_")}
    assert public == {"encode", "encode_async", "rec_apply_async",
                      "materialize", "reconstruct", "verify", "describe",
                      "warm_widths"}
    # no backend widens it either (MeshCoder adds its HLO inspection)
    from seaweedfs_tpu.parallel import MeshCoder
    for cls in (coder_mod.NumpyCoder, coder_mod.CppCoder,
                coder_mod.JaxCoder, coder_mod.PallasCoder):
        extra = {n for n, v in inspect.getmembers(cls, callable)
                 if not n.startswith("_")} - public
        assert not extra, (cls.__name__, extra)
    assert {n for n, v in inspect.getmembers(MeshCoder, callable)
            if not n.startswith("_")} - public == {
                "encode_hlo_text", "encode_is_collective_free"}
    assert sorted(coder_mod._REGISTRY) == ["cpp", "jax", "mesh", "numpy",
                                           "pallas"]


def _product_text():
    roots = [os.path.join(REPO, "seaweedfs_tpu"),
             os.path.join(REPO, "scripts")]
    files = [os.path.join(REPO, f)
             for f in ("bench.py", "chip_smoke.py", "README.md")]
    for root in roots:
        for d, _, names in os.walk(root):
            files += [os.path.join(d, n) for n in names
                      if n.endswith((".py", ".sh"))]
    for path in files:
        with open(path, encoding="utf-8", errors="replace") as f:
            yield os.path.relpath(path, REPO), f.read()


@pytest.mark.parametrize("name", ["WEED_EC_FORMULATION",
                                  "WEED_EC_REC_WINDOW_BATCHES",
                                  "WEED_EC_STAGERS"])
def test_removed_knob_stays_removed(name):
    """Each selected a path that is gone; an option with one value is a
    constant."""
    assert [p for p, text in _product_text() if name in text] == []
