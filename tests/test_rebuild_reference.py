"""A rebuild against the plain reference: `benchmark/reference_rebuild.py`
decodes lost shard rows from the survivors with the reference's own field
arithmetic and checks them against the rows `reference.iter_shard_chunks`
encodes from the sealed `.dat`; `Store.ec_rebuild` (the numpy coder, and
the Pallas kernel interpreted at one tiny width) has to leave those bytes
in the place of every lost file. Loss patterns: data + parity shards lost,
3+1 (the benchmark's), 4+0, 0+4 and 2+2, on two seeds each.
"""

import importlib.util
import os
import shutil
import sys

import numpy as np
import pytest

from seaweedfs_tpu import ec
from seaweedfs_tpu.ec import striping
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.utils import metrics as metrics_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench(name: str):
    """A module of benchmark/ under its own name: `reference_rebuild`
    imports `reference`, and neither imports anything of the program."""
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(ROOT, "benchmark", name + ".py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


reference = _bench("reference")
reference_warmdown = _bench("reference_warmdown")
reference_rebuild = _bench("reference_rebuild")

K, M, LARGE, SMALL = 10, 4, 1 << 13, 1 << 10
GEOMETRY = ec.Geometry(K, M, large_block_size=LARGE, small_block_size=SMALL)
COLLECTION, VID = "heal", 5
PATTERNS = {"3+1": (3, 1), "4+0": (4, 0), "0+4": (0, 4), "2+2": (2, 2)}
SEEDS = (39, 2 ** 31 + 7)
STAGE = "seaweedfs_tpu_ec_stage_seconds"


def lost_shards(pattern: str, seed: int) -> list[int]:
    data, parity = PATTERNS[pattern]
    rng = np.random.default_rng(seed)
    return sorted(int(s) for s in rng.choice(K, data, replace=False)) \
        + sorted(K + int(s) for s in rng.choice(M, parity, replace=False))


CASES = [(p, s) for p in PATTERNS for s in SEEDS]


@pytest.fixture(scope="module")
def volume(tmp_path_factory):
    """A `.dat` of one large row, small rows and a ragged tail, its
    `.idx`, and the reference's fourteen shard files and `.ecx`."""
    d = tmp_path_factory.mktemp("rebuild")
    base = str(d / "sealed")
    rng = np.random.default_rng(39)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, K * LARGE + 3 * K * SMALL + 777,
                             dtype=np.uint8).tobytes())
    idx = np.zeros(3, dtype=reference.IDX_DTYPE)
    idx["key"], idx["offset"], idx["size"] = [4, 2, 9], [1, 30, 60], 100
    with open(base + ".idx", "wb") as f:
        f.write(idx.tobytes())
    good = str(d / "good")
    files = [open(good + e, "wb")
             for e in reference_rebuild.exts(list(range(K + M)))]
    for _, rows in reference.iter_shard_chunks(base + ".dat", K, M, LARGE,
                                               SMALL):
        for f, row in zip(files, rows):
            f.write(row.tobytes())
    for f in files:
        f.close()
    with open(good + ".ecx", "wb") as f:
        f.write(reference.sorted_ecx(idx.tobytes()))
    return base, good


def read(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


@pytest.mark.parametrize("pattern,seed", CASES)
def test_reference_decodes_what_the_encoder_wrote(volume, pattern, seed):
    base, good = volume
    lost = lost_shards(pattern, seed)
    got = reference_rebuild.walk(base, K, M, LARGE, SMALL, lost,
                                 against=good)
    assert got["decode_mismatches"] == 0 and got["differing"] == []
    assert got["survivors"] == [i for i in range(K + M)
                                if i not in lost][:K]
    assert got["shard_bytes"] == os.path.getsize(good + ".ec00") \
        == reference.shard_size(os.path.getsize(base + ".dat"), K, LARGE,
                                SMALL)
    for ext, h in got["hashes"].items():
        assert h == reference_warmdown.digest(read(good + ext)), ext
    # the control stands the parity files in the lost ones' place
    for i, ext in enumerate(reference_rebuild.exts(lost)):
        assert got["control_hashes"][ext] == reference_warmdown.digest(
            read(good + f".ec{K + i:02d}"))
    assert reference_warmdown.files_differing(
        got["hashes"], got["control_hashes"]) \
        == sum(s != K + i for i, s in enumerate(lost))


def store_with_loss(tmp_path, good: str, lost: list[int]) -> tuple:
    """A store over a directory holding the reference's shard set with
    the `lost` files gone, as ec/copy leaves a rebuilder."""
    store = Store([str(tmp_path / "v")], coder_name="numpy",
                  geometry=GEOMETRY)
    base = str(tmp_path / "v" / f"{COLLECTION}_{VID}")
    for ext in reference_rebuild.exts(list(range(K + M))) + [".ecx"]:
        shutil.copyfile(good + ext, base + ext)
    striping.write_layout_marker(base, os.path.getsize(
        os.path.join(os.path.dirname(good), "sealed.dat")), GEOMETRY)
    for ext in reference_rebuild.exts(lost):
        os.remove(base + ext)
    return store, base


def ec_counters() -> dict[str, float]:
    """The shared `ec` registry as /metrics renders it."""
    out = {}
    for line in metrics_mod.shared("ec").render().splitlines():
        if line and not line.startswith("#"):
            key, _, value = line.rpartition(" ")
            out[key] = float(value)
    return out


def rebuild_and_compare(store, base: str, good: str, lost: list[int]
                        ) -> None:
    before = ec_counters()
    assert store.ec_rebuild(VID, COLLECTION) == lost
    after = ec_counters()
    delta = {k: after[k] - before.get(k, 0.0) for k in after}
    for ext in reference_rebuild.exts(lost):
        assert read(base + ext) == read(good + ext), ext
    got = reference_rebuild.walk(os.path.join(os.path.dirname(good),
                                              "sealed"),
                                 K, M, LARGE, SMALL, lost, against=base)
    assert got["differing"] == []
    # what the rebuild handed to the coder: k rows of a shard's columns,
    # in one batch or more, and one `ec.rebuild` around it all
    shard = os.path.getsize(base + ".ec00")
    assert delta["seaweedfs_tpu_ec_rebuild_input_bytes_total"] == K * shard
    assert delta["seaweedfs_tpu_ec_rebuild_batches_total"] >= 1
    assert delta[f'{STAGE}_count{{stage="ec.rebuild"}}'] == 1


@pytest.mark.parametrize("pattern,seed", CASES)
def test_store_rebuild_is_the_reference(volume, tmp_path, pattern, seed):
    _, good = volume
    lost = lost_shards(pattern, seed)
    store, base = store_with_loss(tmp_path, good, lost)
    try:
        rebuild_and_compare(store, base, good, lost)
    finally:
        store.close()


def test_pallas_rebuild_at_one_tiny_width_is_the_reference(volume, tmp_path,
                                                           monkeypatch):
    """The kernel the chip runs, interpreted: one tile of the 12 KiB
    shards, the matrix of the benchmark's 3 + 1 loss as its operand."""
    from seaweedfs_tpu.ec.coder import PallasCoder
    _, good = volume
    lost = lost_shards("3+1", SEEDS[0])
    store, base = store_with_loss(tmp_path, good, lost)
    coder = PallasCoder(K, M, interpret=True)
    monkeypatch.setattr(store, "coder", lambda g: coder)
    try:
        rebuild_and_compare(store, base, good, lost)
    finally:
        store.close()


@pytest.mark.parametrize("damage", ["flip", "truncate", "missing"])
def test_walk_sees_a_damaged_rebuilt_file(volume, tmp_path, damage):
    base, good = volume
    lost = lost_shards("3+1", SEEDS[1])
    mine = str(tmp_path / "x")
    for ext in reference_rebuild.exts(lost):
        shutil.copyfile(good + ext, mine + ext)
    target = reference_rebuild.exts(lost)[1]
    if damage == "flip":
        with open(mine + target, "r+b") as f:
            first = f.read(1)
            f.seek(0)
            f.write(bytes([first[0] ^ 1]))
    elif damage == "truncate":
        os.truncate(mine + target, os.path.getsize(mine + target) - 1)
    else:
        os.remove(mine + target)
    got = reference_rebuild.walk(base, K, M, LARGE, SMALL, lost,
                                 against=mine)
    assert got["differing"] == [target]
