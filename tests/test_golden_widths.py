"""Golden on-disk cross-verification at BOTH offset widths and through
the .ecj delete-fold path.

Round-4 verdict: on-disk formats are the interop surface, so pin more of
them. This suite extends tests/test_reference_fixture.py with:

- the reference fixture's index re-packed at the 5-byte offset width
  (offset_5bytes.go:18-24 wire layout) and its sorted .ecx — pinned;
- a deterministic .ecj (every 7th live needle deleted), folded into the
  .ecx in place (RebuildEcxFile, ec_volume_delete.go:51-97) at both
  widths — pinned;
- the .idx regenerated from ecx+ecj (WriteIdxFileFromEcIndex,
  ec_decoder.go:18-44) at both widths — pinned;
- needle-level identity through the 5-byte index: every live entry's
  .dat bytes equal the shard-assembled bytes.

Every hash below was produced once and is now load-bearing: any drift in
entry packing, sort order, tombstone encoding or fold math changes one.
"""

import hashlib
import os
import shutil

import numpy as np

from seaweedfs_tpu.ec import locate, striping
from seaweedfs_tpu.ec.ec_volume import rebuild_ecx_file
from seaweedfs_tpu.ec.coder import get_coder
from seaweedfs_tpu.ec.geometry import Geometry, to_ext
from seaweedfs_tpu.storage import idx as idx_mod
from seaweedfs_tpu.storage import types as t

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "ec")
SHRUNK = Geometry(10, 4, large_block_size=10000, small_block_size=100)

GOLDEN = {
    "idx_w5":
        "a7703e14807c8a6f654887d85024e8a00ddbcbcd76ec1afaecf75bdd59fe43b5",
    "ecx_w5":
        "3a1bada3cfd9ed4000fb64e468a94c2c91879856aec365da5482370ed6318df2",
    "ecj":
        "024554d06a5fc0eda6394c490de631ea0adfd4835690892081182b5816602436",
    "ecx_w4_folded":
        "3229b0e9f854d1ae1a11079dcb7f7ee4fe1ce4d67e7b57d1d1676c6538563980",
    "idx_w4_from_ec":
        "1c609d40fdaf9c049df18c113bd1efa690d8d22ba27a698ab577b73e43976c47",
    "ecx_w5_folded":
        "51c9c1c03de153fe381b66e08c7ba87d89f88d7413762d86e1c381cfabf4cb39",
    "idx_w5_from_ec":
        "7718ddf3cc41a7bb9ad6d6116ef9455517aae5b456915ccd1a12f2df896d157a",
}


def _sha(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _repack_idx(src: str, dst: str, width_out: int) -> None:
    """Re-pack a 4-byte-offset .idx at another offset width (same keys,
    offsets, sizes)."""
    with open(dst, "wb") as out:
        for key, stored_offset, size in idx_mod.iter_index_file(src):
            out.write(idx_mod.pack_entry(key, stored_offset, size,
                                         offset_size=width_out))


def _doomed_keys(base: str, offset_size: int) -> list[int]:
    live = [k for k, _o, s in
            idx_mod.iter_index_file(base + ".ecx",
                                    offset_size=offset_size)
            if not t.size_is_deleted(s)]
    return live[::7]


def _write_ecj(base: str, keys) -> None:
    with open(base + ".ecj", "wb") as f:
        for k in keys:
            f.write(t.put_u64(k))


def _prepare(tmp_path, width: int) -> str:
    base = str(tmp_path / "1")
    shutil.copy(os.path.join(FIXTURES, "1.dat"), base + ".dat")
    if width == 4:
        shutil.copy(os.path.join(FIXTURES, "1.idx"), base + ".idx")
    else:
        _repack_idx(os.path.join(FIXTURES, "1.idx"), base + ".idx", width)
    striping.write_ec_files(base, get_coder("numpy", 10, 4), SHRUNK,
                            buffer_size=50)
    striping.write_sorted_ecx_from_idx(base, offset_size=width)
    return base


def test_width5_index_and_ecx_pinned(tmp_path):
    base = _prepare(tmp_path, 5)
    assert _sha(base + ".idx") == GOLDEN["idx_w5"]
    assert _sha(base + ".ecx") == GOLDEN["ecx_w5"]
    # entry width really is 17 bytes (8 key + 5 offset + 4 size)
    assert os.path.getsize(base + ".ecx") % 17 == 0


def test_width5_needle_level_identity(tmp_path):
    base = _prepare(tmp_path, 5)
    dat_size = os.path.getsize(base + ".dat")
    shards = []
    for i in range(14):
        with open(base + to_ext(i), "rb") as f:
            shards.append(np.frombuffer(f.read(), dtype=np.uint8))
    with open(base + ".dat", "rb") as f:
        dat = f.read()
    checked = 0
    for key, stored_offset, size in idx_mod.iter_index_file(
            base + ".idx", offset_size=5):
        if t.size_is_deleted(size):
            continue
        offset = t.stored_to_offset(stored_offset)
        got = bytearray()
        for iv in locate.locate_data(SHRUNK, dat_size, offset, size):
            sid, soff = iv.to_shard_id_and_offset(SHRUNK)
            got += shards[sid][soff:soff + iv.size].tobytes()
        assert bytes(got) == dat[offset:offset + size], f"needle {key}"
        checked += 1
    assert checked > 100


def _fold(tmp_path, width: int) -> tuple[str, list[int]]:
    base = _prepare(tmp_path, width)
    doomed = _doomed_keys(base, width)
    assert len(doomed) > 10
    _write_ecj(base, doomed)
    if width == 4:
        assert _sha(base + ".ecj") == GOLDEN["ecj"]
    striping.write_idx_file_from_ec_index(base, offset_size=width)
    rebuild_ecx_file(base, offset_size=width)
    return base, doomed


def test_ecj_fold_width4_pinned(tmp_path):
    base, doomed = _fold(tmp_path, 4)
    assert _sha(base + ".ecx") == GOLDEN["ecx_w4_folded"]
    assert _sha(base + ".idx") == GOLDEN["idx_w4_from_ec"]
    # the fold consumed the journal (RebuildEcxFile drops .ecj)
    assert not os.path.exists(base + ".ecj")
    # every doomed key is tombstoned in the folded ecx, everything else
    # is untouched
    dead = {k for k, _o, s in
            idx_mod.iter_index_file(base + ".ecx")
            if t.size_is_deleted(s)}
    assert set(doomed) <= dead


def test_ecj_fold_width5_pinned(tmp_path):
    base, doomed = _fold(tmp_path, 5)
    assert _sha(base + ".ecx") == GOLDEN["ecx_w5_folded"]
    assert _sha(base + ".idx") == GOLDEN["idx_w5_from_ec"]
    dead = {k for k, _o, s in
            idx_mod.iter_index_file(base + ".ecx", offset_size=5)
            if t.size_is_deleted(s)}
    assert set(doomed) <= dead
    # both widths tombstone the SAME key set: the fold math is
    # width-independent even though the wire layout is not
    sub = tmp_path / "w4"
    sub.mkdir()
    _base4, doomed4 = _fold(sub, 4)
    assert doomed == doomed4
