"""The plain reference of a degraded read: one needle out of a sealed
volume of which some shard files are lost, from the `.dat` and `.idx`
alone. It imports nothing of the program under test; field, matrix,
layout and index are `reference.py`'s, and what is solved here is solved
by Gaussian elimination over GF(2^8) on the survivors' bytes themselves.

A needle's record (version 3: header 16 = cookie 4, id 8, size 4; `size`
bytes of body = data size 4, data, flags 1, ...; CRC 4; append stamp 8;
zeros to a multiple of 8) lies on the data shards in parts that
`reference.locate` gives. A part on a surviving shard is the `.dat`'s
own bytes. A part on a lost shard is solved for: the bytes that the
first k surviving shards hold at the part's place in their files (a data
shard's from the `.dat`, zeros past its end; a parity shard's from the
plain encoder over the row) are the right-hand sides of k equations in
the k data rows, one equation a survivor, its coefficients that shard's
row of the encoding matrix.
"""

from __future__ import annotations

import functools
import os
import struct

import google_crc32c  # CRC-32C (Castagnoli), not the program's
import numpy as np

import reference

HEADER = 16


matrix_of = functools.lru_cache(maxsize=4)(reference.encoding_matrix)


def record_bytes(size: int) -> int:
    """The record's length on disk from the index's `size` (the body's)."""
    raw = HEADER + size + 4 + 8
    return raw + (-raw) % 8


def masked_crc(data: bytes) -> int:
    """What a record stores: CRC-32C of the data, rotated right by 15 and
    offset by 0xA282EAD8 (SeaweedFS needle/crc.go, after LevelDB)."""
    crc = google_crc32c.value(data)
    return ((crc >> 15 | crc << 17) + 0xA282EAD8) & 0xFFFFFFFF


def dat_offset(shard: int, at: int, dat_size: int, k: int, large_block: int,
               small_block: int) -> int:
    """Where byte `at` of data shard `shard`'s file lies in the `.dat`:
    `reference.locate` the other way."""
    n_large = sum(1 for _, b in reference.stripe_rows(
        dat_size, k, large_block, small_block) if b == large_block)
    if at < n_large * large_block:
        row, inner = divmod(at, large_block)
        return (row * k + shard) * large_block + inner
    row, inner = divmod(at - n_large * large_block, small_block)
    return (n_large * k * large_block
            + (row * k + shard) * small_block + inner)


def data_rows(dat: bytes, at: int, size: int, k: int, large_block: int,
              small_block: int) -> np.ndarray:
    """[k, size]: what the k data shards hold at [at, at + size) of their
    files; a part never crosses a block, so each is one range of the
    `.dat`, and zeros past its end."""
    rows = np.zeros((k, size), dtype=np.uint8)
    for j in range(k):
        lo = dat_offset(j, at, len(dat), k, large_block, small_block)
        have = dat[lo:lo + size]
        rows[j, :len(have)] = np.frombuffer(have, dtype=np.uint8)
    return rows


def solve(coeffs: list[list[int]], rhs: np.ndarray) -> np.ndarray:
    """x [k, n] with coeffs [k][k] . x = rhs [k, n] over GF(2^8):
    Gauss-Jordan on the augmented system, a row operation a pass over
    the n columns through the product table."""
    n = len(coeffs)
    a = [list(row) for row in coeffs]
    b = np.array(rhs, dtype=np.uint8, copy=True)
    for c in range(n):
        p = next((r for r in range(c, n) if a[r][c]), None)
        if p is None:
            raise ValueError("singular system")
        if p != c:
            a[c], a[p] = a[p], a[c]
            b[[c, p]] = b[[p, c]]
        inv = reference.gf_inv(a[c][c])
        a[c] = [reference.gf_mul(x, inv) for x in a[c]]
        b[c] = reference.MUL[inv][b[c]]
        for r in range(n):
            f = a[r][c]
            if r != c and f:
                a[r] = [x ^ reference.gf_mul(f, y)
                        for x, y in zip(a[r], a[c])]
                b[r] ^= reference.MUL[f][b[c]]
    return b


def rebuild_part(dat: bytes, shard: int, at: int, size: int,
                 lost: set[int], k: int, m: int, large_block: int,
                 small_block: int) -> bytes:
    """[at, at + size) of lost data shard `shard`'s file, from the first
    k shards that are left."""
    matrix = matrix_of(k, m)
    survivors = [s for s in range(k + m) if s not in lost][:k]
    if len(survivors) < k:
        raise ValueError(f"only {len(survivors)} of {k} shards are left")
    rows = data_rows(dat, at, size, k, large_block, small_block)
    parity = [s for s in survivors if s >= k]
    held = np.empty((k, size), dtype=np.uint8)
    if parity:
        # the pair tables of `apply_rows` cost more than a short part
        apply = (reference.apply_rows if size >= 1 << 18
                 else reference.apply_rows_bytewise)
        coded = apply([matrix[s] for s in parity], rows)
    for i, s in enumerate(survivors):
        held[i] = rows[s] if s < k else coded[parity.index(s)]
    return solve([matrix[s] for s in survivors], held)[shard].tobytes()


def read_degraded(dat: str | bytes, idx: bytes, key: int,
                  lost: list[int], k: int = 10, m: int = 4,
                  large_block: int = 1 << 30, small_block: int = 1 << 20
                  ) -> tuple[int, bytes]:
    """(cookie, data) of needle `key` of the sealed volume (`dat`: its
    path or its bytes; `idx`: its index's bytes) with the shard files
    `lost` gone. KeyError for a needle that is not live, ValueError for
    a record whose checksum does not hold."""
    if isinstance(dat, str):
        with open(os.fspath(dat), "rb") as f:
            dat = f.read()
    keys, offsets, sizes = reference.fold_idx(idx)
    i = int(np.searchsorted(keys, np.uint64(key)))
    if i == len(keys) or int(keys[i]) != key:
        raise KeyError(key)
    gone = set(lost)
    parts = []
    for shard, at, size in reference.locate(
            int(offsets[i]) * 8, record_bytes(int(sizes[i])), len(dat), k,
            large_block, small_block):
        if shard in gone:
            parts.append(rebuild_part(dat, shard, at, size, gone, k, m,
                                      large_block, small_block))
        else:
            lo = dat_offset(shard, at, len(dat), k, large_block,
                            small_block)
            parts.append(dat[lo:lo + size].ljust(size, b"\0"))
    record = b"".join(parts)
    cookie, needle_id, size = struct.unpack_from(">IQI", record)
    if needle_id != key or size != int(sizes[i]):
        raise ValueError(f"record of {key:x} holds {needle_id:x}, "
                         f"{size} bytes")
    n_data, = struct.unpack_from(">I", record, HEADER)
    data = record[HEADER + 4:HEADER + 4 + n_data]
    stored, = struct.unpack_from(">I", record, HEADER + size)
    if stored != masked_crc(data):
        raise ValueError(f"needle {key:x}: CRC mismatch")
    return cookie, data
