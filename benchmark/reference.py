"""The plain reference: RS(k, m) over GF(2^8) and upstream's two-tier
striping, in numpy, from the published constructions. It imports nothing
of the program under test and takes nothing the program made except the
sealed volume's `.dat` and `.idx`, which are the operation's input.

- Field: GF(2^8), polynomial x^8+x^4+x^3+x^2+1 (0x11D), generator 2.
- Matrix: klauspost/reedsolomon's default for New(k, m): the
  (k+m) x k Vandermonde matrix vm[r][c] = r**c, made systematic by
  multiplying with the inverse of its top k x k square; rows k.. are the
  parity rows.
- Layout: SeaweedFS ec_encoder.go: rows of k large blocks while more than
  one whole large row remains, then rows of k small blocks, the last row
  zero-padded; shard i holds block i of every row, in order.
- `.ecx`: the `.idx` journal folded (a later entry replaces an earlier
  one, a zero offset or a tombstone size deletes) and written ascending by
  needle id, 16 bytes an entry (WriteSortedFileFromIdx).
"""

from __future__ import annotations

import functools
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

POLY = 0x11D
TOMBSTONE = 0xFFFFFFFF


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[:255]
    return exp, log


EXP, LOG = _tables()


def gf_mul(a: int, b: int) -> int:
    return 0 if a == 0 or b == 0 else int(EXP[LOG[a] + LOG[b]])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(EXP[255 - LOG[a]])


def gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    return 0 if a == 0 else int(EXP[(LOG[a] * n) % 255])


def _mul_table() -> np.ndarray:
    t = np.zeros((256, 256), dtype=np.uint8)
    for a in range(1, 256):
        for b in range(1, 256):
            t[a, b] = EXP[LOG[a] + LOG[b]]
    return t


MUL = _mul_table()


def mat_mul(a: list[list[int]], b: list[list[int]]) -> list[list[int]]:
    out = [[0] * len(b[0]) for _ in a]
    for i, row in enumerate(a):
        for j in range(len(b[0])):
            acc = 0
            for t, x in enumerate(row):
                acc ^= gf_mul(x, b[t][j])
            out[i][j] = acc
    return out


def mat_inv(m: list[list[int]]) -> list[list[int]]:
    """Gauss-Jordan over GF(2^8); raises on a singular matrix."""
    n = len(m)
    a = [list(row) + [int(i == j) for j in range(n)]
         for i, row in enumerate(m)]
    for c in range(n):
        p = next((r for r in range(c, n) if a[r][c]), None)
        if p is None:
            raise ValueError("singular matrix")
        a[c], a[p] = a[p], a[c]
        inv = gf_inv(a[c][c])
        a[c] = [gf_mul(x, inv) for x in a[c]]
        for r in range(n):
            if r != c and a[r][c]:
                f = a[r][c]
                a[r] = [x ^ gf_mul(f, y) for x, y in zip(a[r], a[c])]
    return [row[n:] for row in a]


def encoding_matrix(k: int, m: int) -> list[list[int]]:
    """The whole systematic (k+m) x k matrix: identity on top, parity
    rows below."""
    vm = [[gf_pow(r, c) for c in range(k)] for r in range(k + m)]
    return mat_mul(vm, mat_inv(vm[:k]))


def apply_rows_bytewise(rows: list[list[int]], data: np.ndarray
                        ) -> np.ndarray:
    """rows [r][k] times data [k, n] uint8 -> [r, n] uint8, one product
    table look-up per coefficient and byte: the definition."""
    out = np.zeros((len(rows), data.shape[1]), dtype=np.uint8)
    for i, row in enumerate(rows):
        for j, c in enumerate(row):
            out[i] ^= MUL[c][data[j]]
    return out


@functools.lru_cache(maxsize=8)
def _pair_tables(rows: tuple[tuple[int, ...], ...]) -> list[np.ndarray]:
    """For up to four output rows: per input row j a table from a PAIR of
    input bytes (as a little-endian uint16) to the four rows' pairs of
    product bytes, packed into one uint64."""
    v = np.arange(65536)
    lo, hi = v & 0xFF, v >> 8
    tables = []
    for j in range(len(rows[0])):
        packed = np.zeros(65536, dtype=np.uint64)
        for i, row in enumerate(rows):
            pair = (MUL[row[j]][lo].astype(np.uint64)
                    | (MUL[row[j]][hi].astype(np.uint64) << np.uint64(8)))
            packed |= pair << np.uint64(16 * i)
        tables.append(packed)
    return tables


def apply_rows(rows: list[list[int]], data: np.ndarray) -> np.ndarray:
    """The same product as apply_rows_bytewise, eight times faster in
    numpy: two input bytes and four output rows per table look-up. Each
    input byte is still multiplied by each coefficient and the products
    are still XORed; only the grouping differs."""
    if sys.byteorder != "little":
        return apply_rows_bytewise(rows, data)
    n = data.shape[1]
    if n % 2:
        wide = np.zeros((data.shape[0], n + 1), dtype=np.uint8)
        wide[:, :n] = data
        return apply_rows(rows, wide)[:, :n]
    data = np.ascontiguousarray(data)
    out = np.empty((len(rows), n), dtype=np.uint8)
    step = 1 << 19  # columns per pass: the working set stays in cache
    got = np.empty(step // 2, dtype=np.uint64)
    idx = np.empty(step // 2, dtype=np.intp)
    acc = np.empty(step // 2, dtype=np.uint64)
    for g in range(0, len(rows), 4):
        group = tuple(tuple(r) for r in rows[g:g + 4])
        tables = _pair_tables(group)
        for lo in range(0, n, step):
            w = (min(step, n - lo)) // 2
            acc[:w] = 0
            for j, table in enumerate(tables):
                idx[:w] = data[j, lo:lo + 2 * w].view(np.uint16)
                np.take(table, idx[:w], out=got[:w], mode="wrap")
                acc[:w] ^= got[:w]
            for i in range(len(group)):
                out[g + i, lo:lo + 2 * w] = (
                    acc[:w] >> np.uint64(16 * i)).astype(
                        np.uint16).view(np.uint8)
    return out


def apply_rows_threaded(rows: list[list[int]], data: np.ndarray,
                        threads: int) -> np.ndarray:
    """apply_rows over column slices in a thread pool (numpy's gather
    releases the interpreter lock)."""
    n = data.shape[1]
    if threads <= 1 or n < (1 << 20):
        return apply_rows(rows, data)
    step = -(-n // threads)
    out = np.empty((len(rows), n), dtype=np.uint8)

    def part(lo: int) -> None:
        out[:, lo:lo + step] = apply_rows(rows, data[:, lo:lo + step])

    with ThreadPoolExecutor(max_workers=threads) as ex:
        for f in [ex.submit(part, lo) for lo in range(0, n, step)]:
            f.result()
    return out


def stripe_rows(dat_size: int, k: int, large_block: int,
                small_block: int):
    """(offset in .dat, block size) of every stripe row, in order."""
    remaining, processed = dat_size, 0
    while remaining > large_block * k:
        yield processed, large_block
        remaining -= large_block * k
        processed += large_block * k
    while remaining > 0:
        yield processed, small_block
        remaining -= small_block * k
        processed += small_block * k


def shard_size(dat_size: int, k: int, large_block: int,
               small_block: int) -> int:
    return sum(b for _, b in stripe_rows(dat_size, k, large_block,
                                         small_block))


def iter_shard_chunks(dat_path: str, k: int, m: int, large_block: int,
                      small_block: int, matrix: list[list[int]] | None = None,
                      threads: int = 1, rows_per_chunk: int = 16):
    """Yield (shard offset, [k+m, width] uint8) over the whole volume: the
    bytes every shard file holds at that offset, data rows on top. The
    array is reused from one chunk to the next."""
    matrix = matrix or encoding_matrix(k, m)
    parity_rows = matrix[k:]
    dat_size = os.path.getsize(dat_path)
    rows = list(stripe_rows(dat_size, k, large_block, small_block))
    shard_off = 0
    buf = None

    def read_block(fd: int, at: int, dst: np.ndarray) -> None:
        # past the end of the .dat a row reads as zeros
        want = max(0, min(len(dst), dat_size - at))
        if want and os.preadv(fd, [dst[:want]], at) != want:
            raise IOError(f"short read of .dat at {at}")
        dst[want:] = 0

    with open(dat_path, "rb", buffering=0) as dat, \
            ThreadPoolExecutor(max_workers=max(1, threads)) as pool:
        i = 0
        while i < len(rows):
            block = rows[i][1]
            # a chunk is a run of rows of one block size, bounded in bytes
            n = 1
            while (i + n < len(rows) and rows[i + n][1] == block
                   and n < rows_per_chunk
                   and (n + 1) * block * k <= (256 << 20)):
                n += 1
            if buf is None or buf.shape[1] != n * block:
                buf = np.empty((k + m, n * block), dtype=np.uint8)
            # block j of a row goes to shard j
            reads = [pool.submit(read_block, dat.fileno(),
                                 rows[i + r][0] + j * block,
                                 buf[j, r * block:(r + 1) * block])
                     for r in range(n) for j in range(k)]
            for f in reads:
                f.result()
            buf[k:] = apply_rows_threaded(parity_rows, buf[:k], threads)
            yield shard_off, buf
            shard_off += n * block
            i += n


IDX_DTYPE = np.dtype([("key", ">u8"), ("offset", ">u4"), ("size", ">u4")])


def fold_idx(idx_bytes: bytes) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(needle ids ascending, stored offsets, sizes) of the live needles:
    the last entry of a needle id holds, and a zero offset or a tombstone
    size deletes."""
    n = len(idx_bytes) // IDX_DTYPE.itemsize
    e = np.frombuffer(idx_bytes, dtype=IDX_DTYPE, count=n)
    keys, first_from_end = np.unique(e["key"][::-1], return_index=True)
    last = e[n - 1 - first_from_end]
    live = (last["offset"] > 0) & (last["size"] != TOMBSTONE)
    return (keys[live].astype(np.uint64),
            last["offset"][live].astype(np.int64),
            last["size"][live].astype(np.int64))


def sorted_ecx(idx_bytes: bytes) -> bytes:
    keys, offsets, sizes = fold_idx(idx_bytes)
    out = np.empty(len(keys), dtype=IDX_DTYPE)
    out["key"], out["offset"], out["size"] = keys, offsets, sizes
    return out.tobytes()


def locate(offset: int, length: int, dat_size: int, k: int,
           large_block: int, small_block: int
           ) -> list[tuple[int, int, int]]:
    """(shard id, offset in the shard file, size) of every interval that
    the .dat range [offset, offset+length) lies on (ec_locate.go)."""
    n_large = 0
    remaining = dat_size
    while remaining > large_block * k:
        n_large += 1
        remaining -= large_block * k
    large_bytes = n_large * large_block * k
    out = []
    while length > 0:
        if offset < large_bytes:
            block, base = large_block, 0
            rel = offset
        else:
            block, base = small_block, n_large * large_block
            rel = offset - large_bytes
        block_index, inner = divmod(rel, block)
        row, shard = divmod(block_index, k)
        take = min(length, block - inner)
        out.append((shard, base + row * block + inner, take))
        offset += take
        length -= take
    return out
