"""The plain reference of a warm-down: what any number of `ec.encode`
passes over one sealed volume must leave beside it. Beside `reference.py`,
whose plain encoder it runs, and like it imports nothing of the program.

- The files: `.ec00` .. `.ec<k+m-1>`, shard i holding block i of every
  stripe row with the parity rows of `reference.encoding_matrix` below the
  data rows, and the `.ecx` of `reference.sorted_ecx`, over the kept
  `.dat` and `.idx`. A pass writes every file anew from the same input, so
  the fifth pass leaves what the first did.
- One walk over the volume gives the hashes a pass's files must have
  (blake2b-128, as `maint.py` takes them) and compares files that stand
  on a disk byte for byte, without writing the reference's own.
- The control: the same walk with a Cauchy matrix in the place of the
  encoding matrix (parity row i, column j = 1 / (x_i + y_j) over GF(2^8),
  x_i = k + i, y_j = j): a valid MDS code, and not the one upstream's
  shards are read back with. Its data shards and `.ecx` are the
  reference's own; every parity file differs.
"""

from __future__ import annotations

import hashlib
import os

import numpy as np

import reference


def exts(k: int, m: int) -> list[str]:
    return [f".ec{i:02d}" for i in range(k + m)] + [".ecx"]


def cauchy_matrix(k: int, m: int) -> list[list[int]]:
    """The systematic (k+m) x k matrix whose parity rows are Cauchy."""
    top = [[int(r == c) for c in range(k)] for r in range(k)]
    return top + [[reference.gf_inv((k + i) ^ j) for j in range(k)]
                  for i in range(m)]


def digest(data: bytes) -> str:
    return hashlib.blake2b(data, digest_size=16).hexdigest()


def walk(ref_base: str, k: int, m: int, large_block: int, small_block: int,
         against: str | None = None, threads: int = 4) -> dict:
    """One pass of the plain encoder over `<ref_base>.dat` / `.idx`.

    Returns `hashes` (extension -> what a pass's file must hash to),
    `control_hashes` (the same under the control's matrix) and, where
    `against` names the base of files that stand on a disk, `differing`:
    the extensions whose file differs from the reference in any byte or
    in length."""
    matrix = reference.encoding_matrix(k, m)
    control_rows = cauchy_matrix(k, m)[k:]
    names = exts(k, m)
    hashers = [hashlib.blake2b(digest_size=16) for _ in range(k + m)]
    control = [hashlib.blake2b(digest_size=16) for _ in range(m)]
    differing: set[str] = set()
    fds = []
    try:
        if against is not None:
            for ext in names[:k + m]:
                try:
                    fds.append(os.open(against + ext, os.O_RDONLY))
                except OSError:
                    fds.append(None)
                    differing.add(ext)
        size = 0
        got = None
        for offset, rows in reference.iter_shard_chunks(
                ref_base + ".dat", k, m, large_block, small_block,
                matrix=matrix, threads=threads):
            width = rows.shape[1]
            size = offset + width
            for i in range(k + m):
                hashers[i].update(rows[i])
            parity = reference.apply_rows_threaded(control_rows, rows[:k],
                                                   threads)
            for i in range(m):
                control[i].update(parity[i])
            if against is None:
                continue
            if got is None or len(got) != width:
                got = np.empty(width, dtype=np.uint8)
            for i, fd in enumerate(fds):
                if fd is None:
                    continue
                if os.preadv(fd, [got], offset) != width \
                        or not np.array_equal(got, rows[i]):
                    differing.add(names[i])
        for i, fd in enumerate(fds):
            if fd is not None and os.fstat(fd).st_size != size:
                differing.add(names[i])
    finally:
        for fd in fds:
            if fd is not None:
                os.close(fd)
    with open(ref_base + ".idx", "rb") as f:
        ecx = reference.sorted_ecx(f.read())
    if against is not None:
        try:
            with open(against + ".ecx", "rb") as f:
                if f.read() != ecx:
                    differing.add(".ecx")
        except OSError:
            differing.add(".ecx")
    hashes = {ext: h.hexdigest() for ext, h in zip(names, hashers)}
    hashes[".ecx"] = digest(ecx)
    control_hashes = dict(hashes)
    for i in range(m):
        control_hashes[names[k + i]] = control[i].hexdigest()
    out = {"hashes": hashes, "control_hashes": control_hashes,
           "shard_bytes": size}
    if against is not None:
        out["differing"] = sorted(differing)
    return out


def files_differing(want: dict[str, str], got: dict[str, str]) -> int:
    """How many of the reference's files a pass's hashes miss or
    contradict."""
    return sum(got.get(ext) != h for ext, h in want.items())
