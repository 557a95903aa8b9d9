"""The plain reference of a rebuild: what `ec.rebuild` must leave in the
place of the shard files a volume lost. Beside `reference.py`, whose plain
encoder it walks and whose field arithmetic it decodes with, and like it
imports nothing of the program.

- Decode: with S the k lowest shard ids that survive, the k x k rows of
  `reference.encoding_matrix` at S are inverted (`mat_inv`), and each lost
  row is its own row of the encoding matrix times that inverse, applied
  (`apply_rows`) to the survivors' bytes: shard i = E_i D and the
  survivors = E_S D, so shard i = E_i E_S^-1 survivors.
- Check: the decoded rows against the rows `iter_shard_chunks` encodes
  from the sealed `.dat` at the lost ids, byte for byte
  (`decode_mismatches`, 0 unless the reference contradicts itself).
- One walk gives the hashes each rebuilt file must have (blake2b-128, as
  `repair.py` takes them and `reference_warmdown.files_differing` counts
  them) and, where `against` names the base of files
  on a disk, the lost files there that differ in any byte or in length.
- The control: in the place of lost file i, the hash of parity file
  k + i, as a rebuild that wrote the parity rows again would leave.
"""

from __future__ import annotations

import hashlib
import os

import numpy as np

import reference


def exts(shard_ids: list[int]) -> list[str]:
    return [f".ec{i:02d}" for i in shard_ids]


def decode_rows(k: int, m: int, survivors: list[int], lost: list[int]
                ) -> list[list[int]]:
    """The len(lost) x k matrix that takes the survivors' bytes, in the
    order given, to the lost shards' bytes."""
    matrix = reference.encoding_matrix(k, m)
    inverse = reference.mat_inv([matrix[s] for s in survivors])
    return reference.mat_mul([matrix[i] for i in lost], inverse)


def walk(ref_base: str, k: int, m: int, large_block: int, small_block: int,
         lost: list[int], against: str | None = None,
         threads: int = 4) -> dict:
    """One pass of the plain encoder over `<ref_base>.dat`, decoding the
    `lost` rows from the k lowest surviving ones.

    Returns `hashes` (extension of a lost file -> what it must hash to),
    `control_hashes` (the parity files' hashes in their place),
    `decode_mismatches`, `shard_bytes` and, where `against` is given,
    `differing`: the lost files there that are not the reference's."""
    lost = sorted(int(s) for s in lost)
    survivors = [i for i in range(k + m) if i not in lost][:k]
    if len(survivors) < k:
        raise ValueError(f"{len(lost)} lost of RS({k},{m}): no k survive")
    rows = decode_rows(k, m, survivors, lost)
    names = exts(lost)
    hashers = [hashlib.blake2b(digest_size=16) for _ in lost]
    control = [hashlib.blake2b(digest_size=16) for _ in lost]
    mismatches = 0
    differing: set[str] = set()
    fds: list[int | None] = []
    size = 0
    try:
        if against is not None:
            for ext in names:
                try:
                    fds.append(os.open(against + ext, os.O_RDONLY))
                except OSError:
                    fds.append(None)
                    differing.add(ext)
        got = None
        for offset, chunk in reference.iter_shard_chunks(
                ref_base + ".dat", k, m, large_block, small_block,
                threads=threads):
            width = chunk.shape[1]
            size = offset + width
            decoded = reference.apply_rows_threaded(rows, chunk[survivors],
                                                    threads)
            for i, shard in enumerate(lost):
                mismatches += int(not np.array_equal(decoded[i],
                                                     chunk[shard]))
                hashers[i].update(decoded[i])
                control[i].update(chunk[k + i])
            if got is None or len(got) != width:
                got = np.empty(width, dtype=np.uint8)
            for i, fd in enumerate(fds):
                if fd is None:
                    continue
                if os.preadv(fd, [got], offset) != width \
                        or not np.array_equal(got, decoded[i]):
                    differing.add(names[i])
        for i, fd in enumerate(fds):
            if fd is not None and os.fstat(fd).st_size != size:
                differing.add(names[i])
    finally:
        for fd in fds:
            if fd is not None:
                os.close(fd)
    out = {"hashes": {e: h.hexdigest() for e, h in zip(names, hashers)},
           "control_hashes": {e: h.hexdigest()
                              for e, h in zip(names, control)},
           "decode_mismatches": mismatches, "shard_bytes": size,
           "survivors": survivors}
    if against is not None:
        out["differing"] = sorted(differing)
    return out
