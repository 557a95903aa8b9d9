"""Seeded inputs: the objects of a volume, what is deleted, where each
object lies, which shards are lost, and an open loop's schedule.

Every seed does the SAME work. The object size and count are the
configuration's (`weed benchmark`'s defaults); the deleted items, the
arrival instants and the multiset of requested items come from the
traffic file's `population_seed` alone. The run's `--seed` decides every
object's bytes and cookie, the order of the requests, which parity shard
is lost, and a permutation of the k data shards: the lost data shards
are the images of the first `lost_data` logical shards under it, and an
object that the population puts on logical shard j lies on the physical
shard the permutation sends j to. So each seed loses other shard files
(any of the C(k, lost_data) sets) and solves with another matrix, while
the same requests meet a lost shard on every seed. Without that the
hot keys of a Zipfian schedule land on lost shards on one seed and not
on the next, and the share of GETs that reconstruct swings by a third.
"""

from __future__ import annotations

import numpy as np

BLOCK_ITEMS = 4096  # objects whose bytes come from one generator


def _rng(*key: int) -> np.random.Generator:
    return np.random.default_rng([int(k) & 0xFFFFFFFFFFFFFFFF for k in key])


def record_bytes(size: int) -> int:
    """What one object of `size` bytes takes in the .dat as the store's
    writer lays it down: a version-3 needle with no name: header 16, body
    size 4, body, flags 1, checksum 4, append stamp 8, padded to 8."""
    raw = 16 + 4 + size + 1 + 4 + 8
    return raw + (-raw) % 8


def dat_bytes(n_items: int, size: int) -> int:
    return 8 + n_items * record_bytes(size)  # the superblock first


def deleted_items(pop: dict, n_items: int) -> list[int]:
    rng = _rng(pop["population_seed"], 2)
    return sorted(int(i) for i in rng.choice(n_items, pop["deletes"],
                                             replace=False))


def shard_permutation(seed: int, volume: int, k: int) -> np.ndarray:
    """logical data shard -> physical data shard, from the seed."""
    return _rng(seed, 6, volume).permutation(k)


def placement(pop: dict, seed: int, volume: int, n_items: int, size: int,
              k: int, small_block: int) -> np.ndarray:
    """order[position] = the item written at that position of volume
    `volume`. The logical order is a permutation from the population
    seed; block j of every stripe row then moves to block perm[j] of the
    same row, item for item. Blocks do not hold the same number of
    records and the last row is ragged, so a few items per block (under
    half a percent of all) find no twin and take the places left over,
    in order."""
    logical = _rng(pop["population_seed"], 3, volume).permutation(n_items)
    perm = shard_permutation(seed, volume, k)
    inverse = np.argsort(perm)
    record = record_bytes(size)
    block = (8 + record * np.arange(n_items, dtype=np.int64)) // small_block
    first = np.searchsorted(block, np.arange(block[-1] + 2))  # per block
    within = np.arange(n_items) - first[block]
    # the logical position whose item comes to each physical position
    twin = block - block % k + inverse[block % k]
    there = twin <= block[-1]
    twin = np.minimum(twin, block[-1])
    source = first[twin] + within
    ok = there & (source < first[twin + 1])
    order = np.full(n_items, -1, dtype=np.int64)
    order[ok] = logical[source[ok]]
    used = np.zeros(n_items, dtype=bool)
    used[source[ok]] = True
    order[~ok] = logical[~used]
    return order


def lost_shards(seed: int, volume: int, k: int, m: int, lost_data: int,
                lost_parity: int) -> list[int]:
    perm = shard_permutation(seed, volume, k)
    parity = _rng(seed, 9, volume).choice(m, lost_parity, replace=False)
    return sorted(int(s) for s in perm[:lost_data]) \
        + sorted(k + int(s) for s in parity)


def cookies(seed: int, volume: int, n_items: int) -> np.ndarray:
    return _rng(seed, 5, volume).integers(0, 1 << 32, n_items,
                                          dtype=np.uint64)


def block_bytes(seed: int, volume: int, block: int, size: int) -> bytes:
    """The bodies of items block*BLOCK_ITEMS.. of a volume, end to end."""
    return _rng(seed, 4, volume, block).bytes(BLOCK_ITEMS * size)


class Bodies:
    """item -> its bytes, a block of items at a time (the load generator
    asks for a few thousand of a million)."""

    def __init__(self, seed: int, volume: int, size: int):
        self.key = (seed, volume)
        self.size = size
        self.block: tuple[int, bytes] | None = None

    def __call__(self, item: int) -> bytes:
        b, i = divmod(item, BLOCK_ITEMS)
        if self.block is None or self.block[0] != b:
            self.block = (b, block_bytes(*self.key, b, self.size))
        return self.block[1][i * self.size:(i + 1) * self.size]


def zipf_schedule(pop: dict, load: dict, live_items: np.ndarray,
                  seconds: float, seed: int
                  ) -> tuple[list[float], list[int]]:
    """(due times, items) of an open loop: exponential gaps at `rate`
    and Zipfian popularity (YCSB's constant 0.99) over the live items,
    both from the population seed; the run's seed shuffles which request
    comes when."""
    rng = _rng(pop["population_seed"], 7)
    n = int(load["rate_per_s"] * seconds)
    due = np.cumsum(rng.exponential(1.0 / load["rate_per_s"], n))
    due = due[due < seconds]
    ranks = np.arange(1, len(live_items) + 1, dtype=np.float64)
    p = ranks ** -load["zipf_theta"]
    order = rng.permutation(len(live_items))  # rank -> item, fixed
    picks = rng.choice(len(live_items), len(due), p=p / p.sum())
    items = live_items[order[picks]][_rng(seed, 8).permutation(len(due))]
    return [float(t) for t in due], [int(i) for i in items]
