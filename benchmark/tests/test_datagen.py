"""The seed moves the loss and leaves the work: where the objects lie
follows the seed's permutation of the data shards, so the same items
meet a lost shard whichever shards those are."""

import numpy as np

import datagen

POP = {"population_seed": 24, "deletes": 7}
N, SIZE, K, M, BLOCK = 20000, 1024, 10, 4, 1 << 16


def on_lost_shards(seed: int) -> tuple[np.ndarray, list[int]]:
    order = datagen.placement(POP, seed, 0, N, SIZE, K, BLOCK)
    assert np.array_equal(np.sort(order), np.arange(N))
    lost = datagen.lost_shards(seed, 0, K, M, 3, 1)
    shard = (8 + datagen.record_bytes(SIZE) * np.arange(N)) // BLOCK % K
    return np.sort(order[np.isin(shard, lost)]), lost


def test_other_shards_are_lost_and_the_same_items_lie_on_them():
    a, lost_a = on_lost_shards(1)
    b, lost_b = on_lost_shards(2 ** 31 + 12345)
    assert lost_a != lost_b and len(lost_a) == len(lost_b) == 4
    assert all(s < K for s in lost_a[:3]) and lost_a[3] >= K
    shared = len(np.intersect1d(a, b))
    assert shared > 0.98 * max(len(a), len(b))


def test_the_schedule_asks_for_the_same_items_in_another_order():
    load = {"rate_per_s": 100, "zipf_theta": 0.99}
    live = np.arange(N)
    due_a, items_a = datagen.zipf_schedule(POP, load, live, 5.0, 1)
    due_b, items_b = datagen.zipf_schedule(POP, load, live, 5.0, 2)
    assert due_a == due_b and items_a != items_b
    assert sorted(items_a) == sorted(items_b)
