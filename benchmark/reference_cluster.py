"""The plain reference of where an EC volume's shards lie on a cluster:
upstream's balanced spread, and the layout a seed asks of four servers.
Beside `reference.py`, and like it imports nothing of the program.

- Spread: SeaweedFS shell/command_ec_encode.go:248-263
  (`balancedEcDistribution`): shard ids in order, each to the next
  server in turn that has a free slot. With room everywhere that is
  round robin: server i of n ends with shards i, i+n, i+2n, ...: 4/4/3/3
  of RS(10,4) on four servers.
- Layout: the wiki's least cluster for 10+4 (*Erasure Coding for warm
  storage*: four servers, 3-4 shards each, any one may die). In logical
  terms the same on every seed, so that the same requests meet a lost
  shard, a local shard or a peer's shard whatever the seed (datagen's
  docstring): with `perm` the seed's permutation of the data shards,
  the server that dies holds the shards the seed loses (perm[0:3] and
  one parity shard), the server that answers holds perm[3:6] and the
  lowest parity shard left, the two other peers hold perm[6:8] and
  perm[8:10] and one of the parity shards left each.
"""

from __future__ import annotations

ROLES = ("chip", "doomed", "peer_a", "peer_b")


def balanced_distribution(free_slots: list[int], total_shards: int
                          ) -> list[list[int]]:
    """Shard ids each server gets, by server in the order given."""
    if not any(s > 0 for s in free_slots):
        raise ValueError("no server has a free slot")
    out: list[list[int]] = [[] for _ in free_slots]
    left = list(free_slots)
    server = 0
    for shard in range(total_shards):
        while left[server] <= 0:
            server = (server + 1) % len(left)
        out[server].append(shard)
        left[server] -= 1
        server = (server + 1) % len(left)
    return out


def seed_layout(perm: list[int], lost: list[int], k: int, m: int
                ) -> dict[str, list[int]]:
    """role -> the shard ids it ends with. `lost` is the seed's answer
    (3 data + 1 parity here): it has to be perm's first data shards and
    one parity shard, or the layout is not the mix's."""
    perm = [int(s) for s in perm]
    lost = sorted(int(s) for s in lost)
    lost_data = [s for s in lost if s < k]
    lost_parity = [s for s in lost if s >= k]
    if sorted(perm) != list(range(k)) or len(lost_parity) != 1 \
            or lost_data != sorted(perm[:len(lost_data)]) \
            or len(lost_data) != 3 or m != 4 or k != 10:
        raise ValueError(f"not the mix's loss: perm {perm}, lost {lost}, "
                         f"RS({k},{m})")
    parity = [s for s in range(k, k + m) if s not in lost_parity]
    return {"doomed": lost,
            "chip": sorted(perm[3:6]) + parity[:1],
            "peer_a": sorted(perm[6:8]) + parity[1:2],
            "peer_b": sorted(perm[8:10]) + parity[2:3]}


def misplaced(held: dict[str, list[int]], want: dict[str, list[int]]
              ) -> int:
    """How many (server, shard) pairs differ between what servers hold
    and what they should: a shard missing where it belongs and a shard
    where it does not belong count one each. A server absent from `want`
    should hold nothing."""
    out = 0
    for server in set(held) | set(want):
        out += len(set(held.get(server, ())) ^ set(want.get(server, ())))
    return out
