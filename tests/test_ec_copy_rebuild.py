"""`ec.rebuild` across servers: `EcCommands.rebuild` on a spread volume
picks the holder with the most shards, pulls the survivors it lacks over
`ec/copy`, rebuilds there and drops the copies; the program counts and
times each part (`ec.copy` a file pulled, `seaweedfs_tpu_ec_copy_bytes`,
`ec.rebuild` around the rebuild, what it handed to the coder) and keeps
`ec.rebuild` out of every request's wide event. And `ec/copy` onto a
path that a mapping holds: the file is swapped in whole, so the mapping
goes on reading the bytes it had.
"""

import mmap
import os
import random
import urllib.request

import pytest

from cluster_util import TEST_GEOMETRY, Cluster
from seaweedfs_tpu.observe import wideevents
from seaweedfs_tpu.shell.ec_commands import EcCommands

COLLECTION = "heal"
STAGE = "seaweedfs_tpu_ec_stage_seconds"
BORN = ("seaweedfs_tpu_ec_copy_bytes_total",
        "seaweedfs_tpu_ec_rebuild_input_bytes_total",
        "seaweedfs_tpu_ec_rebuild_batches_total",
        f'{STAGE}_count{{stage="ec.rebuild"}}',
        f'{STAGE}_sum{{stage="ec.rebuild"}}',
        f'{STAGE}_count{{stage="ec.copy"}}',
        f'{STAGE}_sum{{stage="ec.copy"}}')


def metrics(url: str) -> dict[str, float]:
    with urllib.request.urlopen(f"http://{url}/metrics", timeout=10) as r:
        text = r.read().decode()
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, _, value = line.rpartition(" ")
            try:
                out[key] = float(value)
            except ValueError:
                pass
    return out


@pytest.fixture()
def cluster():
    c = Cluster(n_volume_servers=4)
    # a manual ec.rebuild: the daemon would win the race for the deficit
    c.master.repair_enabled = False
    yield c
    c.shutdown()


def held(c: Cluster, vid: int) -> dict[str, list[int]]:
    return {n["url"]: sorted(s["shard_ids"])
            for n in c.client.dir_status()["nodes"]
            for s in n.get("ec_shards", [])
            if s["id"] == vid and s["shard_ids"]}


def test_counters_are_on_metrics_from_zero(cluster):
    got = metrics(cluster.volume_servers[0].url)
    for name in BORN:
        assert name in got, name


def test_rebuild_counts_what_it_pulled_and_coded(cluster):
    c = cluster
    rng = random.Random(39)
    data = bytes(rng.getrandbits(8) for _ in range(60_000))
    fid = c.client.upload(data, collection=COLLECTION)
    c.wait_heartbeats()
    vid = int(fid.split(",")[0])
    cmds = EcCommands(c.client, TEST_GEOMETRY)
    cmds.encode(vid, COLLECTION)
    c.wait_heartbeats()
    spread = held(c, vid)
    assert sorted(len(s) for s in spread.values()) == [3, 3, 4, 4]
    # a holder of four loses its shards, as the benchmark's dead peer:
    # the rebuilder is the other holder of four, which lacks six of the
    # ten survivors
    victim = max(spread, key=lambda u: (len(spread[u]), u))
    c.client.volume_admin(victim, "ec/delete_shards", {
        "volume_id": vid, "collection": COLLECTION,
        "shard_ids": spread[victim]})
    c.wait_heartbeats()
    before = metrics(c.volume_servers[0].url)
    wideevents.reset()

    out = cmds.rebuild(vid, COLLECTION)

    after = metrics(c.volume_servers[0].url)
    delta = {k: after[k] - before.get(k, 0.0) for k in after}
    assert sorted(out["rebuilt"]) == spread[victim]
    assert len(spread[out["rebuilder"]]) == 4 and out["rebuilder"] != victim
    assert len(out["copied"]) == 6
    rebuilder = next(vs for vs in c.volume_servers
                     if vs.url == out["rebuilder"])
    base = os.path.join(rebuilder.store.locations[0].directory,
                        f"{COLLECTION}_{vid}")
    shard = os.path.getsize(base + ".ec00")
    # (the ec registry is shared by the in-process servers: deltas)
    assert delta[f'{STAGE}_count{{stage="ec.rebuild"}}'] == 1
    assert delta[f'{STAGE}_sum{{stage="ec.rebuild"}}'] > 0
    assert delta[f'{STAGE}_count{{stage="ec.copy"}}'] == 6
    assert delta["seaweedfs_tpu_ec_copy_bytes_total"] == 6 * shard
    assert delta["seaweedfs_tpu_ec_rebuild_input_bytes_total"] \
        == TEST_GEOMETRY.data_shards * shard
    assert delta["seaweedfs_tpu_ec_rebuild_batches_total"] >= 1
    # the borrowed copies are gone, nothing half-pulled is left
    for sid in out["copied"]:
        assert not os.path.exists(base + f".ec{sid:02d}")
    assert not [n for n in os.listdir(os.path.dirname(base))
                if n.endswith(".copying")]
    # `ec.rebuild` encloses: in no request's wide event; each copy is in
    # the wide event of the ec/copy request that pulled it
    events = wideevents.events()
    assert not [e for e in events if "ec.rebuild" in e.get("stages", {})]
    copies = [e for e in events if "ec.copy" in e.get("stages", {})]
    assert copies and all("/admin/ec/copy" in e["name"] for e in copies)
    c.wait_heartbeats()
    assert sum(len(s) for s in held(c, vid).values()) \
        == TEST_GEOMETRY.total_shards
    assert c.client.download(fid) == data


def test_copy_onto_a_mapped_path_swaps_the_file_in_whole(cluster):
    """A mapping of the file at the taker's path reads its old bytes
    after an `ec/copy` onto that path (a copy that truncated in place
    would hand the mapping the new bytes, or a SIGBUS past their end)."""
    c = cluster
    giver, taker = c.volume_servers[0], c.volume_servers[1]
    vid, ext = 77, ".ec03"
    name = f"{COLLECTION}_{vid}{ext}"
    old = bytes(range(256)) * 64
    new = bytes(reversed(range(256))) * 96
    with open(os.path.join(giver.store.locations[0].directory, name),
              "wb") as f:
        f.write(new)
    path = os.path.join(taker.store.locations[0].directory, name)
    with open(path, "wb") as f:
        f.write(old)
    before = metrics(taker.url)
    with open(path, "rb") as f:
        mapped = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
    try:
        c.client.volume_admin(taker.url, "ec/copy", {
            "volume_id": vid, "collection": COLLECTION, "shard_ids": [3],
            "source": giver.url})
        assert mapped[:] == old
    finally:
        mapped.close()
    with open(path, "rb") as f:
        assert f.read() == new
    assert not os.path.exists(path + ".copying")
    after = metrics(taker.url)
    assert after["seaweedfs_tpu_ec_copy_bytes_total"] \
        - before["seaweedfs_tpu_ec_copy_bytes_total"] == len(new)
