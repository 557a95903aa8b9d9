"""An EC volume spread over four volume servers, one of them dead: every
needle read through ONE server, whose survivors come from peers, equals
the plain numpy reading of the same `.dat`.

The deployment of `benchmark/configs/seaweed-4srv-rs10-4.json` at a small
size on the CPU: `EcCommands.encode` spreads 4/4/3/3 by the program's own
plan, shards move to a stated layout with the steps `EcCommands.balance`
issues, the server that holds three data shards and a parity shard stops
(its gRPC heartbeat stream closes, so the master drops it at once), and
the reader takes plain intervals and reconstructions' survivors from the
two peers that are left over `VolumeEcShardRead`.
"""

import importlib.util
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from cluster_util import (TEST_GEOMETRY, Cluster, free_port,
                          free_port_with_grpc_twin)
from seaweedfs_tpu import ec, observe
from seaweedfs_tpu.shell.ec_commands import EcCommands
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COLLECTION = "spread"
STAGE_COUNT = ('seaweedfs_tpu_ec_stage_seconds_count'
               '{stage="ec.get.remote_read"}')


def _bench(name: str):
    """A module of benchmark/ (the plain reference imports nothing of the
    program)."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{name}", os.path.join(ROOT, "benchmark", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _bench("reference")
reference_cluster = _bench("reference_cluster")

# (reader, doomed, peer, peer): three data + one parity die each time
LAYOUTS = {
    "first-data-shards": ([3, 4, 5, 11], [0, 1, 2, 10], [6, 7, 12],
                          [8, 9, 13]),
    "scattered": ([0, 5, 8, 10], [3, 7, 9, 13], [1, 2, 11], [4, 6, 12]),
    "last-parity": ([1, 2, 9, 12], [4, 6, 8, 13], [0, 3, 10], [5, 7, 11]),
}


def _metrics(url: str) -> dict[str, float]:
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


def _held(c: Cluster, vid: int) -> dict[str, list[int]]:
    return {n["url"]: sorted(s["shard_ids"])
            for n in c.client.dir_status()["nodes"]
            for s in n.get("ec_shards", [])
            if s["id"] == vid and s["shard_ids"]}


def _wait(pred, what: str, limit: float = 10.0) -> None:
    deadline = time.time() + limit
    while not pred():
        assert time.time() < deadline, what
        time.sleep(0.02)


@pytest.fixture()
def cluster():
    grpc_port = free_port()
    c = Cluster(n_volume_servers=0, master_grpc_port=grpc_port)
    # the state between a server's death and its repair: the daemon would
    # rebuild the dead server's shards within a few pulses
    c.master.repair_enabled = False
    for _ in range(4):
        c.add_volume_server(with_grpc=True, use_grpc_heartbeat=True)
    c.wait_for_nodes(4)
    yield c
    c.shutdown()


def _fill(c: Cluster) -> tuple[int, dict[int, tuple[int, bytes]], bytes,
                              bytes]:
    """One volume of seeded needles through the store's own writer (as
    the benchmark fills its volume); returns its id, {needle id:
    (cookie, body)} and the sealed .dat and .idx."""
    c.client.grow(count=1, collection=COLLECTION)
    src = next(vs for vs in c.volume_servers
               if any(v.collection == COLLECTION
                      for loc in vs.store.locations
                      for v in loc.volumes.values()))
    vid = next(v.vid for loc in src.store.locations
               for v in loc.volumes.values() if v.collection == COLLECTION)
    rng = np.random.default_rng(28)
    needles = {}
    for nid in range(1, 161):
        body = rng.bytes(int(rng.integers(1, 9000)))
        cookie = int(rng.integers(0, 1 << 32))
        needles[nid] = (cookie, body)
        src.store.write_needle(vid, Needle(cookie=cookie, id=nid,
                                           data=body))
    for nid in (7, 70):  # deleted needles stay deleted
        src.store.delete_needle(vid, Needle(cookie=needles[nid][0],
                                            id=nid))
        del needles[nid]
    base = os.path.join(src.store.locations[0].directory,
                        f"{COLLECTION}_{vid}")
    for loc in src.store.locations:
        for v in loc.volumes.values():
            v.sync()
    with open(base + ".dat", "rb") as f:
        dat = f.read()
    with open(base + ".idx", "rb") as f:
        idx = f.read()
    c.wait_heartbeats()
    return vid, needles, dat, idx


def _move(c: Cluster, vid: int, want: dict[str, list[int]]) -> None:
    """ec/copy -> ec/mount at the taker, then ec/delete_shards at the
    giver: `EcCommands.balance`'s steps."""
    holder = {s: u for u, sids in _held(c, vid).items() for s in sids}
    pairs: dict[tuple[str, str], list[int]] = {}
    for url, sids in want.items():
        for s in sids:
            if holder[s] != url:
                pairs.setdefault((holder[s], url), []).append(s)
    for (src, dst), sids in pairs.items():
        body = {"volume_id": vid, "collection": COLLECTION,
                "shard_ids": sids}
        c.client.volume_admin(dst, "ec/copy", {**body, "source": src,
                                               "copy_ecx_file": True})
        c.client.volume_admin(dst, "ec/mount", body)
    for (src, _), sids in pairs.items():
        c.client.volume_admin(src, "ec/delete_shards", {
            "volume_id": vid, "collection": COLLECTION, "shard_ids": sids})
    _wait(lambda: _held(c, vid) == {u: sorted(s) for u, s in want.items()},
          "the master never saw the layout")


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_every_needle_through_one_server_equals_the_dat(cluster, name):
    c = cluster
    g = TEST_GEOMETRY
    vid, needles, dat, idx = _fill(c)
    EcCommands(c.client, g).encode(vid, COLLECTION)
    _wait(lambda: sum(len(s) for s in _held(c, vid).values()) == 14,
          "the master never saw 14 shards")
    spread = _held(c, vid)
    # upstream's balanced distribution, restated by the plain reference
    assert sorted(len(s) for s in spread.values()) == sorted(
        len(s) for s in reference_cluster.balanced_distribution(
            [8] * 4, g.total_shards))

    urls = [vs.url for vs in c.volume_servers]
    want = dict(zip(urls, LAYOUTS[name]))
    _move(c, vid, want)
    reader, doomed = c.volume_servers[0], c.volume_servers[1]
    lost = set(LAYOUTS[name][1])
    remote = set(LAYOUTS[name][2]) | set(LAYOUTS[name][3])

    c.stop_volume_server(1)
    _wait(lambda: not any(doomed.url in u for u in
                          c.client.ec_lookup(vid)["shards"].values()),
          "the master still names the dead server", limit=5.0)
    assert reference_cluster.misplaced(
        _held(c, vid), {u: s for u, s in want.items()
                        if u != doomed.url}) == 0

    # the plain reading of the sealed .dat: the .idx folded, the body at
    # header 16 + body size 4
    keys, offsets, sizes = reference.fold_idx(idx)
    assert sorted(int(k) for k in keys) == sorted(needles)
    before = _metrics(reader.url)
    on_lost = on_peer = on_local = 0
    for key, off in zip(keys, offsets):
        cookie, body = needles[int(key)]
        at = int(off) * 8 + 20
        assert dat[at:at + len(body)] == body
        shards = {s for s, _, _ in reference.locate(
            at, len(body), len(dat), g.data_shards, g.large_block_size,
            g.small_block_size)}
        on_lost += bool(shards & lost)
        on_peer += bool(shards & remote)
        on_local += bool(shards - lost - remote)
        with urllib.request.urlopen(
                f"http://{reader.url}/{vid},{int(key):x}{cookie:08x}",
                timeout=30) as r:
            assert r.read() == body, (name, int(key))
    # the pattern met every kind of interval
    assert on_lost and on_peer and on_local
    for nid in (7, 70):
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(
                f"http://{reader.url}/{vid},{nid:x}00000000", timeout=30)
        assert e.value.code == 404
        e.value.close()

    # what the reader counted: intervals from peers over gRPC, lookups
    # that found nobody (the lost shards), and a stage for each fetch
    after = _metrics(reader.url)

    def rose(key: str) -> float:
        return after[key] - before.get(key, 0.0)  # a stage is born late

    fam = "seaweedfs_tpu_volume_ec_"
    assert rose(fam + 'remote_shard_reads_total{via="grpc"}') >= on_peer
    assert rose(fam + 'remote_shard_reads_total{via="http"}') == 0
    assert rose(fam + "remote_shard_read_bytes_total") > 0
    assert rose(fam + 'shard_location_lookups_total{result="none"}') > 0
    assert rose(STAGE_COUNT) >= rose(
        fam + 'remote_shard_reads_total{via="grpc"}')


def test_remote_read_counters_are_born_at_zero():
    c = Cluster(n_volume_servers=1)
    try:
        got = _metrics(c.volume_servers[0].url)
        fam = "seaweedfs_tpu_volume_ec_"
        for key in ('remote_shard_reads_total{via="grpc"}',
                    'remote_shard_reads_total{via="http"}',
                    "remote_shard_read_bytes_total",
                    'shard_location_lookups_total{result="holder"}',
                    'shard_location_lookups_total{result="none"}'):
            assert got[fam + key] == 0.0, key
    finally:
        c.shutdown()


def _local_volume(tmp_path):
    """An EC volume on local files with four shards mounted; returns it,
    its payloads and a reader of the files the volume has not mounted."""
    g = ec.Geometry(10, 4, large_block_size=10000, small_block_size=100)
    v = Volume(str(tmp_path), "", 1, create=True)
    rng = np.random.default_rng(5)
    payloads = {}
    for nid in range(1, 41):
        payloads[nid] = rng.bytes(int(rng.integers(1, 900)))
        v.write_needle(Needle(cookie=nid, id=nid, data=payloads[nid]))
    v.close()
    base = os.path.join(str(tmp_path), "1")
    coder = ec.get_coder("numpy", 10, 4)
    ec.write_ec_files(base, coder, g, buffer_size=100)
    ec.write_sorted_ecx_from_idx(base)
    ev = ec.EcVolume(str(tmp_path), "", 1, g, coder=coder)
    for sid in (3, 4, 5, 11):
        ev.add_shard(sid)

    def from_file(sid: int, offset: int, size: int) -> bytes:
        with open(base + ec.to_ext(sid), "rb") as f:
            f.seek(offset)
            return f.read(size)

    return ev, payloads, from_file


def test_a_lost_shard_ordered_first_does_not_hold_up_the_survivors(
        tmp_path):
    """Shards 0, 1, 2 and 10 are held by nobody, and in the fan-out their
    reader is slow to say so (it asks the master first): the six
    survivors that peers hold come back at once, and the reconstruction
    must not wait for the answers about shards ordered before them."""
    ev, payloads, from_file = _local_volume(tmp_path)
    lost = {0, 1, 2, 10}
    gate = threading.Event()
    held_up: list[int] = []

    def reader(sid: int, offset: int, size: int):
        if sid not in lost:
            return from_file(sid, offset, size)
        if threading.current_thread().name.startswith("ec-survivor"):
            held_up.append(sid)
            gate.wait(30)  # "no holder", late
        return None

    # one needle whose first interval lies on lost shard 1: shard 0 is
    # the first candidate of its fan-out, shard 2 the second
    nid = next(n for n in payloads
               if ev.locate(n)[2][0].to_shard_id_and_offset(ev.g)[0] == 1)
    try:
        t0 = time.perf_counter()
        assert ev.read_needle(nid, cookie=nid,
                              shard_reader=reader).data == payloads[nid]
        took = time.perf_counter() - t0
        assert 0 in held_up  # a lost shard was asked for, ahead of all
    finally:
        gate.set()
    assert took < 10, took   # and nobody waited 30 s for its answer
    ev.close()


def test_fewer_than_k_reachable_shards_is_an_error_not_a_hang(tmp_path):
    ev, payloads, from_file = _local_volume(tmp_path)

    def reader(sid: int, offset: int, size: int):
        return from_file(sid, offset, size) if sid in (6, 7, 12) else None

    with pytest.raises(IOError, match="reachable"):
        for nid in payloads:  # some needle lies on a shard not mounted
            ev.read_needle(nid, cookie=nid, shard_reader=reader)
    ev.close()


def test_survivor_fetches_run_under_the_requests_trace(tmp_path):
    ev, payloads, from_file = _local_volume(tmp_path)
    seen: list[str] = []

    def reader(sid: int, offset: int, size: int):
        seen.append(observe.capture().trace_id)
        return None if sid in (0, 1, 2, 10) else from_file(sid, offset,
                                                            size)

    ctx = observe.TraceCtx("feedbeef", "1", "volume", "here")
    for nid, data in payloads.items():
        assert observe.run_with(
            ctx, ev.read_needle, nid, nid, reader).data == data
    # pool threads included: every fetch saw the request's trace id
    assert seen and set(seen) == {"feedbeef"}
    ev.close()


def _spawn(args: list[str], log_path: str) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    with open(log_path, "ab") as logf:
        return subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu.cli", *args],
            env=env, stdout=logf, stderr=logf)


def test_master_drops_a_killed_peer_when_its_stream_closes(tmp_path):
    """`cli volume -grpc_heartbeat` under a master whose maintenance loop
    is off (no time-driven prune): SIGKILL the volume server and the
    master forgets it at once, because the heartbeat stream closed."""
    mport = free_port_with_grpc_twin()
    vport = free_port_with_grpc_twin()
    os.makedirs(tmp_path / "m")
    os.makedirs(tmp_path / "v")
    master = _spawn(["master", "-port", str(mport), "-mdir",
                     str(tmp_path / "m"), "-maintenance_interval", "0"],
                    str(tmp_path / "master.log"))
    volume = None

    def nodes() -> list:
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{mport}/dir/status", timeout=2) as r:
                return [n["url"] for n in json.load(r)["nodes"]]
        except OSError:
            return []

    try:
        volume = _spawn(["volume", "-port", str(vport), "-dir",
                         str(tmp_path / "v"), "-mserver",
                         f"127.0.0.1:{mport}", "-coder", "numpy",
                         "-grpc_heartbeat", "-pulse", "1"],
                        str(tmp_path / "volume.log"))
        _wait(lambda: nodes() == [f"127.0.0.1:{vport}"],
              "the volume server never registered", limit=60.0)
        volume.send_signal(signal.SIGKILL)
        volume.wait()
        t0 = time.time()
        _wait(lambda: nodes() == [] and master.poll() is None,
              "the master kept the dead server", limit=5.0)
        assert time.time() - t0 < 5.0
    finally:
        for p in (volume, master):
            if p is not None and p.poll() is None:
                p.kill()
            if p is not None:
                p.wait()


def test_reference_layout_is_the_seeds_loss_on_every_seed():
    datagen = _bench("datagen")
    for seed in (1, 28, 3100000999, 2**31 + 5):
        perm = datagen.shard_permutation(seed, 0, 10).tolist()
        lost = datagen.lost_shards(seed, 0, 10, 4, 3, 1)
        layout = reference_cluster.seed_layout(perm, lost, 10, 4)
        assert layout["doomed"] == lost
        assert sorted(len(s) for s in layout.values()) == [3, 3, 4, 4]
        assert sorted(s for sids in layout.values() for s in sids) \
            == list(range(14))
        # logical shards 3..5 are local, 6..9 a peer's, whatever the seed
        assert sorted(layout["chip"][:3]) == sorted(perm[3:6])
        assert sorted(layout["peer_a"][:2] + layout["peer_b"][:2]) \
            == sorted(perm[6:10])
