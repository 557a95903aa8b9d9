"""Targeted failure injection (VERDICT r2 #9): raft partition without
split-brain, shard-holder death mid degraded-read, filer death
mid-autochunk with orphan cleanup.

The reference exercises these paths operationally (command_volume_fsck.go,
raft_server.go); here they are deterministic tests: the in-process
cluster lets the test intercept the raft transport and the EC interval
reader at exact points.
"""

import hashlib
import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from cluster_util import TEST_GEOMETRY, Cluster, free_port
from seaweedfs_tpu.shell.ec_commands import EcCommands

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _wait(predicate, timeout=15.0, what=""):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return
        time.sleep(0.2)
    raise AssertionError(f"timeout waiting for {what}")


# --- (a) network partition between 3 masters: no split-brain ---

def test_partition_no_split_brain():
    c = Cluster(n_volume_servers=1, n_masters=3)
    try:
        masters = c.masters
        _wait(lambda: sum(m.raft.is_leader for m in masters) == 1,
              what="initial leader")
        leader = next(m for m in masters if m.raft.is_leader)
        followers = [m for m in masters if m is not leader]

        # cut the leader off from BOTH followers, both directions, at the
        # raft transport (every vote/append/install rides raft._post)
        def cut(raft_node, peer_rafts):
            orig = raft_node._post
            peer_urls = {p.id for p in peer_rafts}

            async def filtered(peer, path, body,
                               _orig=orig, _urls=peer_urls):
                if peer in _urls:
                    return None  # dropped on the floor: partition
                return await _orig(peer, path, body)

            raft_node._post = filtered
            return orig

        originals = [(leader.raft,
                      cut(leader.raft, [f.raft for f in followers]))]
        for f in followers:
            originals.append((f.raft, cut(f.raft, [leader.raft])))

        # majority side elects a fresh leader at a higher term
        old_term = leader.raft.term
        _wait(lambda: sum(f.raft.is_leader for f in followers) == 1,
              what="new leader on the majority side")
        new_leader = next(f for f in followers if f.raft.is_leader)
        assert new_leader.raft.term > old_term

        # the partition isolates the old leader from the volume server
        # too (full network split): its heartbeats land on the majority
        vs = c.volume_servers[0]
        vs_masters_before = list(vs.masters)
        vs.masters = [new_leader.url]
        vs.master_url = new_leader.url

        # the stale leader may still CLAIM leadership, but it cannot
        # commit: an assign through it must not mint a fid (the
        # leader-readiness barrier needs quorum) — so at no point can two
        # masters both serve writes
        if leader.raft.is_leader:
            try:
                with urllib.request.urlopen(
                        f"http://{leader.url}/dir/assign", timeout=8) as r:
                    body = json.load(r)
                assert "fid" not in body, \
                    "stale leader minted a fid without quorum: split-brain"
            except (urllib.error.HTTPError, urllib.error.URLError,
                    TimeoutError, OSError):
                pass  # refusing/timing out is equally safe

        # the real leader keeps assigning (volume servers need a pulse or
        # two to re-home their heartbeats onto it first)
        def new_leader_assigns():
            try:
                with urllib.request.urlopen(
                        f"http://{new_leader.url}/dir/assign",
                        timeout=10) as r:
                    return "fid" in json.load(r)
            except (urllib.error.HTTPError, urllib.error.URLError,
                    OSError):
                return False

        _wait(new_leader_assigns, timeout=20,
              what="assign through the new leader")

        # heal: the stale leader sees the higher term and steps down
        for raft_node, orig in originals:
            raft_node._post = orig
        vs.masters = vs_masters_before
        _wait(lambda: sum(m.raft.is_leader for m in masters) == 1
              and leader.raft.term >= new_leader.raft.term,
              what="partition heal -> single leader, converged terms")
        assert sum(m.raft.is_leader for m in masters) == 1
    finally:
        c.shutdown()


# --- (b) shard holder dies mid degraded-read ---

def test_shard_holder_killed_mid_degraded_read():
    c = Cluster(n_volume_servers=4)
    try:
        import random
        rng = random.Random(5)
        data = bytes(rng.getrandbits(8) for _ in range(60_000))
        fid = c.client.upload(data, collection="chaos")
        c.wait_heartbeats()
        vid = int(fid.split(",")[0])
        EcCommands(c.client, TEST_GEOMETRY).encode(vid, "chaos", apply=True)
        c.wait_heartbeats()

        # the reading server holds SOME shards; remote intervals come from
        # peers. Kill one remote holder after two intervals have already
        # been assembled — deterministically mid-read.
        reader_vs = next(vs for vs in c.volume_servers
                         if vs.store.find_ec_volume(vid) is not None)
        ev = reader_vs.store.find_ec_volume(vid)
        victim = next(vs for vs in c.volume_servers
                      if vs is not reader_vs
                      and vs.store.find_ec_volume(vid) is not None)

        calls = {"n": 0}
        orig = ev._read_interval

        def chaotic(iv, shard_reader, _orig=orig):
            calls["n"] += 1
            if calls["n"] == 2:
                _kill_volume_server(c, victim)
            return _orig(iv, shard_reader)

        ev._read_interval = chaotic
        got = urllib.request.urlopen(
            f"http://{reader_vs.url}/{fid}", timeout=60).read()
        assert hashlib.sha256(got).hexdigest() == \
            hashlib.sha256(data).hexdigest()
        assert calls["n"] >= 2, "read finished before the injection"
    finally:
        c.shutdown()


def _kill_volume_server(c, vs) -> None:
    """Dirty in-process death: drop its EC state and stop its HTTP
    listener so in-flight fetches to it fail."""
    port = vs.url.rsplit(":", 1)[1]
    for loc in vs.store.locations:
        for v_ in list(loc.ec_volumes.values()):
            v_.close()
        loc.ec_volumes.clear()

    async def halt():
        for runner in list(c.runners):
            addrs = [str(a) for a in getattr(runner, "addresses", [])]
            if any(a.endswith(f", {port})") or f":{port}" in a
                   for a in addrs):
                await runner.cleanup()
                return

    c.call(halt())


# --- (c) filer dies mid-autochunk; fsck finds no surviving orphans ---

def _spawn(args, cwd, log_name):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = ":".join(
        p for p in (env.get("PYTHONPATH", ""), _REPO_ROOT) if p)
    log = open(os.path.join(cwd, f"{log_name}.log"), "ab")
    return subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu.cli"] + args,
        cwd=cwd, env=env, stdout=log, stderr=log)


def _wait_http(url, timeout=25):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(url, timeout=2) as r:
                return json.load(r)
        except Exception:
            time.sleep(0.2)
    raise TimeoutError(url)


def test_filer_killed_mid_autochunk_orphans_cleaned(tmp_path):
    from seaweedfs_tpu.client import Client
    from seaweedfs_tpu.shell import commands as shell_commands
    from seaweedfs_tpu.shell.commands import CommandEnv, run_command
    shell_commands._register_all()

    mport, vport, fport = free_port(), free_port(), free_port()
    master = f"127.0.0.1:{mport}"
    filer = f"127.0.0.1:{fport}"
    d = str(tmp_path)
    os.makedirs(os.path.join(d, "vol"), exist_ok=True)
    procs = []
    try:
        procs.append(_spawn(["master", "-port", str(mport),
                             "-mdir", d], d, "master"))
        procs.append(_spawn(["volume", "-port", str(vport), "-dir",
                             os.path.join(d, "vol"), "-mserver", master,
                             "-pulse", "1"], d, "volume"))
        _wait_http(f"http://{master}/cluster/status")
        filer_proc = _spawn(["filer", "-port", str(fport), "-mserver",
                             master, "-store_path",
                             os.path.join(d, "filer.db"),
                             "-chunk_size_mb", "1"], d, "filer")
        procs.append(filer_proc)
        _wait_http(f"http://{filer}/__meta__/info")

        # stream a 6MB PUT in drips; SIGKILL the filer once several 1MB
        # chunks have already landed on the volume server
        import http.client
        conn = http.client.HTTPConnection("127.0.0.1", fport, timeout=30)
        chunk = b"z" * 65536
        total = 6 * 1024 * 1024
        conn.putrequest("PUT", "/partial/big.bin")
        conn.putheader("Content-Length", str(total))
        conn.endheaders()
        sent = 0
        try:
            while sent < total:
                conn.send(chunk)
                sent += len(chunk)
                if sent == 3 * 1024 * 1024:
                    time.sleep(0.5)  # let flushed chunks reach volumes
                    filer_proc.send_signal(signal.SIGKILL)
                    filer_proc.wait(timeout=10)
        except OSError:
            pass
        finally:
            conn.close()

        # restart the filer over the same store; the torn upload has no
        # entry, so its already-written chunks are orphans
        procs.append(_spawn(["filer", "-port", str(fport), "-mserver",
                             master, "-store_path",
                             os.path.join(d, "filer.db"),
                             "-chunk_size_mb", "1"], d, "filer2"))
        _wait_http(f"http://{filer}/__meta__/info")
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://{filer}/partial/big.bin",
                                   timeout=5)

        env = CommandEnv(Client(master), filer=filer)
        out1 = run_command(env, "volume.fsck")
        assert out1["orphan_count"] > 0, \
            "expected orphan chunks after the mid-upload kill"
        out2 = run_command(env, "volume.fsck -purgeOrphans")
        assert out2["purged"] == out2["orphan_count"]
        out3 = run_command(env, "volume.fsck")
        assert out3["orphan_count"] == 0, "orphans survived the purge"
    finally:
        for p in procs:
            try:
                p.kill()
            except OSError:
                pass
