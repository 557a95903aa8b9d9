"""Degraded GETs in an open loop on a spread EC volume: four volume
servers under the harness's master, the volume's shards 4/4/3/3 over
them, one peer killed before the window. Every GET goes to the server
that holds the chip (run.py's own process), whose survivors now come
from peers.

This driver runs in the load generator, after `ops.fill_store` has grown
and filled the volume on the chip's server. It starts the peers as
`cli volume -grpc_heartbeat` children pinned to the CPU, lets
`EcCommands.encode_many` encode on the chip and spread by the program's
own plan, moves shards with the steps `EcCommands.balance` issues until
the layout is the seed's (`reference_cluster.seed_layout`), kills the
peer that holds the seed's lost shards, and waits until the master no
longer names it. The window, its schedule and its samples are
`open_loop_get`'s.
"""

from __future__ import annotations

import os
import queue
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import datagen
import ops
import reference
import reference_cluster
from remote_counters import READS, family
from run import child_start, load_module
from seaweedfs_tpu.client import Client, ClientError

base = load_module("drivers", "open_loop_get")

SETUP_LIMIT_S = 240       # the whole set-up, peers included
PEER_BOOT_LIMIT_S = 90    # three interpreters importing the program
MASTER_DROP_LIMIT_S = 30  # kill -> gone from /col/lookup/ec

INLINE = "seaweedfs_tpu_volume_ec_read_inline_total"
PROXIED = "seaweedfs_tpu_volume_ec_read_proxied_total"
RECONSTRUCTED = "seaweedfs_tpu_ec_reconstruct_intervals_total"


class PinnedClient(Client):
    """The product's client with the volume's location given: a client
    that was told which server to ask (every holder of a shard answers
    an EC GET; the cell's GETs all go to one)."""

    def __init__(self, master: str, volume_url: str):
        super().__init__(master)
        self._pinned = [volume_url]

    def lookup(self, vid: int) -> list[str]:
        return self._pinned


class Peer:
    """One `cli volume` child; its directory and log outlive a re-boot on
    another port."""

    def __init__(self, work: str, n: int):
        self.url = ""
        self.dir = os.path.join(work, f"peer{n}")
        self.log = os.path.join(work, f"peer{n}.log")
        self.boots = 0
        self.proc: subprocess.Popen | None = None

    def start(self, master: str, port: int) -> None:
        self.url = f"127.0.0.1:{port}"
        self.boots += 1
        os.makedirs(self.dir, exist_ok=True)
        with open(self.log, "ab") as logf:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "seaweedfs_tpu.cli", "volume",
                 "-port", str(port), "-dir", self.dir,
                 "-mserver", master, "-coder", "numpy",
                 "-grpc_heartbeat", "-pulse", "1"],
                cwd=self.dir, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                stdout=logf, stderr=logf, preexec_fn=child_start)

    def log_tail(self) -> str:
        with open(self.log, "rb") as f:
            return f.read()[-1500:].decode(errors="replace")

    def kill(self) -> None:
        if self.proc is not None:
            self.proc.kill()  # nothing to a process that has ended
            self.proc.wait()


def peer_ports() -> range:
    """HTTP ports whose +10000 gRPC twin is a valid port and, where the
    machine leaves room, lies with it below the range the kernel hands
    out to outgoing connections: a port out of that range can be taken
    between the probe below and the server's own bind, seconds later
    (on a machine whose range starts at 16000, one boot in 33 died so)."""
    with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
        low = int(f.read().split()[0])
    top = min(low - 10000, 22000)
    return range(2000, top) if top >= 3000 else range(12000, 22000)


def pick_ports(n: int, seed: int) -> list[int]:
    """n free HTTP ports of `peer_ports` whose twin is free as well."""
    ports = peer_ports()
    out: list[int] = []
    for i in range(len(ports)):
        # a stride of 37 spreads the peers over the range
        port = ports[(seed * 7919 + i * 37) % len(ports)]
        try:
            with socket.socket() as a, socket.socket() as b:
                a.bind(("127.0.0.1", port))
                b.bind(("127.0.0.1", port + 10000))
        except OSError:
            continue
        out.append(port)
        if len(out) == n:
            return out
    raise SystemExit("no free port pairs for the peers")


def counters(url: str) -> dict[str, float]:
    """Every sample of a server's /metrics, by its rendered name."""
    with urllib.request.urlopen(f"http://{url}/metrics", timeout=30) as r:
        text = r.read().decode()
    out: dict[str, float] = {}
    for m in re.finditer(r"^([a-zA-Z_:][\w:]*(?:\{[^}]*\})?) "
                         r"([0-9.eE+-]+)$", text, re.M):
        out[m.group(1)] = float(m.group(2))
    return out


def held(ctx: ops.Ctx, vid: int) -> dict[str, list[int]]:
    """url -> the volume's shard ids mounted there, as the master has
    them."""
    out = {}
    for node in ctx.client.dir_status()["nodes"]:
        for s in node.get("ec_shards", []):
            if int(s["id"]) == vid and s["shard_ids"]:
                out[node["url"]] = sorted(int(x) for x in s["shard_ids"])
    return out


def held_when(ctx: ops.Ctx, vid: int, settled, limit_s: float = 10.0
              ) -> dict[str, list[int]]:
    """`held`, asked again until `settled(held)` or the limit: a mount
    or a delete reaches the master with the server's next heartbeat."""
    deadline = time.time() + limit_s
    while True:
        got = held(ctx, vid)
        if settled(got) or time.time() > deadline:
            return got
        time.sleep(0.05)


def move_to(ctx: ops.Ctx, vid: int, want: dict[str, list[int]]) -> int:
    """`EcCommands.balance`'s steps, a (giver, taker) pair at a time:
    every taker copies and mounts first, then every giver deletes, so no
    server is ever left without the volume. Returns shards moved."""
    now = held(ctx, vid)
    holder = {s: url for url, sids in now.items() for s in sids}
    pairs: dict[tuple[str, str], list[int]] = {}
    for url, sids in want.items():
        for s in sids:
            if holder[s] != url:
                pairs.setdefault((holder[s], url), []).append(s)
    for (src, dst), sids in pairs.items():
        body = {"volume_id": vid, "collection": ctx.collection,
                "shard_ids": sids}
        ctx.client.volume_admin(dst, "ec/copy", {
            **body, "source": src, "copy_ecx_file": True})
        ctx.client.volume_admin(dst, "ec/mount", body)
    for (src, _), sids in pairs.items():
        ctx.client.volume_admin(src, "ec/delete_shards", {
            "volume_id": vid, "collection": ctx.collection,
            "shard_ids": sids})
    return sum(len(s) for s in pairs.values())


def plan(ctx: ops.Ctx, state: dict, rate: float) -> None:
    """`open_loop_get.plan`, and for each item the parts of its body on
    a shard that a live peer holds; then the chip's server's counters,
    so that the window's share of them can be told."""
    base.plan(ctx, state, rate)
    state["remote_ranges"] = base.lost_ranges(
        ctx, state["vol"], set(state["want"]), state["remote"])
    state["before"] = counters(ctx.volume_url)


def setup(ctx: ops.Ctx) -> dict:
    def late(*_):
        raise SystemExit(f"set-up took over {SETUP_LIMIT_S}s")
    signal.signal(signal.SIGALRM, late)
    signal.alarm(SETUP_LIMIT_S)
    peers: list[Peer] = []
    try:
        state = _setup(ctx, peers)
    except BaseException:
        for p in peers:
            p.kill()
        raise
    finally:
        signal.alarm(0)
    return state


def _setup(ctx: ops.Ctx, peers: list[Peer]) -> dict:
    load = ctx.traffic["load"]
    master = ctx.raw["master"]
    n_servers = ctx.config["volume_servers"]
    vol = ctx.volumes()[0]
    ops.keep_source(vol)

    t0 = time.time()
    for n, port in enumerate(pick_ports(n_servers - 1, ctx.seed)):
        peers.append(Peer(ctx.work, n))
        peers[-1].start(master, port)
    deadline = time.time() + PEER_BOOT_LIMIT_S
    while True:
        for p in peers:
            if p.proc.poll() is None:
                continue
            # a port can be taken between the probe and the server's own
            # bind, seconds later: once more, on another
            ops.say(f"peer {p.url} exited at boot (rc {p.proc.returncode}"
                    f"): {p.log_tail()}")
            if p.boots > 1:
                raise SystemExit(f"peer {p.url} exited at boot twice")
            p.start(master, pick_ports(1, ctx.seed + 1 + p.boots)[0])
        nodes = {n["url"] for n in ctx.client.dir_status()["nodes"]}
        if all(p.url in nodes for p in peers):
            break
        if time.time() > deadline:
            raise SystemExit("the master never saw every peer")
        time.sleep(0.1)
    boot_s = time.time() - t0

    # encode on the chip, spread by the program's own plan
    t0 = time.time()
    ctx.ec.encode_many([vol.vid], ctx.collection)
    def whole(h: dict) -> bool:  # every shard mounted once
        return sorted(x for s in h.values() for x in s) \
            == list(range(ctx.k + ctx.m))

    spread = held_when(ctx, vol.vid, whole)
    spread_s = time.time() - t0
    counts_want = sorted(len(s) for s in reference_cluster
                         .balanced_distribution([8] * n_servers,
                                                ctx.k + ctx.m))
    spread_off = int(sorted(len(s) for s in spread.values()) != counts_want
                     or not whole(spread))

    # then to the seed's layout
    lost = datagen.lost_shards(ctx.seed, vol.index, ctx.k, ctx.m,
                               load["lost_data"], load["lost_parity"])
    layout = reference_cluster.seed_layout(
        datagen.shard_permutation(ctx.seed, vol.index, ctx.k).tolist(),
        lost, ctx.k, ctx.m)
    doomed, peer_a, peer_b = peers
    want = {ctx.volume_url: layout["chip"], doomed.url: layout["doomed"],
            peer_a.url: layout["peer_a"], peer_b.url: layout["peer_b"]}
    t0 = time.time()
    moved = move_to(ctx, vol.vid, want)
    moves_s = time.time() - t0
    before_kill = held_when(ctx, vol.vid, lambda h: h == want)
    on_disk = sorted(
        int(name[-2:]) for name in os.listdir(ctx.vdir)
        if re.fullmatch(re.escape(os.path.basename(vol.base))
                        + r"\.ec\d\d", name))
    reconstructed = counters(doomed.url).get(RECONSTRUCTED, 0.0)

    # the death of a server, and the master's notice of it
    t0 = time.time()
    doomed.kill()
    deadline = t0 + MASTER_DROP_LIMIT_S
    while any(doomed.url in urls for urls in
              ctx.client.ec_lookup(vol.vid)["shards"].values()):
        if time.time() > deadline:
            raise SystemExit(f"the master still names {doomed.url} "
                             f"{MASTER_DROP_LIMIT_S}s after its death")
        time.sleep(0.02)
    dropped_s = time.time() - t0
    alive = {u: s for u, s in want.items() if u != doomed.url}
    after_kill = held_when(ctx, vol.vid, lambda h: h == alive)
    # as `fill_store` ends: the window meets shard files that were
    # written long ago, not the flush of the copies just made
    os.sync()

    state = {
        "vol": vol, "lost": lost, "peers": peers, "layout": layout,
        "remote": sorted(layout["peer_a"] + layout["peer_b"]),
        "shards_misplaced":
            spread_off
            + reference_cluster.misplaced(before_kill, want)
            + reference_cluster.misplaced(after_kill, alive)
            + len(set(on_disk) ^ set(layout["chip"])),
        "peer_reconstructions": reconstructed,
        "cluster": {"peers_boot_s": boot_s,
                    "peer_boots": [p.boots for p in peers],
                    "peer_ports": [peer_ports().start, peer_ports().stop],
                    "encode_spread_s": spread_s,
                    "shards_moved": moved, "moves_s": moves_s,
                    "master_dropped_s": dropped_s,
                    "spread_counts": sorted(
                        (len(s) for s in spread.values()), reverse=True),
                    "layout": layout}}
    plan(ctx, state, load["rate_per_s"])

    # every client of the pool holds an open connection to the chip's
    # server and has read from a lost shard before the clock starts, and
    # an interval has come from each live peer: the one width a 1 KB
    # interval pads to, the gRPC channel to each peer and the location
    # of every live shard meet no first time in the window
    asked = sorted(state["want"])
    degraded = [i for i in asked if state["ranges"][i]]
    by_peer = []
    for role in ("peer_a", "peer_b"):
        there = base.lost_ranges(ctx, vol, set(asked), layout[role])
        by_peer.append([i for i in asked if there[i]])
    clients: queue.SimpleQueue = queue.SimpleQueue()
    warm_wrong = 0
    for n in range(load["client_threads"]):
        client = PinnedClient(master, ctx.volume_url)
        for i in (degraded[n % len(degraded)], asked[n % len(asked)],
                  *(items[n % len(items)] for items in by_peer)):
            warm_wrong += int(not ops.get_checked(
                client, state["fids"][i], state["bodies"][i]))
        clients.put(client)
    state.update(warm_wrong=warm_wrong, clients=clients,
                 before=counters(ctx.volume_url))
    return state


window = base.window


def summary(state: dict, samples: dict) -> dict:
    out = base.summary(state, samples)
    plain = sum(len(state["remote_ranges"][i]) for i in state["items"])
    lost = out["facts"]["intervals_expected"]
    out["facts"].update(
        state["cluster"],
        gets_on_peer_shards=sum(bool(state["remote_ranges"][i])
                                for i in state["items"]),
        # an interval on a live peer's shard is one read from it; one on
        # a lost shard takes the k survivors, all but the local ones
        # from peers
        remote_intervals_expected=plain + lost,
        remote_reads_expected=plain + lost * (
            len(state["remote"])))
    return out


def verify(ctx: ops.Ctx, state: dict, samples: dict) -> dict:
    vol = state["vol"]
    after = counters(ctx.volume_url)
    before = state["before"]
    out = summary(state, samples)
    facts = out["facts"]
    wrong = sum(not x for x in samples["ok"])
    # the control, as in `open_loop_get`: bytes on a lost shard answered
    # as zeros
    answer = {}
    for item, body in state["bodies"].items():
        body = bytearray(body)
        for pos, size in state["ranges"][item]:
            body[pos:pos + size] = bytes(size)
        answer[item] = base.digest(bytes(body)) == state["want"][item]
    control_wrong = sum(not answer[item] for item in state["items"])
    facts["control_gets_wrong"] = control_wrong
    if ctx.control:
        wrong = control_wrong

    # who answered, and from where
    served = sum(after.get(k, 0.0) - before.get(k, 0.0)
                 for k in (INLINE, PROXIED))
    elsewhere = max(0, len(samples["ok"]) - int(served))
    live = [p for p in state["peers"] if p.proc.poll() is None]
    peer_reconstructions = state["peer_reconstructions"]
    for p in live:
        got = counters(p.url)
        peer_reconstructions += got.get(RECONSTRUCTED, 0.0)
        elsewhere += int(sum(got.get(k, 0.0) for k in (INLINE, PROXIED)))
    reads_after, reads_before = (family(c, READS)
                                 for c in (after, before))
    if reads_after is None:
        # a program that does not count its remote reads (this cell's
        # parent commit): where the bytes came from rests on the layout
        # alone (`shards_misplaced`: the master's view and the files in
        # the chip's server's directory)
        remote_reads, short = None, 0
    else:
        remote_reads = reads_after - (reads_before or 0.0)
        short = max(0, facts["remote_intervals_expected"]
                    - int(remote_reads))
    facts.update(remote_reads_counted=remote_reads,
                 gets_served_by_chip_server=served,
                 peers_alive=len(live))

    pinned = PinnedClient(ctx.raw["master"], ctx.volume_url)
    back = 0
    for item in ctx.deleted:
        try:
            pinned.download(vol.fid(item))
            back += 1
        except ClientError:
            pass
    with open(vol.ref + ".idx", "rb") as f:
        ecx_want = reference.sorted_ecx(f.read())
    ecx = 0
    for d in [ctx.vdir] + [p.dir for p in live]:
        with open(os.path.join(d, os.path.basename(vol.base) + ".ecx"),
                  "rb") as f:
            ecx += int(f.read() != ecx_want)
    ops.drop_source(vol)
    for p in state["peers"]:
        p.kill()
    return {
        **out,
        "attempted": len(samples["ok"]), "failed": wrong,
        "checks": [
            ops.check("gets_wrong", wrong, 0),
            ops.check("warmup_gets_wrong", state["warm_wrong"], 0),
            ops.check("deleted_needles_back", back, 0),
            ops.check("ecx_files_differing", ecx, 0),
            ops.check("shards_misplaced", state["shards_misplaced"], 0),
            ops.check("gets_not_by_chip_server", elsewhere, 0),
            ops.check("peer_reconstructions", int(peer_reconstructions), 0),
            ops.check("remote_reads_short", short, 0),
            ops.check("live_peers_missing",
                      len(state["peers"]) - 1 - len(live), 0)]}
