#!/usr/bin/env python3
"""The maintenance script's `ec.rebuild -force`, one volume after
another: a child of the load generator, pinned to the CPU, that runs the
product's shell command on one EC volume that a dead server left with
four of fourteen shards missing.

    python3 benchmark/repair.py '<context as JSON>'

A pass is one volume of the script's queue. Under
`overload.priority(CLASS_BG)`, as the master runs its maintenance
scripts, it calls `EcCommands.rebuild` (what `cli shell ec.rebuild`
runs): the plan picks the rebuilder, which copies the survivors it lacks
from the live peers (`ec/copy`), rebuilds the lost shards
(`ec/rebuild`), mounts them and drops the borrowed copies. A call that
is still shed (503) after the client's own backoff is asked again; every
shed answer is counted. Then it waits until the master lists all
fourteen shards, hashes the rebuilt files as another process reads
them, deletes them on the rebuilder (`ec/delete_shards`: the next
volume's loss, so the next pass has the same work) and waits until the
master lists ten again. One JSON line a pass: the rebuild call's start
and end on the host's monotonic clock (one clock for every process of
the machine), the sheds, how many requests the pass sent and how many
went without the bg tag, what the plan chose, the hashes, which of the
volume's shard files the rebuilder's directory holds after the pass, and
one series of its /metrics (the driver tells from it when the rebuild
has settled on a batch width). Passes go on until a line arrives on its
stdin; the pass in flight is finished.
"""

from __future__ import annotations

import json
import os
import re
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from maint import HASH_THREADS, file_hash, probe
from seaweedfs_tpu import overload
from seaweedfs_tpu.client import Client, ClientError
from seaweedfs_tpu.shell.ec_commands import EcCommands
from seaweedfs_tpu.utils import retry

PASS_LIMIT_S = 120
LISTED_LIMIT_S = 30


class Outbound:
    """What the pass sent: every request the client library tags (its
    pool calls `overload.inject` on each), how many went out without the
    background tag, and how many answers were sheds."""

    def __init__(self) -> None:
        self.requests = self.untagged = self.shed = 0
        inject, is_shed = overload.inject, retry.is_shed

        def tagged(headers: dict) -> dict:
            out = inject(headers)
            self.requests += 1
            self.untagged += not overload.is_bg(
                out.get(overload.PRIORITY_HEADER, ""))
            return out

        def shed(status, headers) -> bool:
            got = is_shed(status, headers)
            self.shed += bool(got)
            return got

        overload.inject = tagged
        retry.is_shed = shed


def listed(client: Client, vid: int) -> dict[str, list[int]]:
    """url -> the volume's shard ids mounted there, as the master has
    them."""
    out = {}
    for node in client.dir_status()["nodes"]:
        for s in node.get("ec_shards", []):
            if int(s["id"]) == vid and s["shard_ids"]:
                out[node["url"]] = sorted(int(x) for x in s["shard_ids"])
    return out


def wait_listed(client: Client, vid: int, total: int) -> None:
    deadline = time.monotonic() + LISTED_LIMIT_S
    while sum(len(s) for s in listed(client, vid).values()) != total:
        if time.monotonic() > deadline:
            raise SystemExit(f"the master does not list {total} shards of "
                             f"volume {vid}: {listed(client, vid)}")
        time.sleep(0.01)


def rebuild(cmds: EcCommands, vid: int, collection: str,
            out: Outbound) -> tuple[float, float, dict]:
    """One acknowledged `ec.rebuild` of the volume: (start of the call
    that went through, its end, what it answered)."""
    deadline = time.monotonic() + PASS_LIMIT_S
    while True:
        start, shed = time.monotonic(), out.shed
        try:
            answer = cmds.rebuild(vid, collection)
            return start, time.monotonic(), answer
        except ClientError as e:
            # background work yields to foreground: a call shed past
            # the client's own backoff is asked again, never dropped
            if out.shed == shed or time.monotonic() > deadline:
                raise SystemExit(f"ec.rebuild of volume {vid}: {e}")
            time.sleep(0.25)


def main() -> None:
    ctx = json.loads(sys.argv[1])
    stop = threading.Event()
    threading.Thread(target=lambda: (sys.stdin.readline(), stop.set()),
                     daemon=True).start()
    vid, collection = ctx["vid"], ctx["collection"]
    total = ctx["total_shards"]
    client = Client(ctx["master"])
    cmds = EcCommands(client)
    out = Outbound()
    shard_file = re.compile(re.escape(os.path.basename(ctx["base"]))
                            + r"\.ec(\d\d)(\.copying)?")
    n = 0
    with ThreadPoolExecutor(max_workers=HASH_THREADS) as pool, \
            overload.priority(overload.CLASS_BG):
        while not stop.is_set():
            sent, untagged, shed = out.requests, out.untagged, out.shed
            start, end, answer = rebuild(cmds, vid, collection, out)
            t0 = time.monotonic()
            rebuilt = sorted(answer.get("rebuilt", []))
            wait_listed(client, vid, total)
            paths = {f".ec{s:02d}": ctx["base"] + f".ec{s:02d}"
                     for s in rebuilt}
            hashes = dict(zip(paths, pool.map(file_hash, paths.values())))
            if rebuilt:
                client.volume_admin(answer["rebuilder"], "ec/delete_shards",
                                    {"volume_id": vid,
                                     "collection": collection,
                                     "shard_ids": rebuilt})
            wait_listed(client, vid, total - len(rebuilt))
            held = [shard_file.fullmatch(name)
                    for name in os.listdir(os.path.dirname(ctx["base"]))]
            sys.stdout.write(json.dumps(
                {"pass": n, "start": start, "end": end,
                 "shed": out.shed - shed, "requests": out.requests - sent,
                 "untagged": out.untagged - untagged,
                 "rebuilder": answer.get("rebuilder"),
                 "copied": sorted(answer.get("copied", [])),
                 "rebuilt": rebuilt, "hashes": hashes,
                 "files_after": sorted(int(m.group(1)) for m in held
                                       if m and not m.group(2)),
                 "partial_files": sum(bool(m and m.group(2)) for m in held),
                 "probe": probe(ctx["volume"], ctx["probe"]),
                 "gap_s": time.monotonic() - t0}) + "\n")
            sys.stdout.flush()
            n += 1
    sys.stdout.write(json.dumps({"done": n}) + "\n")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
