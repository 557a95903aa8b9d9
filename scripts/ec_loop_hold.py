#!/usr/bin/env python3
"""How long one EC GET holds the volume server's event loop, by size.

The measurement behind `ec_volume.NOWAIT_MAX_SIZE`. One process is the
volume server (`run_volume_server` with its fast path, as `cli volume`
runs it; the host coder: a present needle never meets the device) over
one EC volume at the default geometry, every shard mounted, holding
needles of each size in `--sizes`. A child sends `--gets` GETs of one
size down one connection, twice: with the limit lifted, so that the
loop's thread serves every one, and with the limit at 0, so that every
one is declined to the executor. Per size it prints one JSON line:

  hold_ms      S(ec.get.ecx + .shard_read + .parse + .resume) a served
               GET: the time the loop's thread is held (the search, the
               slices, the join and CRC, etag + head + body into the
               transport)
  declined     the same GET through the executor: the same four stages
               and `ec.get.queue`; only `.shard_read` and `.parse` of
               them leave the loop's thread (the search runs before the
               decline, `respond` after the hand-back)
  p50_ms       the client's median, both ways

S is the delta of `seaweedfs_tpu_ec_stage_seconds_sum` on /metrics. Run
it on the host whose loop it is to size (through the chip tool for the
chip's host): `python scripts/ec_loop_hold.py`.
"""

import argparse
import asyncio
import http.client
import json
import os
import re
import socket
import statistics
import subprocess
import sys
import tempfile
import threading
import time

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

COOKIE = 0x1234
PER_SIZE = 16
WARM = 20
HELD = ("ec.get.ecx", "ec.get.shard_read", "ec.get.parse", "ec.get.resume")
STAGES = HELD + ("ec.get.queue", "ec.get")


def client(port: int, size: int, first: int, gets: int) -> None:
    """`gets` + WARM GETs, one after another, over the PER_SIZE needles
    from id `first` on; the median of all but the first WARM."""
    from seaweedfs_tpu.storage.file_id import FileId
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    took = []
    for i in range(gets + WARM):
        t0 = time.perf_counter()
        conn.request("GET", "/" + str(FileId(1, first + i % PER_SIZE, COOKIE)))
        body = conn.getresponse().read()
        took.append(time.perf_counter() - t0)
        assert len(body) == size, (len(body), size)
    print(json.dumps({"p50_ms": 1e3 * statistics.median(took[WARM:])}))


def scrape(port: int) -> dict[str, float]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("GET", "/metrics")
    text = conn.getresponse().read().decode()
    conn.close()
    out = {m.group(1): float(m.group(2)) for m in re.finditer(
        r'^seaweedfs_tpu_ec_stage_seconds_sum\{stage="([^"]+)"\} (\S+)$',
        text, re.M)}
    for result in ("served", "declined"):
        m = re.search(r'^seaweedfs_tpu_volume_ec_read_nowait_total'
                      rf'\{{result="{result}"\}} (\S+)$', text, re.M)
        out[result] = float(m.group(1)) if m else 0.0
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="65536,131072,262144,524288,1048576")
    ap.add_argument("--gets", type=int, default=2000)
    ap.add_argument("--client", nargs=3, type=int,
                    metavar=("PORT", "SIZE", "FIRST_ID"))
    args = ap.parse_args()
    if args.client:
        client(*args.client, args.gets)
        return
    sizes = [int(s) for s in args.sizes.split(",")]
    with tempfile.TemporaryDirectory(prefix="ec_loop_hold.") as tmp:
        measure(tmp, sizes, args.gets)


def measure(tmp: str, sizes: list[int], gets: int) -> None:
    from seaweedfs_tpu.ec.ec_volume import EcVolume
    from seaweedfs_tpu.server.volume_server import run_volume_server
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store

    store = Store([tmp], coder_name="cpp")
    store.add_volume(1)
    for key in range(1, len(sizes) * PER_SIZE + 1):
        store.write_needle(1, Needle(
            id=key, cookie=COOKIE,
            data=os.urandom(sizes[(key - 1) // PER_SIZE])))
    store.ec_generate(1)
    store.ec_mount(1, "", list(range(14)))
    store.delete_volume(1)
    store.find_ec_volume(1).locate(1)  # the layout marker's one read
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    loop = asyncio.new_event_loop()
    ready = threading.Event()

    def serve() -> None:
        asyncio.set_event_loop(loop)
        loop.run_until_complete(run_volume_server(
            "127.0.0.1", port, store, master_url="127.0.0.1:1",
            pulse_seconds=3600))
        ready.set()
        loop.run_forever()

    threading.Thread(target=serve, daemon=True).start()
    assert ready.wait(60), "the volume server did not start"
    n = gets + WARM

    def run(size: int, on_loop: bool) -> dict:
        before = scrape(port)
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--gets", str(gets),
             "--client", str(port), str(size),
             str(1 + PER_SIZE * sizes.index(size))],
            check=True, stdout=subprocess.PIPE, text=True).stdout
        after = scrape(port)
        d = {k: after[k] - before.get(k, 0.0) for k in after}
        assert d["served" if on_loop else "declined"] == n, d
        per = {s: round(1e3 * d.get(s, 0.0) / n, 5) for s in STAGES}
        return {"hold_ms": round(sum(per[s] for s in HELD), 5),
                **json.loads(out), "stages_ms": per}

    defaults = EcVolume.read_needle_nowait.__defaults__
    rows = {}
    for limit in (1 << 30, 0):  # every GET served; every GET declined
        EcVolume.read_needle_nowait.__defaults__ = defaults[:-1] + (limit,)
        rows[limit] = {size: run(size, bool(limit)) for size in sizes}
    for size in sizes:
        declined = rows[0][size]
        declined["four_stages_and_queue_ms"] = round(
            declined.pop("hold_ms") + declined["stages_ms"]["ec.get.queue"],
            5)
        print(json.dumps({"size": size, **rows[1 << 30][size],
                          "declined": declined}), flush=True)
    store.close()


if __name__ == "__main__":
    main()
