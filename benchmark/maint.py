#!/usr/bin/env python3
"""The maintenance script's runner: a child of the load generator that
stands for the master's `[master.maintenance]` loop while it runs
`ec.encode` on one volume server.

    python3 benchmark/maint.py '<context as JSON>'

It posts `/admin/ec/generate {"volume_id": <vid>}` to the volume server,
tagged `X-Seaweed-Priority: bg` as the master tags its maintenance calls,
one call after another until a line arrives on its stdin; the pass in
flight is finished. A 503 (background shed for foreground) is asked again
after its Retry-After and counted. Between two passes it reads one
series of the server's /metrics (how many batches the server has
dispatched: the driver tells from it when the encode has settled on a
width), hashes the fifteen files the pass left, as another process reads
them, and writes one JSON line: the pass's start and end on the host's
monotonic clock (one clock for every process of the machine), how often
it was shed, the series' value and the hashes. Nothing of the program is
imported: urllib and hashlib.
"""

from __future__ import annotations

import hashlib
import json
import re
import sys
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

HASH_THREADS = 4   # hashlib lets go of the interpreter lock
PASS_LIMIT_S = 120
CHUNK = 1 << 22


def file_hash(path: str) -> str:
    h = hashlib.blake2b(digest_size=16)
    with open(path, "rb", buffering=0) as f:
        while True:
            chunk = f.read(CHUNK)
            if not chunk:
                return h.hexdigest()
            h.update(chunk)


def generate(url: str, vid: int) -> tuple[float, float, int]:
    """One acknowledged pass: (start of the call that was admitted, its
    end, how many calls before it were shed)."""
    req = urllib.request.Request(
        f"http://{url}/admin/ec/generate",
        data=json.dumps({"volume_id": vid}).encode(),
        headers={"Content-Type": "application/json",
                 "X-Seaweed-Priority": "bg"})
    shed = 0
    deadline = time.monotonic() + PASS_LIMIT_S
    while True:
        start = time.monotonic()
        try:
            with urllib.request.urlopen(req, timeout=PASS_LIMIT_S) as r:
                out = json.loads(r.read())
            if not out.get("ok"):
                raise SystemExit(f"ec/generate answered {out}")
            return start, time.monotonic(), shed
        except urllib.error.HTTPError as e:
            with e:
                if e.code != 503 or time.monotonic() > deadline:
                    raise SystemExit(f"ec/generate: {e.code} "
                                     f"{e.read()[:300]!r}")
                wait = float(e.headers.get("Retry-After") or 1)
            shed += 1
            time.sleep(min(wait, 1.0))


def probe(url: str, series: str) -> float | None:
    """One sample of a server's /metrics by its rendered name; None
    where the program has no such series (a parent commit)."""
    with urllib.request.urlopen(f"http://{url}/metrics", timeout=30) as r:
        m = re.search(rf"^{re.escape(series)} ([0-9.eE+-]+)$",
                      r.read().decode(), re.M)
    return float(m.group(1)) if m else None


def main() -> None:
    ctx = json.loads(sys.argv[1])
    stop = threading.Event()
    threading.Thread(target=lambda: (sys.stdin.readline(), stop.set()),
                     daemon=True).start()
    paths = {ext: ctx["base"] + ext for ext in ctx["exts"]}
    n = 0
    with ThreadPoolExecutor(max_workers=HASH_THREADS) as pool:
        while not stop.is_set():
            start, end, shed = generate(ctx["volume"], ctx["vid"])
            t0 = time.monotonic()
            probed = probe(ctx["volume"], ctx["probe"])
            hashes = dict(zip(paths, pool.map(file_hash, paths.values())))
            sys.stdout.write(json.dumps(
                {"pass": n, "start": start, "end": end, "shed": shed,
                 "probe": probed, "gap_s": time.monotonic() - t0,
                 "hashes": hashes}) + "\n")
            sys.stdout.flush()
            n += 1
    sys.stdout.write(json.dumps({"done": n}) + "\n")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
