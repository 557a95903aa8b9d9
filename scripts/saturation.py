"""2-shard saturation smoke: boots a master + a WEED_SERVE_SHARDS=2
volume server (the SO_REUSEPORT fleet forked by the CLI), then drives
concurrent PUT/GET traffic for a few seconds.

Pass criteria (any failure exits non-zero):
  * zero 5xx / transport errors across the storm;
  * every uploaded blob reads back byte-identical afterwards (covers
    the sendfile path, cross-shard proxying, and group commit when
    WEED_VOLUME_GROUP_COMMIT_US is set in the environment);
  * /healthz on the shared port reports both shards alive.

Invoked by scripts/saturation.sh; knobs: SAT_SECONDS (default 5),
SAT_THREADS (default 8), WEED_SERVE_SHARDS (default 2).
"""

import hashlib
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def wait_http(url: str, timeout: float = 30.0) -> None:
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            urllib.request.urlopen(url, timeout=2).read()
            return
        except Exception as e:  # noqa: BLE001 - startup polling
            last = e
            time.sleep(0.2)
    raise SystemExit(f"timeout waiting for {url}: {last}")


def main() -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from seaweedfs_tpu.client import Client

    shards = int(os.environ.get("WEED_SERVE_SHARDS", "2") or 2)
    seconds = float(os.environ.get("SAT_SECONDS", "5") or 5)
    threads_n = int(os.environ.get("SAT_THREADS", "8") or 8)
    tmp = tempfile.mkdtemp(prefix="swfs-sat-")
    os.makedirs(os.path.join(tmp, "m"))
    os.makedirs(os.path.join(tmp, "v"))
    mport, vport = free_port(), free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               WEED_SERVE_SHARDS=str(shards))
    procs = []
    try:
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu.cli", "master",
             "-port", str(mport), "-mdir", os.path.join(tmp, "m"),
             "-grpc_port", "0", "-pulse", "1"], env=env))
        wait_http(f"http://127.0.0.1:{mport}/healthz")
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu.cli", "volume",
             "-port", str(vport), "-dir", os.path.join(tmp, "v"),
             "-mserver", f"127.0.0.1:{mport}", "-grpc_port", "0",
             "-pulse", "1"], env=env))
        wait_http(f"http://127.0.0.1:{vport}/healthz")
        # let the shards publish their first heartbeats/blobs
        time.sleep(2.0)

        client = Client(f"127.0.0.1:{mport}")
        # warmup: the first assign races the master's initial volume
        # growth; retry until a volume is writable so the storm only
        # measures steady-state behavior
        warm_deadline = time.time() + 30.0
        while True:
            try:
                client.upload(b"warmup", filename="warmup")
                break
            except Exception as e:  # noqa: BLE001 - startup polling
                if time.time() > warm_deadline:
                    raise SystemExit(f"warmup upload never succeeded: {e}")
                time.sleep(0.5)
        stop = time.time() + seconds
        lock = threading.Lock()
        written: dict[str, str] = {}      # fid -> sha256
        errors: list[str] = []
        counts = {"put": 0, "get": 0}

        def worker(idx: int) -> None:
            rng_i = 0
            while time.time() < stop:
                rng_i += 1
                data = hashlib.sha256(
                    f"{idx}:{rng_i}".encode()).digest() * (idx % 7 + 1)
                try:
                    fid = client.upload(data, filename=f"s{idx}-{rng_i}")
                    with lock:
                        written[fid] = hashlib.sha256(data).hexdigest()
                        counts["put"] += 1
                except Exception as e:  # noqa: BLE001 - tallied below
                    with lock:
                        errors.append(f"put: {e}")
                    continue
                try:
                    back = client.download(fid)
                    with lock:
                        counts["get"] += 1
                    if hashlib.sha256(back).hexdigest() != \
                            hashlib.sha256(data).hexdigest():
                        with lock:
                            errors.append(f"get {fid}: bytes differ")
                except Exception as e:  # noqa: BLE001 - tallied below
                    with lock:
                        errors.append(f"get {fid}: {e}")

        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(threads_n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

        # full read-back pass: every acked write must come back
        # byte-identical after the storm (cross-shard routing included)
        mismatches = 0
        for fid, digest in written.items():
            back = client.download(fid)
            if hashlib.sha256(back).hexdigest() != digest:
                mismatches += 1
                errors.append(f"readback {fid}: bytes differ")

        health = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{vport}/healthz", timeout=5).read())
        shard_view = health.get("shards", {})
        alive = shard_view.get("alive", 1 if shards == 1 else 0)

        print(json.dumps({
            "shards": shards, "alive": alive, "seconds": seconds,
            "puts": counts["put"], "gets": counts["get"],
            "errors": len(errors), "readback_mismatches": mismatches,
        }, indent=2))
        if errors:
            for e in errors[:20]:
                print("ERROR:", e, file=sys.stderr)
            return 1
        if counts["put"] == 0:
            print("ERROR: no writes completed", file=sys.stderr)
            return 1
        if shards > 1 and alive < shards:
            print(f"ERROR: /healthz reports {alive}/{shards} shards",
                  file=sys.stderr)
            return 1
        print("saturation smoke: PASS")
        return 0
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
