#!/usr/bin/env python3
"""The quickest proof that the served EC path still starts on the chip.

Drives the README quick start once, at the default geometry — RS(10,4),
1 GiB large / 1 MiB small blocks (upstream ec_encoder.go:17-23) — through
the entry points a user calls:

  cli master + cli volume -coder auto   (subprocesses; the volume server
                                         is the ONE process on the chip)
  fill one volume to >= 1 GB over HTTP  (seeded blobs, mixed sizes, a
                                         ragged stripe tail, a few deletes)
  cli shell ec.encode                   -> /admin/ec/generate ->
                                           Store.ec_generate -> stream_encode
  GET a sample                          (EC volume, all shards)
  drop 4 of 14 shards (>= 1 parity)     -> GET again: _reconstruct_interval
                                           on the device coder
  cli shell ec.rebuild                  -> Store.ec_rebuild -> stream_rebuild
  GET again

The decision is byte identity: all fourteen .ecNN files after encode and
again after rebuild equal what striping.write_ec_files produces from the
same .dat under the host coder (cpp, else numpy) in a child pinned to
JAX_PLATFORMS=cpu; every GET returns the uploaded bytes; deleted needles
stay deleted. The volume server must report the Pallas coder on a TPU.
Any phase that fails raises: the exit code is non-zero and no result line
is printed. This process never imports jax.

The volume is BASELINE config 1's "single 1 GB volume" in decimal bytes: the
.dat ends between 1.000e9 and 1.009e9 bytes, under 2**30. The machine the
driver checks on caps a file at 1 GiB (RLIMIT_FSIZE; the write that crosses it
fails with EFBIG), so a .dat past 2**30 cannot exist there. The limit is read
before anything starts and a run that cannot fit says so and exits.

Not covered here: the large-block tier (a .dat over 10 GiB meets its first
1 GiB row) is ROADMAP B1's cell, not this smoke.

With WEED_EC_MESH_DEVICES=N (N >= 2) in the environment the same run goes
through the MeshCoder and also requires every chip to have staged bytes.

--platform cpu is for debugging the script where there is no chip: it
runs every phase on the host coder and then exits 3 without a result.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import re
import resource
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from importlib import metadata

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from seaweedfs_tpu.client import Client, ClientError  # noqa: E402
from seaweedfs_tpu.ops import native  # noqa: E402
from seaweedfs_tpu.utils import compile_cache  # noqa: E402

COLLECTION = "smoke"
TOTAL_SHARDS, DATA_SHARDS = 14, 10
SMALL_ROW = DATA_SHARDS * 1024 * 1024
MAX_BLOB = 4 * 1024 * 1024
SAMPLE = 48


def log(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, body: dict | None = None, timeout: float = 60.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def blob_bytes(seed: int, i: int, size: int) -> bytes:
    return random.Random(f"{seed}:{i}").randbytes(size)


def sha_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while chunk := f.read(1 << 24):
            h.update(chunk)
    return h.hexdigest()


def cache_entries(path: str) -> int:
    try:
        return sum(len(files) for _, _, files in os.walk(path))
    except OSError:
        return 0


def reference_main(base: str) -> None:
    """Child mode (JAX_PLATFORMS=cpu): the plain reference — synchronous
    write_ec_files under the host coder over the same .dat."""
    assert os.environ.get("JAX_PLATFORMS") == "cpu", "reference must be CPU"
    from seaweedfs_tpu import ec
    try:
        coder = ec.get_coder("cpp", DATA_SHARDS, TOTAL_SHARDS - DATA_SHARDS)
    except RuntimeError:
        coder = ec.get_coder("numpy", DATA_SHARDS,
                             TOTAL_SHARDS - DATA_SHARDS)
    ec.write_ec_files(base, coder, ec.DEFAULT, buffer_size=1024 * 1024)
    print(json.dumps({"coder": type(coder).__name__}))


class Cluster:
    def __init__(self, work: str, platform: str):
        self.work = work
        self.procs: list[subprocess.Popen] = []
        self.cpu_env = dict(os.environ, JAX_PLATFORMS="cpu",
                            PYTHONPATH=REPO + os.pathsep
                            + os.environ.get("PYTHONPATH", ""))
        # the one process that may hold the chip: with JAX_PLATFORMS=tpu a
        # missing or busy chip is an error from JAX, never a CPU run
        self.chip_env = dict(self.cpu_env, JAX_PLATFORMS=platform)
        self.master = f"127.0.0.1:{free_port()}"
        self.volume = f"127.0.0.1:{free_port()}"
        self.vdir = os.path.join(work, "v")

    def spawn(self, args: list[str], env: dict, tag: str) -> subprocess.Popen:
        logf = open(os.path.join(self.work, f"{tag}.log"), "ab")
        p = subprocess.Popen([sys.executable, "-m", "seaweedfs_tpu.cli",
                              *args], cwd=self.work, env=env,
                             stdout=logf, stderr=logf)
        logf.close()
        self.procs.append(p)
        return p

    def log_tail(self, tag: str, n: int = 30) -> str:
        try:
            with open(os.path.join(self.work, f"{tag}.log"), "rb") as f:
                return b"\n".join(f.read().splitlines()[-n:]).decode(
                    "utf-8", "replace")
        except OSError:
            return ""

    def start(self) -> None:
        os.makedirs(os.path.join(self.work, "m"))
        os.makedirs(self.vdir)
        mport = self.master.rsplit(":", 1)[1]
        vport = self.volume.rsplit(":", 1)[1]
        # the master's repair daemon would rebuild the dropped shards on
        # its own and race the degraded-read phase: off for this run
        self.spawn(["master", "-port", mport, "-mdir",
                    os.path.join(self.work, "m"),
                    "-maintenance_interval", "0"], self.cpu_env, "master")
        vs = self.spawn(["volume", "-port", vport, "-dir", self.vdir,
                         "-mserver", self.master, "-coder", "auto"],
                        self.chip_env, "volume")
        deadline = time.time() + 180
        while time.time() < deadline:
            if vs.poll() is not None:
                raise SystemExit(
                    "volume server exited at boot (no TPU for "
                    f"JAX_PLATFORMS={self.chip_env['JAX_PLATFORMS']}?):\n"
                    + self.log_tail("volume"))
            try:
                if http_json(f"http://{self.master}/dir/status",
                             timeout=5).get("nodes"):
                    return
            except OSError:
                pass
            time.sleep(0.5)
        raise SystemExit("cluster never came up:\n" + self.log_tail("volume"))

    def shell(self, *cmd: str) -> dict:
        """One-shot admin shell, exactly as the README runs it."""
        out = subprocess.run(
            [sys.executable, "-m", "seaweedfs_tpu.cli", "shell",
             "-server", self.master, *cmd],
            env=self.cpu_env, cwd=self.work, capture_output=True, text=True,
            timeout=900)
        if out.returncode != 0:
            raise SystemExit(f"shell {' '.join(cmd)} failed "
                             f"(rc={out.returncode}):\n{out.stderr[-3000:]}\n"
                             + self.log_tail("volume"))
        return json.loads(out.stdout.strip().splitlines()[-1])

    def ec_status(self) -> dict:
        return http_json(f"http://{self.volume}/admin/ec/mesh_status")

    def metrics_text(self) -> str:
        with urllib.request.urlopen(f"http://{self.volume}/metrics",
                                    timeout=30) as r:
            return r.read().decode()

    def metric(self, name: str) -> float:
        text = self.metrics_text()
        m = re.search(rf"^{re.escape(name)}(?:{{[^}}]*}})? ([0-9.e+-]+)$",
                      text, re.M)
        return float(m.group(1)) if m else 0.0

    def cold_dispatches(self) -> int:
        """Degraded-read dispatches that met a width not yet compiled
        (`..._ec_reconstruct_dispatch_total{warm="no",width=..}`), over
        every width."""
        return int(sum(float(v) for v in re.findall(
            r'^seaweedfs_tpu_ec_reconstruct_dispatch_total'
            r'\{warm="no",[^}]*\} ([0-9.e+-]+)$', self.metrics_text(),
            re.M)))

    def wait_warm(self, limit_s: float = 120.0) -> list[dict]:
        """What each coder says of the warm-up that the store's first
        generate or mount began, once none is under way (a host coder
        says nothing)."""
        deadline = time.time() + limit_s
        while True:
            warm = [d["warm"] for d in self.ec_status()["coder"]["resolved"]
                    if "warm" in d]
            if all(w["state"] not in ("idle", "running") for w in warm):
                return warm
            if time.time() > deadline:
                raise SystemExit(f"the warm-up of the degraded read's "
                                 f"widths has not ended: {warm}")
            time.sleep(0.1)

    def stop(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def check_coder(status: dict, mesh: int, require_tpu: bool) -> dict:
    """The coder the volume server resolved `auto` to, read back from its
    status surface; on a chip run it must be the Pallas kernel on a TPU."""
    resolved = status["coder"]["resolved"]
    if not resolved:
        raise SystemExit(f"volume server resolved no coder: {status}")
    desc = resolved[0]
    if require_tpu:
        want = "MeshCoder" if mesh else "PallasCoder"
        dev = desc.get("device") or {}
        pallas = (desc.get("formulation") == "pallas" if mesh
                  else not desc.get("interpret", True))
        if desc["coder"] != want or dev.get("platform") != "tpu" \
                or not pallas:
            raise SystemExit(f"encode did not run on the Pallas coder on a "
                             f"TPU: server reports {desc}")
        if mesh and desc.get("mesh_devices") != mesh:
            raise SystemExit(f"asked for a {mesh}-chip mesh, have {desc}")
    return desc


def fill(client: Client, seed: int, target: int) -> tuple[dict, list, int]:
    """Upload seeded blobs (log-uniform 1 KiB..4 MiB) to one volume until
    its .dat passes `target`; returns ({fid: (i, size)}, deleted, vid)."""
    rng = random.Random(seed)
    sizes, total = [], 0
    while total < target + SMALL_ROW // 3:
        n = int(2 ** rng.uniform(10, 22))  # < MAX_BLOB
        sizes.append(n)
        total += n

    def put(i: int) -> str:
        return client.upload(blob_bytes(seed, i, sizes[i]),
                             collection=COLLECTION)

    # in order, from one thread: the .dat layout (and so every interval a
    # degraded read reconstructs) is a function of the seed alone
    fids = [put(i) for i in range(len(sizes))]
    vids = {int(f.split(",")[0]) for f in fids}
    if len(vids) != 1:
        raise SystemExit(f"blobs spread over volumes {vids}, wanted one")
    blobs = {fid: (i, sizes[i]) for i, fid in enumerate(fids)}
    deleted = rng.sample(sorted(blobs), 7)
    for fid in deleted:
        client.delete(fid)
        del blobs[fid]
    return blobs, deleted, vids.pop()


def check_reads(client: Client, seed: int, blobs: dict, sample: list,
                deleted: list, phase: str, after_get=None) -> list[float]:
    """GET every sampled fid and compare bytes; deleted fids must stay
    gone. Returns the per-GET seconds, in order; after_get(seconds) runs
    after each GET, outside its timing."""
    times = []
    for fid in sample:
        i, size = blobs[fid]
        t0 = time.perf_counter()
        got = client.download(fid)
        times.append(time.perf_counter() - t0)
        if got != blob_bytes(seed, i, size):
            raise SystemExit(f"{phase}: GET {fid} returned wrong bytes")
        if after_get is not None:
            after_get(times[-1])
    for fid in deleted:
        try:
            client.download(fid)
        except ClientError:
            continue
        raise SystemExit(f"{phase}: deleted needle {fid} came back")
    return times


def device_view(cluster: "Cluster", client: Client, fids: list,
                require_tpu: bool, seconds: float = 5.0) -> dict:
    """What an operator sees of a running `cli volume`: a device trace
    window (`GET /debug/xprof`) while degraded GETs are served. On the
    chip the answer has to show the kernel and the GETs' stages; on a
    host coder the endpoint says 501 and that is recorded."""
    stop = threading.Event()

    def reads() -> None:
        while not stop.is_set():
            for fid in fids:
                client.download(fid)
                if stop.is_set():
                    return

    reader = threading.Thread(target=reads, daemon=True)
    reader.start()
    try:
        view = http_json(f"http://{cluster.volume}/debug/xprof"
                         f"?seconds={seconds}", timeout=seconds + 120)
    except urllib.error.HTTPError as e:
        if require_tpu or e.code != 501:
            raise SystemExit(f"/debug/xprof answered {e.code}: "
                             f"{e.read()[:300]!r}")
        return {"status": 501}
    finally:
        stop.set()
        reader.join(60)
    if (view["device_busy_s"] <= 0
            or not any("gf_apply" in op for op in view["device_ops"])
            or not view["stages"].get("ec.get.d2h_wait")):
        raise SystemExit(f"/debug/xprof saw no degraded read on the "
                         f"device: {view}")
    return view


def shard_hashes(base: str) -> list[str]:
    return [sha_file(f"{base}.ec{sid:02d}") for sid in range(TOTAL_SHARDS)]


def compare_shards(vbase: str, want: list[str], phase: str) -> None:
    for sid, (got, ref) in enumerate(zip(shard_hashes(vbase), want)):
        if got != ref:
            raise SystemExit(f"{phase}: shard .ec{sid:02d} differs from the "
                             "host coder's")


def check_file_limit(dat_bytes: int) -> None:
    """Fail before anything starts when this process's file-size limit
    (inherited by the volume server) cannot hold the .dat."""
    soft, _ = resource.getrlimit(resource.RLIMIT_FSIZE)
    need = dat_bytes + SMALL_ROW // 3 + 2 * MAX_BLOB
    if soft != resource.RLIM_INFINITY and soft < need:
        raise SystemExit(f"RLIMIT_FSIZE is {soft} bytes; the .dat needs up "
                         f"to {need}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--bytes", type=int, default=10 ** 9,
                    help="fill the volume past this many .dat bytes")
    ap.add_argument("--platform", choices=["tpu", "cpu"], default="tpu")
    ap.add_argument("--reference", metavar="BASE", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.reference:
        reference_main(args.reference)
        return

    t_start = time.time()
    require_tpu = args.platform == "tpu"
    mesh_env = os.environ.get("WEED_EC_MESH_DEVICES", "").strip()
    mesh = int(mesh_env) if mesh_env.isdigit() and int(mesh_env) > 1 else 0
    info: dict = {"seed": args.seed, "jax": metadata.version("jax"),
                  "libtpu": metadata.version("libtpu")}

    check_file_limit(args.bytes)
    # the host coder is the reference: it is rebuilt here from
    # native/rs_core.cpp for this host, never taken as found
    if not native.available():
        raise SystemExit("cannot build native/libseaweedtpu.so")

    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or compile_cache.CACHE_DIR)
    cache_before = cache_entries(cache_dir)
    info["compile_cache"] = {"dir": cache_dir,
                             "state": "warm" if cache_before else "cold",
                             "entries_before": cache_before}

    work = tempfile.mkdtemp(prefix="chip_smoke_")
    cluster = Cluster(work, args.platform)
    try:
        t0 = time.time()
        cluster.start()
        desc = check_coder(cluster.ec_status(), mesh, require_tpu)
        info["boot_s"] = round(time.time() - t0, 2)
        info["coder"] = desc
        log(f"volume server up in {info['boot_s']}s; EC coder: {desc}")

        client = Client(cluster.master)
        t0 = time.time()
        blobs, deleted, vid = fill(client, args.seed, args.bytes)
        vbase = os.path.join(cluster.vdir, f"{COLLECTION}_{vid}")
        dat_size = os.path.getsize(vbase + ".dat")
        if dat_size < args.bytes or dat_size % SMALL_ROW == 0:
            raise SystemExit(f".dat is {dat_size} bytes: too small or no "
                             "ragged tail")
        info["fill"] = {"blobs": len(blobs) + len(deleted),
                        "deleted": len(deleted), "dat_bytes": dat_size,
                        "tail_bytes": dat_size % SMALL_ROW,
                        "seconds": round(time.time() - t0, 2)}
        log(f"filled volume {vid}: {info['fill']}")
        # ec.encode retires the source: keep the .dat's inode for the
        # reference through a hard link
        rbase = os.path.join(work, "ref", f"{COLLECTION}_{vid}")
        os.makedirs(os.path.dirname(rbase))
        os.link(vbase + ".dat", rbase + ".dat")

        rng = random.Random(args.seed + 1)
        picked = rng.sample(sorted(blobs), min(2 * SAMPLE, len(blobs)))
        # the degraded phase reads needles no earlier phase has read, so
        # nothing but the shards can answer them
        sample, cold_sample = picked[::2], picked[1::2]

        t0 = time.time()
        cluster.shell("ec.encode", "-volumeId", str(vid),
                      "-collection", COLLECTION)
        info["encode"] = {"bytes": dat_size,
                          "wall_s": round(time.time() - t0, 2)}
        feed = cluster.ec_status()["feed"]
        info["encode"]["stage_seconds"] = {
            k.split('"')[1]: v for k, v in feed.items()
            if k.startswith("feed_stage_seconds")}
        info["encode"]["batch_bytes"] = feed.get("feed_batch_bytes")
        log(f"ec.encode: {info['encode']}")
        if os.path.exists(vbase + ".dat"):
            raise SystemExit("ec.encode left the source .dat in place")
        if os.path.getsize(rbase + ".dat") != dat_size:
            raise SystemExit("the .dat changed size during the encode")

        t0 = time.time()
        ref = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--reference",
             rbase], env=cluster.cpu_env, capture_output=True, text=True,
            timeout=900)
        if ref.returncode != 0:
            raise SystemExit(f"host reference failed:\n{ref.stderr[-3000:]}")
        info["reference"] = {**json.loads(ref.stdout.splitlines()[-1]),
                             "seconds": round(time.time() - t0, 2)}
        want = shard_hashes(rbase)
        shutil.rmtree(os.path.dirname(rbase))
        compare_shards(vbase, want, "after ec.encode")
        log(f"14 shard files byte-identical to {info['reference']}")

        times = check_reads(client, args.seed, blobs, sample, deleted,
                            "EC volume")
        info["get_full_ms_median"] = round(
            sorted(times)[len(times) // 2] * 1e3, 2)

        # four shards go, at least one parity and at least two data
        n_parity = rng.choice([1, 2])
        lost = sorted(rng.sample(range(DATA_SHARDS), 4 - n_parity)
                      + rng.sample(range(DATA_SHARDS, TOTAL_SHARDS),
                                   n_parity))
        http_json(f"http://{cluster.volume}/admin/ec/delete_shards",
                  {"volume_id": vid, "collection": COLLECTION,
                   "shard_ids": lost})
        gone = [s for s in lost if not os.path.exists(f"{vbase}.ec{s:02d}")]
        if gone != lost:
            raise SystemExit(f"shards {lost} not all removed: {gone}")
        # ec.encode generated and mounted the shards, and with the first
        # of those the store began compiling every width a degraded read
        # can meet: once that has ended no read may meet a cold width
        warm = cluster.wait_warm()
        if require_tpu and [w["state"] for w in warm] != ["done"]:
            raise SystemExit(f"the server's warm-up did not end well: "
                             f"{warm}")
        cold_before = cluster.cold_dispatches()
        counter = "seaweedfs_tpu_ec_reconstruct_intervals_total"
        seen = [cluster.metric(counter)]
        inline = "seaweedfs_tpu_volume_ec_read_inline_total"
        inline_before = cluster.metric(inline)
        lookups = 'seaweedfs_tpu_volume_ecx_lookups_total{via="%s"}'
        mapped_before = cluster.metric(lookups % "mmap")
        rec_times: list[float] = []  # GETs that reconstructed an interval

        def after_get(seconds: float) -> None:
            seen.append(cluster.metric(counter))
            if seen[-1] > seen[-2]:
                rec_times.append(seconds)

        check_reads(client, args.seed, blobs, cold_sample, deleted,
                    "degraded", after_get)
        if len(rec_times) < 2:
            raise SystemExit(f"{len(rec_times)} of {len(cold_sample)} "
                             "degraded GETs reconstructed an interval, "
                             "wanted >= 2")
        # the fast path answers an EC GET itself: none took the hop to
        # the aiohttp plane
        answered_inline = int(cluster.metric(inline) - inline_before)
        proxied = int(cluster.metric(
            "seaweedfs_tpu_volume_ec_read_proxied_total"))
        if proxied or answered_inline < len(cold_sample):
            raise SystemExit(f"{answered_inline} of {len(cold_sample)} "
                             f"degraded GETs answered on the fast path, "
                             f"{proxied} EC GETs proxied")
        # every GET looked its needle up in the mapped .ecx: none paid
        # a pread a probe
        mapped = int(cluster.metric(lookups % "mmap") - mapped_before)
        by_pread = int(cluster.metric(lookups % "pread"))
        if by_pread or mapped < len(cold_sample):
            raise SystemExit(f"{mapped} of {len(cold_sample)} degraded "
                             f"GETs looked the needle up in the mapped "
                             f".ecx, {by_pread} lookups by pread")
        info["degraded"] = {
            "lost": lost, "reconstructed_intervals": int(seen[-1] - seen[0]),
            "answered_inline": answered_inline, "proxied": proxied,
            "ecx_lookups_mmap": mapped, "ecx_lookups_pread": by_pread,
            "reconstructing_gets": len(rec_times),
            "first_get_s": round(rec_times[0], 3),
            "second_get_s": round(rec_times[1], 3),
            "get_ms_median": round(
                sorted(rec_times)[len(rec_times) // 2] * 1e3, 2),
            "get_s_max": round(max(rec_times), 3)}
        log(f"degraded reads: {info['degraded']}")
        info["xprof"] = device_view(cluster, client, cold_sample,
                                    require_tpu)
        log(f"/debug/xprof while degraded GETs ran: {info['xprof']}")
        cold = cluster.cold_dispatches() - cold_before
        if cold:
            raise SystemExit(f"{cold} degraded-read dispatches met a width "
                             f"that was not compiled, after the warm-up "
                             f"had ended: {warm}")
        info["degraded"]["warm"] = warm

        deadline = time.time() + 60
        while True:  # ec.rebuild plans from the master's view of the loss
            node = http_json(f"http://{cluster.master}/dir/status")["nodes"][0]
            have = [s["shard_ids"] for s in node.get("ec_shards", [])
                    if int(s["id"]) == vid]
            if have and len(have[0]) == TOTAL_SHARDS - len(lost):
                break
            if time.time() > deadline:
                raise SystemExit(f"master never saw the loss: {have}")
            time.sleep(0.5)
        t0 = time.time()
        out = cluster.shell("ec.rebuild", "-volumeId", str(vid),
                            "-collection", COLLECTION)
        if sorted(out.get("rebuilt", [])) != lost:
            raise SystemExit(f"ec.rebuild rebuilt {out}, lost {lost}")
        info["rebuild"] = {
            "survivor_bytes": DATA_SHARDS * os.path.getsize(
                f"{vbase}.ec{lost[0]:02d}"),
            "rows": len(lost), "wall_s": round(time.time() - t0, 2)}
        log(f"ec.rebuild: {info['rebuild']}")
        compare_shards(vbase, want, "after ec.rebuild")
        check_reads(client, args.seed, blobs, sample + cold_sample, deleted,
                    "rebuilt")

        status = cluster.ec_status()
        desc = check_coder(status, mesh, require_tpu)
        if mesh:
            chips = status.get("chips", {})
            idle = [i for i in range(mesh)
                    if not chips.get(str(i), {}).get("staged_bytes")]
            if idle:
                raise SystemExit(f"chips {idle} staged no bytes: {chips}")
            info["chip_staged_bytes"] = {
                i: c.get("staged_bytes") for i, c in sorted(chips.items())}
    finally:
        cluster.stop()
        shutil.rmtree(work, ignore_errors=True)

    if "jax" in sys.modules:
        raise SystemExit("the smoke's parent imported jax")
    info["compile_cache"]["entries_after"] = cache_entries(cache_dir)
    info["total_s"] = round(time.time() - t_start, 1)
    print(json.dumps({"info": info}))
    if not require_tpu:
        log("debug run on the host coder: not a chip result")
        raise SystemExit(3)
    dev = desc["device"]
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["count"]}}))


if __name__ == "__main__":
    main()
