#!/usr/bin/env python3
"""Operator checks: host-only and CPU-pinned phases around the EC tier.

The repo's benchmark is benchmark/run.py (BENCHMARK.json); this file is
what is left of the pre-benchmark harness and measures nothing the driver
reads. The parent orchestrates PHASES, the jax-touching ones each in its
OWN subprocess (one process owns a device at a time), checkpoints every
phase into BENCH_DETAIL.json as it completes, and prints one JSON line
at the end.

Phases:
  fused    compaction + gzip + RS in one pass against the chained path,
           with per-stage seconds
  system   req/s of the write/read data plane
  saturation, largefile, degraded, overload, lifecycle, georepl,
  multichip (virtual CPU mesh), metadata, observe, lint, scale,
  recovery, needle_map: see each phase's docstring

Prints one JSON line: {"phases": [...], "extra": {...}}
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

HARD_BUDGET_S = 1400.0
MB = 1024 * 1024


def _host_coder():
    from seaweedfs_tpu import ec
    try:
        return ec.get_coder("cpp", 10, 4)
    except Exception:
        return ec.get_coder("numpy", 10, 4)


# ----------------------------------------------------------------- phases

def _phase_checkpoint(work: str, name: str, out: dict) -> None:
    """Atomically persist a phase's partial record NOW. The driver reads
    <name>_partial.json when the phase times out or dies, so a stuck
    sub-step can no longer null every number the phase already
    measured."""
    try:
        path = os.path.join(work, f"{name}_partial.json")
        with open(path + ".tmp", "w") as f:
            json.dump(out, f)
        os.replace(path + ".tmp", path)
    except OSError:
        pass


def _load_partial(work: str, name: str) -> dict:
    try:
        with open(os.path.join(work, f"{name}_partial.json")) as f:
            d = json.load(f)
        d["partial"] = True
        return d
    except Exception:
        return {}


def phase_fused(work: str, budget_s: float = 580.0) -> dict:
    """Config 5: the one-pass warm-down (ec/fused.py) against the
    chained vacuum -> gzip -> encode -> scrub-digest path it replaces,
    over the same mixed volume (half compressible, half not — real
    volumes are a mix; round 3's all-text volume measured gzip only).

    `gbps` is the fused steady rate (commit fsyncs excluded — they
    overlap the NEXT volume in the lifecycle batcher's window),
    `gbps_durable` includes them, `speedup` is fused steady over the
    chained wall. `phase_s` breaks the pass down by governor stage
    (ec.compact / ec.gzip / ec.read / ec.kernel / ec.write / ec.digest)
    from the same observe spans the feed governor retunes on.
    `scrub_redigests` proves the scrubber's first verification rode the
    pass: stamp_shard_digests finds nothing left to recompute. Each
    step checkpoints via _phase_checkpoint so a budget kill keeps every
    number already measured; late steps self-skip when the budget runs
    low."""
    import jax

    from seaweedfs_tpu import observe
    from seaweedfs_tpu.ec import pipeline, striping
    from seaweedfs_tpu.ec.fused import fused_vacuum_gzip_encode
    from seaweedfs_tpu.ec.geometry import DEFAULT as GEO, to_ext
    from seaweedfs_tpu.storage import idx as idx_mod
    from seaweedfs_tpu.storage import types as st
    from seaweedfs_tpu.storage.needle import FLAG_IS_COMPRESSED, Needle
    from seaweedfs_tpu.storage.superblock import SuperBlock
    from seaweedfs_tpu.storage.volume import Volume
    from seaweedfs_tpu.utils import compression
    from seaweedfs_tpu.utils import metrics as metrics_mod

    t_phase0 = time.perf_counter()

    def left() -> float:
        return budget_s - (time.perf_counter() - t_phase0)

    out: dict = {"backend": jax.default_backend()}
    vdir = os.path.join(work, "fusedvol")
    os.makedirs(vdir, exist_ok=True)
    v = Volume(vdir, "", 7, create=True)
    rng = np.random.default_rng(11)
    text = (b"fused bench payload: compressible text block. " * 5700)
    count = 0
    target = 192 * MB
    written = 0
    while written < target:
        count += 1
        if count % 2:
            data = text[:256 * 1024]
        else:
            data = rng.integers(0, 256, 256 * 1024,
                                dtype=np.uint8).tobytes()
        v.write_needle(Needle(cookie=count, id=count, data=data))
        written += len(data)
    # delete half of EACH kind (ids 1,2 mod 4): the survivors stay a
    # text/random mix — deleting every other id would remove exactly the
    # text needles (odd ids) and leave an all-random volume
    for i in range(1, count + 1):
        if i % 4 in (1, 2):
            v.delete_needle(Needle(cookie=i, id=i))
    src_bytes = v.data_file_size()
    out["src_bytes"] = src_bytes
    _phase_checkpoint(work, "fused", out)

    host = _host_coder()

    # step 1: the one-pass warm-down, under its own trace so the stage
    # breakdown below aggregates exactly this run's governor spans
    dst = os.path.join(vdir, "out_7")
    tctx = observe.TraceCtx(observe.new_id(), "", "bench", "")
    res = observe.run_with(tctx, fused_vacuum_gzip_encode, v, dst, host,
                           batch_size=4 * MB)
    wall_s = res["wall_s"]
    commit_s = res["commit_s"]
    steady_s = max(wall_s - commit_s, 1e-3)
    out.update({
        "compacted_bytes": res["compacted_bytes"],
        "live_needles": res["live_needles"],
        "gzipped_needles": res["gzipped_needles"],
        "gzip_workers": res["gzip_workers"],
        "gbps": round(src_bytes / steady_s / 1e9, 3),
        "gbps_durable": round(src_bytes / wall_s / 1e9, 3),
        "fused_wall_s": round(wall_s, 3),
        "fused_commit_s": round(commit_s, 3),
    })
    totals = observe.stage_totals(tctx.trace_id, prefix="ec.")
    out["phase_s"] = {name[3:]: round(us / 1e6, 3)
                      for name, (_, us) in sorted(totals.items())}
    stages = {k: v for k, v in out["phase_s"].items()
              if k in ("compact", "gzip", "read", "dispatch",
                       "kernel", "write", "digest")}
    if stages:
        out["bottleneck"] = max(stages, key=stages.get)
    _phase_checkpoint(work, "fused", out)

    # step 2: scrubber rides the pass — stamp_shard_digests (the mount/
    # scrub path's backfill) must find every digest already in the .ecm,
    # and the stamped values must match a fresh host digest of the bytes
    reg = metrics_mod.shared("ec")
    before = reg.value("ec_digest_host_recompute")
    pipeline.stamp_shard_digests(dst, GEO)
    out["scrub_redigests"] = int(
        reg.value("ec_digest_host_recompute") - before)
    stamped = pipeline.read_stamped_digests(dst)
    shard_ids = list(range(GEO.total_shards))
    true_dig = pipeline.shard_file_digest(dst, shard_ids)
    for sid in shard_ids:
        if stamped.get(sid) != int(true_dig[sid]):
            raise AssertionError(
                f"fused stamped digest wrong for shard {sid}")
    _phase_checkpoint(work, "fused", out)

    # step 3: the chained baseline it replaces — per-needle compact +
    # gzip into dst, then stream_encode, sorted .ecx, and the host
    # re-digest the scrubber's first verification used to cost
    if left() < 45.0:
        out["baseline"] = {"error": "skipped (budget)"}
        v.close()
        _phase_checkpoint(work, "fused", out)
        return out
    seq = os.path.join(vdir, "seq_7")
    t0 = time.perf_counter()
    with v._lock:
        snapshot = [nv for nv in v.nm.values()
                    if st.size_is_valid(nv.size)]
        sb = SuperBlock(
            version=v.super_block.version,
            replica_placement=v.super_block.replica_placement,
            ttl=v.super_block.ttl,
            compaction_revision=v.super_block.compaction_revision + 1,
            extra=v.super_block.extra)
    snapshot.sort(key=lambda nv: nv.offset)
    with open(seq + ".dat", "wb") as dat, open(seq + ".idx", "wb") as ix:
        dat.write(sb.to_bytes())
        offset = len(sb.to_bytes())
        for nv in snapshot:
            n = v.read_needle_at(st.stored_to_offset(nv.offset), nv.size)
            if n.data and not n.is_compressed \
                    and v.version != st.VERSION1:
                head = n.data[:4096]
                trial = compression.compress(head, level=1)
                if len(trial) * 10 < len(head) * 9:
                    comp = compression.compress(n.data, level=1)
                    if len(comp) * 10 < len(n.data) * 9:
                        n.data = comp
                        n.set_flag(FLAG_IS_COMPRESSED)
            record = n.to_bytes(v.version)
            if offset % st.NEEDLE_PADDING_SIZE:
                pad = (-offset) % st.NEEDLE_PADDING_SIZE
                dat.write(bytes(pad))
                offset += pad
            dat.write(record)
            ix.write(idx_mod.pack_entry(
                nv.key, st.offset_to_stored(offset, v.offset_size),
                n.size, offset_size=v.offset_size))
            offset += len(record)
    t_compact_gzip = time.perf_counter() - t0
    v.close()
    t0 = time.perf_counter()
    pipeline.stream_encode(seq, host, batch_size=4 * MB)
    striping.write_sorted_ecx_from_idx(seq, offset_size=v.offset_size)
    t_encode = time.perf_counter() - t0
    t0 = time.perf_counter()
    pipeline.shard_file_digest(seq, shard_ids)  # scrubber's first verify
    t_scrub = time.perf_counter() - t0
    baseline_wall = t_compact_gzip + t_encode + t_scrub
    out["baseline"] = {
        "compact_gzip_s": round(t_compact_gzip, 3),
        "encode_s": round(t_encode, 3),
        "scrub_digest_s": round(t_scrub, 3),
        "wall_s": round(baseline_wall, 3),
        "gbps": round(src_bytes / baseline_wall / 1e9, 3),
    }
    out["speedup"] = round(
        out["gbps"] / max(out["baseline"]["gbps"], 1e-9), 2)
    _phase_checkpoint(work, "fused", out)

    # step 4: identity spot check — same compacted bytes, same shards
    for ext in (".dat", ".ecx", to_ext(0), to_ext(GEO.total_shards - 1)):
        with open(seq + ext, "rb") as a, open(dst + ext, "rb") as b:
            if a.read() != b.read():
                raise AssertionError(
                    f"fused output diverges from chained path at {ext}")
    out["identical_to_chained"] = True
    _phase_checkpoint(work, "fused", out)
    return out


def bench_system(work: str, n: int = 6000, size: int = 1024,
                 concurrency: int = 16) -> dict:
    """System req/s vs the reference's published benchmark
    (README.md:504-553: 15,708 writes/s, 47,019 reads/s at 1KB, c=16 on
    a multi-core 2014 MacBook i7 running BOTH the Go server and the Go
    client). Here the combined server + the raw-socket self-validating
    client share this host; workers scale with available cores."""
    import urllib.request

    from seaweedfs_tpu.utils.bench_client import run_benchmark

    import seaweedfs_tpu
    pkg_root = os.path.dirname(os.path.dirname(seaweedfs_tpu.__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")

    def _one(workers: int, tag: str) -> dict:
        mport, vport = 19555, 18555
        data_dir = os.path.join(work, f"sysbench_{tag}")
        os.makedirs(data_dir, exist_ok=True)
        proc = subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu.cli", "server",
             "-ip", "127.0.0.1", "-master_port", str(mport),
             "-port", str(vport), "-dir", data_dir,
             "-volume_workers", str(workers)],
            cwd=data_dir, env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        try:
            deadline = time.time() + 30
            while True:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{mport}/dir/assign",
                            timeout=2) as r:
                        if "fid" in json.loads(r.read()):
                            break
                except Exception:
                    pass
                if time.time() > deadline:
                    raise RuntimeError("combined server failed to start")
                time.sleep(0.3)
            # warm pass (discarded): volume growth, page allocation and
            # connection setup otherwise land in the first timed batch
            run_benchmark(f"127.0.0.1:{mport}", n=400, size=size,
                          concurrency=concurrency)
            return run_benchmark(f"127.0.0.1:{mport}", n=n, size=size,
                                 concurrency=concurrency)
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
            time.sleep(0.5)  # let the ports free before the next boot

    workers = max(1, min(4, (os.cpu_count() or 1) - 1)) \
        if (os.cpu_count() or 1) > 1 else 1
    out = _one(workers, "w1")
    # worker-scaling row (round-4 verdict: prove or drop the per-core
    # parity claim). On a 1-core host a flat/negative slope IS the
    # measured ceiling evidence: the binding resource is the shared
    # core, not the worker count.
    try:
        w2 = _one(workers + 1, "w2")
        out["scaling"] = {
            "volume_workers": workers + 1,
            "write_req_s": w2["write"]["req_s"],
            "read_req_s": w2["read"]["req_s"],
            "write_slope_vs_base": round(
                w2["write"]["req_s"] / max(out["write"]["req_s"], 1), 3),
            "read_slope_vs_base": round(
                w2["read"]["req_s"] / max(out["read"]["req_s"], 1), 3),
            "note": ("server+client share os.cpu_count() core(s); a "
                     "slope <= 1.0 on a 1-core host means the shared "
                     "core, not the worker count, is the ceiling "
                     "(extra workers only add context switching there; "
                     "on multi-core hosts each worker is a "
                     "share-nothing process on its own core)"),
        }
    except Exception as e:
        out["scaling"] = {"error": str(e)}

    def _one_sharded(shards: int) -> dict:
        # the share-nothing SO_REUSEPORT fleet (server/sharded.py): the
        # combined `server` command doesn't fork shards, so this boots
        # the phase_saturation shape — master + WEED_SERVE_SHARDS=N
        # volume — on this phase's ports
        mport, vport = 19555, 18555
        base = os.path.join(work, f"sysbench_sh{shards}")
        mdir, vdir = os.path.join(base, "m"), os.path.join(base, "v")
        os.makedirs(mdir, exist_ok=True)
        os.makedirs(vdir, exist_ok=True)
        senv = dict(env, WEED_SERVE_SHARDS=str(shards))
        procs = [subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu.cli", "master",
             "-port", str(mport), "-mdir", mdir, "-grpc_port", "0",
             "-pulse", "1"], env=senv,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)]
        try:
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "seaweedfs_tpu.cli", "volume",
                 "-port", str(vport), "-dir", vdir,
                 "-mserver", f"127.0.0.1:{mport}", "-grpc_port", "0",
                 "-pulse", "1"], env=senv,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
            deadline = time.time() + 60
            while True:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{mport}/dir/assign",
                            timeout=2) as r:
                        if "fid" in json.loads(r.read()):
                            break
                except Exception:
                    pass
                if time.time() > deadline:
                    raise RuntimeError(
                        f"shards={shards} fleet failed to start")
                time.sleep(0.3)
            time.sleep(1.0)  # first stripe tick publishes shard routes
            run_benchmark(f"127.0.0.1:{mport}", n=400, size=size,
                          concurrency=concurrency)
            return run_benchmark(f"127.0.0.1:{mport}", n=n, size=size,
                                 concurrency=concurrency)
        finally:
            for p in reversed(procs):
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
            time.sleep(0.5)

    # multi-core re-baseline row: the single-process numbers above stand
    # next to the sharded fleet's, so the next bench round on a
    # multi-core host re-anchors the serving baseline without a code
    # change; a 1-core host records WHY there's no row instead of a null
    cores = os.cpu_count() or 1
    if cores > 1:
        shards = max(2, min(4, cores))
        try:
            sh = _one_sharded(shards)
            out["sharded"] = {
                "shards": shards,
                "write_req_s": sh["write"]["req_s"],
                "read_req_s": sh["read"]["req_s"],
                "write_slope_vs_single": round(
                    sh["write"]["req_s"] / max(out["write"]["req_s"], 1),
                    3),
                "read_slope_vs_single": round(
                    sh["read"]["req_s"] / max(out["read"]["req_s"], 1),
                    3),
            }
        except Exception as e:
            out["sharded"] = {"error": f"{type(e).__name__}: "
                                       f"{str(e)[:160]}"}
    else:
        out["sharded"] = ("skipped: 1-core host (the fleet only adds "
                          "context switching; boots when "
                          "os.cpu_count() > 1)")
    out["cpu_count"] = os.cpu_count()
    out["volume_workers"] = workers
    out["vs_reference"] = {
        "ref_write_req_s": 15708, "ref_read_req_s": 47019,
        "write_ratio": round(out["write"]["req_s"] / 15708, 4),
        "read_ratio": round(out["read"]["req_s"] / 47019, 4),
        "note": ("reference ran server+client on a multi-core i7; this "
                 "host pins both to os.cpu_count() core(s). Per-core "
                 "(ref assumed 4 cores): write "
                 f"{round(out['write']['req_s'] / max((os.cpu_count() or 1), 1) / (15708 / 4), 2)}x, "
                 "read "
                 f"{round(out['read']['req_s'] / max((os.cpu_count() or 1), 1) / (47019 / 4), 2)}x"),
    }
    return out


def phase_saturation(work: str, budget_s: float = 240.0,
                     n: int = 2500, size: int = 1024,
                     concurrency: int = 16) -> dict:
    """Share-nothing shard-fleet saturation: boots a master plus a
    WEED_SERVE_SHARDS=N volume server (the SO_REUSEPORT fleet forked
    by the CLI) and runs the same 1KB write/read benchmark once at
    shards=1 (the single-process path) and once at shards=min(4,
    host cores, 2 minimum). Acceptance on multi-core hosts is
    saturation throughput >= 2.5x the single-shard run; on a 1-core
    host the fleet only adds context switching, so host_cores is
    recorded and the slope stands as measured-ceiling evidence
    (same verdict idiom as bench_system's worker-scaling row)."""
    import urllib.request

    from seaweedfs_tpu.utils.bench_client import run_benchmark

    import seaweedfs_tpu
    pkg_root = os.path.dirname(os.path.dirname(seaweedfs_tpu.__file__))
    cores = os.cpu_count() or 1
    fleet = max(2, min(4, cores))
    deadline = time.time() + budget_s

    def _one(shards: int, tag: str) -> dict:
        mport, vport = 19666, 18666
        base = os.path.join(work, f"sat_{tag}")
        mdir, vdir = os.path.join(base, "m"), os.path.join(base, "v")
        os.makedirs(mdir, exist_ok=True)
        os.makedirs(vdir, exist_ok=True)
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   WEED_SERVE_SHARDS=str(shards))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get(
            "PYTHONPATH", "")
        procs = [subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu.cli", "master",
             "-port", str(mport), "-mdir", mdir, "-grpc_port", "0",
             "-pulse", "1"], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)]
        try:
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "seaweedfs_tpu.cli", "volume",
                 "-port", str(vport), "-dir", vdir,
                 "-mserver", f"127.0.0.1:{mport}", "-grpc_port", "0",
                 "-pulse", "1"], env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
            boot_deadline = time.time() + 60
            while True:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{mport}/dir/assign",
                            timeout=2) as r:
                        if "fid" in json.loads(r.read()):
                            break
                except Exception:
                    pass
                if time.time() > boot_deadline:
                    raise RuntimeError(
                        f"shards={shards} fleet failed to start")
                time.sleep(0.3)
            time.sleep(1.0)  # first stripe tick publishes shard routes
            # warm pass (discarded): volume growth + route discovery
            run_benchmark(f"127.0.0.1:{mport}", n=min(300, n),
                          size=size, concurrency=concurrency)
            out = run_benchmark(f"127.0.0.1:{mport}", n=n, size=size,
                                concurrency=concurrency)
            out["shards"] = shards
            return out
        finally:
            for p in reversed(procs):
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
            time.sleep(0.5)  # let the reuseport group free the port

    single = _one(1, "s1")
    out: dict = {
        "host_cores": cores,
        "shards": fleet,
        "single": {"write_req_s": single["write"]["req_s"],
                   "read_req_s": single["read"]["req_s"]},
    }
    if time.time() > deadline:
        out["fleet"] = {"error": "skipped (budget)"}
        return out
    try:
        multi = _one(fleet, f"s{fleet}")
        out["fleet"] = {"write_req_s": multi["write"]["req_s"],
                        "read_req_s": multi["read"]["req_s"]}
        w_x = round(multi["write"]["req_s"]
                    / max(single["write"]["req_s"], 1), 3)
        r_x = round(multi["read"]["req_s"]
                    / max(single["read"]["req_s"], 1), 3)
        out["speedup"] = {"write": w_x, "read": r_x}
        out["accept"] = {
            "target": "fleet >= 2.5x single (multi-core hosts only)",
            "applies": cores >= fleet,
            "write_2_5x": w_x >= 2.5,
            "read_2_5x": r_x >= 2.5,
            "note": (None if cores >= fleet else
                     f"host has {cores} core(s): {fleet} shards time-"
                     "slice one core, so the slope measures context-"
                     "switch overhead, not per-core scaling"),
        }
    except Exception as e:  # noqa: BLE001 - recorded, not fatal
        out["fleet"] = {"error": str(e)}
    return out


def phase_largefile(work: str, size_mb: int = 64) -> dict:
    """Write-tier number beyond req/s: single-stream large-file filer
    PUT and GET MB/s through the pipelined chunk-upload window + fid
    lease (ISSUE 5). Boots master+volume+filer in one combined-server
    process (8 MB chunks -> size_mb/8 chunks per PUT), uploads one
    large body, reads it back, verifies byte identity. Every measured
    value checkpoints to largefile_partial.json the moment it exists."""
    import hashlib
    import urllib.request

    import seaweedfs_tpu
    pkg_root = os.path.dirname(os.path.dirname(seaweedfs_tpu.__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")

    mport, vport, fport = 19666, 18666, 18999
    data_dir = os.path.join(work, "largefile")
    os.makedirs(data_dir, exist_ok=True)
    out: dict = {"size_mb": size_mb}
    proc = subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu.cli", "server",
         "-ip", "127.0.0.1", "-master_port", str(mport),
         "-port", str(vport), "-dir", data_dir,
         "-filer", "-filer_port", str(fport),
         "-filer_db", os.path.join(data_dir, "filer.db")],
        cwd=data_dir, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.time() + 30
        while True:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{fport}/healthz",
                        timeout=2) as r:
                    if json.load(r).get("ok"):
                        break
            except Exception:
                pass
            if time.time() > deadline:
                raise RuntimeError("combined server failed to start")
            time.sleep(0.3)

        rng = np.random.default_rng(11)
        body = rng.integers(0, 256, size_mb * 1024 * 1024,
                            dtype=np.uint8).tobytes()
        digest = hashlib.md5(body).hexdigest()

        def put(path: str) -> float:
            req = urllib.request.Request(
                f"http://127.0.0.1:{fport}{path}", data=body,
                method="PUT",
                headers={"Content-Type": "application/octet-stream"})
            t0 = time.perf_counter()
            with urllib.request.urlopen(req, timeout=300) as r:
                r.read()
            return time.perf_counter() - t0

        def get(path: str) -> tuple[float, str]:
            t0 = time.perf_counter()
            h = hashlib.md5()
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{fport}{path}", timeout=300) as r:
                while True:
                    block = r.read(1 << 20)
                    if not block:
                        break
                    h.update(block)
            return time.perf_counter() - t0, h.hexdigest()

        put("/bench/warm.bin")  # volume growth + connection warmup
        put_s = put("/bench/large.bin")
        out["put_mb_s"] = round(size_mb / put_s, 1)
        out["put_wall_s"] = round(put_s, 3)
        _phase_checkpoint(work, "largefile", out)
        get_s, got = get("/bench/large.bin")
        out["get_mb_s"] = round(size_mb / get_s, 1)
        out["get_wall_s"] = round(get_s, 3)
        out["verified"] = got == digest
        if not out["verified"]:
            out["error"] = "GET digest mismatch"
        # lease effectiveness during the run, straight from the filer
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{fport}/metrics", timeout=5) as r:
                text = r.read().decode()
            vals = {}
            for line in text.splitlines():
                if line.startswith("seaweedfs_tpu_filer_assign_lease_"):
                    k, _, v = line.partition(" ")
                    vals[k.rsplit("_", 2)[-2]] = float(v)
            h_, m_ = vals.get("hit", 0.0), vals.get("miss", 0.0)
            if h_ + m_:
                out["assign_lease_hit_rate"] = round(h_ / (h_ + m_), 3)
        except Exception:
            pass
        _phase_checkpoint(work, "largefile", out)
        return out
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
        time.sleep(0.5)


def bench_needle_map(work: str, n: int = 5_000_000) -> dict:
    from seaweedfs_tpu.storage.needle_map import DiskNeedleMap

    rec = np.empty(n, dtype=[("k", ">u8"), ("o", ">u4"), ("s", ">u4")])
    rec["k"] = np.arange(1, n + 1)
    rec["o"] = np.arange(1, n + 1)
    rec["s"] = 1000
    path = os.path.join(work, "nmbench.idx")
    rec.tofile(path)
    del rec
    t0 = time.perf_counter()
    nm = DiskNeedleMap(path)
    cold_s = time.perf_counter() - t0
    nm.close()
    t0 = time.perf_counter()
    nm = DiskNeedleMap(path)
    warm_s = time.perf_counter() - t0
    rng = np.random.default_rng(3)
    keys = rng.integers(1, n + 1, 2000)
    lat = []
    for key in keys:
        t0 = time.perf_counter()
        nm.get(int(key))
        lat.append(time.perf_counter() - t0)
    nm.close()
    lat.sort()
    return {"entries": n, "cold_build_s": round(cold_s, 3),
            "warm_open_s": round(warm_s, 4),
            "lookup_p50_us": round(lat[len(lat) // 2] * 1e6, 1),
            "lookup_p99_us": round(lat[int(len(lat) * 0.99)] * 1e6, 1)}


def phase_degraded(work: str, budget_s: float = 240.0,
                   n_reads: int = 300) -> dict:
    """p50/p99 degraded-read latency with one shard holder faulted —
    the warm-storage tier's brownout regime. A real multi-process
    cluster (master + 4 volume server subprocesses, so the fault
    registry is per NODE) EC-encodes the uploaded volume, then
    ``POST /admin/faults`` makes one holder answer every shard read
    with an injected error: reads served by another holder reconstruct
    the missing intervals from the survivors. Budget-aware and
    checkpointed into degraded_partial.json like the other phases."""
    import random as random_mod
    import socket
    import urllib.request

    started = time.perf_counter()

    def left() -> float:
        return budget_s - (time.perf_counter() - started)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from seaweedfs_tpu.client import Client
    from seaweedfs_tpu.shell.ec_commands import EcCommands

    import seaweedfs_tpu
    pkg_root = os.path.dirname(os.path.dirname(seaweedfs_tpu.__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def spawn(args, tag):
        log = open(os.path.join(work, f"degraded_{tag}.log"), "ab")
        return subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu.cli"] + args,
            cwd=work, env=env, stdout=log, stderr=log)

    procs = []
    out: dict = {}
    try:
        mport = free_port()
        master = f"127.0.0.1:{mport}"
        procs.append(spawn(["master", "-port", str(mport), "-mdir", work],
                           "master"))
        for i in range(4):
            vdir = os.path.join(work, f"degraded_vs{i}")
            os.makedirs(vdir, exist_ok=True)
            procs.append(spawn(["volume", "-port", str(free_port()),
                                "-dir", vdir, "-mserver", master,
                                "-pulse", "1"], f"vs{i}"))
        client = Client(master)
        deadline = time.time() + 45
        nodes_up = 0
        while time.time() < deadline:
            try:
                nodes_up = len(client.dir_status().get("nodes", []))
                if nodes_up >= 4:
                    break
            except Exception:
                pass
            time.sleep(0.3)
        if nodes_up == 0:
            raise RuntimeError("degraded cluster never booted "
                               "(0/4 volume servers after 45s)")

        # setup is budget-governed too: on a slow host, uploads against
        # a half-booted cluster retry forever — without these checks the
        # phase hangs PAST its budget instead of recording an error
        rng = random_mod.Random(5)
        blobs: dict[str, bytes] = {}
        for _ in range(60):
            if left() < budget_s * 0.5:
                raise RuntimeError(
                    f"setup over half budget after {len(blobs)}/60 "
                    f"uploads ({nodes_up}/4 volume servers up)")
            data = bytes(rng.getrandbits(8)
                         for _ in range(rng.randint(4096, 32768)))
            blobs[client.upload(data, collection="deg")] = data
        time.sleep(2.0)  # heartbeat rounds so the master sees the volumes
        vids = sorted({int(f.split(",")[0]) for f in blobs})
        shell = EcCommands(client)  # production RS(10,4) geometry
        for vid in vids:
            if left() < 60:
                raise RuntimeError(
                    f"budget exhausted before encoding volume {vid}")
            shell.encode(vid, "deg", apply=True)
        time.sleep(2.0)

        # a ~1.5MB volume striped at 1MB small blocks puts ALL the data
        # in shards 0-1 — fault the holder of shard 0 (where the bytes
        # live) and read through a holder that has NO data shard
        # locally, so every measured read crosses the wire and shard-0
        # reads reconstruct from survivors
        shards_map = client.ec_lookup(vids[0]).get("shards", {})
        holder_urls = sorted({u for urls in shards_map.values()
                              for u in urls})
        assert len(holder_urls) >= 2, holder_urls
        data_holders = {u for sid in ("0", "1")
                        for u in shards_map.get(sid, [])}
        victim = shards_map["0"][0]
        non_data = [u for u in holder_urls if u not in data_holders]
        reader = non_data[0] if non_data else next(
            u for u in holder_urls if u != victim)
        fids = list(blobs)

        def measure(n: int) -> list[float]:
            lat = []
            for i in range(n):
                if left() < 20:
                    break
                fid = fids[i % len(fids)]
                t0 = time.perf_counter()
                with urllib.request.urlopen(
                        f"http://{reader}/{fid}", timeout=30) as r:
                    body = r.read()
                lat.append(time.perf_counter() - t0)
                assert body == blobs[fid], f"corrupt read of {fid}"
            return lat

        def pctl(lat: list[float], q: float) -> float:
            return round(
                sorted(lat)[min(len(lat) - 1, int(len(lat) * q))] * 1e3, 3)

        healthy = measure(min(n_reads, 100))
        out["healthy_p50_ms"] = pctl(healthy, 0.50)
        out["healthy_p99_ms"] = pctl(healthy, 0.99)
        _phase_checkpoint(work, "degraded", out)

        # fault the victim's shard serving (both its HTTP shard endpoint
        # and its gRPC plane): reads touching its shards now reconstruct
        req = urllib.request.Request(
            f"http://{victim}/admin/faults",
            data=json.dumps({"set": [
                {"point": "ec.shard_read", "action": "error"},
                {"point": "rpc.VolumeEcShardRead", "action": "error"},
            ]}).encode(),
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=10).close()
        degraded = measure(n_reads)
        out.update({
            "n_reads": len(degraded),
            "degraded_p50_ms": pctl(degraded, 0.50),
            "degraded_p99_ms": pctl(degraded, 0.99),
            "degraded_over_healthy_p50": round(
                pctl(degraded, 0.50) / max(out["healthy_p50_ms"], 1e-6),
                2),
            "faulted_holder": victim,
            "note": ("one shard holder answers every shard read with an "
                     "injected error (fault plane, per-process "
                     "registry); reads served by another holder "
                     "reconstruct missing intervals from survivors"),
        })
        _phase_checkpoint(work, "degraded", out)
    finally:
        for p in procs:
            try:
                p.terminate()
            except OSError:
                pass
        for p in procs:
            try:
                p.wait(timeout=5)
            except Exception:
                try:
                    p.kill()
                except OSError:
                    pass
    return out


def _reader_storm(vport: int, fids: list, n_fg: int, n_bg: int,
                  seconds: float, breaker=None) -> dict:
    """Closed-loop reader storm against the volume fastpath
    (shared by phase_overload and phase_georepl — the georepl
    acceptance measures replication lag under exactly the
    overload phase's 3x-offered saturation shape).

    fg workers ride raw keep-alive connections and never honor
    Retry-After (they ARE the overload); bg workers go through
    HttpPool so shed answers exercise the breaker-exemption
    path."""
    import http.client as http_client
    import random as random_mod
    import threading

    from seaweedfs_tpu.cache.http_pool import HttpPool
    results: list = []
    lock = threading.Lock()
    stop_at = time.perf_counter() + seconds
    pool = HttpPool(breaker=breaker, shed_retries=0) \
        if n_bg else None

    def fg_worker(seed: int) -> None:
        r = random_mod.Random(seed)
        conn = None
        codes: dict = {}
        lat: list = []
        while time.perf_counter() < stop_at:
            fid = fids[r.randrange(len(fids))]
            t0 = time.perf_counter()
            try:
                if conn is None:
                    conn = http_client.HTTPConnection(
                        "127.0.0.1", vport, timeout=10)
                conn.request("GET", f"/{fid}")
                resp = conn.getresponse()
                resp.read()
                code = resp.status
                if resp.will_close:
                    conn.close()
                    conn = None
            except Exception:
                if conn is not None:
                    conn.close()
                conn = None
                continue
            codes[code] = codes.get(code, 0) + 1
            if code == 200:
                lat.append(time.perf_counter() - t0)
            else:
                # hold the offered rate instead of amplifying
                # it: an instantly-answered 503 re-sent in a
                # tight loop would turn "3x offered" into an
                # unbounded retry storm whose client threads
                # also starve the single-core server of CPU —
                # exactly the anti-pattern Retry-After exists
                # to prevent
                time.sleep(0.05)
        if conn is not None:
            conn.close()
        with lock:
            results.append(("fg", codes, lat))

    def bg_worker(seed: int) -> None:
        r = random_mod.Random(seed)
        codes: dict = {}
        while time.perf_counter() < stop_at:
            fid = fids[r.randrange(len(fids))]
            try:
                resp = pool.request(
                    "GET", f"http://127.0.0.1:{vport}/{fid}",
                    headers={"X-Seaweed-Priority": "bg"},
                    timeout=10)
                codes[resp.status] = codes.get(resp.status,
                                               0) + 1
            except Exception:
                continue
            time.sleep(0.01)  # repair-ish pacing, still pushy
        with lock:
            results.append(("bg", codes, {}))

    threads = [threading.Thread(target=fg_worker, args=(i,))
               for i in range(n_fg)]
    threads += [threading.Thread(target=bg_worker,
                                 args=(1000 + i,))
                for i in range(n_bg)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if pool is not None:
        pool.close()
    fg_codes: dict = {}
    bg_codes: dict = {}
    fg_lat: list = []
    for cls, codes, lat in results:
        tgt = fg_codes if cls == "fg" else bg_codes
        for k, v in codes.items():
            tgt[k] = tgt.get(k, 0) + v
        fg_lat.extend(lat)
    fg_lat.sort()

    def pctl(q: float) -> float:
        if not fg_lat:
            return 0.0
        return round(fg_lat[min(len(fg_lat) - 1,
                                int(len(fg_lat) * q))] * 1e3, 3)

    return {
        "goodput_req_s": round(fg_codes.get(200, 0) / seconds,
                               1),
        "fg_codes": {str(k): v for k, v in
                     sorted(fg_codes.items())},
        "bg_codes": {str(k): v for k, v in
                     sorted(bg_codes.items())},
        "p50_ms": pctl(0.50),
        "p99_ms": pctl(0.99),
    }


def phase_overload(work: str, budget_s: float = 150.0) -> dict:
    """Admitted goodput and p99 at >=2x offered saturation — the
    overload plane's headline numbers. A combined server boots with a
    deliberately small foreground pipe (WEED_ADMISSION_FG_CONCURRENCY=8,
    queue 8) and a 20ms injected service time on volume reads (fault
    plane — same delay in both phases, so capacity is identical and the
    ratio is honest). Phase A saturates the pipe exactly (8 closed-loop
    readers = capacity); phase B offers 3x that (24 fg readers + 4
    bg-tagged readers). Acceptance: admitted goodput under overload
    >= 85% of the single-saturation peak, zero bg requests admitted
    while fg is being shed (server-side inversion counter AND
    client-side observation), and no circuit breaker opened by shed
    responses (bg riders use a threshold-1 breaker)."""
    import random as random_mod
    import socket
    import urllib.request

    started = time.perf_counter()

    def left() -> float:
        return budget_s - (time.perf_counter() - started)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from seaweedfs_tpu.client import Client
    from seaweedfs_tpu.utils.retry import CircuitBreaker

    import seaweedfs_tpu
    pkg_root = os.path.dirname(os.path.dirname(seaweedfs_tpu.__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               WEED_ADMISSION_FG_CONCURRENCY="8",
               WEED_ADMISSION_FG_QUEUE="8",
               WEED_ADMISSION_QUEUE_TIMEOUT_MS="2000",
               WEED_ADMISSION_BG_CONCURRENCY="4",
               WEED_ADMISSION_RETRY_AFTER_S="1")
    env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    mport, vport = free_port(), free_port()
    data_dir = os.path.join(work, "overload_srv")
    os.makedirs(data_dir, exist_ok=True)
    logf = open(os.path.join(work, "overload_srv.log"), "ab")
    proc = subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu.cli", "server",
         "-ip", "127.0.0.1", "-master_port", str(mport),
         "-port", str(vport), "-dir", data_dir],
        cwd=data_dir, env=env, stdout=logf, stderr=logf)
    out: dict = {}
    try:
        deadline = time.time() + 45
        while True:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{mport}/dir/assign",
                        timeout=2) as r:
                    if "fid" in json.loads(r.read()):
                        break
            except Exception:
                pass
            if time.time() > deadline:
                raise RuntimeError("overload server failed to start")
            time.sleep(0.3)

        client = Client(f"127.0.0.1:{mport}")
        rng = random_mod.Random(13)
        fids = [client.upload(bytes(rng.getrandbits(8)
                                    for _ in range(1024)))
                for _ in range(64)]

        # 20ms injected service time on the volume read path — the knob
        # that makes capacity deterministic (8 slots / ~21.5ms ~= 370
        # req/s) AND leaves CPU headroom on the shared host, so the
        # overload phase measures the admission queue, not GIL
        # contention between the storm threads and the server process
        req = urllib.request.Request(
            f"http://127.0.0.1:{vport}/admin/faults",
            data=json.dumps({"set": [
                {"point": "volume.read", "action": "delay", "ms": 20},
            ]}).encode(),
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=10).close()

        peak = _reader_storm(vport, fids, 8, 0,
                             min(4.0, max(left() - 30, 2.0)))
        out["peak"] = peak
        _phase_checkpoint(work, "overload", out)

        breaker = CircuitBreaker(failure_threshold=1)
        over = _reader_storm(vport, fids, 24, 4,
                             min(5.0, max(left() - 15, 2.0)),
                             breaker=breaker)
        out["overload"] = over
        out["offered_factor"] = 3.0  # 24 closed-loop readers vs 8
        peak_good = max(peak["goodput_req_s"], 1e-6)
        out["goodput_ratio"] = round(
            over["goodput_req_s"] / peak_good, 3)
        out["fg_shed"] = over["fg_codes"].get("503", 0)
        out["bg_shed"] = over["bg_codes"].get("503", 0)
        out["bg_admitted_during_storm"] = over["bg_codes"].get("200", 0)
        out["client_breaker_opened"] = breaker.is_open(
            f"127.0.0.1:{vport}")
        _phase_checkpoint(work, "overload", out)

        # server-side evidence from /metrics: the inversion counter
        # (bg admitted under fg pressure — must not exist/stay 0) and
        # breaker_opened (shed answers must not have tripped anything)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{vport}/metrics", timeout=10) as r:
            text = r.read().decode()

        def metric(needle: str) -> float:
            for line in text.splitlines():
                if needle in line and not line.startswith("#"):
                    try:
                        return float(line.rsplit(" ", 1)[1])
                    except ValueError:
                        pass
            return 0.0

        out["server_metrics"] = {
            "admitted_fg": metric('admission_admitted_total{cls="fg"}'),
            "admitted_bg": metric('admission_admitted_total{cls="bg"}'),
            "shed_fg": metric('admission_shed_total{cls="fg"}'),
            "shed_bg": metric('admission_shed_total{cls="bg"}'),
            "inversions": metric("admission_inversion_total"),
            "breaker_opened": metric("breaker_opened_total"),
        }
        out["acceptance"] = {
            "goodput_ge_85pct_of_peak": out["goodput_ratio"] >= 0.85,
            # judged by the server's invariant counter (bg admitted WHILE
            # fg pressure exists, checked at admit time) — a whole-window
            # client-side count would flag a bg 200 that legitimately
            # landed before fg pressure formed at storm start;
            # bg_admitted_during_storm stays above as informational
            "zero_bg_admitted_while_fg_shed":
                out["server_metrics"]["inversions"] == 0,
            "no_breaker_opened_by_shed":
                out["server_metrics"]["breaker_opened"] == 0
                and not out["client_breaker_opened"],
        }
        _phase_checkpoint(work, "overload", out)
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
        logf.close()
        time.sleep(0.5)
    return out



def phase_observe(work: str, budget_s: float = 180.0) -> dict:
    """Telemetry-plane overhead gate: read p50 with the whole plane
    armed (19Hz sampling profiler + per-request wide events + trace
    spans — the shipping default) vs fully disarmed, same server shape
    and workload. Acceptance: armed p50 regression <= 3% — the number
    that justifies always-on in production. Each config boots its own
    server (the knobs are read at startup) and is measured twice with
    the min taken, so a one-off host hiccup can't fail the gate.

    Both configs get the same fault-injected 2ms service time on
    volume.read (phase_overload's determinism trick): without a floor
    the raw p50 on this host is ~0.8ms and swings +-25% run to run from
    scheduler noise alone — far above the ~10us/request the plane
    actually costs (measured separately and reported as
    per_request_overhead_us, so the absolute cost stays visible and
    isn't laundered by the floor)."""
    import socket
    import urllib.request

    started = time.perf_counter()

    def left() -> float:
        return budget_s - (time.perf_counter() - started)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from seaweedfs_tpu.client import Client

    import seaweedfs_tpu
    pkg_root = os.path.dirname(os.path.dirname(seaweedfs_tpu.__file__))

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def measure(tag: str, env_extra: dict, armed: bool = False) -> dict:
        env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get(
            "PYTHONPATH", "")
        mport, vport = free_port(), free_port()
        data_dir = os.path.join(work, f"observe_{tag}")
        os.makedirs(data_dir, exist_ok=True)
        with open(os.path.join(work, f"observe_{tag}.log"), "ab") as logf:
            proc = subprocess.Popen(
                [sys.executable, "-m", "seaweedfs_tpu.cli", "server",
                 "-ip", "127.0.0.1", "-master_port", str(mport),
                 "-port", str(vport), "-dir", data_dir],
                cwd=data_dir, env=env, stdout=logf, stderr=logf)
            try:
                deadline = time.time() + 45
                while True:
                    try:
                        with urllib.request.urlopen(
                                f"http://127.0.0.1:{mport}/dir/assign",
                                timeout=2) as r:
                            if "fid" in json.loads(r.read()):
                                break
                    except Exception:
                        pass
                    if time.time() > deadline:
                        raise RuntimeError(
                            f"observe/{tag} server failed to start")
                    time.sleep(0.3)
                client = Client(f"127.0.0.1:{mport}")
                fids = [client.upload(b"telemetry overhead " * 50)
                        for _ in range(32)]
                req = urllib.request.Request(
                    f"http://127.0.0.1:{vport}/admin/faults",
                    data=json.dumps({"set": [
                        {"point": "volume.read", "action": "delay",
                         "ms": 2},
                    ]}).encode(),
                    headers={"Content-Type": "application/json"})
                urllib.request.urlopen(req, timeout=10).close()
                # 2 closed-loop readers (not more): the storm threads
                # share this process's GIL, and their own scheduling
                # noise at higher counts dwarfs the ~10us/request being
                # measured. min over several storms estimates the
                # interference-free p50 (min-statistics: noise is
                # strictly additive here)
                secs = min(3.0, max(left() / 16.0, 1.5))
                _reader_storm(vport, fids, 2, 0, secs)  # warm
                runs = [_reader_storm(vport, fids, 2, 0, secs)
                        for _ in range(4)]
                best = min(runs, key=lambda r: r["p50_ms"] or 1e9)
                res = {"p50_ms": best["p50_ms"],
                       "p99_ms": best["p99_ms"],
                       "goodput_req_s": best["goodput_req_s"]}
                if armed:
                    # prove the plane was actually live while measured
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{vport}/debug/pprof"
                            "?format=stats", timeout=10) as r:
                        res["profiler"] = json.loads(r.read())
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{vport}/debug/events"
                            "?limit=1", timeout=10) as r:
                        res["wide_events_seen"] = json.loads(
                            r.read())["count"]
                return res
            finally:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
                time.sleep(0.5)

    configs = {"off": {"WEED_PROFILE": "0", "WEED_WIDE_EVENTS": "0"},
               "armed": {"WEED_PROFILE": "1", "WEED_WIDE_EVENTS": "1"}}
    # alternate boots (off, armed, off, armed): server-process placement
    # varies boot to boot, and a drifting host biases any
    # all-of-A-then-all-of-B ordering; min across boots cancels it
    out: dict = {}
    rounds: dict = {"off": [], "armed": []}
    for rnd in range(2):
        if rnd == 1 and left() < 40:
            break
        for tag, env_extra in configs.items():
            rounds[tag].append(measure(f"{tag}{rnd}", env_extra,
                                       armed=(tag == "armed")))
            _phase_checkpoint(work, "observe",
                              {**out, "rounds": rounds})
    for tag in configs:
        out[tag] = min(rounds[tag], key=lambda r: r["p50_ms"] or 1e9)
    out["boots_per_config"] = len(rounds["off"])
    out["round_p50s"] = {tag: [r["p50_ms"] for r in rs]
                         for tag, rs in rounds.items()}
    p50_off = out["off"]["p50_ms"] or 1e-9
    out["p50_regression_pct"] = round(
        (out["armed"]["p50_ms"] - p50_off) / p50_off * 100.0, 2)
    out["per_request_overhead_us"] = round(
        (out["armed"]["p50_ms"] - p50_off) * 1000.0, 1)
    out["acceptance"] = {
        "plane_live_while_measured":
            out["armed"].get("profiler", {}).get("samples", 0) > 0
            and out["armed"].get("wide_events_seen", 0) > 0,
        "p50_regression_le_3pct": out["p50_regression_pct"] <= 3.0,
    }
    _phase_checkpoint(work, "observe", out)
    return out


def phase_georepl(work: str, budget_s: float = 240.0) -> dict:
    """Cluster-to-cluster replication lag: steady-state vs under the
    overload storm.  Two combined servers (master+volume+filer) boot as
    separate clusters; the primary's geo daemon replicates bucket "geo"
    to the replica per a PutBucketReplication-shaped rule.  Lag is
    measured end-to-end with PROBE objects: write through the primary
    filer, poll the replica filer until the bytes are visible — no
    trust in internal gauges.  The storm phase replays phase_overload's
    3x-offered saturation (_reader_storm, 24 closed-loop fg readers
    against the primary volume fastpath with a 20ms injected service
    time) while probes keep flowing.  Acceptance: storm-phase median
    lag <= 2x steady-state median (floored at 0.25s — sub-100ms medians
    make the ratio noise), zero priority inversions at the primary
    (replication traffic is CLASS_BG and must shed first, never
    displace fg), zero poisoned events."""
    import socket
    import threading
    import urllib.request

    started = time.perf_counter()

    def left() -> float:
        return budget_s - (time.perf_counter() - started)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from seaweedfs_tpu.client import Client

    import seaweedfs_tpu
    pkg_root = os.path.dirname(os.path.dirname(seaweedfs_tpu.__file__))

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    pm, pv, pf = free_port(), free_port(), free_port()
    rm, rv, rf = free_port(), free_port(), free_port()
    base_env = dict(os.environ, JAX_PLATFORMS="cpu")
    base_env["PYTHONPATH"] = pkg_root + os.pathsep + \
        base_env.get("PYTHONPATH", "")
    # the primary gets the small fg pipe + the geo daemon; the replica
    # is a plain cluster
    prim_env = dict(base_env,
                    WEED_GEO_FILER=f"127.0.0.1:{pf}",
                    WEED_GEO_INTERVAL="0.5",
                    WEED_ADMISSION_FG_CONCURRENCY="8",
                    WEED_ADMISSION_FG_QUEUE="8",
                    WEED_ADMISSION_QUEUE_TIMEOUT_MS="2000",
                    WEED_ADMISSION_BG_CONCURRENCY="4",
                    WEED_ADMISSION_RETRY_AFTER_S="1")

    def boot(tag: str, env: dict, mport: int, vport: int,
             fport: int):
        data_dir = os.path.join(work, f"georepl_{tag}")
        os.makedirs(data_dir, exist_ok=True)
        logf = open(os.path.join(work, f"georepl_{tag}.log"), "ab")
        proc = subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu.cli", "server",
             "-ip", "127.0.0.1", "-master_port", str(mport),
             "-port", str(vport), "-dir", data_dir,
             "-filer", "-filer_port", str(fport),
             "-filer_db", os.path.join(data_dir, "filer.db")],
            cwd=data_dir, env=env, stdout=logf, stderr=logf)
        return proc, logf

    def wait_up(mport: int, fport: int) -> None:
        deadline = time.time() + 60
        while True:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{mport}/dir/assign",
                        timeout=2) as r:
                    if "fid" in json.loads(r.read()):
                        with urllib.request.urlopen(
                                f"http://127.0.0.1:{fport}/healthz",
                                timeout=2):
                            return
            except Exception:
                pass
            if time.time() > deadline:
                raise RuntimeError("georepl cluster failed to start")
            time.sleep(0.3)

    def http(method: str, url: str, body=None, headers=None):
        req = urllib.request.Request(url, data=body, method=method,
                                     headers=headers or {})
        with urllib.request.urlopen(req, timeout=20) as r:
            return r.status, r.read()

    def filer_put(fport: int, path: str, data: bytes) -> None:
        http("PUT", f"http://127.0.0.1:{fport}{path}", data,
             {"Content-Type": "application/octet-stream"})

    def replica_has(path: str, want: bytes) -> bool:
        try:
            return http("GET",
                        f"http://127.0.0.1:{rf}{path}")[1] == want
        except Exception:
            return False

    out: dict = {}
    prim, prim_log = boot("primary", prim_env, pm, pv, pf)
    repl, repl_log = boot("replica", base_env, rm, rv, rf)
    try:
        wait_up(pm, pf)
        wait_up(rm, rf)
        # bucket on both sides + the replication rule on the primary's
        # bucket entry (the JSON the S3 PutBucketReplication route
        # stores; set via the meta API so the phase needs no gateway)
        for fport in (pf, rf):
            http("POST",
                 f"http://127.0.0.1:{fport}/buckets/geo?op=mkdir")
        rule = [{"id": "bench", "status": "Enabled", "prefix": "",
                 "dest_bucket": "geo",
                 "endpoint": f"127.0.0.1:{rf}"}]
        entry = {"path": "/buckets/geo",
                 "attr": {"mode": 0o40770, "mtime": time.time(),
                          "crtime": time.time()},
                 "chunks": [],
                 "extended": {"seaweed-replication":
                              json.dumps(rule, sort_keys=True)}}
        http("POST", f"http://127.0.0.1:{pf}/__meta__/create_entry",
             json.dumps({"entry": entry}).encode(),
             {"Content-Type": "application/json"})
        http("POST", f"http://127.0.0.1:{pm}/geo/run",
             json.dumps({}).encode(),
             {"Content-Type": "application/json"})

        rng = __import__("random").Random(17)
        blob = bytes(rng.getrandbits(8) for _ in range(4096))

        def probe_lag(tag: str, n: int, spacing: float) -> list:
            """Replication lag per probe: time from the primary WRITE
            COMPLETING to the bytes being readable on the replica.  A
            shed PUT (the storm saturates the fg pipe; the filer
            answers 502/503) is retried like any cooperative client
            would — that admission wait is the overload plane's number
            (phase_overload p99), not geo lag, so the lag clock starts
            when the write lands."""
            lags = []
            for i in range(n):
                path = f"/buckets/geo/{tag}{i:03d}"
                t_first = time.perf_counter()
                put_ok = False
                while True:
                    try:
                        filer_put(pf, path, blob)
                        put_ok = True
                        break
                    except Exception:
                        if time.perf_counter() - t_first > 30:
                            break
                        time.sleep(0.1)
                if not put_ok:
                    lags.append(30.0)  # the WRITE never landed
                    continue
                t0 = time.perf_counter()
                while not replica_has(path, blob):
                    if time.perf_counter() - t0 > 30:
                        lags.append(30.0)  # loudly saturated, not lost
                        break
                    time.sleep(0.02)
                else:
                    lags.append(time.perf_counter() - t0)
                time.sleep(spacing)
            return lags

        def med(xs: list) -> float:
            ys = sorted(xs)
            return ys[len(ys) // 2] if ys else 0.0

        # steady state
        steady = probe_lag("s", 10, 0.2)
        out["steady_lag_s"] = {
            "median": round(med(steady), 3),
            "max": round(max(steady), 3),
            "samples": [round(x, 3) for x in steady]}
        _phase_checkpoint(work, "georepl", out)

        # the overload storm: 20ms injected volume.read service time +
        # 24 closed-loop fg readers = phase_overload's 3x-offered shape
        client = Client(f"127.0.0.1:{pm}")
        fids = [client.upload(blob[:1024]) for _ in range(32)]
        http("POST", f"http://127.0.0.1:{pv}/admin/faults",
             json.dumps({"set": [{"point": "volume.read",
                                  "action": "delay",
                                  "ms": 20}]}).encode(),
             {"Content-Type": "application/json"})
        storm_secs = min(10.0, max(left() - 40, 4.0))
        storm_out: dict = {}

        def run_storm() -> None:
            storm_out.update(_reader_storm(pv, fids, 24, 0,
                                           storm_secs))

        storm_thread = threading.Thread(target=run_storm)
        storm_thread.start()
        time.sleep(0.3)  # let the storm form before probing
        stormy = probe_lag("o", 8, 0.1)
        storm_thread.join()
        http("POST", f"http://127.0.0.1:{pv}/admin/faults",
             json.dumps({"clear": "*"}).encode(),
             {"Content-Type": "application/json"})
        out["storm"] = storm_out
        out["storm_lag_s"] = {
            "median": round(med(stormy), 3),
            "max": round(max(stormy), 3),
            "samples": [round(x, 3) for x in stormy]}
        _phase_checkpoint(work, "georepl", out)

        # evidence: inversions + geo job state
        with urllib.request.urlopen(
                f"http://127.0.0.1:{pv}/metrics", timeout=10) as r:
            vol_metrics = r.read().decode()
        inversions = 0.0
        for line in vol_metrics.splitlines():
            if line.startswith("admission_inversion_total"):
                inversions = float(line.rsplit(" ", 1)[1])
        with urllib.request.urlopen(
                f"http://127.0.0.1:{pm}/geo/status", timeout=10) as r:
            geo_status = json.loads(r.read())
        job = (geo_status.get("jobs") or {}).get("geo", {})
        out["geo_job"] = {k: job.get(k) for k in
                          ("applied", "skipped", "poisoned", "state",
                           "lag_s")}
        out["inversions"] = inversions
        steady_floor = max(out["steady_lag_s"]["median"], 0.25)
        out["lag_ratio"] = round(
            out["storm_lag_s"]["median"] / steady_floor, 3)
        out["acceptance"] = {
            "storm_lag_le_2x_steady": out["lag_ratio"] <= 2.0,
            "zero_inversions": inversions == 0,
            "zero_poisoned": (job.get("poisoned") or 0) == 0,
        }
        _phase_checkpoint(work, "georepl", out)
    finally:
        for proc, logf in ((prim, prim_log), (repl, repl_log)):
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
            logf.close()
        time.sleep(0.5)
    return out


def phase_lifecycle(work: str, budget_s: float = 240.0,
                    n_idle: int = 6) -> dict:
    """Time-to-warm for a batch of idle volumes under the lifecycle
    daemon, with proof the hot path doesn't degrade while transitions
    run. A real multi-process cluster (master + 4 volume servers) boots
    with the lifecycle knobs compressed — WEED_LIFECYCLE_WARM_AFTER=5s
    and a near-zero FULL_FRACTION artificially age every seeded volume
    — then `n_idle` single-volume collections are seeded and left
    alone while one "hot" collection is read in a closed loop the
    whole time (which also keeps it off the warm path: idleness, not
    just fullness, gates the transition). The daemon seals, vacuums,
    EC-encodes, and spreads every idle volume with ZERO operator
    commands; we record each volume's time from seeding to 14/14
    shards, and compare hot-read p50 measured before the first
    transition against p50 measured while they run. Budget-aware and
    checkpointed into lifecycle_partial.json like the other phases."""
    import random as random_mod
    import socket
    import urllib.request

    started = time.perf_counter()

    def left() -> float:
        return budget_s - (time.perf_counter() - started)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from seaweedfs_tpu.client import Client

    import seaweedfs_tpu
    pkg_root = os.path.dirname(os.path.dirname(seaweedfs_tpu.__file__))
    WARM_AFTER_S = 5.0
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               WEED_LIFECYCLE_WARM_AFTER=f"{WARM_AFTER_S:.0f}",
               WEED_LIFECYCLE_INTERVAL="0.5",
               # any volume holding data counts as sealed: the bench
               # ages volumes by compressing the clock, not by writing
               # 30GB each
               WEED_LIFECYCLE_FULL_FRACTION="0.000001")
    env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def spawn(args, tag):
        log = open(os.path.join(work, f"lifecycle_{tag}.log"), "ab")
        return subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu.cli"] + args,
            cwd=work, env=env, stdout=log, stderr=log)

    procs = []
    out: dict = {"n_idle_volumes": n_idle,
                 "warm_after_s": WARM_AFTER_S}
    try:
        mport = free_port()
        master = f"127.0.0.1:{mport}"
        procs.append(spawn(["master", "-port", str(mport), "-mdir", work],
                           "master"))
        for i in range(4):
            vdir = os.path.join(work, f"lifecycle_vs{i}")
            os.makedirs(vdir, exist_ok=True)
            procs.append(spawn(["volume", "-port", str(free_port()),
                                "-dir", vdir, "-mserver", master,
                                "-pulse", "1"], f"vs{i}"))
        client = Client(master)
        deadline = time.time() + 45
        while time.time() < deadline:
            try:
                if len(client.dir_status().get("nodes", [])) >= 4:
                    break
            except Exception:
                pass
            time.sleep(0.3)

        rng = random_mod.Random(7)
        # the hot set: small blobs read in a closed loop throughout
        hot_blobs: dict[str, bytes] = {}
        for _ in range(16):
            data = bytes(rng.getrandbits(8) for _ in range(4096))
            hot_blobs[client.upload(data, collection="hot")] = data
        hot_fids = list(hot_blobs)
        hot_vids = {int(f.split(",")[0]) for f in hot_fids}
        hot_urls = {v: client.lookup(v)[0] for v in hot_vids}

        # the idle batch: one collection per volume, 2x48KB random
        # (incompressible) blobs each — enough to cross the sealed bar
        idle_blobs: dict[str, bytes] = {}
        for i in range(n_idle):
            for _ in range(2):
                data = bytes(rng.getrandbits(8) for _ in range(48 * 1024))
                idle_blobs[client.upload(data, collection=f"lc{i}")] = data
        idle_vids = sorted({int(f.split(",")[0]) for f in idle_blobs})
        t_seeded = time.time()
        out["seeded_idle_vids"] = idle_vids
        _phase_checkpoint(work, "lifecycle", out)

        def hot_read_once() -> float:
            fid = hot_fids[rng.randrange(len(hot_fids))]
            vid = int(fid.split(",")[0])
            t0 = time.perf_counter()
            with urllib.request.urlopen(
                    f"http://{hot_urls[vid]}/{fid}", timeout=30) as r:
                body = r.read()
            dt = time.perf_counter() - t0
            assert body == hot_blobs[fid], f"corrupt hot read of {fid}"
            return dt

        def pctl(lat: list[float], q: float) -> float:
            return round(
                sorted(lat)[min(len(lat) - 1, int(len(lat) * q))] * 1e3, 3)

        def shard_count(vid: int) -> int:
            try:
                return len(client.ec_lookup(vid).get("shards", {}))
            except Exception:
                return 0

        # baseline hot p50: the warm window hasn't elapsed yet, so no
        # transition can be running while this samples
        before: list[float] = []
        while time.time() - t_seeded < WARM_AFTER_S - 1.5 and left() > 60:
            before.append(hot_read_once())
        out["hot_p50_before_ms"] = pctl(before, 0.50) if before else None
        out["hot_p99_before_ms"] = pctl(before, 0.99) if before else None
        _phase_checkpoint(work, "lifecycle", out)

        # now the daemon takes over: keep hammering the hot set (which
        # also keeps it off the warm path) and record when each idle
        # volume reaches the full shard set
        during: list[float] = []
        warm_at: dict[int, float] = {}
        next_poll = 0.0
        while len(warm_at) < len(idle_vids) and left() > 25:
            during.append(hot_read_once())
            if time.time() < next_poll:
                continue
            next_poll = time.time() + 0.3
            for vid in idle_vids:
                if vid not in warm_at and shard_count(vid) >= 14:
                    warm_at[vid] = time.time() - t_seeded
        warmed = sorted(warm_at.values())
        out.update({
            "warmed_volumes": len(warm_at),
            "time_to_warm_first_s": round(warmed[0], 2) if warmed
            else None,
            "time_to_warm_p50_s": round(
                warmed[len(warmed) // 2], 2) if warmed else None,
            "time_to_warm_all_s": round(warmed[-1], 2) if warmed
            else None,
            "hot_p50_during_ms": pctl(during, 0.50) if during else None,
            "hot_p99_during_ms": pctl(during, 0.99) if during else None,
            "hot_reads_sampled": len(before) + len(during),
        })
        if before and during:
            out["hot_p50_ratio"] = round(
                out["hot_p50_during_ms"]
                / max(out["hot_p50_before_ms"], 1e-6), 2)
        _phase_checkpoint(work, "lifecycle", out)

        # every blob is still readable from the warm tier
        client._vid_cache.clear()
        for fid, data in idle_blobs.items():
            assert client.download(fid) == data, \
                f"blob {fid} lost through the warm transition"

        with urllib.request.urlopen(f"http://{master}/metrics",
                                    timeout=10) as r:
            text = r.read().decode()

        def metric(needle: str) -> float:
            for line in text.splitlines():
                if needle in line and not line.startswith("#"):
                    try:
                        return float(line.rsplit(" ", 1)[1])
                    except ValueError:
                        pass
            return 0.0

        out["server_metrics"] = {
            "transitions_warm_ok": metric(
                'lifecycle_transitions_total{kind="warm",outcome="ok"}'),
            "transitions_warm_failed": metric(
                'lifecycle_transitions_total'
                '{kind="warm",outcome="failed"}'),
        }
        out["acceptance"] = {
            "all_idle_volumes_warmed":
                len(warm_at) == len(idle_vids),
            # "unchanged" within single-shared-host noise: the encodes
            # run on the same CPUs as the reads, so allow 2x on p50
            "hot_p50_within_2x":
                bool(before and during and out["hot_p50_ratio"] <= 2.0),
            "warm_data_intact": True,  # the asserts above would throw
        }
        out["note"] = (
            "time-to-warm counts from the last seed write to 14/14 "
            "shards visible in ec_lookup; the daemon sealed, vacuumed, "
            "encoded, and spread every volume itself (zero operator "
            "commands, WEED_LIFECYCLE_WARM_AFTER=5s, bg-class "
            "transitions bounded by the repair semaphore). Hot p50 is "
            "measured on direct volume-server GETs of a collection "
            "kept hot by the same closed loop.")
        _phase_checkpoint(work, "lifecycle", out)
    finally:
        for p in procs:
            try:
                p.terminate()
            except OSError:
                pass
        for p in procs:
            try:
                p.wait(timeout=5)
            except Exception:
                try:
                    p.kill()
                except OSError:
                    pass
    return out


def phase_multichip(work: str, budget_s: float = 240.0) -> dict:
    """Mesh-sharded encode/rebuild fabric on the 8-device virtual CPU
    mesh (the MULTICHIP dryrun substrate, now through the PRODUCTION
    MeshCoder + pipeline instead of the kernel demo).

    What each number means — and what the substrate can and cannot
    show:

      * aggregate_wall_gbps[n]: real wall-clock aggregate of the mesh
        path at mesh size n, weak-scaled workload (n * per-chip bytes).
        Virtual CPU devices SHARE the host's cores (one XLA device
        already saturates the machine), so this curve is flat-ish here
        by construction; on ICI-attached chips each device is its own
        silicon and the wall curve IS the projection below.
      * per_chip_slice_gbps[n]: measured single-device rate at exactly
        the per-chip slice width mesh size n deals each device.
      * fabric_overhead[n]: mesh-executable wall over n * single-device
        slice wall — the work the fabric ADDS (padding, resharding,
        collectives, dispatch serialization). ~1.0 means the shard_map
        program does per-chip work and nothing else.
      * aggregate_projected_gbps[n] = n * per_chip_slice_gbps[n]
        / fabric_overhead[n]: the aggregate on hardware where chips
        don't share cores. Valid exactly when collective_free holds —
        which is asserted from the compiled HLO, not assumed.

    Plus: shard byte-identity vs the single-chip striping layout at
    RS(10,4) AND RS(20,4) (odd batch width → padded shard_map path),
    and a simulated rack-loss rebuild storm (6 volumes) drained through
    the master's WEED_EC_ENCODE_WORKERS pool vs serial dispatch.
    """
    # must land BEFORE the first jax import in this process
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import hashlib

    import jax

    from seaweedfs_tpu import ec
    from seaweedfs_tpu.ec import pipeline
    from seaweedfs_tpu.ec.coder import JaxCoder
    from seaweedfs_tpu.parallel import mesh_coder

    started = time.perf_counter()
    out: dict = {"backend": jax.default_backend(),
                 "devices": len(jax.devices())}
    _phase_checkpoint(work, "multichip", out)

    def left() -> float:
        return budget_s - (time.perf_counter() - started)

    # --- scaling curve: weak-scaled encode over mesh sizes 1/2/4/8 ---
    k, m = 10, 4
    per_chip_w = MB  # per-chip slice: [10, 1MB]
    reps = 3
    rng = np.random.default_rng(11)
    curve: dict = {}
    single = JaxCoder(k, m)
    for n in (1, 2, 4, 8):
        if left() < 30:
            curve[str(n)] = f"skipped: budget ({left():.0f}s left)"
            continue
        coder = mesh_coder.coder(k, m, n_devices=n)
        data = rng.integers(0, 256, (k, n * per_chip_w), dtype=np.uint8)
        slice_data = data[:, :per_chip_w]
        # mesh wall (includes per-chip staging)
        h = coder.encode_async(data)  # compile + warm
        np.asarray(getattr(h, "arr", h))
        t0 = time.perf_counter()
        for _ in range(reps):
            h = coder.encode_async(data)
        np.asarray(getattr(h, "arr", h))
        t_mesh = (time.perf_counter() - t0) / reps
        # single-device slice wall (the per-chip work at this mesh size)
        hs = single.encode_async(slice_data)
        np.asarray(hs)
        t0 = time.perf_counter()
        for _ in range(reps):
            hs = single.encode_async(slice_data)
        np.asarray(hs)
        t_dev = (time.perf_counter() - t0) / reps
        per_chip_gbps = k * per_chip_w / t_dev / 1e9
        overhead = t_mesh / (n * t_dev) if n > 1 else t_mesh / t_dev
        projected = n * per_chip_gbps / max(overhead, 1e-9)
        curve[str(n)] = {
            "aggregate_wall_gbps": round(k * n * per_chip_w / t_mesh / 1e9,
                                         3),
            "per_chip_slice_gbps": round(per_chip_gbps, 3),
            "fabric_overhead": round(overhead, 3),
            "aggregate_projected_gbps": round(projected, 3),
        }
        out["scaling"] = curve
        _phase_checkpoint(work, "multichip", out)
    mesh8 = mesh_coder.coder(k, m, n_devices=min(8, len(jax.devices())))
    out["collective_free"] = bool(
        getattr(mesh8, "encode_is_collective_free", lambda: True)())
    _phase_checkpoint(work, "multichip", out)

    # --- byte-identity: mesh pipeline vs single-chip striping layout ---
    def _identity(geometry: "ec.Geometry", seed: int) -> bool:
        kk, mm = geometry.data_shards, geometry.parity_shards
        size = 61_007
        r = np.random.default_rng(seed)
        payload = r.integers(0, 256, size, dtype=np.uint8).tobytes()
        ref = os.path.join(work, f"mc_ref_{kk}_{mm}_1")
        mesh_base = os.path.join(work, f"mc_mesh_{kk}_{mm}_1")
        for base in (ref, mesh_base):
            with open(base + ".dat", "wb") as f:
                f.write(payload)
        ec.write_ec_files(ref, _host_coder_km(kk, mm), geometry,
                          buffer_size=100)
        # odd batch width: not divisible by the mesh -> padded path
        pipeline.stream_encode(mesh_base,
                               mesh_coder.coder(kk, mm,
                                                n_devices=min(
                                                    8, len(jax.devices()))),
                               geometry, batch_size=999)
        for i in range(geometry.total_shards):
            a = hashlib.sha256(
                open(ref + ec.to_ext(i), "rb").read()).hexdigest()
            b = hashlib.sha256(
                open(mesh_base + ec.to_ext(i), "rb").read()).hexdigest()
            if a != b:
                return False
        return True

    def _host_coder_km(kk: int, mm: int):
        try:
            return ec.get_coder("cpp", kk, mm)
        except Exception:
            return ec.get_coder("numpy", kk, mm)

    ident: dict = {}
    for label, g in (("10+4", ec.Geometry(10, 4, large_block_size=10000,
                                          small_block_size=100)),
                     ("20+4", ec.Geometry(20, 4, large_block_size=10000,
                                          small_block_size=100))):
        if left() < 30:
            ident[label] = f"skipped: budget ({left():.0f}s left)"
            continue
        try:
            ident[label] = bool(_identity(g, seed=len(label)))
        except Exception as e:
            ident[label] = f"error: {type(e).__name__}: {str(e)[:160]}"
        out["byte_identity"] = ident
        _phase_checkpoint(work, "multichip", out)

    # --- rebuild storm: worker pool vs serial dispatch ---
    if left() > 30:
        try:
            out["rebuild_storm"] = _multichip_storm()
        except Exception as e:
            out["rebuild_storm"] = {"error":
                                    f"{type(e).__name__}: {str(e)[:300]}"}
    else:
        out["rebuild_storm"] = f"skipped: budget ({left():.0f}s left)"
    _phase_checkpoint(work, "multichip", out)

    c = {n: v for n, v in curve.items() if isinstance(v, dict)}
    proj = {n: v["aggregate_projected_gbps"] for n, v in c.items()}
    storm = out.get("rebuild_storm")
    out["accept"] = {
        "collective_free": out.get("collective_free") is True,
        "scaling_1_to_2_ge_1p7": bool(
            proj.get("1") and proj.get("2")
            and proj["2"] / proj["1"] >= 1.7),
        "scaling_monotone_to_8": bool(
            len(proj) == 4
            and all(proj[str(2 * i)] >= 0.95 * proj[str(i)]
                    for i in (1, 2, 4))),
        "byte_identity_both_geometries": all(
            v is True for v in ident.values()) and len(ident) == 2,
        "storm_drain_under_0p6x_serial": bool(
            isinstance(storm, dict)
            and (storm.get("drain_ratio") or 9.9) < 0.6),
    }
    return out


def _multichip_storm(volumes: int = 6, rpc_s: float = 0.2) -> dict:
    """Rack-loss rebuild storm through the REAL master repair plumbing
    (planner, 2-pass deficit confirmation, semaphore pool, per-worker
    logs): 6 EC volumes short of shards, every rebuild RPC stubbed to a
    fixed service time (the master's wall time IS dispatch wait — the
    rebuild compute runs on the volume servers). Measures drain wall
    with the WEED_EC_ENCODE_WORKERS pool vs serial dispatch."""
    import asyncio

    from seaweedfs_tpu.cluster import raft as raft_mod
    from seaweedfs_tpu.server.master import MasterServer

    total = 14

    def build_master(workers: int) -> "MasterServer":
        master = MasterServer(repair_concurrency=workers,
                              maintenance_interval_seconds=3600.0)
        master.raft.role = raft_mod.LEADER
        # rack r2 died taking shards {3, 7, 11} of every volume with it
        # (11 survivors >= k=10, so each volume is rebuildable); racks
        # r0/r1 hold the survivors, r2's replacement node sits empty
        lost = {3, 7, 11}
        holdings = {0: [s for s in range(total)
                        if s not in lost and s % 2 == 0],
                    1: [s for s in range(total)
                        if s not in lost and s % 2 == 1],
                    2: []}
        for i in range(3):
            payload = {"volumes": [], "ec_shards": [
                {"id": vid, "collection": "",
                 "shard_ids": list(holdings[i])}
                for vid in range(1, volumes + 1)] if holdings[i] else []}
            master.topology.register_heartbeat(
                f"n{i}", f"127.0.0.1:{18080 + i}", "", "dc1", f"r{i}",
                100, payload)

        calls: list = []

        async def fake_admin_post(url, op, body, timeout=60.0):
            calls.append((url, op))
            await asyncio.sleep(rpc_s)
            if op == "ec/rebuild":
                return {"rebuilt": []}
            return {"ok": True}

        master._admin_post = fake_admin_post
        master._storm_calls = calls
        return master

    async def drain(workers: int) -> float:
        master = build_master(workers)
        await master._repair_pass()   # pass 1: deficit seen
        t0 = time.perf_counter()
        await master._repair_pass()   # pass 2: confirmed -> launch
        while master._repair_tasks:
            await asyncio.gather(*list(master._repair_tasks),
                                 return_exceptions=True)
        wall = time.perf_counter() - t0
        rebuilds = sum(1 for _, op in master._storm_calls
                       if op == "ec/rebuild")
        assert rebuilds == volumes, (rebuilds, volumes)
        return wall

    env_workers = os.environ.get("WEED_EC_ENCODE_WORKERS", "")
    try:
        pool = max(2, int(env_workers)) if env_workers else 4
    except ValueError:
        pool = 4
    serial_wall = asyncio.run(drain(1))
    pool_wall = asyncio.run(drain(pool))
    return {
        "volumes": volumes, "rebuild_rpc_s": rpc_s, "workers": pool,
        "serial_drain_s": round(serial_wall, 3),
        "pool_drain_s": round(pool_wall, 3),
        "drain_ratio": round(pool_wall / serial_wall, 3)
        if serial_wall > 1e-9 else None,
    }


_RING_BENCH_REPLICAS = 1


def _meta_noop() -> None:
    """Pool warm-up target (spawn + interpreter start happen here, not
    inside a timed row)."""


def _meta_driver_shard(pkg_root: str, peers: list, ring_dict,
                       op: str, n_dirs: int, indices: list,
                       threads: int, n_create: int) -> int:
    """One load-generator shard (its own PROCESS: a single GIL-bound
    driver saturates below three filer loops' capacity, so the client
    must scale out too).  Returns the shard's error count."""
    import http.client
    import threading as _threading
    from concurrent.futures import ThreadPoolExecutor

    sys.path.insert(0, pkg_root)
    ring = None
    if ring_dict is not None:
        from seaweedfs_tpu.metaring import DirectoryRing
        ring = DirectoryRing.from_dict(ring_dict)
    conns: dict = {}

    def conn_for(peer: str):
        key = (_threading.get_ident(), peer)
        c = conns.get(key)
        if c is None:
            host, _, port = peer.rpartition(":")
            c = http.client.HTTPConnection(host, int(port), timeout=20)
            conns[key] = c
        return c

    def req(peer: str, method: str, path: str, body=None) -> int:
        headers = {"Content-Type": "application/json"} if body else {}
        for _ in range(2):
            c = conn_for(peer)
            try:
                c.request(method, path, body=body, headers=headers)
                r = c.getresponse()
                r.read()
                return r.status
            except (http.client.HTTPException, OSError):
                c.close()
                conns.pop((_threading.get_ident(), peer), None)
        return 599

    def route(i: int) -> tuple:
        d = f"/bench/d{i % n_dirs}"
        if ring is None:
            return peers[0], d
        return ring.owner(d) or peers[0], d

    errors = [0]

    def one(i: int) -> None:
        peer, d = route(i)
        if op == "create":
            entry = {"path": f"{d}/f{i}.txt",
                     "attr": {"mtime": 1.0, "crtime": 1.0, "mode": 432,
                              "uid": 0, "gid": 0, "mime": "",
                              "ttl_sec": 0, "user_name": "",
                              "group_names": [], "symlink_target": "",
                              "md5": "", "replication": "",
                              "collection": ""},
                     "chunks": [], "extended": {}, "hard_link_id": ""}
            if req(peer, "POST", "/__meta__/create_entry",
                   json.dumps({"entry": entry}).encode()) != 200:
                errors[0] += 1
        elif op == "lookup":
            # probe entries the create section actually placed: file
            # j lives in d{j % n_dirs}, so the directory must derive
            # from the FILE index or most probes are negative lookups
            j = i % n_create
            dj = f"/bench/d{j % n_dirs}"
            pj = ring.owner(dj) if ring is not None else peers[0]
            if req(pj or peers[0], "GET",
                   f"/__meta__/lookup?path={dj}/f{j}.txt") != 200:
                errors[0] += 1
        else:
            if req(peer, "GET",
                   f"/__meta__/list?dir={d}&limit=128") != 200:
                errors[0] += 1

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(one, indices))
    for c in conns.values():
        c.close()
    return errors[0]


def phase_metadata(work: str, budget_s: float = 240.0) -> dict:
    """Namespace-op throughput (metaring plane): create/lookup/list
    req/s against the filer meta API, one filer vs a 3-peer
    consistent-hash ring (each peer its own subprocess).  The driver is
    ring-aware — it fetches /dir/ring from the master and routes every
    op to the parent directory's owner, the smart-client shape
    production gateways use — so the 3-peer row measures partition
    scaling, not proxy-hop overhead.  Acceptance: 3-peer aggregate
    >= 1.8x single-peer."""
    global _RING_BENCH_REPLICAS
    import multiprocessing as mp
    import urllib.request

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from seaweedfs_tpu.metaring import DirectoryRing

    pkg_root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
    deadline = time.time() + budget_s

    # read-heavy mix (Haystack-shaped metadata traffic: reads dominate
    # writes by a wide margin); the load generator is 4 PROCESSES x 8
    # threads — one GIL-bound driver saturates below what three filer
    # loops serve
    N_CREATE, N_LOOKUP, N_LIST = 2000, 10000, 3000
    N_DIRS, PROCS, THREADS = 192, 6, 8

    def _wait_http(url: str, timeout: float = 30.0) -> None:
        end = time.time() + timeout
        while time.time() < end:
            try:
                with urllib.request.urlopen(url, timeout=2) as r:
                    r.read()
                    return
            except Exception:
                time.sleep(0.2)
        raise RuntimeError(f"server at {url} failed to start")

    def _drive(peers: list, ring: "DirectoryRing | None",
               pool) -> dict:
        ring_dict = ring.to_dict() if ring is not None else None
        out: dict = {}
        total_ops = 0
        total_s = 0.0
        for name, n in (("create", N_CREATE), ("lookup", N_LOOKUP),
                        ("list", N_LIST)):
            shards = [list(range(k, n, PROCS)) for k in range(PROCS)]
            t0 = time.perf_counter()
            errs = pool.starmap(_meta_driver_shard, [
                (pkg_root, peers, ring_dict, name, N_DIRS, shard,
                 THREADS, N_CREATE) for shard in shards])
            dt = time.perf_counter() - t0
            out[f"{name}_req_s"] = round(n / dt, 1)
            out["errors"] = out.get("errors", 0) + sum(errs)
            total_ops += n
            total_s += dt
        out["namespace_ops_s"] = round(total_ops / total_s, 1)
        return out

    def _boot(n_peers: int, base_port: int) -> tuple:
        mport = base_port
        peers = [f"127.0.0.1:{base_port + 1 + i}"
                 for i in range(n_peers)]
        procs = [subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu.cli", "master",
             "-ip", "127.0.0.1", "-port", str(mport)],
            env=dict(env, WEED_FILER_RING_PEERS=",".join(peers)
                     if n_peers > 1 else ""),
            cwd=work, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)]
        _wait_http(f"http://127.0.0.1:{mport}/cluster/status")
        for p in peers:
            port = p.rsplit(":", 1)[1]
            cmd = [sys.executable, "-m", "seaweedfs_tpu.cli", "filer",
                   "-ip", "127.0.0.1", "-port", port,
                   "-mserver", f"127.0.0.1:{mport}",
                   "-store", "memory"]
            if n_peers > 1:
                cmd += ["-ring_peers", ",".join(peers)]
            procs.append(subprocess.Popen(
                cmd,
                env=dict(env, WEED_FILER_RING_REPLICAS=str(
                    _RING_BENCH_REPLICAS),
                         WEED_FILER_RING_VNODES="256"),
                cwd=work, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL))
        for p in peers:
            _wait_http(f"http://{p}/__meta__/info")
        return procs, peers, mport

    def _kill(procs) -> None:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        time.sleep(0.5)

    out: dict = {"driver": {"processes": PROCS, "threads": THREADS},
                 "ops": {"create": N_CREATE, "lookup": N_LOOKUP,
                         "list": N_LIST},
                 # the scaling rows run replicas=1 (pure partition
                 # scaling — the DirectoryRing's own axis); the
                 # replicated row below prices the durability knob
                 "ring_replicas": _RING_BENCH_REPLICAS}
    ctx = mp.get_context("spawn")
    with ctx.Pool(PROCS) as client_pool:
        # warm the pool (spawn + import cost must not land in a row)
        client_pool.starmap(_meta_noop, [() for _ in range(PROCS)])
        # both clusters stay up and the rows INTERLEAVE, median-of-3
        # each: this shared host drifts on the tens-of-seconds scale,
        # so back-to-back pass pairs see the same machine while
        # separated rows would eat the drift as a phantom (anti-)speedup
        procs_1, peers_1, _ = _boot(1, 21555)
        procs_3, peers_3, _ = _boot(3, 22555)
        try:
            ring = DirectoryRing(peers=peers_3, vnodes=256,
                                 replicas=_RING_BENCH_REPLICAS)
            single_rows, ring_rows = [], []
            for _ in range(3):
                single_rows.append(_drive(peers_1, None, client_pool))
                ring_rows.append(_drive(peers_3, ring, client_pool))
                _phase_checkpoint(work, "metadata", out)
                if time.time() > deadline - 30 and single_rows:
                    break
            out["single"] = sorted(
                single_rows,
                key=lambda r: r["namespace_ops_s"])[len(single_rows) // 2]
            out["ring3"] = sorted(
                ring_rows,
                key=lambda r: r["namespace_ops_s"])[len(ring_rows) // 2]
        finally:
            _kill(procs_1 + procs_3)
        _phase_checkpoint(work, "metadata", out)
        # informational: the same ring at replicas=2 (synchronous
        # successor mirrors on every write) — the price of the
        # zero-loss-on-peer-kill contract, NOT an acceptance row
        if time.time() < deadline - 45:
            saved = _RING_BENCH_REPLICAS
            _RING_BENCH_REPLICAS = 2
            try:
                procs_r, peers_r, _ = _boot(3, 23555)
                try:
                    ring_r = DirectoryRing(peers=peers_r, vnodes=256,
                                           replicas=2)
                    out["ring3_replicated"] = _drive(peers_r, ring_r,
                                                     client_pool)
                finally:
                    _kill(procs_r)
            except Exception as e:
                out["ring3_replicated"] = {"error": str(e)}
            finally:
                _RING_BENCH_REPLICAS = saved
    ratio = round(out["ring3"]["namespace_ops_s"]
                  / max(out["single"]["namespace_ops_s"], 1), 3)
    out["scaling_3p"] = ratio
    out["accept"] = {"threex_vs_single_ge_1_8": ratio >= 1.8,
                     "zero_errors": out["single"]["errors"] == 0
                     and out["ring3"]["errors"] == 0}
    _phase_checkpoint(work, "metadata", out)
    return out


def phase_recovery(work: str, budget_s: float = 240.0,
                   target_mb: int = 1024) -> dict:
    """Crash-consistency plane: cold-start recovery wall time for a
    torn ~1GB volume (the ISSUE 15 acceptance shape) plus crashsim
    sweep throughput (crash points/sec).

    The volume is built with a mid-stream sync() watermark, an un-synced
    tail, and a deliberate tear (truncate mid-record + garbage stump).
    recovery_wall_s is the watermarked open — the production cold-start
    cost; full_scan_gbps prices the legacy no-watermark CRC scan the
    same open would pay on a pre-`.swm` volume."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from seaweedfs_tpu.crashsim.harness import sweep_all
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    t_start = time.perf_counter()
    out: dict = {"target_mb": target_mb}
    vdir = os.path.join(work, "recovery_vol")
    os.makedirs(vdir, exist_ok=True)

    # budget-aware sizing: the 1GB target needs ~30s of build headroom
    if budget_s < 120:
        target_mb = min(target_mb, 256)
        out["target_mb"] = target_mb

    payload = (b"\xa5" * 65536)
    t0 = time.perf_counter()
    v = Volume(vdir, "", 77, create=True)
    nid = 0
    target = target_mb * MB
    while v.data_file_size() < target * 0.97:
        nid += 1
        v.write_needle(Needle(cookie=0xCC, id=nid,
                              data=payload + nid.to_bytes(8, "big")))
    v.sync()
    synced_ids = nid
    wm_size = v.data_file_size()
    for _ in range(12):                       # un-synced tail
        nid += 1
        v.write_needle(Needle(cookie=0xCC, id=nid, data=payload))
    torn_size = v.data_file_size()
    v.nm.close()
    v._dat.close()
    out["build_s"] = round(time.perf_counter() - t0, 2)
    out["volume_bytes"] = torn_size
    base = v.base_file_name()
    with open(base + ".dat", "r+b") as f:     # tear the last record
        f.truncate(torn_size - 30000)
        f.seek(torn_size - 62000)
        f.write(os.urandom(4096))
    _phase_checkpoint(work, "recovery", out)

    t0 = time.perf_counter()
    v2 = Volume(vdir, "", 77)
    out["recovery_wall_s"] = round(time.perf_counter() - t0, 3)
    # the cut may keep whole un-synced tail records before the tear —
    # legal (un-acked, intact); everything acked must be byte-exact
    recovered_ok = (wm_size <= v2.data_file_size() < torn_size
                    and len(v2.nm) >= synced_ids)
    sample = {1, synced_ids // 2, synced_ids}
    for sid in sample:
        n = v2.read_needle(sid)
        recovered_ok = recovered_ok and \
            n.data == payload + sid.to_bytes(8, "big")
    out["recovered_byte_exact"] = recovered_ok
    # legacy cost: the full CRC scan a watermark-less volume would pay
    t0 = time.perf_counter()
    cut, records = v2._scan_valid_records(
        v2.super_block.block_size(), v2.data_file_size())
    full_scan_s = time.perf_counter() - t0
    out["full_scan_s"] = round(full_scan_s, 3)
    out["full_scan_gbps"] = round(
        v2.data_file_size() / max(full_scan_s, 1e-9) / 1e9, 3)
    out["full_scan_records"] = len(records)
    v2.close()
    shutil.rmtree(vdir, ignore_errors=True)
    _phase_checkpoint(work, "recovery", out)

    t0 = time.perf_counter()
    summary = sweep_all(seeds=2, points=20)
    sweep_s = time.perf_counter() - t0
    out["crashsim_points"] = summary["total_points"]
    out["crashsim_violations"] = summary["total_violations"]
    out["crashsim_points_per_s"] = round(
        summary["total_points"] / max(sweep_s, 1e-9), 1)
    out["crashsim_sweep_s"] = round(sweep_s, 2)
    out["accept"] = {
        "recovered_byte_exact": bool(recovered_ok),
        "zero_sweep_violations": summary["total_violations"] == 0,
        "sweep_points_ge_200": summary["total_points"] >= 200,
    }
    out["phase_wall_s"] = round(time.perf_counter() - t_start, 2)
    _phase_checkpoint(work, "recovery", out)
    return out


V2_RULES = ("blocking-call-transitive,lock-held-await-transitive,"
            "deadline-propagation,resource-leak-interproc,lock-ordering")


def phase_lint(work: str = "", budget_s: float = 60.0) -> dict:
    """weedlint smoke: the full-tree static-analysis gate must stay
    cheap enough to live inside the tier-1 pytest run — WITH the v2
    call-graph pass included. Runs the exact CI invocation
    (scripts/lint.sh's command line) in a subprocess and records wall
    time (lint_wall_s), then the inter-procedural subset alone
    (lint_v2_wall_s: call-graph build + summary closure cost);
    acceptance is clean exits AND full run < 10s."""
    repo = os.path.dirname(os.path.abspath(__file__))
    cmd = [sys.executable, "-m", "seaweedfs_tpu.analysis",
           "--baseline", ".weedlint-baseline.json",
           "seaweedfs_tpu/", "tests/"]
    t0 = time.perf_counter()
    p = subprocess.run(cmd, cwd=repo, capture_output=True, text=True,
                       timeout=budget_s)
    wall = time.perf_counter() - t0
    tail = (p.stdout.strip().splitlines() or [""])[-1]

    cmd_v2 = [sys.executable, "-m", "seaweedfs_tpu.analysis",
              "--rules", V2_RULES, "--baseline",
              ".weedlint-baseline.json", "seaweedfs_tpu/", "tests/"]
    t0 = time.perf_counter()
    p2 = subprocess.run(cmd_v2, cwd=repo, capture_output=True,
                        text=True, timeout=budget_s)
    wall_v2 = time.perf_counter() - t0

    out = {
        "lint_wall_s": round(wall, 2),
        "lint_v2_wall_s": round(wall_v2, 2),
        "clean": p.returncode == 0 and p2.returncode == 0,
        "files": int(tail.split(" files")[0].rsplit(" ", 1)[-1])
        if " files" in tail else None,
        "summary": tail[:200],
        "accept": {"clean_exit": p.returncode == 0,
                   "v2_clean_exit": p2.returncode == 0,
                   "under_10s": wall < 10.0},
    }
    if p.returncode != 0 or p2.returncode != 0:
        out["error"] = (p.stdout + p.stderr + p2.stdout
                        + p2.stderr)[-1500:]
    return out


def phase_scale(work: str = "", budget_s: float = 240.0) -> dict:
    """Planet-scale control plane at 1000 virtual nodes (clustersim):
    planner decision latency over a fully-registered skewed topology,
    then the scenario sweep's moved-bytes ratio / convergence /
    violation counts.  Pure CPU python — no TPU, no sockets, virtual
    clock — so the numbers are control-plane algorithm costs, not I/O.
    Checkpointed per scenario: a timeout keeps every scenario already
    measured."""
    from seaweedfs_tpu.balance.planner import plan_moves
    from seaweedfs_tpu.clustersim import scenarios
    from seaweedfs_tpu.clustersim.sim import ClusterSim

    deadline = time.perf_counter() + budget_s
    out: dict = {"nodes": 1000}

    # planner decision latency: a 1000-node topology with 3 hot nodes,
    # registered through the real heartbeat intake, planned repeatedly
    sim = ClusterSim(nodes=1000, seed=0)
    for i in range(3):
        for vid in sorted(sim.node(i).volumes):
            sim.at(1, "heat", i, vid, 2.0)
    sim.run(10)
    durs = []
    for _ in range(30):
        t0 = time.perf_counter()
        plan = plan_moves(sim.topology, sim.cfg, sim.clock.now(),
                          seed=0, frozen=frozenset())
        durs.append((time.perf_counter() - t0) * 1000.0)
    durs.sort()
    out["plan_p50_ms"] = round(durs[len(durs) // 2], 2)
    out["plan_p95_ms"] = round(durs[int(len(durs) * 0.95)], 2)
    out["plan_moves_proposed"] = len(plan)
    _phase_checkpoint(work, "scale", out)

    total_violations = 0
    for name in ("skew", "churn", "rackloss"):
        if time.perf_counter() > deadline - 30:
            out[name] = {"error": "skipped (budget)"}
            continue
        t0 = time.perf_counter()
        rep = scenarios.run_scenario(name, seed=0, nodes=1000)
        total_violations += len(rep["violations"])
        out[name] = {
            "wall_s": round(time.perf_counter() - t0, 2),
            "ticks": rep["ticks"],
            "moves": rep["moves"],
            "repairs": rep["repairs"],
            "moved_bytes_ratio": rep["moved_bytes_ratio"],
            "converge_tick": rep.get("converge_tick"),
            "violations": rep["violations"],
        }
        _phase_checkpoint(work, "scale", out)
    out["moved_bytes_ratio"] = (out.get("skew") or {}).get(
        "moved_bytes_ratio")
    out["violations_total"] = total_violations
    out["accept"] = {"zero_violations": total_violations == 0,
                     "plan_under_1s": out["plan_p50_ms"] < 1000.0}
    return out


# ------------------------------------------------------------ orchestration

# phases that pin themselves to the virtual CPU mesh or never need a
# device; every other subprocess phase is a chip phase
_CPU_PHASES = frozenset({"multichip"})


def _run_phase(name: str, work: str, timeout_s: float) -> dict:
    """Run one phase in a fresh subprocess (the chip belongs to one
    process at a time); the phase prints its JSON on the LAST stdout
    line. A chip phase gets JAX_PLATFORMS=tpu, so a chip that is missing
    or taken is that phase's error and never a CPU run. A phase that
    times out or dies still contributes whatever it checkpointed into
    <name>_partial.json (merged under the error record) instead of
    nulling every number it had already measured."""
    from seaweedfs_tpu.utils import compile_cache
    t0 = time.perf_counter()
    env = dict(os.environ,
               JAX_PLATFORMS="cpu" if name in _CPU_PHASES else "tpu")
    # one persistent compilation cache shared by every phase (and every
    # run). Same rule as the product (utils/compile_cache.py): the
    # environment's directory if it names one, else the fixed one in
    # the checkout
    env.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache.CACHE_DIR)
    try:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--phase", name, "--work", work,
             "--budget", str(int(timeout_s * 0.9))],
            capture_output=True, text=True, timeout=timeout_s, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        return {"error": f"phase {name} timed out after {timeout_s:.0f}s",
                **_load_partial(work, name)}
    dur = time.perf_counter() - t0
    if p.returncode != 0:
        tail = (p.stderr or "")[-2000:]
        return {"error": f"phase {name} rc={p.returncode}: {tail}",
                **_load_partial(work, name)}
    try:
        out = json.loads(p.stdout.strip().splitlines()[-1])
    except Exception as e:
        return {"error": f"phase {name} bad output: {e}; "
                         f"stdout tail: {p.stdout[-500:]}",
                **_load_partial(work, name)}
    out["phase_wall_s"] = round(dur, 1)
    return out


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


DETAIL_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_DETAIL.json")


def _checkpoint(detail: dict, path: str = "") -> None:
    """Write the (partial) detail record NOW, atomically. Each phase
    checkpoints as it completes, so a later phase timing out — or the
    whole run being killed — no longer nulls every earlier number."""
    path = path or DETAIL_PATH
    try:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(detail, f, indent=1)
        os.replace(tmp, path)
    except OSError as e:
        _log(f"checkpoint write failed: {e}")


def main() -> None:
    started = time.perf_counter()
    work = tempfile.mkdtemp(prefix="swfs_bench_")

    def left() -> float:
        return HARD_BUDGET_S - (time.perf_counter() - started)

    try:
        # per-phase incremental record: every phase lands in
        # BENCH_DETAIL.json the moment it completes. The parent stays
        # off jax: each jax-touching phase owns its device in a child
        detail: dict = {"incomplete": True}

        fused = ({"error": "skipped (budget)"} if left() < 120
                 else _run_phase("fused", work, min(240.0, left())))
        _log(f"fused: {fused.get('gbps')} GB/s steady "
             f"({fused.get('speedup')}x chained, "
             f"scrub redigests {fused.get('scrub_redigests')})")
        detail["fused_compact_gzip_rs"] = fused
        _checkpoint(detail)

        try:
            system = bench_system(work)
            _log(f"system: w {system['write']['req_s']} r "
                 f"{system['read']['req_s']}")
            sh = system.get("sharded")
            if isinstance(sh, dict) and "write_req_s" in sh:
                _log(f"system (sharded x{sh['shards']}): "
                     f"w {sh['write_req_s']} r {sh['read_req_s']}")
            elif isinstance(sh, str):
                _log(f"system (sharded): {sh}")
        except Exception as e:
            system = {"error": str(e)}
        detail["system_req_s"] = system
        _checkpoint(detail)

        saturation: dict = {"error": "skipped (budget)"}
        if left() > 150:
            try:
                saturation = phase_saturation(
                    work, budget_s=min(240.0, left() - 90.0))
                _log(f"saturation: {saturation.get('host_cores')} cores,"
                     f" shards={saturation.get('shards')}, speedup "
                     f"{saturation.get('speedup')}")
            except Exception as e:
                saturation = {"error": str(e)}
        detail["saturation"] = saturation
        _checkpoint(detail)

        largefile: dict = {"error": "skipped (budget)"}
        if left() > 90:
            try:
                largefile = phase_largefile(work)
                _log(f"largefile: PUT {largefile.get('put_mb_s')} MB/s "
                     f"GET {largefile.get('get_mb_s')} MB/s")
            except Exception as e:
                largefile = {"error": str(e),
                             **_load_partial(work, "largefile")}
        detail["largefile_mb_s"] = largefile
        _checkpoint(detail)

        degraded: dict = {"error": "skipped (budget)"}
        if left() > 120:
            try:
                degraded = phase_degraded(
                    work, budget_s=min(240.0, left() - 60.0))
                _log(f"degraded: p50 {degraded.get('degraded_p50_ms')}ms "
                     f"p99 {degraded.get('degraded_p99_ms')}ms")
            except Exception as e:
                degraded = {"error": str(e), **_load_partial(work,
                                                             "degraded")}
        detail["degraded_read"] = degraded
        _checkpoint(detail)

        overload: dict = {"error": "skipped (budget)"}
        if left() > 80:
            try:
                overload = phase_overload(
                    work, budget_s=min(150.0, left() - 30.0))
                _log(f"overload: peak "
                     f"{(overload.get('peak') or {}).get('goodput_req_s')}"
                     f" req/s, 3x-offered goodput ratio "
                     f"{overload.get('goodput_ratio')}")
            except Exception as e:
                overload = {"error": str(e), **_load_partial(work,
                                                             "overload")}
        detail["overload"] = overload
        _checkpoint(detail)

        lifecycle: dict = {"error": "skipped (budget)"}
        if left() > 100:
            try:
                lifecycle = phase_lifecycle(
                    work, budget_s=min(240.0, left() - 30.0))
                _log(f"lifecycle: {lifecycle.get('warmed_volumes')} "
                     f"warmed, batch {lifecycle.get('time_to_warm_all_s')}"
                     f"s, hot p50 ratio {lifecycle.get('hot_p50_ratio')}")
            except Exception as e:
                lifecycle = {"error": str(e),
                             **_load_partial(work, "lifecycle")}
        detail["lifecycle"] = lifecycle
        _checkpoint(detail)

        georepl: dict = {"error": "skipped (budget)"}
        if left() > 90:
            try:
                georepl = phase_georepl(
                    work, budget_s=min(240.0, left() - 30.0))
                _log(f"georepl: steady lag "
                     f"{(georepl.get('steady_lag_s') or {}).get('median')}s, "
                     f"storm ratio {georepl.get('lag_ratio')}")
            except Exception as e:
                georepl = {"error": str(e),
                           **_load_partial(work, "georepl")}
        detail["georepl"] = georepl
        _checkpoint(detail)

        # multichip runs in its own subprocess because it must pin
        # JAX_PLATFORMS=cpu + the 8-virtual-device flag BEFORE jax
        # initializes (the phase body sets both)
        multichip: dict = {"error": "skipped (budget)"}
        if left() > 90:
            multichip = _run_phase("multichip", work, min(260.0, left()))
            sc = multichip.get("scaling") or {}
            _log(f"multichip: projected "
                 f"{[(n, (v.get('aggregate_projected_gbps') if isinstance(v, dict) else v)) for n, v in sorted(sc.items())]}, "
                 f"storm ratio "
                 f"{(multichip.get('rebuild_storm') or {}).get('drain_ratio') if isinstance(multichip.get('rebuild_storm'), dict) else None}")
        detail["multichip"] = multichip
        _checkpoint(detail)

        metadata: dict = {"error": "skipped (budget)"}
        if left() > 90:
            try:
                metadata = phase_metadata(
                    work, budget_s=min(240.0, left() - 30.0))
                _log(f"metadata: single "
                     f"{(metadata.get('single') or {}).get('namespace_ops_s')}"
                     f" ops/s, 3-peer ring x{metadata.get('scaling_3p')}")
            except Exception as e:
                metadata = {"error": str(e),
                            **_load_partial(work, "metadata")}
        detail["metadata"] = metadata
        _checkpoint(detail)

        observe_res: dict = {"error": "skipped (budget)"}
        if left() > 90:
            try:
                observe_res = phase_observe(
                    work, budget_s=min(150.0, left() - 60.0))
                _log(f"observe: p50 off {observe_res['off']['p50_ms']}ms "
                     f"armed {observe_res['armed']['p50_ms']}ms "
                     f"({observe_res['p50_regression_pct']}%)")
            except Exception as e:
                observe_res = {"error": str(e),
                               **_load_partial(work, "observe")}
        detail["observe"] = observe_res
        _checkpoint(detail)

        try:
            lint = phase_lint(work)
            _log(f"lint: {lint.get('lint_wall_s')}s over "
                 f"{lint.get('files')} files, clean={lint.get('clean')}")
        except Exception as e:
            lint = {"error": str(e)}
        detail["lint"] = lint
        _checkpoint(detail)

        scale: dict = {"error": "skipped (budget)"}
        if left() > 60:
            try:
                scale = phase_scale(work, budget_s=min(180.0, left() - 30.0))
                _log(f"scale: 1000-node plan p50 "
                     f"{scale.get('plan_p50_ms')}ms, skew moved-bytes "
                     f"ratio {scale.get('moved_bytes_ratio')}, "
                     f"{scale.get('violations_total')} violations")
            except Exception as e:
                scale = {"error": str(e), **_load_partial(work, "scale")}
        detail["scale"] = scale
        _checkpoint(detail)

        recovery: dict = {"error": "skipped (budget)"}
        if left() > 60:
            try:
                recovery = phase_recovery(
                    work, budget_s=min(240.0, left() - 20.0))
                _log(f"recovery: torn-{recovery.get('target_mb')}MB "
                     f"cold start {recovery.get('recovery_wall_s')}s, "
                     f"crashsim {recovery.get('crashsim_points')} pts @ "
                     f"{recovery.get('crashsim_points_per_s')}/s, "
                     f"{recovery.get('crashsim_violations')} violations")
            except Exception as e:
                recovery = {"error": str(e),
                            **_load_partial(work, "recovery")}
        detail["recovery"] = recovery
        _checkpoint(detail)

        try:
            needle_map = bench_needle_map(work)
        except Exception as e:
            needle_map = {"error": str(e)}
        detail["disk_needle_map"] = needle_map

        detail.pop("incomplete", None)
        # final full record; stdout's LAST line stays small and
        # single-line so a caller's parse cannot truncate it
        _checkpoint(detail)
        print(json.dumps({
            "phases": [k for k, v in detail.items()
                       if isinstance(v, dict) and "error" not in v],
            "extra": {
                "system_write_req_s":
                    (system.get("write") or {}).get("req_s")
                    if isinstance(system.get("write"), dict) else None,
                "system_read_req_s":
                    (system.get("read") or {}).get("req_s")
                    if isinstance(system.get("read"), dict) else None,
                "largefile_put_mb_s": largefile.get("put_mb_s"),
                "largefile_get_mb_s": largefile.get("get_mb_s"),
                "degraded_read_p50_ms": degraded.get("degraded_p50_ms"),
                "degraded_read_p99_ms": degraded.get("degraded_p99_ms"),
                "overload_goodput_ratio": overload.get("goodput_ratio"),
                "overload_p99_ms":
                    (overload.get("overload") or {}).get("p99_ms"),
                "observe_p50_regression_pct":
                    observe_res.get("p50_regression_pct"),
                "lifecycle_time_to_warm_s":
                    lifecycle.get("time_to_warm_all_s"),
                "lifecycle_hot_p50_ratio":
                    lifecycle.get("hot_p50_ratio"),
                "georepl_steady_lag_s":
                    (georepl.get("steady_lag_s") or {}).get("median"),
                "georepl_lag_ratio": georepl.get("lag_ratio"),
                "metadata_single_ops_s":
                    (metadata.get("single") or {}).get(
                        "namespace_ops_s"),
                "metadata_ring3_ops_s":
                    (metadata.get("ring3") or {}).get(
                        "namespace_ops_s"),
                "metadata_scaling_3p": metadata.get("scaling_3p"),
                "multichip_scaling": multichip.get("scaling"),
                "multichip_storm_drain_ratio":
                    (multichip.get("rebuild_storm") or {}).get(
                        "drain_ratio")
                    if isinstance(multichip.get("rebuild_storm"), dict)
                    else None,
                "fused_gbps": fused.get("gbps"),
                "fused_speedup_vs_chained": fused.get("speedup"),
                "fused_scrub_redigests": fused.get("scrub_redigests"),
                "lint_wall_s": lint.get("lint_wall_s"),
                "lint_v2_wall_s": lint.get("lint_v2_wall_s"),
                "recovery_wall_s": recovery.get("recovery_wall_s"),
                "recovery_full_scan_gbps":
                    recovery.get("full_scan_gbps"),
                "crashsim_points_per_s":
                    recovery.get("crashsim_points_per_s"),
                "scale_plan_p50_ms": scale.get("plan_p50_ms"),
                "scale_moved_bytes_ratio":
                    scale.get("moved_bytes_ratio"),
                "scale_violations": scale.get("violations_total"),
                "detail_file": "BENCH_DETAIL.json",
            },
        }))
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    if "--phase" in sys.argv:
        name = sys.argv[sys.argv.index("--phase") + 1]
        work = sys.argv[sys.argv.index("--work") + 1]
        budget = (float(sys.argv[sys.argv.index("--budget") + 1])
                  if "--budget" in sys.argv else 580.0)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        fn = {"fused": lambda w: phase_fused(w, budget_s=budget),
              "multichip": lambda w: phase_multichip(w, budget_s=budget),
              "degraded": lambda w: phase_degraded(w, budget_s=budget),
              "largefile": phase_largefile,
              "overload": lambda w: phase_overload(w, budget_s=budget),
              "observe": lambda w: phase_observe(w, budget_s=budget),
              "lifecycle": lambda w: phase_lifecycle(w, budget_s=budget),
              "georepl": lambda w: phase_georepl(w, budget_s=budget),
              "metadata": lambda w: phase_metadata(w, budget_s=budget),
              "lint": lambda w: phase_lint(w, budget_s=budget),
              "scale": lambda w: phase_scale(w, budget_s=budget),
              "recovery": lambda w: phase_recovery(w, budget_s=budget),
              }[name]
        print(json.dumps(fn(work)))
    else:
        main()
