"""Command-line interface: the `weed`-equivalent entry point.

Subcommands mirror the reference CLI (weed/command/command.go:10-33):
master, volume, server (master+volume in one process), upload, download,
delete, benchmark, shell ops (ec.encode / ec.rebuild / ec.balance /
ec.decode, volume.vacuum), status.

  python -m seaweedfs_tpu.cli master -port 9333
  python -m seaweedfs_tpu.cli volume -port 8080 -dir /data -mserver localhost:9333
  python -m seaweedfs_tpu.cli upload -server localhost:9333 FILE...
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import sys


def _run_forever(coro) -> None:
    loop = asyncio.new_event_loop()
    loop.run_until_complete(coro)
    try:
        loop.run_forever()
    except KeyboardInterrupt:
        pass


def _maybe_sharded(boot_fn) -> None:
    """Run ``boot_fn(shard_ctx) -> coroutine`` across the WEED_SERVE_SHARDS
    fleet.  The fork MUST happen here, before _run_forever news an event
    loop — a pre-fork epoll fd would be shared by every child (weedlint's
    fork-then-asyncio rule pins the ordering).  One shard (the default)
    skips all of it: boot_fn(None) on today's proven path."""
    from .server import sharded
    n = sharded.shards_from_env()
    if n <= 1:
        _run_forever(boot_fn(None))
        return
    import secrets
    ctx = sharded.ShardContext.create(n, secrets.token_hex(16))
    sharded.run_sharded(ctx, lambda c: _run_forever(boot_fn(c)))


def _resolve_coder(store, who: str) -> None:
    """Resolve the store's EC coder at boot and say what it is, so a chip
    that is missing or taken is an error now and not at the first encode
    (ec.get_coder's "auto" never falls back from a TPU to the host)."""
    from .utils import glog
    glog.info("%s: EC coder %r resolved to %s", who, store.coder_name,
              store.coder().describe())


def _load_guard():
    """Build a security Guard from security.toml (weed/command/scaffold.go
    security section; keys jwt.signing.key etc.)."""
    from .security.guard import Guard
    from .utils.config import load_configuration
    cfg = load_configuration("security")
    white = cfg.get_string("guard.white_list", "")
    return Guard(
        whitelist=[w for w in white.split(",") if w],
        signing_key=cfg.get_string("jwt.signing.key", ""),
        expires_seconds=cfg.get_int("jwt.signing.expires_after_seconds", 10),
        read_signing_key=cfg.get_string("jwt.signing.read.key", ""),
        read_expires_seconds=cfg.get_int(
            "jwt.signing.read.expires_after_seconds", 60))


def _load_tls():
    """TLS config from security.toml [tls]; None when not configured."""
    from .security.tls import load_tls_config
    cfg = load_tls_config()
    return cfg if cfg.enabled else None


def cmd_master(args) -> None:
    from .server.master import run_master
    url = f"{args.ip}:{args.port}"
    peers = [p.strip() for p in args.peers.split(",") if p.strip()]
    sequencer = None
    if args.sequencer_kv:
        # external atomic-counter sequencer (etcd_sequencer.go role):
        # redis-protocol INCRBY key-range leases
        from .topology.sequence import KvSequencer
        host, _, port = args.sequencer_kv.rpartition(":")
        if not host or not port.isdigit():
            raise SystemExit(
                f"-sequencer_kv must be host:port, got {args.sequencer_kv!r}")
        sequencer = KvSequencer(host, int(port))
    _run_forever(run_master(
        args.ip, args.port,
        volume_size_limit_mb=args.volume_size_limit_mb,
        default_replication=args.default_replication,
        pulse_seconds=args.pulse,
        guard=_load_guard(),
        tls=_load_tls(),
        url=url,
        peers=peers or None,
        sequencer=sequencer,
        raft_state_dir=args.mdir or None,
        grpc_port=(args.port + 10000 if args.grpc_port < 0
                   else args.grpc_port),
        maintenance_interval_seconds=(None if args.maintenance_interval < 0
                                      else args.maintenance_interval),
        repair_concurrency=args.repair_concurrency))


def cmd_volume(args) -> None:
    def boot(shard_ctx):
        if shard_ctx is not None and shard_ctx.index > 0:
            # one process per chip: shard 0 owns the device, every other
            # shard is pinned to the host. This has to precede the
            # imports below — they import jax, which reads the variable
            # once (the fork itself happened before jax was imported)
            os.environ["JAX_PLATFORMS"] = "cpu"
        from .ec.geometry import Geometry
        from .server.volume_server import run_volume_server
        from .storage.store import Store
        dirs = args.dir.split(",")
        if shard_ctx is not None and shard_ctx.index > 0:
            # share-nothing: every shard owns private volume dirs;
            # shard 0 keeps the base dirs so pre-sharding (legacy)
            # volumes stay served where they already live
            dirs = [os.path.join(d, f"shard{shard_ctx.index}")
                    for d in dirs]
            for d in dirs:
                os.makedirs(d, exist_ok=True)
        geometry = Geometry(
            large_block_size=args.ec_large_block,
            small_block_size=args.ec_small_block)
        store = Store(dirs,
                      max_volume_counts=[args.max] * len(dirs),
                      coder_name=args.coder, geometry=geometry,
                      needle_map_kind=args.index,
                      min_free_space_percent=args.min_free_space_percent,
                      preallocate=args.preallocate * 1024 * 1024)
        shard0 = shard_ctx is None or shard_ctx.index == 0
        _resolve_coder(store, f"volume server {args.ip}:{args.port}"
                       + ("" if shard_ctx is None
                          else f" shard {shard_ctx.index}"))
        return run_volume_server(
            args.ip, args.port, store, args.mserver,
            data_center=args.data_center, rack=args.rack,
            pulse_seconds=args.pulse, guard=_load_guard(), tls=_load_tls(),
            # the gRPC surfaces bind fixed ports: shard 0 owns them,
            # siblings serve HTTP/fastpath only
            use_grpc_heartbeat=args.grpc_heartbeat and shard0,
            grpc_port=((args.port + 10000 if args.grpc_port < 0
                        else args.grpc_port) if shard0 else 0),
            internal_token=(shard_ctx.token if shard_ctx else None),
            shard_ctx=shard_ctx)

    _maybe_sharded(boot)


def cmd_server(args) -> None:
    """master + volume (+ filer + s3) in one process
    (weed/command/server.go:117-221)."""
    from .ec.geometry import Geometry
    from .server.master import run_master
    from .server.volume_server import run_volume_server
    from .storage.store import Store

    async def boot():
        guard = _load_guard()
        tls = _load_tls()
        master_url = f"{args.ip}:{args.master_port}"
        await run_master(args.ip, args.master_port,
                         default_replication=args.default_replication,
                         guard=guard, url=master_url, tls=tls,
                         grpc_port=args.master_port + 10000)
        geometry = Geometry(large_block_size=args.ec_large_block,
                            small_block_size=args.ec_small_block)
        store = Store(args.dir.split(","), coder_name=args.coder,
                      geometry=geometry)
        _resolve_coder(store, f"volume server {args.ip}:{args.port}")
        await run_volume_server(args.ip, args.port, store, master_url,
                                guard=guard, tls=tls,
                                grpc_port=args.port + 10000)
        if getattr(args, "volume_workers", 1) > 1:
            # share-nothing worker processes: each owns its volumes; the
            # master balances assigns across them like any other nodes.
            # One process per chip: this process owns the device, the
            # workers are pinned to the host and log their coder at boot
            import atexit
            import subprocess
            procs = []
            base_dir = args.dir.split(",")[0]
            worker_env = dict(os.environ, JAX_PLATFORMS="cpu")
            for k in range(1, args.volume_workers):
                wdir = os.path.join(base_dir, f"worker{k}")
                os.makedirs(wdir, exist_ok=True)
                procs.append(subprocess.Popen(
                    [sys.executable, "-m", "seaweedfs_tpu.cli", "volume",
                     "-ip", args.ip, "-port", str(args.port + k),
                     "-dir", wdir, "-mserver", master_url,
                     "-coder", args.coder,
                     # geometry must match the parent's, or shard sets
                     # from different workers misaddress on rebuild/copy
                     "-ec_large_block", str(args.ec_large_block),
                     "-ec_small_block", str(args.ec_small_block)],
                    env=worker_env))
            atexit.register(lambda: [p.terminate() for p in procs])
        if args.filer:
            from .server.filer_server import run_filer
            await run_filer(args.ip, args.filer_port, master_url,
                            store_name="sqlite",
                            store_kwargs={"path": args.filer_db},
                            guard=guard, tls=tls,
                            grpc_port=args.filer_port + 10000)
        if args.s3:
            if not args.filer:
                raise SystemExit("-s3 needs -filer")
            from .s3.s3_server import run_s3
            iam = None
            if args.s3_config:
                from .s3.auth import Iam
                iam = Iam.from_file(args.s3_config)
            await run_s3(args.ip, args.s3_port,
                         f"{args.ip}:{args.filer_port}", iam=iam)

    _run_forever(boot())


def cmd_filer(args) -> None:
    from .notification.queues import load_notifier
    from .server.filer_server import run_filer
    from .utils.config import load_configuration
    store_kwargs = {}
    if args.store in ("sqlite", "leveldb", "leveldb2"):
        store_kwargs["path"] = args.store_path
    if args.store_servers:
        if args.store in ("redis", "redis2", "mongodb", "cassandra"):
            host, _, port = args.store_servers.rpartition(":")
            store_kwargs["host"], store_kwargs["port"] = host, int(port)
        elif args.store in ("etcd", "elastic"):
            store_kwargs["servers"] = args.store_servers
    notifier = load_notifier(load_configuration("notification"))
    ring_config = None
    if args.ring_peers:
        from .metaring import RingConfig
        base = RingConfig.from_env()
        ring_config = RingConfig(
            peers=[p for p in args.ring_peers.split(",") if p],
            vnodes=base.vnodes, replicas=base.replicas)
    def boot(shard_ctx):
        shard0 = shard_ctx is None or shard_ctx.index == 0
        return run_filer(
            args.ip, args.port, args.mserver, store_name=args.store,
            store_kwargs=store_kwargs,
            chunk_size=args.chunk_size_mb * 1024 * 1024,
            default_replication=args.default_replication,
            default_collection=args.collection,
            meta_log_path=args.meta_log,
            peers=[p for p in args.peers.split(",") if p],
            notifier=notifier, guard=_load_guard(), tls=_load_tls(),
            cipher=args.encrypt_volume_data,
            url=f"{args.ip}:{args.port}",
            ring_config=ring_config,
            grpc_port=((args.port + 10000 if args.grpc_port < 0
                        else args.grpc_port) if shard0 else 0),
            shard_ctx=shard_ctx)

    _maybe_sharded(boot)


def cmd_filer_copy(args) -> None:
    """Parallel file/tree upload through a filer (weed filer.copy,
    weed/command/filer_copy.go:78,365 — there a goroutine worker pool per
    file; here a thread pool driving the filer's autochunk PUT)."""
    import fnmatch
    import mimetypes
    import time as time_mod
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor
    from urllib.parse import quote, urlparse

    dest = args.dest
    u = urlparse(dest)
    if not u.scheme.startswith("http") or not u.netloc:
        raise SystemExit("destination must be http://filer:port/path/")
    if not u.path.endswith("/"):
        raise SystemExit('destination should be a folder ending with "/"')

    jobs: list[tuple[str, str]] = []  # (local path, filer-relative path)
    for src in args.sources:
        if os.path.isdir(src):
            base = os.path.basename(os.path.normpath(src))
            for root, _dirs, fnames in os.walk(src):
                for fn in sorted(fnames):
                    if args.include and not fnmatch.fnmatch(fn,
                                                            args.include):
                        continue
                    full = os.path.join(root, fn)
                    rel = os.path.join(base,
                                       os.path.relpath(full, src))
                    jobs.append((full, rel))
        elif os.path.exists(src):
            jobs.append((src, os.path.basename(src)))
        else:
            raise SystemExit(f"no such file or directory: {src}")

    total = [0]
    errors: list[str] = []
    t0 = time_mod.perf_counter()

    def one(job: tuple[str, str]) -> None:
        full, rel = job
        target = (f"{u.scheme}://{u.netloc}{u.path}"
                  f"{quote(rel.replace(os.sep, '/'))}")
        if args.collection:
            target += f"?collection={args.collection}"
        mime = mimetypes.guess_type(full)[0] or "application/octet-stream"
        try:
            with open(full, "rb") as f:
                data = f.read()
            req = urllib.request.Request(
                target, data=data, method="PUT",
                headers={"Content-Type": mime})
            with urllib.request.urlopen(req, timeout=120) as r:
                r.read()
            total[0] += len(data)
        except Exception as e:
            errors.append(f"{full}: {e}")

    with ThreadPoolExecutor(max_workers=args.concurrency) as pool:
        list(pool.map(one, jobs))
    dt = time_mod.perf_counter() - t0
    for err in errors:
        print(f"error: {err}", file=sys.stderr)
    print(f"copied {len(jobs) - len(errors)}/{len(jobs)} files, "
          f"{total[0]} bytes in {dt:.2f}s "
          f"({total[0] / max(dt, 1e-9) / 1e6:.1f} MB/s, "
          f"c={args.concurrency})")
    if errors:
        raise SystemExit(1)


def cmd_watch(args) -> None:
    """Live-tail filer metadata events (weed watch,
    weed/command/watch.go:36)."""
    from .replication.replicator import Replicator
    r = Replicator(args.filer, None, args.path_prefix)
    for e in r.subscribe_events(since=args.since):
        if e.directory.startswith(args.path_prefix):
            print(json.dumps(e.to_dict()), flush=True)


def _offset_path(stem: str, *parts: str) -> str:
    """Default resume-offset file: stable per-user directory (not CWD, so
    daemon restarts with a different working dir still resume) +
    human-readable first part + a hash of the full job identity
    (source, sink, prefix) so distinct jobs never share an offset
    (filer_sync.go setOffset/getOffset keys by signature)."""
    import hashlib
    base = os.path.join(os.path.expanduser("~"), ".seaweedfs_tpu", "offsets")
    os.makedirs(base, exist_ok=True)
    job_key = hashlib.sha256("|".join(parts).encode()).hexdigest()[:12]
    human = parts[0].replace(":", "_").replace("/", "_") if parts else ""
    return os.path.join(base, f"{stem}.{human}.{job_key}")


def cmd_filer_replicate(args) -> None:
    """Continuously replicate one filer into a sink configured by
    replication.toml (weed filer.replicate). With -from_queue the events
    come from the configured [source.*] queue (file spool or messaging
    broker) instead of a live subscribe stream — the reference's
    Kafka/SQS-fed mode (weed/replication/sub)."""
    import time as _time

    from .replication.replicator import Replicator, run_from_queue
    from .replication.sink import load_sink
    from .utils.config import load_configuration
    cfg = load_configuration("replication")
    sink = load_sink(cfg)
    if sink is None:
        raise SystemExit("no enabled [sink.*] in replication.toml "
                         "(run scaffold -config replication)")
    offset = args.offset_file or _offset_path(
        "replicate_offset", args.filer, sink.identity(), args.path_prefix)
    r = Replicator(args.filer, sink, args.path_prefix, offset_path=offset)
    if args.from_queue:
        from .replication.sub import load_notification_input
        inp = load_notification_input(cfg)
        if inp is None:
            raise SystemExit("-from_queue needs an enabled [source.*] in "
                             "replication.toml")
        while True:
            run_from_queue(r, inp, idle_timeout=2.0)
            _time.sleep(1.0)
    else:
        r.run()


def cmd_filer_sync(args) -> None:
    """Active-active sync of two filers with signature loop prevention
    (weed filer.sync, weed/command/filer_sync.go:81-330)."""
    import threading
    import urllib.request

    from .replication.replicator import Replicator
    from .replication.sink import FilerSink

    def signature_of(filer: str) -> int:
        with urllib.request.urlopen(
                f"http://{filer}/__meta__/info", timeout=10) as r:
            return int(json.load(r)["signature"])

    sig_a, sig_b = signature_of(args.a), signature_of(args.b)

    def one_direction(src: str, dst: str, dst_sig: int) -> None:
        # exclude events the destination already processed — the loop break
        # of filer.sync (filer_sync.go signature filtering); per-direction
        # offsets (keyed by src, dst AND prefix) persisted so restarts
        # resume instead of full replay
        if args.offset_file:
            # sanitize only the per-direction suffix, never the user path
            suffix = f"{src}_{dst}".replace(":", "_").replace("/", "_")
            offset = f"{args.offset_file}.{suffix}"
        else:
            offset = _offset_path("sync_offset", src, dst, args.path_prefix)
        Replicator(src, FilerSink(dst), args.path_prefix,
                   offset_path=offset).run(exclude_sig=dst_sig)

    ta = threading.Thread(target=one_direction,
                          args=(args.a, args.b, sig_b), daemon=True)
    ta.start()
    one_direction(args.b, args.a, sig_a)


def cmd_s3(args) -> None:
    from .s3.s3_server import run_s3
    if bool(args.access_key) != bool(args.secret_key):
        raise SystemExit(
            "-access_key and -secret_key must be provided together "
            "(omit both for anonymous mode)")
    iam = None
    if args.config:
        from .s3.auth import Iam
        iam = Iam.from_file(args.config)
    _maybe_sharded(lambda shard_ctx: run_s3(
        args.ip, args.port, args.filer,
        access_key=args.access_key,
        secret_key=args.secret_key,
        iam=iam, shard_ctx=shard_ctx))


def cmd_upload(args) -> None:
    from .client import Client
    c = Client(args.server)
    out = []
    for path in args.files:
        with open(path, "rb") as f:
            data = f.read()
        fid = c.upload(data, filename=os.path.basename(path),
                       collection=args.collection,
                       replication=args.replication, ttl=args.ttl)
        out.append({"file": path, "fid": fid, "size": len(data)})
        print(json.dumps(out[-1]))


def cmd_download(args) -> None:
    from .client import Client
    c = Client(args.server)
    data = c.download(args.fid)
    if args.output == "-":
        sys.stdout.buffer.write(data)
    else:
        with open(args.output, "wb") as f:
            f.write(data)
        print(f"{args.fid} -> {args.output} ({len(data)} bytes)")


def cmd_delete(args) -> None:
    from .client import Client
    c = Client(args.server, guard=_load_guard())
    for fid in args.fids:
        c.delete(fid)
        print(f"deleted {fid}")


def cmd_shell(args) -> None:
    """Admin shell: one-shot `weed shell <cmd> [args]` or interactive REPL
    (weed/shell/shell_liner.go)."""
    from .client import Client
    from .ec.geometry import Geometry
    from .shell import commands as shell_commands
    from .shell.commands import CommandEnv, COMMANDS, run_command
    shell_commands._register_all()
    c = Client(args.server)

    # back-compat with the round-1 flag style (`shell ec.encode -volume N
    # -ec_large_block B`): argparse REMAINDER swallows those flags, so
    # fold them back into the geometry / new-style argv here
    large, small = args.ec_large_block, args.ec_small_block
    argv: list[str] = []
    raw = list(args.cmd or [])
    i = 0
    while i < len(raw):
        tok = raw[i]
        needs_value = tok in ("-volume", "-ec_large_block",
                              "-ec_small_block")
        if needs_value and i + 1 >= len(raw):
            raise SystemExit(f"shell: flag {tok} needs a value")
        try:
            if tok == "-volume":
                argv += ["-volumeId", raw[i + 1]]
                i += 2
            elif tok == "-dry_run":
                argv.append("-dryRun")
                i += 1
            elif tok == "-ec_large_block":
                large = int(raw[i + 1])
                i += 2
            elif tok == "-ec_small_block":
                small = int(raw[i + 1])
                i += 2
            else:
                argv.append(tok)
                i += 1
        except ValueError:
            raise SystemExit(f"shell: bad value for {tok}: {raw[i + 1]!r}")
    if argv and args.volume:
        argv += ["-volumeId", str(args.volume)]
    if argv and args.collection:
        argv += ["-collection", args.collection]
    if argv and args.dry_run:
        argv.append("-dryRun")

    geometry = Geometry(large_block_size=large, small_block_size=small)
    env = CommandEnv(c, geometry, filer=args.filer)

    def show(result) -> None:
        if isinstance(result, bytes):
            import sys as sys_mod
            sys_mod.stdout.buffer.write(result)
            sys_mod.stdout.buffer.flush()
        else:
            print(json.dumps(result, indent=None, default=str))

    if argv:
        show(run_command(env, argv))
        return

    # REPL
    import sys as sys_mod
    print(f"seaweedfs-tpu shell: {len(COMMANDS)} commands; "
          "'help' lists them, ctrl-d exits", file=sys_mod.stderr)
    while True:
        try:
            line = input("> ")
        except EOFError:
            break
        line = line.strip()
        if not line:
            continue
        if line in ("exit", "quit"):
            break
        try:
            show(run_command(env, line))
        except Exception as e:
            print(json.dumps({"error": str(e)}))
    if env.locked:
        env.release_lock()


def cmd_backup(args) -> None:
    """Incrementally pull a volume into a local replica directory
    (weed backup, weed/command/backup.go:64)."""
    from .client import Client
    from .storage import volume_backup
    from .storage.volume import Volume
    import os
    c = Client(args.server)
    os.makedirs(args.dir, exist_ok=True)
    create = not os.path.exists(
        os.path.join(args.dir, (f"{args.collection}_" if args.collection
                                else "") + f"{args.volumeId}.dat"))
    v = Volume(args.dir, args.collection, args.volumeId, create=create)
    applied = volume_backup.incremental_backup(
        v, 0, lambda since: c.tail_volume(args.volumeId, since))
    print(json.dumps({"volume": args.volumeId, "applied": applied,
                      "file_count": v.file_count()}))
    v.close()


def cmd_fix(args) -> None:
    """Rebuild .idx by scanning .dat (weed fix, weed/command/fix.go:61)."""
    from .storage import volume_backup
    count = volume_backup.rebuild_idx(args.dir, args.collection,
                                      args.volumeId)
    print(json.dumps({"volume": args.volumeId, "live_needles": count}))


def cmd_export(args) -> None:
    """Export a volume's live needles to a tar archive
    (weed export, weed/command/export.go:149)."""
    import tarfile
    import io
    from .storage.volume import Volume
    v = Volume(args.dir, args.collection, args.volumeId)
    n_out = 0
    with tarfile.open(args.output, "w") as tar:
        from .storage import types as t

        def visit(n, byte_offset):
            nonlocal n_out
            if len(n.data) == 0:
                return
            nv = v.nm.get(n.id)
            if nv is None or nv.size < 0:
                return  # deleted
            if t.stored_to_offset(nv.offset) != byte_offset:
                return  # superseded by a later version of the same fid
            name = (n.name.decode("utf-8", "replace")
                    if n.name else f"{v.vid}_{n.id:x}")
            info = tarfile.TarInfo(name=name)
            info.size = len(n.data)
            info.mtime = n.last_modified
            tar.addfile(info, io.BytesIO(n.data))
            n_out += 1
        v.scan(visit)
    v.close()
    print(json.dumps({"volume": args.volumeId, "files": n_out,
                      "tar": args.output}))


def cmd_compact(args) -> None:
    """Offline vacuum of one volume (weed compact, weed/command/compact.go)."""
    from .storage.volume import Volume
    v = Volume(args.dir, args.collection, args.volumeId)
    before = v.data_file_size()
    v.compact()
    after = v.data_file_size()
    v.close()
    print(json.dumps({"volume": args.volumeId, "bytes_before": before,
                      "bytes_after": after, "reclaimed": before - after}))


def cmd_status(args) -> None:
    from .client import Client
    print(json.dumps(Client(args.server).cluster_status(), indent=2))


def cmd_benchmark(args) -> None:
    """Self-validating write/read benchmark (weed/command/benchmark.go):
    seeded unique payloads, hash-checked on read-back, latency
    percentiles. Raw-socket keep-alive engine (utils/bench_client.py) so
    the harness is not the bottleneck it measures."""
    from .utils.bench_client import run_benchmark

    master = args.server.split(",")[0]
    out = run_benchmark(master, n=args.n, size=args.size,
                        concurrency=args.concurrency)
    w, r = out["write"], out["read"]
    print(f"writes: {w['n']} in {w['wall_s']}s -> {w['req_s']} req/s, "
          f"p50={w.get('p50_ms')}ms p95={w.get('p95_ms')}ms "
          f"p99={w.get('p99_ms')}ms ({out['write_errors']} errors)")
    print(f"reads: {r['n']} in {r['wall_s']}s -> {r['req_s']} req/s, "
          f"{out['corrupt']} corrupt")
    if out["corrupt"] or out["write_errors"]:
        raise SystemExit(1)


def cmd_mount(args) -> None:
    from .mount.fuse_mount import mount
    mount(args.filer, args.dir, collection=args.collection,
          replication=args.replication,
          chunk_size=args.chunk_size_mb * 1024 * 1024)


def cmd_webdav(args) -> None:
    from .server.webdav_server import run_webdav
    _run_forever(run_webdav(args.ip, args.port, args.filer))


def cmd_msg_broker(args) -> None:
    from .messaging.broker import run_broker
    _run_forever(run_broker(args.ip, args.port, filer_url=args.filer,
                            tls=_load_tls()))


def cmd_scaffold(args) -> None:
    """Emit commented default TOML templates (weed/command/scaffold.go:30)."""
    from .utils.scaffold import TEMPLATES
    name = args.config
    if name not in TEMPLATES:
        raise SystemExit(f"unknown config {name}; one of {list(TEMPLATES)}")
    text = TEMPLATES[name]
    if args.output:
        with open(os.path.join(args.output, name + ".toml"), "w") as f:
            f.write(text)
    else:
        sys.stdout.write(text)


def cmd_version(args) -> None:
    from . import __version__
    print(f"seaweedfs-tpu {__version__}")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="seaweedfs-tpu")
    p.add_argument("-v", type=int, default=0, dest="verbosity",
                   help="glog verbosity level")
    p.add_argument("-vmodule", default="",
                   help="per-file verbosity, e.g. volume=2,store=4")
    p.add_argument("-logFile", default="", dest="log_file")
    p.add_argument("-cpuprofile", default="",
                   help="write a cProfile dump here at exit "
                        "(grace.SetupProfiling analog)")
    sub = p.add_subparsers(dest="cmd", required=True)

    m = sub.add_parser("master", help="run a master server")
    m.add_argument("-ip", default="127.0.0.1")
    m.add_argument("-port", type=int, default=9333)
    m.add_argument("-volume_size_limit_mb", type=int, default=30 * 1024)
    m.add_argument("-default_replication", default="000")
    m.add_argument("-peers", default="",
                   help="comma-separated ip:port of ALL masters (incl. self)"
                        " for raft HA (weed master -peers)")
    m.add_argument("-sequencer_kv", default="",
                   help="host:port of a redis-protocol KV; file keys are "
                        "leased from its atomic counter (etcd-sequencer "
                        "role) instead of the in-memory sequencer")
    m.add_argument("-mdir", default="",
                   help="directory for persisted raft state")
    m.add_argument("-pulse", type=float, default=5.0,
                   help="expected heartbeat interval (drives dead-node "
                        "pruning)")
    m.add_argument("-grpc_port", type=int, default=-1,
                   help="gRPC control-plane port (default HTTP+10000; "
                        "0 disables)")
    m.add_argument("-maintenance_interval", type=float, default=-1.0,
                   help="seconds between maintenance-daemon passes "
                        "(prune + repair planner; default: pulse, "
                        "0 disables the daemon)")
    m.add_argument("-repair_concurrency", type=int, default=None,
                   help="max concurrent repairs (re-replication / "
                        "auto ec.rebuild / lifecycle encodes) the "
                        "daemon drives; default WEED_EC_ENCODE_WORKERS "
                        "or 2")
    m.set_defaults(fn=cmd_master)

    v = sub.add_parser("volume", help="run a volume server")
    v.add_argument("-ip", default="127.0.0.1")
    v.add_argument("-port", type=int, default=8080)
    v.add_argument("-dir", default="./data")
    v.add_argument("-max", type=int, default=8)
    v.add_argument("-mserver", default="127.0.0.1:9333")
    v.add_argument("-dataCenter", dest="data_center", default="")
    v.add_argument("-rack", default="")
    v.add_argument("-pulse", type=float, default=5.0)
    v.add_argument("-coder", default="auto")
    v.add_argument("-index", default="memory", choices=["memory", "compact", "leveldb", "leveldbMedium", "leveldbLarge"],
                   help="needle map kind (weed volume -index)")
    v.add_argument("-minFreeSpacePercent", dest="min_free_space_percent",
                   type=float, default=1.0)
    v.add_argument("-preallocate", type=int, default=0,
                   help="MB to fallocate per new volume "
                        "(volume_create_linux.go)")
    v.add_argument("-grpc_heartbeat", action="store_true",
                   help="stream heartbeats over gRPC instead of HTTP "
                        "polling")
    v.add_argument("-grpc_port", type=int, default=-1,
                   help="gRPC admin/stream port (default HTTP+10000; "
                        "0 disables)")
    v.add_argument("-ec_large_block", type=int, default=1024 * 1024 * 1024)
    v.add_argument("-ec_small_block", type=int, default=1024 * 1024)
    v.set_defaults(fn=cmd_volume)

    s = sub.add_parser("server",
                       help="master + volume (+ filer + s3) in one process")
    s.add_argument("-ip", default="127.0.0.1")
    s.add_argument("-master_port", type=int, default=9333)
    s.add_argument("-port", type=int, default=8080)
    s.add_argument("-dir", default="./data")
    s.add_argument("-default_replication", default="000")
    s.add_argument("-coder", default="auto")
    s.add_argument("-ec_large_block", type=int, default=1024 * 1024 * 1024)
    s.add_argument("-ec_small_block", type=int, default=1024 * 1024)
    s.add_argument("-filer", action="store_true",
                   help="also run a filer (weed server -filer)")
    s.add_argument("-filer_port", type=int, default=8888)
    s.add_argument("-filer_db", default="./filer.db")
    s.add_argument("-s3", action="store_true",
                   help="also run the S3 gateway (needs -filer)")
    s.add_argument("-s3_port", type=int, default=8333)
    s.add_argument("-s3_config", default="",
                   help="JSON identities file for the embedded S3 gateway"
                        " (anonymous without it, like `weed s3`)")
    s.add_argument("-volume_workers", type=int, default=1,
                   help="extra volume-server worker PROCESSES (ports "
                        "port+1..port+N-1, own dirs): CPython's analog of "
                        "the reference's one multi-core Go server — "
                        "req/s scales with cores, the master spreads "
                        "assigns across workers")
    s.set_defaults(fn=cmd_server)

    f = sub.add_parser("filer", help="run a filer server")
    f.add_argument("-ip", default="127.0.0.1")
    f.add_argument("-port", type=int, default=8888)
    f.add_argument("-mserver", default="127.0.0.1:9333")
    f.add_argument("-store", default="sqlite",
                   help="metadata store: sqlite | memory | leveldb | "
                        "leveldb2 | redis | redis2 | etcd | mongodb | "
                        "elastic | cassandra")
    f.add_argument("-store_path", default="./filer.db")
    f.add_argument("-store_servers", default="",
                   help="host:port (or URL) for network stores (redis, "
                        "redis2, etcd, mongodb, elastic, cassandra)")
    f.add_argument("-chunk_size_mb", type=int, default=8)
    f.add_argument("-default_replication", default="")
    f.add_argument("-collection", default="")
    f.add_argument("-meta_log", default="",
                   help="path for the persisted metadata event log")
    f.add_argument("-encryptVolumeData", dest="encrypt_volume_data",
                   action="store_true",
                   help="AES-256-GCM encrypt chunk data on volume servers"
                        " (weed filer -encryptVolumeData)")
    f.add_argument("-peers", default="",
                   help="comma-separated peer filer host:port for "
                        "active-active metadata sync")
    f.add_argument("-ring_peers", default="",
                   help="comma-separated filer host:port members of the"
                        " metadata scale-out ring (partitioned"
                        " namespace; see also WEED_FILER_RING_*)")
    f.add_argument("-grpc_port", type=int, default=-1,
                   help="gRPC meta-plane port (default HTTP+10000; "
                        "0 disables)")
    f.set_defaults(fn=cmd_filer)

    w = sub.add_parser("watch", help="live-tail filer metadata events")
    w.add_argument("-filer", default="127.0.0.1:8888")
    w.add_argument("-pathPrefix", dest="path_prefix", default="/")
    w.add_argument("-since", type=int, default=0)
    w.set_defaults(fn=cmd_watch)

    fc = sub.add_parser("filer.copy",
                        help="copy files or whole folders to a filer "
                             "folder (weed filer.copy)")
    fc.add_argument("sources", nargs="+",
                    help="files or directories to upload")
    fc.add_argument("dest",
                    help="http://filer:port/path/to/folder/ (must end /)")
    fc.add_argument("-include", default="",
                    help="file name pattern, e.g. *.pdf")
    fc.add_argument("-concurrency", type=int, default=8)
    fc.add_argument("-collection", default="")
    fc.set_defaults(fn=cmd_filer_copy)

    fr = sub.add_parser("filer.replicate",
                        help="replicate filer changes into a sink "
                             "(replication.toml)")
    fr.add_argument("-filer", default="127.0.0.1:8888")
    fr.add_argument("-pathPrefix", dest="path_prefix", default="/")
    fr.add_argument("-offsetFile", dest="offset_file", default="",
                    help="resume-offset file (default derived from -filer)")
    fr.add_argument("-from_queue", action="store_true",
                    help="consume events from the [source.*] queue in "
                         "replication.toml instead of a live subscribe")
    fr.set_defaults(fn=cmd_filer_replicate)

    fsync = sub.add_parser("filer.sync",
                           help="active-active sync between two filers")
    fsync.add_argument("-a", required=True, help="filer A host:port")
    fsync.add_argument("-b", required=True, help="filer B host:port")
    fsync.add_argument("-pathPrefix", dest="path_prefix", default="/")
    fsync.add_argument("-offsetFile", dest="offset_file", default="",
                       help="resume-offset file stem (default: "
                            "~/.seaweedfs_tpu/offsets/, keyed by job)")
    fsync.set_defaults(fn=cmd_filer_sync)

    mt = sub.add_parser("mount", help="FUSE-mount a filer path")
    mt.add_argument("-filer", default="127.0.0.1:8888")
    mt.add_argument("-dir", required=True, help="local mountpoint")
    mt.add_argument("-collection", default="")
    mt.add_argument("-replication", default="")
    mt.add_argument("-chunk_size_mb", type=int, default=8)
    mt.set_defaults(fn=cmd_mount)

    wd = sub.add_parser("webdav", help="run the WebDAV gateway")
    wd.add_argument("-ip", default="127.0.0.1")
    wd.add_argument("-port", type=int, default=7333)
    wd.add_argument("-filer", default="127.0.0.1:8888")
    wd.set_defaults(fn=cmd_webdav)

    mb = sub.add_parser("msg.broker", help="run a pub/sub message broker")
    mb.add_argument("-ip", default="127.0.0.1")
    mb.add_argument("-port", type=int, default=17777)
    mb.add_argument("-filer", default="",
                    help="filer host:port for segment persistence "
                         "(empty: memory only)")
    mb.set_defaults(fn=cmd_msg_broker)

    s3p = sub.add_parser("s3", help="run the S3 gateway")
    s3p.add_argument("-ip", default="127.0.0.1")
    s3p.add_argument("-port", type=int, default=8333)
    s3p.add_argument("-filer", default="127.0.0.1:8888")
    s3p.add_argument("-access_key", default="")
    s3p.add_argument("-secret_key", default="")
    s3p.add_argument("-config", default="",
                     help="JSON identities file with per-action ACLs "
                          "(weed s3 -config)")
    s3p.set_defaults(fn=cmd_s3)

    u = sub.add_parser("upload", help="upload files")
    u.add_argument("-server", default="127.0.0.1:9333")
    u.add_argument("-collection", default="")
    u.add_argument("-replication", default="")
    u.add_argument("-ttl", default="")
    u.add_argument("files", nargs="+")
    u.set_defaults(fn=cmd_upload)

    d = sub.add_parser("download", help="download a file by fid")
    d.add_argument("-server", default="127.0.0.1:9333")
    d.add_argument("-output", default="-")
    d.add_argument("fid")
    d.set_defaults(fn=cmd_download)

    rm = sub.add_parser("delete", help="delete fids")
    rm.add_argument("-server", default="127.0.0.1:9333")
    rm.add_argument("fids", nargs="+")
    rm.set_defaults(fn=cmd_delete)

    sh = sub.add_parser("shell", help="admin shell (REPL or one-shot)")
    sh.add_argument("-server", default="127.0.0.1:9333")
    sh.add_argument("-filer", default="",
                    help="filer host:port for fs.*/bucket.*/fsck commands")
    sh.add_argument("-volume", type=int, default=0)
    sh.add_argument("-collection", default="")
    sh.add_argument("-dry_run", action="store_true")
    sh.add_argument("-ec_large_block", type=int, default=1024 * 1024 * 1024)
    sh.add_argument("-ec_small_block", type=int, default=1024 * 1024)
    sh.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="command + args (empty for interactive REPL)")
    sh.set_defaults(fn=cmd_shell)

    bk = sub.add_parser("backup", help="incrementally pull a volume locally")
    bk.add_argument("-server", default="127.0.0.1:9333")
    bk.add_argument("-dir", default="./backup")
    bk.add_argument("-collection", default="")
    bk.add_argument("-volumeId", type=int, required=True)
    bk.set_defaults(fn=cmd_backup)

    fx = sub.add_parser("fix", help="rebuild .idx by scanning .dat")
    fx.add_argument("-dir", default="./data")
    fx.add_argument("-collection", default="")
    fx.add_argument("-volumeId", type=int, required=True)
    fx.set_defaults(fn=cmd_fix)

    ex = sub.add_parser("export", help="export volume to tar")
    ex.add_argument("-dir", default="./data")
    ex.add_argument("-collection", default="")
    ex.add_argument("-volumeId", type=int, required=True)
    ex.add_argument("-output", default="volume.tar")
    ex.set_defaults(fn=cmd_export)

    cp = sub.add_parser("compact", help="offline vacuum of one volume")
    cp.add_argument("-dir", default="./data")
    cp.add_argument("-collection", default="")
    cp.add_argument("-volumeId", type=int, required=True)
    cp.set_defaults(fn=cmd_compact)

    st = sub.add_parser("status", help="cluster status")
    st.add_argument("-server", default="127.0.0.1:9333")
    st.set_defaults(fn=cmd_status)

    b = sub.add_parser("benchmark", help="write/read benchmark")
    b.add_argument("-server", default="127.0.0.1:9333")
    b.add_argument("-n", type=int, default=1000)
    b.add_argument("-size", type=int, default=1024)
    b.add_argument("-concurrency", type=int, default=16)
    b.set_defaults(fn=cmd_benchmark)

    sc = sub.add_parser("scaffold", help="emit default TOML config templates")
    sc.add_argument("-config", default="security",
                    help="security|filer|master|notification|replication")
    sc.add_argument("-output", default="",
                    help="directory to write <config>.toml into "
                         "(default: stdout)")
    sc.set_defaults(fn=cmd_scaffold)

    ver = sub.add_parser("version", help="print version")
    ver.set_defaults(fn=cmd_version)

    return p


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    from .utils import glog
    glog.setup(args.verbosity, args.vmodule, args.log_file)
    if args.cpuprofile:
        from .observe.profiler import setup_cpu_profile
        setup_cpu_profile(args.cpuprofile)
    args.fn(args)


if __name__ == "__main__":
    main()
