#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process IS the volume server: it builds the Store and awaits
`run_volume_server`, the coroutine `cli volume` calls, on a background
event loop, so it is the one process on the chip and can put a
`jax.profiler` session around the window. The master and the load
generator are children pinned to JAX_PLATFORMS=cpu. This process writes
the mix's volumes through the store's own writer; the load generator
(`loadgen.py`) imports the cell's driver by the name its traffic file
gives, drives the window over HTTP, and checks every answer.

Nothing here knows a cell: the cell names a configuration file and a
traffic file, the traffic file names a driver under `drivers/`, and each
per-layer metric is a reader of its own under `layer_metrics/`.

`--rehearsal` is for a machine without a chip: tiny volumes on the host
coder through every phase, then exit 3 with no result line, so that no
CPU number can be taken for a cell's. `--control` puts the driver's
control (the reference with one stated guarantee broken) in the
program's place at verification; such a run has to end `correct: false`.
`--sweep` offers a window at each of several rates after one set-up: how
the rate in a mix was found.
"""

from __future__ import annotations

T_PROCESS_START = __import__("time").time()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402
import urllib.request  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK_ROOT = os.path.join(ROOT, ".bench_work")
sys.path.insert(0, HERE)

import reduce  # noqa: E402  (benchmark/reduce.py)

CHILD_TIMEOUT_S = 300


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py`, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{re.sub(r'[^0-9a-zA-Z_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve_cell(bench: dict, name: str) -> tuple[dict, dict, dict]:
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def child_start() -> None:
    """preexec_fn: a child is killed when this process goes, however it
    goes (PR_SET_PDEATHSIG), so that no run leaves a process behind."""
    import ctypes
    ctypes.CDLL(None).prctl(1, signal.SIGKILL)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, timeout: float = 30.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def check_room(need_file: int, need_disk: int, where: str) -> None:
    """Fail with the numbers before anything is written when the largest
    file (RLIMIT_FSIZE: the driver's machine caps a file at 1 GiB) or the
    whole run cannot fit."""
    soft, _ = resource.getrlimit(resource.RLIMIT_FSIZE)
    if soft != resource.RLIM_INFINITY and soft < need_file:
        raise SystemExit(f"RLIMIT_FSIZE is {soft} bytes; the cell's "
                         f"largest file needs {need_file}")
    st = os.statvfs(where)
    free = st.f_bavail * st.f_frsize
    if free < need_disk:
        raise SystemExit(f"{where} has {free} bytes free; the cell needs "
                         f"{need_disk}")


class Hosted:
    """The cluster of one run: this process as the volume server, a
    master child, both on loopback ports picked at start."""

    def __init__(self, work: str, policy: str):
        self.work = work
        self.policy = policy
        self.master_url = f"127.0.0.1:{free_port()}"
        self.volume_url = f"127.0.0.1:{free_port()}"
        self.vdir = os.path.join(work, "v")
        self.child_env = dict(
            os.environ, JAX_PLATFORMS="cpu", WEED_EC_GEOMETRY=policy,
            PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
        self.master: subprocess.Popen | None = None
        self.loop: asyncio.AbstractEventLoop | None = None
        self.thread: threading.Thread | None = None
        self.store = None
        self.runner = None

    def start(self, collection: str) -> dict:
        from seaweedfs_tpu.ec.geometry import GeometryPolicy
        from seaweedfs_tpu.server.volume_server import run_volume_server
        from seaweedfs_tpu.storage.store import Store
        os.makedirs(os.path.join(self.work, "m"))
        os.makedirs(self.vdir)
        with open(os.path.join(self.work, "master.log"), "ab") as logf:
            # the repair daemon would rebuild shards a cell deletes on
            # purpose and race its window: off (config: assumed)
            self.master = subprocess.Popen(
                [sys.executable, "-m", "seaweedfs_tpu.cli", "master",
                 "-port", self.master_url.rsplit(":", 1)[1],
                 "-mdir", os.path.join(self.work, "m"),
                 "-maintenance_interval", "0"],
                cwd=self.work, env=self.child_env, stdout=logf, stderr=logf,
                preexec_fn=child_start)
        self.store = Store([self.vdir], coder_name="auto",
                           geometry_policy=GeometryPolicy.parse(self.policy))
        # as `cli volume` does at boot, for the cell's geometry: a missing
        # chip is an error now and not at the first encode
        desc = self.store.coder(
            self.store.geometry_for(collection)).describe()
        self.loop = asyncio.new_event_loop()
        started = threading.Event()
        failure: list[BaseException] = []

        def serve() -> None:
            asyncio.set_event_loop(self.loop)
            try:
                host, port = self.volume_url.rsplit(":", 1)
                self.runner = self.loop.run_until_complete(
                    run_volume_server(host, int(port), self.store,
                                      self.master_url, grpc_port=0))
            except BaseException as e:  # reported by start()
                failure.append(e)
                started.set()
                return
            started.set()
            self.loop.run_forever()

        self.thread = threading.Thread(target=serve, name="volume-server",
                                       daemon=True)
        self.thread.start()
        started.wait(120)
        if failure:
            raise failure[0]
        deadline = time.time() + 120
        while time.time() < deadline:
            if self.master.poll() is not None:
                raise SystemExit("master exited at boot; see master.log")
            try:
                if http_json(f"http://{self.master_url}/dir/status",
                             timeout=5).get("nodes"):
                    return desc
            except OSError:
                pass
            time.sleep(0.1)
        raise SystemExit("the master never saw the volume server")

    def counters(self) -> dict[str, float]:
        """Every sample of the volume server's /metrics, summed over
        label sets that differ only in `chip`."""
        with urllib.request.urlopen(f"http://{self.volume_url}/metrics",
                                    timeout=30) as r:
            text = r.read().decode()
        out: dict[str, float] = {}
        for m in re.finditer(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})? "
                             r"([0-9.eE+-]+|NaN)$", text, re.M):
            labels = re.sub(r'chip="[^"]*",?', "", m.group(2) or "")
            key = m.group(1) + (labels if labels not in ("", "{}") else "")
            try:
                out[key] = out.get(key, 0.0) + float(m.group(3))
            except ValueError:
                pass
        return out

    def stop(self) -> None:
        if self.loop is not None and self.runner is not None:
            fut = asyncio.run_coroutine_threadsafe(self.runner.cleanup(),
                                                   self.loop)
            try:
                fut.result(30)
            except Exception as e:
                log(f"volume server cleanup: {type(e).__name__}: {e}")
        if self.loop is not None:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self.thread.join(30)
        if self.store is not None:
            self.store.close()
        if self.master is not None and self.master.poll() is None:
            self.master.terminate()
            try:
                self.master.wait(10)
            except subprocess.TimeoutExpired:
                self.master.kill()
                self.master.wait()


def require_chip(desc: dict, status: dict, chips: int) -> dict:
    """The device as JAX reports it; refuses anything but the Pallas
    coder on a TPU with at least `chips` chips, read back from the
    server's own status surface."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"need {chips} TPU chip(s), JAX reports "
                         f"{len(devs)} x {devs[0].platform}")
    resolved = status["coder"]["resolved"]
    want = "MeshCoder" if chips > 1 else "PallasCoder"
    ok = [d for d in resolved if d.get("coder") == want
          and (d.get("device") or {}).get("platform") == "tpu"
          and (d.get("formulation") == "pallas" if chips > 1
               else d.get("interpret") is False)]
    if not ok or len(ok) != len(resolved):
        raise SystemExit(f"`auto` did not resolve to {want} on a TPU: "
                         f"{resolved}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileCounter:
    """Counts what JAX compiles or lowers while `armed`."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self) -> None:
        self.armed = False
        self.counts = {e.rsplit("/", 1)[1]: 0 for e in self.EVENTS}
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and event in self.EVENTS:
            self.counts[event.rsplit("/", 1)[1]] += 1


class Child:
    """The load generator: one JSON object a line on its stdout, one
    word a line on its stdin."""

    def __init__(self, ctx: dict, env: dict):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py"),
             json.dumps(ctx)],
            env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True, bufsize=1, preexec_fn=child_start)

    def expect(self, event: str) -> dict:
        deadline = time.time() + CHILD_TIMEOUT_S
        while time.time() < deadline:
            line = self.proc.stdout.readline()
            if not line:
                raise SystemExit(f"load generator ended (rc="
                                 f"{self.proc.wait()}) before {event!r}")
            try:
                msg = json.loads(line)
            except ValueError:
                sys.stderr.write(line)
                continue
            if msg.get("event") == event:
                return msg
            if msg.get("event") == "log":
                log(msg["msg"])
        raise SystemExit(f"load generator silent for {CHILD_TIMEOUT_S}s "
                         f"before {event!r}")

    def tell(self, word: str) -> None:
        self.proc.stdin.write(word + "\n")
        self.proc.stdin.flush()

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self.proc.stdout.close()
        self.proc.stdin.close()


def memory_peak() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, rehearsal: bool = False,
             control: bool = False, keep_trace: str | None = None,
             sweep: tuple[float, ...] = ()) -> dict:
    cell, config, traffic = resolve_cell(bench, workload)
    if rehearsal:
        config = {**config, **traffic.get("rehearsal", {})}
    chips = int(cell["chips"])
    if chips > 1:
        os.environ["WEED_EC_MESH_DEVICES"] = str(chips)
    import ops  # after main() has found the program it imports
    need_file, need_disk = ops.room(config, traffic)
    os.makedirs(WORK_ROOT, exist_ok=True)
    check_room(need_file, need_disk, WORK_ROOT)
    work = tempfile.mkdtemp(prefix=workload + ".", dir=WORK_ROOT)
    host = Hosted(work, config["geometry_policy"])
    child = None
    tracing = False
    summary = None
    try:
        desc = host.start(config["collection"])
        status = http_json(
            f"http://{host.volume_url}/admin/ec/mesh_status")
        if rehearsal:
            device = {"platform": "cpu", "kind": "rehearsal", "count": 0}
        else:
            device = require_chip(desc, status, chips)
        log(f"volume server (this process) up; coder {desc}")
        compiles = CompileCounter()
        ctx = {"seed": seed, "seconds": seconds, "config": config,
               "traffic": traffic, "collection": config["collection"],
               "master": host.master_url, "volume": host.volume_url,
               "vdir": host.vdir, "work": work, "control": control,
               "sweep": list(sweep)}
        log(ops.fill_store(host.store, ops.Ctx(ctx)))
        child = Child(ctx, host.child_env)
        ready = child.expect("ready")
        # what the fill and the encode left behind is collected now and
        # not by a full collection somewhere in the window (a third of a
        # second over a million needle-map entries, PR 24)
        gc.collect()
        before = host.counters()
        trace_dir = os.path.join(work, "trace")
        if trace:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing = True
        compiles.armed = True
        setup_s = time.time() - T_PROCESS_START
        child.tell("go")
        child.expect("window_start")
        end = child.expect("window_end")
        compiles.armed = False
        if trace:
            import jax
            jax.profiler.stop_trace()
            tracing = False
        after = host.counters()
        peak = 0 if rehearsal else memory_peak()
        log(f"window {end['window_s']:.3f}s; compiled inside it: "
            f"{compiles.counts}")
        child.tell("verify")
        result = child.expect("result")
        child.proc.wait(30)
        if trace:
            raw = reduce.extract(reduce.find_xplane(trace_dir))
            if keep_trace:
                import gzip
                with gzip.open(keep_trace, "wt") as f:
                    json.dump(raw, f)
            summary = reduce.summarize(raw)
    finally:
        if tracing:
            import jax
            jax.profiler.stop_trace()
        if child is not None:
            child.stop()
        host.stop()
        shutil.rmtree(work, ignore_errors=True)

    metrics = dict(result["metrics"])
    metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    wanted = [m["name"] for m in bench["end_to_end"]
              if workload in m.get("workloads", [workload])]
    e2e = {name: metrics[name] for name in wanted}
    device["memory_peak_bytes"] = peak
    out = {"correct": bool(result["correct"]),
           "attempted": result["attempted"], "failed": result["failed"],
           "metrics": e2e, "device": device,
           "compiled_in_window": compiles.counts,
           "setup": ready.get("setup", {}), "facts": result["facts"],
           # the program's own per-stage gauges: overwritten per run and
           # overlapping, so a diagnosis and never a metric
           "feed_gauges": {k: v for k, v in after.items()
                           if "feed_stage_seconds" in k
                           or "feed_batch_bytes" in k
                           or "feed_queue_depth" in k}}
    if trace:
        run = {"trace": summary, "facts": result["facts"],
               "counters": {k: after.get(k, 0.0) - before.get(k, 0.0)
                            for k in after},
               "config": config, "device_kind": device["kind"],
               "chips": chips}
        layer = {}
        for m in bench["per_layer"]:
            if workload not in m.get("workloads", [workload]):
                continue
            value = load_module("layer_metrics", m["name"]).read(run)
            if value is not None:
                layer[m["name"]] = {"value": value, "unit": m["unit"]}
        out["end_to_end"] = e2e
        out["metrics"] = layer
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        out["breakdown"] = reduce.breakdown(summary)
    out["checks"] = result["checks"]
    for c in result["checks"]:
        print(f"check {c['name']}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny volumes on the host coder, exit 3, no result")
    ap.add_argument("--keep-trace", metavar="FILE.json.gz",
                    help="with --trace 1: also keep what reduce.extract "
                         "read from the trace (how the recorded trace "
                         "under tests/ was made)")
    ap.add_argument("--sweep", metavar="RATES", default="",
                    help="comma-separated offered rates: a window at each "
                         "before the cell's own, after one set-up, logged "
                         "on stderr (how the mix's rate was found)")
    ap.add_argument("--control", action="store_true",
                    help="verify the driver's control in the program's "
                         "place: has to end correct=false")
    args = ap.parse_args()
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        import seaweedfs_tpu  # noqa: F401
    except ImportError:
        sys.path.insert(0, ROOT)
        try:
            import seaweedfs_tpu  # noqa: F401
        except ImportError:
            raise SystemExit("the program under test (seaweedfs_tpu) is "
                             "not in this checkout")
    shutil.rmtree(WORK_ROOT, ignore_errors=True)
    # a polite kill still runs the clean-up in run_cell's `finally`
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), args.rehearsal, args.control,
                   args.keep_trace,
                   tuple(float(x) for x in args.sweep.split(",") if x))
    if args.rehearsal:
        log("rehearsal on the host coder, not a chip result: "
            + json.dumps(out))
        raise SystemExit(3)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
