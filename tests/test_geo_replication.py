"""Geo plane chaos suite: cluster-to-cluster replication, S3
versioning, replica failover.

Two real in-process clusters (each: master + volume server + filer).
The replica cluster's filer uses a leveldb store in a fixed directory
and a fixed port, so "kill the replica mid-replication and restart it"
is a real process-shaped restart: same address, same durable store,
fresh everything else.
"""

from __future__ import annotations

import json
import time
import urllib.error
import urllib.parse
import urllib.request

import pytest

from seaweedfs_tpu import faults
from seaweedfs_tpu.geo import GeoConfig
from seaweedfs_tpu.geo import rules as geo_rules

from cluster_util import Cluster, free_port


# ---------------------------------------------------------------- helpers

def filer_put(filer: str, path: str, data: bytes) -> None:
    req = urllib.request.Request(
        f"http://{filer}{urllib.parse.quote(path)}", data=data,
        method="PUT",
        headers={"Content-Type": "application/octet-stream"})
    urllib.request.urlopen(req, timeout=30).close()


def filer_get(filer: str, path: str):
    try:
        with urllib.request.urlopen(
                f"http://{filer}{urllib.parse.quote(path)}",
                timeout=30) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, b""
    except OSError:
        return -1, b""


def meta(filer: str, op: str, body: dict) -> dict:
    req = urllib.request.Request(
        f"http://{filer}/__meta__/{op}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.load(r)


def meta_lookup(filer: str, path: str):
    try:
        with urllib.request.urlopen(
                f"http://{filer}/__meta__/lookup?"
                + urllib.parse.urlencode({"path": path}),
                timeout=30) as r:
            return json.load(r)
    except (urllib.error.HTTPError, OSError):
        return None


def make_bucket(filer: str, name: str, rule: dict | None = None) -> None:
    extended = {}
    if rule is not None:
        extended[geo_rules.BUCKET_ATTR] = geo_rules.rules_to_json([rule])
    meta(filer, "create_entry", {"entry": {
        "path": f"/buckets/{name}",
        "attr": {"mode": 0o40770, "mtime": time.time(),
                 "crtime": time.time()},
        "chunks": [], "extended": extended}})


def wait_until(fn, timeout: float = 30.0, interval: float = 0.1,
               what: str = "condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        out = fn()
        if out:
            return out
        time.sleep(interval)
    raise TimeoutError(f"timed out waiting for {what}")


# ---------------------------------------------------------------- fixture

class GeoPair:
    """Primary + replica cluster, replica filer restartable in place."""

    def __init__(self, tmpdir: str):
        self.primary = Cluster(n_volume_servers=1)
        self.replica = Cluster(n_volume_servers=1)
        self.src = self.primary.add_filer()
        self.replica_store = {"path": f"{tmpdir}/replica.ldb"}
        self.replica_port = free_port()
        self.dst = None
        self._dst_runner = None
        self.start_replica_filer()

    def start_replica_filer(self):
        self.dst = self.replica.add_filer(
            store_name="leveldb", store_kwargs=dict(self.replica_store),
            port=self.replica_port)
        self._dst_runner = self.replica.runners[-1]
        return self.dst

    def kill_replica_filer(self):
        runner = self._dst_runner

        async def halt():
            await runner.cleanup()

        self.replica.call(halt())
        self.replica.runners.remove(runner)
        self._dst_runner = None

    def geo_daemon(self, **cfg_kwargs):
        """Configure + return the primary master's geo daemon (the real
        one master boots; tests drive pass_once explicitly)."""
        master = self.primary.master
        cfg_kwargs.setdefault("filer", self.src.url)
        cfg_kwargs.setdefault("interval", 0.5)
        cfg_kwargs.setdefault("appliers", 2)
        master.geo.cfg = GeoConfig(**cfg_kwargs)
        return master.geo

    def run_geo_pass(self) -> dict:
        return self.primary.call(self.primary.master.geo.pass_once())

    def stop_geo(self) -> None:
        self.primary.call(self.primary.master.geo.aclose())

    def shutdown(self):
        try:
            self.stop_geo()
        except Exception:
            pass
        self.primary.shutdown()
        self.replica.shutdown()


@pytest.fixture(scope="module")
def pair(tmp_path_factory):
    p = GeoPair(str(tmp_path_factory.mktemp("geo")))
    yield p
    p.shutdown()


def _rule(pair: GeoPair, dest_bucket: str, prefix: str = "") -> dict:
    return {"id": "r1", "status": "Enabled", "prefix": prefix,
            "dest_bucket": dest_bucket, "endpoint": pair.dst.url}


# ---------------------------------------------------------------- tests

def test_replicates_and_survives_replica_kill(pair):
    """The headline chaos drill: backfill + live tail, kill the replica
    filer mid-replication, restart it, converge byte-identical with
    zero loss, zero poison, and bounded re-apply."""
    bucket = "geo"
    payload = {f"k{i:03d}": f"geo payload {i}".encode() * 20
               for i in range(10)}
    make_bucket(pair.src.url, bucket, rule=_rule(pair, bucket))
    make_bucket(pair.dst.url, bucket)
    # pre-rule objects: the job must BACKFILL these
    for k in list(payload)[:5]:
        filer_put(pair.src.url, f"/buckets/{bucket}/{k}", payload[k])

    daemon = pair.geo_daemon(max_event_retries=10)
    out = pair.run_geo_pass()
    assert bucket in out["started"]

    def replicated(keys):
        def check():
            return all(filer_get(pair.dst.url,
                                 f"/buckets/{bucket}/{k}")[0] == 200
                       for k in keys)
        return check

    wait_until(replicated(list(payload)[:5]), timeout=30,
               what="backfill of 5 pre-rule objects")

    # live tail: write more, kill the replica filer mid-stream,
    # keep writing into the outage, restart, converge
    for k in list(payload)[5:7]:
        filer_put(pair.src.url, f"/buckets/{bucket}/{k}", payload[k])
    wait_until(replicated(list(payload)[5:7]), timeout=30,
               what="live tail of 2 objects")

    pair.kill_replica_filer()
    for k in list(payload)[7:]:
        filer_put(pair.src.url, f"/buckets/{bucket}/{k}", payload[k])
    # give the job time to hit the dead replica and enter reconnect
    time.sleep(1.0)
    pair.start_replica_filer()

    wait_until(replicated(list(payload)), timeout=40,
               what="convergence after replica restart")
    # byte-identical everywhere, zero loss
    for k, want in payload.items():
        st, got = filer_get(pair.dst.url, f"/buckets/{bucket}/{k}")
        assert st == 200 and got == want, k
    job = daemon.jobs[bucket]
    s = job.status()
    assert s["poisoned"] == 0
    # bounded re-apply: every apply beyond one-per-mutation is a replay
    # of the in-flight window after a teardown — bounded by the pool's
    # queue budget, not by history size
    mutations = len(payload)
    window = daemon.cfg.appliers * daemon.cfg.queue_depth
    assert s["applied"] + s["backfilled"] <= mutations + window + 5
    # offset is durable: it lives on the source filer, not in memory
    assert meta_lookup(pair.src.url, job._offset_path()) is not None
    pair.stop_geo()


def test_injected_apply_fault_recovers_without_loss(pair):
    """A transient geo.apply fault (count-budgeted error) tears the
    stream down and the retry-from-offset path re-delivers: zero loss,
    zero poison."""
    bucket = "geofault"
    make_bucket(pair.src.url, bucket, rule=_rule(pair, bucket))
    make_bucket(pair.dst.url, bucket)
    pair.geo_daemon(max_event_retries=10)
    pair.run_geo_pass()
    faults.set_fault("geo.apply", "error", count=2)
    try:
        for i in range(6):
            filer_put(pair.src.url, f"/buckets/{bucket}/f{i}",
                      f"fault body {i}".encode())
        wait_until(
            lambda: all(
                filer_get(pair.dst.url, f"/buckets/{bucket}/f{i}")[0]
                == 200 for i in range(6)),
            timeout=30, what="convergence through injected faults")
    finally:
        faults.clear("geo.apply")
    job = pair.primary.master.geo.jobs[bucket]
    assert job.status()["poisoned"] == 0
    pair.stop_geo()


def _serve_s3(cluster: Cluster, filer_url: str, **kwargs) -> str:
    from seaweedfs_tpu.s3.s3_server import S3Server
    port = free_port()
    s3 = S3Server(filer_url, url=f"127.0.0.1:{port}", **kwargs)
    cluster.serve(s3.app, port)
    return f"127.0.0.1:{port}"


def _s3_req(addr: str, method: str, path: str, data: bytes = None,
            headers: dict | None = None):
    req = urllib.request.Request(f"http://{addr}{path}", data=data,
                                 method=method,
                                 headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def test_s3_versioning_e2e_and_replicated_history(pair):
    """Overwrite -> both versions listable and GET-able; delete ->
    marker; the replicated cluster shows the same version history."""
    bucket = "vbuck"
    s3 = _serve_s3(pair.primary, pair.src.url)
    assert _s3_req(s3, "PUT", f"/{bucket}")[0] == 200
    # replication rule rides the same bucket entry (set via the S3 API)
    rule_xml = (
        "<ReplicationConfiguration><Rule><Status>Enabled</Status>"
        f"<Destination><Bucket>arn:aws:s3:::{bucket}</Bucket>"
        f"<Endpoint>{pair.dst.url}</Endpoint></Destination>"
        "</Rule></ReplicationConfiguration>").encode()
    assert _s3_req(s3, "PUT", f"/{bucket}?replication",
                   rule_xml)[0] == 200
    st, _, body = _s3_req(s3, "GET", f"/{bucket}?replication")
    assert st == 200 and b"Endpoint" in body
    # enable versioning
    ver_xml = (b"<VersioningConfiguration>"
               b"<Status>Enabled</Status></VersioningConfiguration>")
    assert _s3_req(s3, "PUT", f"/{bucket}?versioning", ver_xml)[0] == 200
    st, _, body = _s3_req(s3, "GET", f"/{bucket}?versioning")
    assert st == 200 and b"Enabled" in body

    # two versions of one key
    st, h1, _ = _s3_req(s3, "PUT", f"/{bucket}/doc", b"version ONE")
    assert st == 200
    v1 = h1["x-amz-version-id"]
    st, h2, _ = _s3_req(s3, "PUT", f"/{bucket}/doc", b"version TWO!")
    v2 = h2["x-amz-version-id"]
    assert v1 != v2

    st, h, body = _s3_req(s3, "GET", f"/{bucket}/doc")
    assert st == 200 and body == b"version TWO!" \
        and h["x-amz-version-id"] == v2
    st, _, body = _s3_req(s3, "GET", f"/{bucket}/doc?versionId={v1}")
    assert st == 200 and body == b"version ONE"
    st, _, body = _s3_req(s3, "GET", f"/{bucket}/doc?versionId={v2}")
    assert st == 200 and body == b"version TWO!"

    # both versions listable, newest latest
    st, _, body = _s3_req(s3, "GET", f"/{bucket}?versions")
    text = body.decode()
    assert v1 in text and v2 in text
    assert text.index(v2) < text.index(v1)
    assert "<IsLatest>true</IsLatest>" in text

    # the .versions plumbing must not leak into plain listings
    st, _, body = _s3_req(s3, "GET", f"/{bucket}")
    assert b".versions" not in body

    # delete -> marker; old versions survive
    st, h, _ = _s3_req(s3, "DELETE", f"/{bucket}/doc")
    assert st == 204 and h["x-amz-delete-marker"] == "true"
    marker = h["x-amz-version-id"]
    assert _s3_req(s3, "GET", f"/{bucket}/doc")[0] == 404
    st, _, body = _s3_req(s3, "GET", f"/{bucket}/doc?versionId={v2}")
    assert st == 200 and body == b"version TWO!"
    st, _, body = _s3_req(s3, "GET", f"/{bucket}?versions")
    assert b"DeleteMarker" in body and marker.encode() in body

    # removing the delete marker un-deletes: newest real version is
    # promoted back to the object path
    st, _, _ = _s3_req(s3, "DELETE",
                       f"/{bucket}/doc?versionId={marker}")
    assert st == 204
    st, _, body = _s3_req(s3, "GET", f"/{bucket}/doc")
    assert st == 200 and body == b"version TWO!"

    # CopyObject onto a versioned key archives the replaced version
    assert _s3_req(s3, "PUT", f"/{bucket}/src", b"copy source")[0] == 200
    st, h, _ = _s3_req(s3, "PUT", f"/{bucket}/doc2", b"doc2 v1")
    d2v1 = h["x-amz-version-id"]
    st, h, _ = _s3_req(s3, "PUT", f"/{bucket}/doc2", None,
                       {"x-amz-copy-source": f"/{bucket}/src"})
    assert st == 200
    copy_vid = h["x-amz-version-id"]
    assert copy_vid != d2v1
    st, _, body = _s3_req(s3, "GET", f"/{bucket}/doc2")
    assert st == 200 and body == b"copy source"
    st, _, body = _s3_req(s3, "GET",
                          f"/{bucket}/doc2?versionId={d2v1}")
    assert st == 200 and body == b"doc2 v1"

    # DeleteObjects (batch) lays a marker instead of freeing bytes
    st, _, body = _s3_req(
        s3, "POST", f"/{bucket}?delete",
        b"<Delete><Object><Key>doc2</Key></Object></Delete>")
    assert st == 200 and b"DeleteMarker" in body
    assert _s3_req(s3, "GET", f"/{bucket}/doc2")[0] == 404
    st, _, body = _s3_req(s3, "GET",
                          f"/{bucket}/doc2?versionId={copy_vid}")
    assert st == 200 and body == b"copy source"

    # replicate and compare version history on the replica cluster
    make_bucket(pair.dst.url, bucket)
    pair.geo_daemon()
    pair.run_geo_pass()
    s3_replica = _serve_s3(pair.replica, pair.dst.url)

    def replica_history_matches():
        st, _, body = _s3_req(s3_replica, "GET", f"/{bucket}?versions")
        if st != 200:
            return False
        text = body.decode()
        return v1 in text and v2 in text
    wait_until(replica_history_matches, timeout=30,
               what="replicated version history")
    st, _, body = _s3_req(s3_replica, "GET",
                          f"/{bucket}/doc?versionId={v1}")
    assert st == 200 and body == b"version ONE"
    st, _, body = _s3_req(s3_replica, "GET", f"/{bucket}/doc")
    assert st == 200 and body == b"version TWO!"
    pair.stop_geo()


def test_active_passive_failover_serves_reads(pair):
    """Primary filer dies -> S3 GETs served from the replica cluster,
    marked stale-ok; the primary's breaker opens and later reads fail
    fast into the replica path."""
    from seaweedfs_tpu.utils.retry import shared_breaker
    bucket = "fob"
    doomed = pair.primary.add_filer()
    doomed_runner = pair.primary.runners[-1]
    make_bucket(doomed.url, bucket,
                rule={"id": "r", "status": "Enabled", "prefix": "",
                      "dest_bucket": bucket, "endpoint": pair.dst.url})
    make_bucket(pair.dst.url, bucket)
    filer_put(doomed.url, f"/buckets/{bucket}/obj", b"survives the DR")
    pair.geo_daemon(filer=doomed.url)
    pair.run_geo_pass()
    wait_until(lambda: filer_get(pair.dst.url,
                                 f"/buckets/{bucket}/obj")[0] == 200,
               timeout=30, what="failover object replication")
    pair.stop_geo()

    s3 = _serve_s3(pair.primary, doomed.url,
                   replica_filer_url=pair.dst.url)
    # healthy primary: no stale marker
    st, h, body = _s3_req(s3, "GET", f"/{bucket}/obj")
    assert st == 200 and body == b"survives the DR"
    assert "X-Seaweed-Stale-Ok" not in h

    async def halt():
        await doomed_runner.cleanup()
    pair.primary.call(halt())
    pair.primary.runners.remove(doomed_runner)

    for _ in range(6):  # enough failures to open the primary's breaker
        st, h, body = _s3_req(s3, "GET", f"/{bucket}/obj")
        assert st == 200 and body == b"survives the DR"
        assert h.get("X-Seaweed-Stale-Ok") == "1"
    assert shared_breaker().is_open(doomed.url)
    # breaker open: the read is still served (fast) from the replica
    st, h, _ = _s3_req(s3, "GET", f"/{bucket}/obj")
    assert st == 200 and h.get("X-Seaweed-Stale-Ok") == "1"


def test_active_active_pair_converges_without_looping(pair):
    """Both clusters replicate the same bucket at each other: writes on
    either side land on both, and signature-based loop prevention stops
    the ping-pong — applied counts stabilize instead of growing
    forever."""
    bucket = "geoaa"
    make_bucket(pair.src.url, bucket,
                rule={"id": "a2b", "status": "Enabled", "prefix": "",
                      "dest_bucket": bucket, "endpoint": pair.dst.url})
    make_bucket(pair.dst.url, bucket,
                rule={"id": "b2a", "status": "Enabled", "prefix": "",
                      "dest_bucket": bucket, "endpoint": pair.src.url})
    pair.geo_daemon()
    pair.run_geo_pass()
    # the replica cluster's own daemon drives the reverse direction
    rmaster = pair.replica.master
    rmaster.geo.cfg = GeoConfig(filer=pair.dst.url, interval=0.5,
                                appliers=2)
    pair.replica.call(rmaster.geo.pass_once())
    try:
        filer_put(pair.src.url, f"/buckets/{bucket}/from-a", b"A wrote")
        filer_put(pair.dst.url, f"/buckets/{bucket}/from-b", b"B wrote")
        for filer in (pair.src.url, pair.dst.url):
            wait_until(
                lambda f=filer: (
                    filer_get(f, f"/buckets/{bucket}/from-a")
                    == (200, b"A wrote")
                    and filer_get(f, f"/buckets/{bucket}/from-b")
                    == (200, b"B wrote")),
                timeout=30, what=f"active/active convergence on {filer}")
        # loop prevention: applied counts must STABILIZE — a replay
        # ping-pong would keep both sides' counters climbing
        jobs = (pair.primary.master.geo.jobs[bucket],
                rmaster.geo.jobs[bucket])
        # a job counts an event a moment after the object it wrote can
        # be read: let each side count its one before the snapshot
        wait_until(lambda: all(j.status()["applied"] >= 1 for j in jobs),
                   timeout=30, what="both sides counted their apply")
        counts = [j.status()["applied"] for j in jobs]
        time.sleep(2.0)
        assert [j.status()["applied"] for j in jobs] == counts
        assert all(j.status()["poisoned"] == 0 for j in jobs)
    finally:
        pair.replica.call(rmaster.geo.aclose())
        pair.stop_geo()


def test_prefix_rule_bounds_replication_and_backfill(pair):
    """A Prefix=logs/ rule replicates only keys under logs/ — not a
    file merely NAMED 'log', and not out-of-prefix keys — in both the
    backfill and the live tail."""
    bucket = "geopfx"
    make_bucket(pair.src.url, bucket,
                rule=_rule(pair, bucket, prefix="logs/"))
    make_bucket(pair.dst.url, bucket)
    # pre-rule content: in-prefix, out-of-prefix, and the name-trap
    filer_put(pair.src.url, f"/buckets/{bucket}/logs/in1", b"in one")
    filer_put(pair.src.url, f"/buckets/{bucket}/other/out1", b"out")
    filer_put(pair.src.url, f"/buckets/{bucket}/log", b"name trap")
    pair.geo_daemon()
    pair.run_geo_pass()
    wait_until(lambda: filer_get(pair.dst.url,
                                 f"/buckets/{bucket}/logs/in1")[0]
               == 200, timeout=30, what="prefix backfill")
    # live tail respects the prefix too
    filer_put(pair.src.url, f"/buckets/{bucket}/logs/in2", b"in two")
    filer_put(pair.src.url, f"/buckets/{bucket}/other/out2", b"out2")
    wait_until(lambda: filer_get(pair.dst.url,
                                 f"/buckets/{bucket}/logs/in2")[0]
               == 200, timeout=30, what="prefix live tail")
    assert filer_get(pair.dst.url,
                     f"/buckets/{bucket}/other/out1")[0] == 404
    assert filer_get(pair.dst.url,
                     f"/buckets/{bucket}/other/out2")[0] == 404
    assert filer_get(pair.dst.url, f"/buckets/{bucket}/log")[0] == 404
    pair.stop_geo()


def test_geo_shell_commands(pair):
    """geo.status / geo.sync drive the master's /geo endpoints."""
    from seaweedfs_tpu.client import Client
    from seaweedfs_tpu.shell.commands import (CommandEnv, _register_all,
                                              run_command)
    _register_all()
    bucket = "geoshell"
    make_bucket(pair.src.url, bucket, rule=_rule(pair, bucket))
    make_bucket(pair.dst.url, bucket)
    pair.geo_daemon()
    env = CommandEnv(Client(f"127.0.0.1:{pair.primary.master_port}"),
                     filer=pair.src.url)
    out = run_command(env, "geo.sync")
    assert out["ok"] and bucket in out["started"]
    st = run_command(env, "geo.status")
    assert st["enabled"] and bucket in st["jobs"]
    st = run_command(env, ["geo.status", "-bucket", bucket])
    assert list(st["jobs"]) == [bucket]
    pair.stop_geo()


def test_deletes_and_overwrites_replicate(pair):
    bucket = "geomut"
    make_bucket(pair.src.url, bucket, rule=_rule(pair, bucket))
    make_bucket(pair.dst.url, bucket)
    pair.geo_daemon()
    pair.run_geo_pass()
    filer_put(pair.src.url, f"/buckets/{bucket}/a", b"v1")
    wait_until(lambda: filer_get(pair.dst.url,
                                 f"/buckets/{bucket}/a")[0] == 200,
               timeout=30, what="create replication")
    filer_put(pair.src.url, f"/buckets/{bucket}/a", b"v2-overwritten")
    wait_until(lambda: filer_get(pair.dst.url,
                                 f"/buckets/{bucket}/a")[1]
               == b"v2-overwritten", timeout=30,
               what="overwrite replication")
    meta(pair.src.url, "delete", {"path": f"/buckets/{bucket}/a"})
    wait_until(lambda: filer_get(pair.dst.url,
                                 f"/buckets/{bucket}/a")[0] == 404,
               timeout=30, what="delete replication")
    pair.stop_geo()
