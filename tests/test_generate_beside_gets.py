"""A warm-down beside reads on one volume server: `/admin/ec/generate`
runs again and again over a sealed volume, tagged background as the
master's maintenance calls are, while the same server answers checked
GETs on an EC volume that lacks 3 data + 1 parity shards.

Every GET is right, every pass leaves the fourteen shard files and the
`.ecx` of the synchronous reference (`write_ec_files`), the source is
untouched, and what the passes cost is on `/metrics`: the enclosing stage
`ec.generate` once a pass and `ec_encode_input_bytes_total` /
`ec_encode_batches_total` by what the pipeline handed to the coder.
"""

import asyncio
import hashlib
import json
import os
import re
import shutil
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from seaweedfs_tpu import ec, observe
from seaweedfs_tpu.ec import pipeline
from seaweedfs_tpu.ec.geometry import Geometry
from seaweedfs_tpu.server.volume_server import run_volume_server
from seaweedfs_tpu.storage.file_id import FileId
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.utils import metrics as metrics_mod

GEOMETRY = Geometry(10, 4, large_block_size=64 * 1024,
                    small_block_size=4 * 1024)
COOKIE = 0x77
N_READ = 60      # needles of the EC volume that is read
N_SEALED = 300   # needles of the sealed volume that is generated
PASSES = 5
LOST = [0, 3, 7, 12]  # 3 data + 1 parity
EXTS = [ec.to_ext(i) for i in range(14)] + [".ecx"]


def payload(i: int, vid: int) -> bytes:
    return bytes([(i * 7 + vid) % 251]) * (600 + 13 * (i % 31))


def file_hashes(base: str) -> dict[str, str]:
    out = {}
    for ext in EXTS:
        with open(base + ext, "rb") as f:
            out[ext] = hashlib.blake2b(f.read(), digest_size=16).hexdigest()
    return out


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Served:
    def __init__(self, tmpdir: str):
        self.dir = os.path.join(tmpdir, "v")
        os.makedirs(self.dir)
        self.store = Store([self.dir], coder_name="numpy",
                           geometry=GEOMETRY)
        for vid, n in ((1, N_READ), (2, N_SEALED)):
            self.store.add_volume(vid)
            for i in range(n):
                self.store.write_needle(vid, Needle(
                    id=i + 1, cookie=COOKIE, data=payload(i, vid)))
        self.store.ec_generate(1)
        self.store.ec_mount(1, "", list(range(14)))
        self.store.delete_volume(1)
        for sid in LOST:
            self.store.find_ec_volume(1).delete_shard(sid)
        # the sealed volume, as the maintenance script finds it; a copy
        # of its source is what the reference encodes
        sealed = self.store.find_volume(2)
        sealed.read_only = True
        sealed.sync()
        self.base = sealed.base_file_name()
        self.ref = os.path.join(tmpdir, "ref", "2")
        os.makedirs(os.path.dirname(self.ref))
        for ext in (".dat", ".idx"):
            shutil.copy(self.base + ext, self.ref + ext)
        ec.write_ec_files(self.ref, ec.get_coder("numpy", 10, 4), GEOMETRY)
        ec.write_sorted_ecx_from_idx(self.ref)
        self.port = free_port()
        self.loop = asyncio.new_event_loop()
        self.runner = None
        ready = threading.Event()

        def run() -> None:
            asyncio.set_event_loop(self.loop)
            self.runner = self.loop.run_until_complete(run_volume_server(
                "127.0.0.1", self.port, self.store,
                master_url="127.0.0.1:1",  # no master: heartbeats warn
                pulse_seconds=3600))
            ready.set()
            self.loop.run_forever()

        self.thread = threading.Thread(target=run, daemon=True)
        self.thread.start()
        assert ready.wait(30), "the volume server did not start"

    def get(self, path: str) -> bytes:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{self.port}/{path}", timeout=60) as r:
            return r.read()

    def generate(self) -> dict:
        """One pass, tagged background; a shed (503: foreground has the
        server) is asked again, as the script's next round would."""
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}/admin/ec/generate",
            data=json.dumps({"volume_id": 2}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Seaweed-Priority": "bg"})
        deadline = time.time() + 60
        while True:
            try:
                with urllib.request.urlopen(req, timeout=60) as r:
                    return json.loads(r.read())
            except urllib.error.HTTPError as e:
                with e:
                    if e.code != 503 or time.time() > deadline:
                        raise
                time.sleep(0.05)

    def sample(self, name: str) -> float | None:
        m = re.search(rf"^{re.escape(name)} (\S+)$",
                      self.get("metrics").decode(), re.M)
        return float(m.group(1)) if m else None

    def stop(self) -> None:
        asyncio.run_coroutine_threadsafe(self.runner.cleanup(),
                                         self.loop).result(10)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(5)
        self.store.close()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    s = Served(str(tmp_path_factory.mktemp("maint")))
    yield s
    s.stop()


BYTES = "seaweedfs_tpu_ec_encode_input_bytes_total"
BATCHES = "seaweedfs_tpu_ec_encode_batches_total"
PASS_COUNT = 'seaweedfs_tpu_ec_stage_seconds_count{stage="ec.generate"}'


def test_generates_run_beside_checked_degraded_gets(served):
    source = {ext: open(served.base + ext, "rb").read()
              for ext in (".dat", ".idx")}
    # born at 0 (the registry is the process's: the fixture's own encode
    # of volume 1 has counted already, so deltas from here)
    before = {name: served.sample(name) for name in (BYTES, BATCHES)}
    assert None not in before.values()
    passes_before = served.sample(PASS_COUNT) or 0.0
    want = file_hashes(served.ref)
    hashes: list[dict] = []
    errors: list[BaseException] = []

    def maint() -> None:
        try:
            for _ in range(PASSES):
                out = served.generate()
                assert out["ok"] and out["shards"] == list(range(14))
                hashes.append(file_hashes(served.base))
        except BaseException as e:
            errors.append(e)

    script = threading.Thread(target=maint, daemon=True)
    script.start()
    gets = 0
    ev = served.store.find_ec_volume(1)
    degraded = 0
    while script.is_alive() or gets < 2 * N_READ:
        i = gets % N_READ
        fid = str(FileId(1, i + 1, COOKIE))
        assert served.get(fid) == payload(i, 1), fid
        shards = {iv.to_shard_id_and_offset(GEOMETRY)[0]
                  for iv in ev.locate(i + 1)[2]}
        degraded += bool(shards & set(LOST))
        gets += 1
    script.join(120)
    assert not script.is_alive() and not errors, errors
    assert degraded > 0 and gets >= 2 * N_READ

    assert hashes == [want] * PASSES
    shard_bytes = os.path.getsize(served.base + ec.to_ext(0))
    assert served.sample(BYTES) - before[BYTES] \
        == PASSES * GEOMETRY.data_shards * shard_bytes
    assert served.sample(BATCHES) - before[BATCHES] >= PASSES
    assert served.sample(PASS_COUNT) - passes_before == PASSES
    for ext, data in source.items():
        with open(served.base + ext, "rb") as f:
            assert f.read() == data, ext
    # still a plain, sealed volume of this server: nothing was mounted
    assert served.store.find_volume(2).read_only
    assert served.store.find_ec_volume(2) is None


def test_a_second_pass_records_the_digests_of_what_it_wrote(tmp_path):
    """`stamp_shard_digests` is merge-only: a stamped shard keeps its
    value, so that a rotted file cannot launder itself into the record.
    A generate over shard files that exist is no stamp: it truncates and
    rewrites every file and commits a fresh marker whose digests are the
    sums of the rows it wrote."""
    store = Store([str(tmp_path)], coder_name="numpy", geometry=GEOMETRY)
    try:
        store.add_volume(3)
        for i in range(N_READ):
            store.write_needle(3, Needle(id=i + 1, cookie=COOKIE,
                                         data=payload(i, 3)))
        store.ec_generate(3)
        base = store.find_volume(3).base_file_name()
        first = pipeline.read_stamped_digests(base)
        true = {i: int(d) for i, d in enumerate(
            pipeline.shard_file_digest(base, range(14)))}
        assert first == true
        good = open(base + ec.to_ext(2), "rb").read()

        # a shard rots and the record goes stale: the merge keeps both
        with open(base + ec.to_ext(2), "r+b") as f:
            f.write(bytes(len(good[:64])))
        with open(base + ".ecm") as f:
            meta = json.load(f)
        meta["shard_digests"]["5"] = (true[5] + 1) & 0xFFFFFFFF
        with open(base + ".ecm", "w") as f:
            json.dump(meta, f)
        kept = pipeline.stamp_shard_digests(base, GEOMETRY)
        assert kept[5] == (true[5] + 1) & 0xFFFFFFFF and kept[2] == true[2]

        recomputes = metrics_mod.shared("ec").value(
            "ec_digest_host_recompute")
        observe.reset()
        store.ec_generate(3)
        assert pipeline.read_stamped_digests(base) == true
        assert open(base + ec.to_ext(2), "rb").read() == good
        # from the rows as they streamed: the host read no file back
        assert metrics_mod.shared("ec").value(
            "ec_digest_host_recompute") == recomputes
        names = [s["name"] for s in observe.spans()]
        assert names.count("ec.generate") == 1
        assert names.index("ec.seal") < names.index("ec.stamp") \
            < names.index("ec.generate")
    finally:
        store.close()
