"""Multi-chip EC fabric (parallel/mesh_coder.py) in the production plane.

The contract under test: a MeshCoder over the 8-device virtual CPU mesh
is byte-identical to the single-chip path at every batch width
(including widths not divisible by the mesh — the padded shard_map
path), mixed-geometry windows stream through `ec_generate_many` on the
mesh unchanged, a mid-encode failure tears the reader pool down without
leaking staging buffers, the encode HLO stays collective-free, and the
master's WEED_EC_ENCODE_WORKERS pool actually bounds + labels repair
concurrency. conftest.py forces --xla_force_host_platform_device_count=8.
"""

import asyncio
import hashlib
import os

import numpy as np
import pytest

from seaweedfs_tpu import ec
from seaweedfs_tpu.ec import feed as feed_mod
from seaweedfs_tpu.ec import governor, pipeline
from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.parallel import MeshCoder, coder as mesh_coder_factory
from seaweedfs_tpu.parallel import mesh_device_count, mesh_status

GEO = ec.Geometry(10, 4, large_block_size=10000, small_block_size=100)
WIDE = ec.Geometry(20, 4, large_block_size=10000, small_block_size=100)


@pytest.fixture(autouse=True)
def fresh_governor():
    governor.reset()
    yield
    governor.reset()


@pytest.fixture(scope="module")
def mesh8():
    return MeshCoder(10, 4, n_devices=8)


def _sha(path: str) -> str:
    return hashlib.sha256(open(path, "rb").read()).hexdigest()


def _write_dat(tmp_path, name: str, size: int, seed: int) -> str:
    rng = np.random.default_rng(seed)
    base = os.path.join(str(tmp_path), name)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
    return base


# ------------------------------------------------------ kernel identity

@pytest.mark.parametrize("width", [8 * 512, 1000, 999, 7, 13])
def test_mesh_encode_matches_single_chip(mesh8, width):
    """Every width — divisible by the mesh or not (the padded path) —
    produces the exact single-chip parity bytes."""
    rng = np.random.default_rng(width)
    data = rng.integers(0, 256, (10, width), dtype=np.uint8)
    got = mesh8.encode(data)
    assert got.shape == (4, width)
    assert np.array_equal(got, gf256.encode_parity(data, 4))


def test_mesh_rebuild_matches(mesh8):
    """Column-sharded survivors reconstruct the exact missing rows on
    each chip's slice (odd width -> padded column slices too)."""
    rng = np.random.default_rng(21)
    data = rng.integers(0, 256, (10, 4999), dtype=np.uint8)
    parity = gf256.encode_parity(data, 4)
    rows = list(data) + list(parity)
    missing = (0, 7, 10, 12)
    present = tuple(i for i in range(14) if i not in missing)[:10]
    survivors = np.stack([rows[i] for i in present])
    out = mesh8.materialize(
        mesh8.rec_apply_async(present, missing)(survivors))
    for got, want_id in zip(out, missing):
        assert np.array_equal(got, rows[want_id]), want_id


def test_mesh_pallas_method_matches_single_chip():
    """method='pallas' keeps the hand-tiled kernel inside the shard_map
    step (interpret mode, asked for here) — the path a TPU host's auto
    coder lifts onto — and stays byte-identical."""
    mc = MeshCoder(10, 4, n_devices=8, method="pallas", interpret=True)
    rng = np.random.default_rng(33)
    data = rng.integers(0, 256, (10, 512), dtype=np.uint8)
    assert np.array_equal(mc.encode(data), gf256.encode_parity(data, 4))


def test_encode_hlo_is_collective_free(mesh8):
    """The property MULTICHIP_r05 proved for the demo kernel, asserted
    for the production coder from the compiled HLO: encode inserts no
    cross-chip collective, so aggregate throughput is linear in mesh
    size on ICI-attached hardware."""
    assert mesh8.encode_is_collective_free()


def test_one_device_request_degenerates_to_jaxcoder(monkeypatch):
    monkeypatch.setenv("WEED_EC_MESH_DEVICES", "1")
    c = mesh_coder_factory(10, 4)
    assert type(c).__name__ == "JaxCoder"
    monkeypatch.setenv("WEED_EC_MESH_DEVICES", "all")
    c = mesh_coder_factory(10, 4)
    assert isinstance(c, MeshCoder) and c.mesh_devices == 8
    assert mesh_device_count() == 8
    monkeypatch.setenv("WEED_EC_MESH_DEVICES", "0")
    assert mesh_device_count() == 0


# --------------------------------------------------- pipeline identity

def test_stream_encode_mesh_byte_identical_odd_batch(tmp_path, mesh8):
    """stream_encode through the mesh at an odd batch width (999 is not
    divisible by 8: every batch takes the padded shard_map path) writes
    the exact striping.write_ec_files bytes."""
    size = 61_007
    ref = _write_dat(tmp_path, "ref_1", size, seed=3)
    ec.write_ec_files(ref, ec.get_coder("numpy", 10, 4), GEO,
                      buffer_size=100)
    base = _write_dat(tmp_path, "mesh_1", size, seed=3)
    pipeline.stream_encode(base, mesh8, GEO, batch_size=999)
    for i in range(14):
        assert _sha(ref + ec.to_ext(i)) == _sha(base + ec.to_ext(i)), i


def test_stream_rebuild_mesh_byte_identical(tmp_path, mesh8):
    size = 47_501
    base = _write_dat(tmp_path, "1", size, seed=5)
    pipeline.stream_encode(base, mesh8, GEO, batch_size=1000)
    golden = {i: _sha(base + ec.to_ext(i)) for i in range(14)}
    victims = [0, 5, 11, 13]
    for v in victims:
        os.remove(base + ec.to_ext(v))
    rebuilt = pipeline.stream_rebuild(base, mesh8, GEO, batch_size=512)
    assert sorted(rebuilt) == victims
    for i in range(14):
        assert _sha(base + ec.to_ext(i)) == golden[i], i


def test_governed_mesh_run_exports_chips(tmp_path, mesh8):
    """A governed (no explicit batch) mesh encode plans with the
    coder's mesh width and exports feed_mesh_devices."""
    base = _write_dat(tmp_path, "1", 20_001, seed=13)
    pipeline.stream_encode(base, mesh8, GEO)
    gov = governor.get()
    assert gov.metrics.value("feed_mesh_devices") == 8


# ------------------------------------------- mixed-geometry mesh window

def test_generate_many_mixed_geometries_on_mesh(tmp_path, monkeypatch):
    """RS(10,4) and RS(20,4) volumes through ONE ec_generate_many window
    on a mesh-enabled store: each geometry group streams through its own
    mesh coder and every shard is byte-identical to the single-chip
    reference writer."""
    import shutil

    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store

    monkeypatch.setenv("WEED_EC_MESH_DEVICES", "8")
    vol_dir = tmp_path / "vols"
    vol_dir.mkdir()
    policy = ec.GeometryPolicy.parse("default=10+4,wide=20+4")
    store = Store([str(vol_dir)], coder_name="auto",
                  geometry_policy=policy)
    assert getattr(store.coder(store.geometry_for("")),
                   "mesh_devices", 1) == 8
    assert getattr(store.coder(store.geometry_for("wide")),
                   "mesh_devices", 1) == 8
    for vid, collection in ((3, ""), (4, "wide")):
        store.add_volume(vid, collection=collection)
        for i in range(3):
            store.write_needle(vid, Needle(id=i + 1, cookie=1,
                                           data=bytes([vid, i]) * 1500))
    refs = {}
    for vid in (3, 4):
        v = store.find_volume(vid)
        v.sync()
        ref = str(tmp_path / f"ref_{vid}")
        shutil.copyfile(v.base_file_name() + ".dat", ref + ".dat")
        refs[vid] = ref
    out = store.ec_generate_many([3, 4])
    assert out[3] == list(range(14))
    assert out[4] == list(range(24))
    for vid, collection in ((3, ""), (4, "wide")):
        g = store.geometry_for(collection)
        ec.write_ec_files(refs[vid],
                          ec.get_coder("numpy", g.data_shards,
                                       g.parity_shards), g)
        base = store.find_volume(vid).base_file_name()
        for sid in range(g.total_shards):
            assert _sha(base + ec.to_ext(sid)) == \
                _sha(refs[vid] + ec.to_ext(sid)), (vid, sid)


def test_store_explicit_backend_never_meshed(tmp_path, monkeypatch):
    """coder_name='numpy' (byte-exact reference in tests) stays numpy
    even with the mesh env set — only auto-selected device backends
    lift onto the mesh."""
    from seaweedfs_tpu.storage.store import Store

    monkeypatch.setenv("WEED_EC_MESH_DEVICES", "8")
    store = Store([str(tmp_path)], coder_name="numpy")
    assert type(store.coder()).__name__ == "NumpyCoder"


# ------------------------------------------------- mid-encode teardown

def test_mid_encode_failure_recycles_staging_and_unblocks_pool(
        tmp_path, monkeypatch, mesh8):
    """A mesh dispatch that dies mid-encode must propagate, join every
    reader-pool thread, and leave zero staging buffers lent out — the
    error path recycles per-device staging instead of stranding the
    pooled feed for the rest of the process."""
    monkeypatch.setenv("WEED_EC_MMAP", "0")  # force pooled staging
    base = _write_dat(tmp_path, "1", 50_001, seed=17)

    feeds: list = []
    real_open = feed_mod.open_feed

    def capture_open(*args, **kwargs):
        kwargs.setdefault("readers", 4)
        src = real_open(*args, **kwargs)
        feeds.append(src)
        return src

    monkeypatch.setattr(pipeline.feed_mod, "open_feed", capture_open)

    class Dying(MeshCoder):
        def __init__(self):
            super().__init__(10, 4, n_devices=8)
            self.calls = 0

        def encode_async(self, data):
            self.calls += 1
            if self.calls >= 2:
                raise RuntimeError("injected mid-encode death")
            return super().encode_async(data)

    with pytest.raises(RuntimeError, match="injected"):
        pipeline.stream_encode(base, Dying(), GEO, batch_size=999)
    assert len(feeds) == 1
    src = feeds[0]
    with src._lent_lock:
        assert not src._lent  # every staging buffer recycled
    assert src._rpool is None  # reader pool joined and dropped
    assert src.pool._closed.is_set()


# --------------------------------------- encode worker pool (master)

def test_encode_workers_env_sizes_repair_pool(monkeypatch):
    from seaweedfs_tpu.server.master import MasterServer

    monkeypatch.setenv("WEED_EC_ENCODE_WORKERS", "5")
    master = MasterServer()
    assert master.repair_concurrency == 5
    assert master._repair_sem._value == 5
    assert sorted(master._repair_worker_free) == [0, 1, 2, 3, 4]
    monkeypatch.delenv("WEED_EC_ENCODE_WORKERS")
    master = MasterServer(repair_concurrency=3)
    assert master.repair_concurrency == 3


def test_repair_pool_checks_out_numbered_workers(monkeypatch):
    """While a repair holds the semaphore it owns a numbered worker slot
    (the per-worker assignment the daemon logs + gauges), returned on
    completion even when the repair fails."""
    from seaweedfs_tpu.server.master import MasterServer

    monkeypatch.setenv("WEED_EC_ENCODE_WORKERS", "2")
    master = MasterServer()

    async def scenario():
        seen = []
        gate = asyncio.Event()

        async def hold():
            seen.append(len(master._repair_worker_free))
            await gate.wait()
            return True

        async def boom():
            raise RuntimeError("repair dies")

        t1 = asyncio.create_task(master._run_repair(("ec", 1), hold))
        t2 = asyncio.create_task(master._run_repair(("ec", 2), hold))
        await asyncio.sleep(0.05)
        assert master._repair_worker_free == []  # both slots busy
        assert master.metrics.value("repair_workers_busy") == 2
        gate.set()
        await asyncio.gather(t1, t2)
        assert sorted(master._repair_worker_free) == [0, 1]
        await master._run_repair(("ec", 3), boom)  # failure path
        assert sorted(master._repair_worker_free) == [0, 1]
        assert master.metrics.value("repair_workers_busy") == 0

    asyncio.run(scenario())


# -------------------------------------------------------- status faces

def test_mesh_status_reports_chips_after_staging(mesh8):
    mesh8.materialize(mesh8.encode_async(np.zeros((10, 800), dtype=np.uint8)))
    st = mesh_status()
    assert st["mesh_devices"] == 8
    assert len(st["chips"]) == 8
    assert all("staged_bytes" in c for c in st["chips"].values())


def test_weedlint_rules_cover_parallel_tree():
    """The mesh fabric is production code: the async/resource/metric
    rules named in the re-anchor must analyze seaweedfs_tpu/parallel/
    like any other plane."""
    from seaweedfs_tpu.analysis.engine import registry

    rules = registry()
    for name in ("resource-leak", "ctx-propagation",
                 "async-blocking-call", "metric-label-registry"):
        assert rules[name].applies_to(
            "seaweedfs_tpu/parallel/mesh_coder.py"), name
        assert rules[name].applies_to(
            "seaweedfs_tpu/parallel/sharded.py"), name
