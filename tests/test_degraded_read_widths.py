"""Degraded reads above one tile: every width a read can be dispatched at,
against the plain reference, and the warm-up that compiles them.

The deployment of `benchmark/configs/haystack-photo-rs10-4.json` at sizes
a CPU holds: the Pallas coder in interpret mode, its host call bucketing
an interval's width to a power of two of tiles. `EcVolume.read_needle`
and `read_needle_nowait` give what `benchmark/reference_large.py`
(numpy, Gaussian elimination on the survivors' bytes) gives, for needles
inside a block, across one boundary and across several, with every
choice of lost shards that touches them; what was dispatched is counted
by width; and once a store has mounted an EC volume and the coder's
warm-up has ended, no degraded read of any width compiles.
"""

import asyncio
import importlib.util
import itertools
import json
import os
import re
import socket
import sys
import threading
import time
import urllib.request

import jax.monitoring
import numpy as np
import pytest

from seaweedfs_tpu import ec
from seaweedfs_tpu.ec import coder as coder_mod
from seaweedfs_tpu.ec.geometry import Geometry
from seaweedfs_tpu.ops import rs_pallas
from seaweedfs_tpu.server.volume_server import run_volume_server
from seaweedfs_tpu.storage.file_id import FileId
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.utils import metrics as metrics_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench(name: str):
    """A module of benchmark/ under its own name: `reference_large`
    imports `reference`, and neither imports anything of the program."""
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(ROOT, "benchmark", name + ".py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


reference = _bench("reference")
reference_large = _bench("reference_large")

K, M = 10, 4
COOKIE = 0x5EAF00D
KIB, MIB = 1 << 10, 1 << 20


class Compiles:
    """What JAX compiled or lowered while `armed` (as run.py counts)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        self.armed = False
        self.seen: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and event in self.EVENTS:
            self.seen.append(event)


COMPILES = Compiles()


def record_bytes(n_data: int) -> int:
    """A record of `n_data` bytes of data and nothing else, on disk."""
    raw = 16 + 4 + n_data + 1 + 4 + 8
    return raw + (-raw) % 8


def lay_out(spec: list[tuple[str, int, float]], block: int
            ) -> list[tuple[str, int]]:
    """(name, data bytes) of the needles to write, in order: each named
    needle of `spec` (name, data bytes, where in the `.dat` its record
    starts, in blocks) behind a filler that brings it there."""
    out, at = [], 8  # the superblock
    for name, n_data, start in spec:
        gap = int(start * block) // 8 * 8 - at
        assert gap >= 40, (name, gap)
        out.append((f"filler-before-{name}", gap - 33))
        at += gap
        out.append((name, n_data))
        at += record_bytes(n_data)
    return out


class Built:
    """A sealed volume written by the store's own writer, encoded by the
    host coder, served by an `EcVolume` over the Pallas coder."""

    def __init__(self, directory: str, geometry: Geometry, tile: int,
                 spec: list[tuple[str, int, float]], seed: int,
                 tail: int = 0):
        self.g = geometry
        rng = np.random.default_rng(seed)
        v = Volume(directory, "", 1, create=True)
        self.ids: dict[str, int] = {}
        self.data: dict[int, bytes] = {}
        needles = lay_out(spec, geometry.small_block_size)
        if tail:
            needles.append(("tail", tail))
        for i, (name, n_data) in enumerate(needles, start=1):
            self.ids[name] = i
            self.data[i] = rng.bytes(n_data)
            v.write_needle(Needle(cookie=COOKIE, id=i, data=self.data[i]))
        self.base = v.base_file_name()
        v.close()
        ec.write_ec_files(self.base, ec.get_coder("numpy", K, M), geometry)
        ec.write_sorted_ecx_from_idx(self.base)
        with open(self.base + ".dat", "rb") as f:
            self.dat = f.read()
        with open(self.base + ".idx", "rb") as f:
            self.idx = f.read()
        self.coder = coder_mod.PallasCoder(K, M, tile=tile, interpret=True)
        self.ev = ec.EcVolume(directory, "", 1, geometry, coder=self.coder)
        for sid in range(K + M):
            self.ev.add_shard(sid)
        self.ev.locate(1)  # the layout marker's first read

    def touched(self, name: str) -> set[int]:
        return {iv.to_shard_id_and_offset(self.g)[0]
                for iv in self.ev.locate(self.ids[name])[2]}

    def reference(self, name: str, lost) -> bytes:
        cookie, data = reference_large.read_degraded(
            self.dat, self.idx, self.ids[name], list(lost), K, M,
            self.g.large_block_size, self.g.small_block_size)
        assert cookie == COOKIE
        return data

    def read(self, name: str, lost) -> tuple[bytes, bool]:
        """The needle's data with `lost` unmounted, as the server's two
        steps read it, and whether the loop's step served it."""
        key = self.ids[name]
        for sid in lost:
            assert self.ev.delete_shard(sid)
        try:
            n, located = self.ev.read_needle_nowait(key, COOKIE,
                                                    max_size=1 << 30)
            if n is not None:
                return n.data, True
            assert located is not None
            return self.ev.read_needle(key, COOKIE,
                                       located=located).data, False
        finally:
            for sid in lost:
                self.ev.add_shard(sid)


def choices(touched: set[int], n_lost: int):
    """Every set of `n_lost` of the fourteen shards that holds one of
    `touched`."""
    return [c for c in itertools.combinations(range(K + M), n_lost)
            if touched & set(c)]


def check(built: Built, name: str, losses) -> None:
    key = built.ids[name]
    touched = built.touched(name)
    for lost in losses:
        got, on_loop = built.read(name, lost)
        assert got == built.data[key] == built.reference(name, lost), \
            (name, lost)
        # the loop's step serves exactly what lies whole on shards here
        assert on_loop == (not touched & set(lost)), (name, lost)


# --- small blocks: every choice of lost shards that touches a needle ---

SMALL = Geometry(K, M, large_block_size=64 * KIB, small_block_size=4 * KIB)
SMALL_TILE = 256  # widths 256 B ... 16 KiB; a large block's part is wider
# name, data bytes, start of the record as a share of a small block. The
# first 640 KiB are one row of large blocks (sixteen small blocks each)
SMALL_SPEC = [
    ("large-mid-block", 1000, 3.5),
    ("large-1.5-blocks-two-boundaries", 96 * KIB, 16 * 2 - 4.0),
    ("large-to-small-tier", 6 * KIB, 160 - 0.5),
    ("one-byte", 1, 161.25),
    ("1k-mid-block", KIB, 162.25),
    ("1k-one-boundary", KIB, 163.9),
    ("block-less-a-byte", 4 * KIB - 1, 165.5),
    ("a-block", 4 * KIB, 168.25),
    ("1.5-blocks-two-boundaries", 6 * KIB, 171.75),
    ("3-blocks", 12 * KIB, 175.5),
    ("last-row-ragged", 3 * KIB, 181.5),
]


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    return Built(str(tmp_path_factory.mktemp("small")), SMALL, SMALL_TILE,
                 SMALL_SPEC, seed=34)


def test_small_volume_has_both_tiers_and_the_reference_its_layout(small):
    dat_size = len(small.dat)
    rows = list(reference.stripe_rows(dat_size, K, SMALL.large_block_size,
                                      SMALL.small_block_size))
    assert [b for _, b in rows].count(SMALL.large_block_size) == 1
    assert len(rows) > 3 and dat_size % (K * SMALL.small_block_size)
    assert os.path.getsize(small.base + ".ec00") == reference.shard_size(
        dat_size, K, SMALL.large_block_size, SMALL.small_block_size)
    assert len(small.touched("large-1.5-blocks-two-boundaries")) == 3
    assert len(small.touched("1.5-blocks-two-boundaries")) == 3
    assert len(small.touched("3-blocks")) == 4
    assert len(small.touched("1k-one-boundary")) == 2
    assert len(small.touched("1k-mid-block")) == 1


@pytest.mark.parametrize("n_lost", [1, 2, 3, 4])
@pytest.mark.parametrize("name", [n for n, _, _ in SMALL_SPEC])
def test_small_blocks_every_loss_that_touches(small, name, n_lost):
    check(small, name, choices(small.touched(name), n_lost))


@pytest.mark.parametrize("name", [n for n, _, _ in SMALL_SPEC])
def test_small_blocks_untouched_is_served_on_the_loop(small, name):
    away = sorted(set(range(K + M)) - small.touched(name))
    check(small, name, [(), tuple(away[:4]), tuple(away[-4:])])


# --- the real 1 MiB block: a handful of needles, all seven widths ---

REAL = Geometry(K, M)  # 1 GiB large, 1 MiB small
REAL_SPEC = [
    ("one-byte", 1, 0.1),
    ("1k-mid-block", KIB, 0.2),
    ("64k-mid-block", 64 * KIB, 0.3),
    ("1k-one-boundary", KIB, 0.9996),
    ("64k-one-boundary", 64 * KIB, 1.965),       # 36 KiB + 28 KiB
    ("block-less-a-byte", MIB - 1, 2.6),         # 0.4 + 0.6 MiB
    ("a-block-two-boundaries", MIB, 3.8),        # 0.2 + 1 + the rest
    ("3-blocks", 3 * MIB, 5.875),                # 0.125 + 1 + 1 + 0.875
]
REAL_SEEDED = 4  # of the choices of 2, 3 and 4 lost shards, a needle


@pytest.fixture(scope="module")
def real(tmp_path_factory):
    return Built(str(tmp_path_factory.mktemp("real")), REAL, rs_pallas.TILE,
                 REAL_SPEC, seed=3400)


def real_losses(real: Built, name: str, n_lost: int):
    every = choices(real.touched(name), n_lost)
    if n_lost == 1:
        return every
    rng = np.random.default_rng([34, n_lost, real.ids[name]])
    picked = [every[i] for i in rng.choice(len(every), REAL_SEEDED,
                                           replace=False)]
    if n_lost == 4:
        # what the benchmark's cell loses: 3 data + 1 parity, and every
        # data shard the needle lies on among them
        on = sorted(real.touched(name))[:3]
        rest = [s for s in range(K) if s not in on]
        picked.append(tuple(sorted(on + rest[:3 - len(on)] + [K + 2])))
    return picked


@pytest.mark.parametrize("n_lost", [1, 2, 3, 4])
@pytest.mark.parametrize("name", [n for n, _, _ in REAL_SPEC])
def test_real_block_losses_that_touch(real, name, n_lost):
    check(real, name, real_losses(real, name, n_lost))


def counters() -> dict[str, float]:
    return metrics_mod.shared("ec").snapshot("reconstruct_")


def delta(before: dict, after: dict) -> dict[str, float]:
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)}


def dispatch_key(width, warm: str) -> str:
    return f'reconstruct_dispatch{{warm="{warm}",width="{width}"}}'


def test_all_seven_widths_are_dispatched_and_counted_by_arithmetic(real):
    """Every needle with every data shard it lies on lost: each interval
    is one dispatch at the power of two of tiles that holds it."""
    assert coder_mod.DISPATCH_WIDTHS == rs_pallas.host_widths() \
        == tuple(16 * KIB << i for i in range(7))
    before = counters()
    want_widths: dict[int, int] = {}
    asked = padded = 0
    for name, _, _ in REAL_SPEC:
        lost = tuple(sorted(real.touched(name)))
        for iv in real.ev.locate(real.ids[name])[2]:
            width = 16 * KIB
            while width < iv.size:
                width *= 2
            want_widths[width] = want_widths.get(width, 0) + 1
            asked += iv.size
            padded += width
        check(real, name, [lost])
    got = delta(before, counters())
    assert sorted(want_widths) == list(rs_pallas.host_widths())
    assert got.pop("reconstruct_interval_bytes") == asked \
        == got.pop("reconstruct_bytes")
    assert got.pop("reconstruct_padded_bytes") == padded
    assert got.pop("reconstruct_intervals") == sum(want_widths.values())
    by_width: dict[int, float] = {}
    for key, n in got.items():
        m = re.fullmatch(r'reconstruct_dispatch\{warm="(yes|no)",'
                         r'width="(\d+)"\}', key)
        assert m, key
        by_width[int(m.group(2))] = by_width.get(int(m.group(2)), 0) + n
    assert by_width == want_widths


def test_a_part_of_a_large_block_is_wider_than_any_bucket(small):
    """64 KiB of a large block at a 256-byte tile: 256 tiles, dispatched
    as they are and labelled apart from the seven."""
    before = counters()
    name = "large-1.5-blocks-two-boundaries"
    check(small, name, [tuple(sorted(small.touched(name)))])
    got = delta(before, counters())
    wider = sum(n for key, n in got.items() if 'width="wider"' in key)
    assert wider >= 1
    assert got["reconstruct_padded_bytes"] >= got[
        "reconstruct_interval_bytes"] >= 64 * KIB


# --- the warm-up: a store's first EC mount, the server's status ---

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


WARM_TILE = 2048    # a program no other test of the process has run
LATE_TILE = 4096
BOOT_TILE = 8192


def interpret_coder(tile: int, monkeypatch) -> str:
    """The name under which a store finds the Pallas coder in interpret
    mode at `tile`, for as long as the test runs."""
    name = f"pallas-interpret-{tile}"
    monkeypatch.setitem(
        coder_mod._REGISTRY, name,
        lambda k, m: coder_mod.PallasCoder(k, m, tile=tile, interpret=True))
    return name


SIZES = [1, 900, 3000, 5000, 9000, 20000, 40000, 70000, 131000]


class Served:
    """A store over the Pallas coder in interpret mode at `tile`, one
    volume written and generated, behind a volume server."""

    def __init__(self, tmpdir: str, tile: int, monkeypatch,
                 on_store=None):
        name = interpret_coder(tile, monkeypatch)
        self.tile = tile
        self.g = Geometry(K, M, large_block_size=MIB,
                          small_block_size=128 * KIB)
        self.store = Store([os.path.join(tmpdir, "v")], coder_name=name,
                           geometry=self.g)
        if on_store is not None:
            on_store(self.store)
        rng = np.random.default_rng(tile)
        self.store.add_volume(1)
        self.data = {i + 1: rng.bytes(n) for i, n in enumerate(SIZES)}
        for key, data in self.data.items():
            self.store.write_needle(1, Needle(id=key, cookie=COOKIE,
                                              data=data))
        self.store.ec_generate(1)
        self.state = rs_pallas.host_state(1, K, tile, True)
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

    def warm(self) -> dict:
        resolved = json.loads(self.get("admin/ec/mesh_status"))[
            "coder"]["resolved"]
        assert len(resolved) == 1
        return resolved[0]["warm"]

    def mount_and_lose(self, lost) -> None:
        self.store.ec_mount(1, "", list(range(K + M)))
        self.store.delete_volume(1)
        for sid in lost:
            self.store.find_ec_volume(1).delete_shard(sid)

    def get_all(self) -> int:
        """Every needle over HTTP, checked; how many met a lost shard."""
        ev = self.store.find_ec_volume(1)
        degraded = 0
        for key, data in self.data.items():
            assert self.get(str(FileId(1, key, COOKIE))) == data, key
            shards = {iv.to_shard_id_and_offset(self.g)[0]
                      for iv in ev.locate(key)[2]}
            degraded += bool(shards - set(ev.shard_ids()))
        return degraded

    def stop(self) -> None:
        asyncio.run_coroutine_threadsafe(self.runner.cleanup(),
                                         self.loop).result(10)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(5)
        self.store.close()


def wait_done(state, seconds: float = 120.0) -> None:
    deadline = time.time() + seconds
    while state.state in ("idle", "running") and time.time() < deadline:
        time.sleep(0.02)
    assert state.state == "done", state.state


def test_no_read_compiles_once_the_first_generate_has_warmed_up(
        tmp_path, monkeypatch):
    state = rs_pallas.host_state(1, K, WARM_TILE, True)
    assert state.status() == {"state": "idle", "widths": []}
    lock_free, stores = [], []
    real_run = state._run

    def run():  # the warm-up's thread does not hold the store's lock
        lock_free.append(stores[0]._lock.acquire(timeout=0))
        if lock_free[-1]:
            stores[0]._lock.release()
        real_run()
    monkeypatch.setattr(state, "_run", run)
    served = Served(str(tmp_path), WARM_TILE, monkeypatch, stores.append)
    try:
        metrics = served.get("metrics").decode()
        # on /metrics from the start, a 0 and not an absence (the
        # registry is the process's, so a value only where nothing has
        # counted yet)
        for name in ("seaweedfs_tpu_ec_reconstruct_interval_bytes_total",
                     "seaweedfs_tpu_ec_reconstruct_padded_bytes_total"):
            assert re.search(rf"^{name} [0-9.e+]+$", metrics, re.M), name
        for width in coder_mod.DISPATCH_WIDTHS:
            for warm in ("yes", "no"):
                assert re.search(
                    r"^seaweedfs_tpu_ec_reconstruct_dispatch_total"
                    rf'\{{warm="{warm}",width="{width}"\}} [0-9.e+]+$',
                    metrics, re.M), (width, warm)
        # the store's first generate began it; the mount finds it begun
        assert served.state is state and state.state != "idle"
        served.mount_and_lose([0, 3, 7, 12])
        wait_done(state)
        assert lock_free == [True]
        widths = list(rs_pallas.host_widths(WARM_TILE))
        assert served.warm() == {"state": "done", "widths": widths}
        stages = ('seaweedfs_tpu_ec_stage_seconds_count'
                  '{stage="ec.warm_widths"}')
        before_count = re.search(
            rf"^{re.escape(stages)} (\S+)$",
            served.get("metrics").decode(), re.M).group(1)

        before = counters()
        COMPILES.seen.clear()
        COMPILES.armed = True
        try:
            degraded = served.get_all()
        finally:
            COMPILES.armed = False
        assert degraded >= 5 and COMPILES.seen == []
        got = delta(before, counters())
        assert not any('warm="no"' in key for key in got), got
        met = {int(re.search(r'width="(\d+)"', key).group(1))
               for key in got if key.startswith("reconstruct_dispatch")}
        assert len(met) >= 5 and met <= set(widths)
        # one warm-up a program for the life of the process: neither the
        # mount nor the reads began another
        assert f"{stages} {before_count}" in served.get("metrics").decode()
    finally:
        served.stop()


def test_a_get_before_the_warm_up_ends_is_answered_and_counted_cold(
        tmp_path, monkeypatch):
    gate = threading.Event()
    real_widths = rs_pallas.host_widths

    def held(tile=rs_pallas.TILE):
        if tile == LATE_TILE:
            assert gate.wait(120)
        return real_widths(tile)
    monkeypatch.setattr(rs_pallas, "host_widths", held)
    s = Served(str(tmp_path), LATE_TILE, monkeypatch)
    try:
        s.mount_and_lose([0, 2, 5, 11])
        assert s.warm() == {"state": "running", "widths": []}
        before = counters()
        assert s.get_all() >= 5
        cold = delta(before, counters())
        n_cold = sum(n for key, n in cold.items() if 'warm="no"' in key)
        # each width compiled inside the first read that met it, once
        assert n_cold == len(s.state.done) >= 5
        assert s.warm()["state"] == "running"
        gate.set()
        wait_done(s.state)
        assert s.warm()["widths"] == list(real_widths(LATE_TILE))
        before = counters()
        assert s.get_all() >= 5
        assert not any('warm="no"' in key
                       for key in delta(before, counters()))
    finally:
        gate.set()
        s.stop()


def test_a_store_that_loads_ec_volumes_at_boot_warms_up_too(tmp_path,
                                                            monkeypatch):
    first = Served(str(tmp_path), BOOT_TILE, monkeypatch)
    first.mount_and_lose([])
    wait_done(first.state)
    first.stop()
    first.state.done.clear()
    first.state.state = "idle"
    again = Store([os.path.join(str(tmp_path), "v")],
                  coder_name=interpret_coder(BOOT_TILE, monkeypatch),
                  geometry=first.g)
    try:
        assert again.find_ec_volume(1) is not None
        wait_done(first.state)
    finally:
        again.close()


def test_a_server_that_mounts_no_ec_volume_compiles_nothing(tmp_path,
                                                            monkeypatch):
    store = Store([str(tmp_path)],
                  coder_name=interpret_coder(WARM_TILE, monkeypatch),
                  geometry=Geometry(K, M, large_block_size=MIB,
                                    small_block_size=128 * KIB))
    try:
        before = threading.active_count()
        store.add_volume(7)
        store.write_needle(7, Needle(id=1, cookie=COOKIE, data=b"x" * 99))
        assert store.coder_status()["resolved"] == []
        assert threading.active_count() == before
    finally:
        store.close()


def test_a_host_coder_has_nothing_to_warm():
    for name in ("numpy", "jax"):
        coder = ec.get_coder(name, K, M)
        coder.warm_widths()
        assert "warm" not in coder.describe()
