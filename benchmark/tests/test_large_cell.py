"""What `degraded-get-large-rs10-4` adds beside the harness: the plain
reference of a degraded read against `reference.py`'s own encoder on a
seeded volume, the three readers of the dispatch counters on hand-made
deltas (the value; None where the program has no such counter, as the
parent commit), the driver's size classes, and the cell through `run.py`
without a chip: correct as it stands, not correct under its control, not
correct with a reconstructed interval broken underneath it."""

import itertools
import json
import os
import struct
import subprocess
import sys

import numpy as np
import pytest

import reference
import reference_large
import run as harness
from conftest import ROOT

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "degraded-get-large-rs10-4"
K, M, LARGE, SMALL = 10, 4, 1 << 16, 1 << 12
INTERVAL = "seaweedfs_tpu_ec_reconstruct_interval_bytes_total"
PADDED = "seaweedfs_tpu_ec_reconstruct_padded_bytes_total"
DISPATCH = "seaweedfs_tpu_ec_reconstruct_dispatch_total"
STAGE = "seaweedfs_tpu_ec_stage_seconds"


def reader(name: str):
    return harness.load_module("layer_metrics", name).read


# --- the plain reference ---

def record(key: int, cookie: int, data: bytes) -> bytes:
    """A version-3 needle record, made here from the format's words."""
    body = struct.pack(">I", len(data)) + data + b"\0"  # flags: none
    out = (struct.pack(">IQI", cookie, key, len(body)) + body
           + struct.pack(">IQ", reference_large.masked_crc(data), 1234567))
    return out + bytes(-len(out) % 8)


SIZES = [1, 700, 5000, 4096 - 33, 4096, 6144, 3 * 4096, 100000, 20, 9000]


@pytest.fixture(scope="module")
def volume(tmp_path_factory):
    """A `.dat` of one large row and some small rows with a ragged tail,
    needles from a byte to a block and a half of either tier, one
    overwritten and one deleted; the fourteen shard files as
    `reference.py`'s own encoder writes them."""
    d = tmp_path_factory.mktemp("large")
    base = str(d / "3")
    rng = np.random.default_rng(34)
    dat = bytearray(b"\3" + bytes(7))  # the superblock
    idx, data = [], {}
    sizes = [90000, 300000] + SIZES * 3 + [250000] + SIZES
    for key, n in enumerate(sizes, start=1):
        data[key] = rng.bytes(n)
        idx.append((key, len(dat) // 8, n + 5))
        dat += record(key, 0xC00C1E + key, data[key])
    # needle 4 written again at the end; needle 6 deleted
    data[4] = rng.bytes(3000)
    idx.append((4, len(dat) // 8, 3005))
    dat += record(4, 0xC00C1E + 4, data[4])
    idx.append((6, 0, reference.TOMBSTONE))
    del data[6]
    entries = np.zeros(len(idx), dtype=reference.IDX_DTYPE)
    entries["key"], entries["offset"], entries["size"] = zip(*idx)
    with open(base + ".dat", "wb") as f:
        f.write(dat)
    shards = [bytearray() for _ in range(K + M)]
    for _, rows in reference.iter_shard_chunks(base + ".dat", K, M, LARGE,
                                               SMALL):
        for shard, row in zip(shards, rows):
            shard += row.tobytes()
    tiers = [b for _, b in reference.stripe_rows(len(dat), K, LARGE, SMALL)]
    assert tiers.count(LARGE) == 1 and tiers.count(SMALL) >= 3
    assert len(dat) % (K * SMALL)
    return bytes(dat), entries.tobytes(), data, [bytes(s) for s in shards]


def read(volume, key: int, lost):
    dat, idx, _, _ = volume
    return reference_large.read_degraded(dat, idx, key, list(lost), K, M,
                                         LARGE, SMALL)


LOSSES = [(), (0,), (9,), (0, 1, 2, 3), (6, 7, 8, 9), (1, 4, 8, 12),
          (0, 5, 10, 13), (2, 3, 11, 12), (10, 11, 12, 13), (3, 7, 13)]


@pytest.mark.parametrize("lost", LOSSES)
def test_every_needle_comes_back_whatever_is_lost(volume, lost):
    _, _, data, _ = volume
    for key, want in data.items():
        assert read(volume, key, lost) == (0xC00C1E + key, want), key


def test_a_rebuilt_part_is_the_lost_shard_files_own_bytes(volume):
    """Against `reference.py`'s encoder: what is solved for a part of a
    lost data shard is what that shard's file holds there, in either
    tier, for every way of losing four that keeps fewer than k data
    shards among the first k survivors."""
    dat, idx, data, shards = volume
    keys, offsets, sizes = reference.fold_idx(idx)
    rng = np.random.default_rng(7)
    checked = {LARGE: 0, SMALL: 0}
    for i in rng.choice(len(keys), 12, replace=False):
        for shard, at, size in reference.locate(
                int(offsets[i]) * 8,
                reference_large.record_bytes(int(sizes[i])), len(dat), K,
                LARGE, SMALL):
            tier = LARGE if at < LARGE else SMALL
            others = [s for s in range(K + M) if s != shard]
            for extra in [()] + [tuple(rng.choice(others, n, replace=False))
                                 for n in (1, 2, 3, 3)]:
                lost = {shard, *map(int, extra)}
                got = reference_large.rebuild_part(
                    dat, shard, at, size, lost, K, M, LARGE, SMALL)
                assert got == shards[shard][at:at + size], (shard, at, lost)
                checked[tier] += 1
    assert min(checked.values()) > 0


def test_the_parity_rows_it_solves_with_are_the_encoders(volume):
    """Every way of losing all but k shards that include all four parity
    shards: the survivors' equations are the encoder's rows."""
    _, _, data, _ = volume
    key = 23  # a block and a half of the small tier
    for lost in itertools.combinations(range(K), 4):
        assert read(volume, key, lost)[1] == data[key]


def test_what_cannot_be_read_raises(volume):
    dat, idx, data, _ = volume
    with pytest.raises(KeyError):
        read(volume, 6, ())       # deleted
    with pytest.raises(KeyError):
        read(volume, 999, ())     # never written
    shard = reference.locate(8 + 0, 64, len(dat), K, LARGE, SMALL)[0][0]
    with pytest.raises(ValueError, match="only 9 of 10"):
        read(volume, 1, (shard, 10, 11, 12, 13))
    # a byte of the data changed on disk: the checksum does not hold
    bad = bytearray(dat)
    bad[8 + 16 + 4 + 100] ^= 1
    with pytest.raises(ValueError, match="CRC"):
        reference_large.read_degraded(bytes(bad), idx, 1, [], K, M, LARGE,
                                      SMALL)


def test_solve_is_gaussian_elimination_over_the_field():
    rng = np.random.default_rng(1)
    matrix = reference.encoding_matrix(K, M)
    x = rng.integers(0, 256, (K, 333), dtype=np.uint8)
    for rows in ([0, 1, 2, 3, 4, 5, 10, 11, 12, 13], list(range(4, 14)),
                 [13, 0, 12, 1, 11, 2, 10, 3, 9, 4]):
        coeffs = [matrix[r] for r in rows]
        rhs = reference.apply_rows_bytewise(coeffs, x)
        assert np.array_equal(reference_large.solve(coeffs, rhs), x)
    with pytest.raises(ValueError, match="singular"):
        reference_large.solve([matrix[0]] * K, x)


# --- the three readers, on hand-made deltas ---

def counters(widths: dict[int, tuple[int, int]], asked: int,
             dispatch_s: float, d2h_s: float) -> dict:
    """The window's deltas as run.py hands them: `widths` maps a width
    to its (warm, cold) dispatches."""
    out = {INTERVAL: float(asked),
           PADDED: float(sum(w * (a + b) for w, (a, b) in widths.items())),
           f'{STAGE}_sum{{stage="ec.get.dispatch"}}': dispatch_s,
           f'{STAGE}_sum{{stage="ec.get.d2h_wait"}}': d2h_s}
    for w, (warm, cold) in widths.items():
        out[f'{DISPATCH}{{warm="yes",width="{w}"}}'] = float(warm)
        out[f'{DISPATCH}{{warm="no",width="{w}"}}'] = float(cold)
    return out


def as_run(c: dict) -> dict:
    return {"counters": c, "facts": {"gets_completed": 100},
            "config": {"geometry": "10+4"}, "trace": {}, "chips": 1,
            "device_kind": "TPU v5 lite"}


def test_readers_on_recorded_deltas():
    widths = {16384: (10, 0), 32768: (9, 1), 65536: (12, 0),
              131072: (160, 2)}
    padded = 16384 * 10 + 32768 * 10 + 65536 * 12 + 131072 * 162
    run = as_run(counters(widths, 11_000_000, 0.14, 0.35))
    assert reader("reconstruct.pad_ratio.get")(run) \
        == pytest.approx(padded / 11_000_000)
    assert reader("reconstruct.roundtrip_mib_per_s.get")(run) \
        == pytest.approx(11 * padded / 0.49 / (1 << 20))
    assert reader("reconstruct.cold_dispatch_pct.get")(run) \
        == pytest.approx(100.0 * 3 / 194)
    # all warm: 0 and not None
    run = as_run(counters({131072: (50, 0), 16384: (0, 0)}, 3_000_000,
                          0.03, 0.09))
    assert reader("reconstruct.cold_dispatch_pct.get")(run) == 0.0
    # a width past the buckets is a dispatch like any other
    c = counters({131072: (3, 0)}, 300_000, 0.01, 0.01)
    c[f'{DISPATCH}{{warm="no",width="wider"}}'] = 1.0
    assert reader("reconstruct.cold_dispatch_pct.get")(as_run(c)) == 25.0


NEW = ["reconstruct.pad_ratio.get", "reconstruct.roundtrip_mib_per_s.get",
       "reconstruct.cold_dispatch_pct.get"]


@pytest.mark.parametrize("name", NEW)
def test_readers_give_nothing_on_a_program_without_the_counters(name):
    """The parent commit: the stage family is there, the counters are
    not; and a window in which nothing was dispatched."""
    parent = {f'{STAGE}_sum{{stage="ec.get.dispatch"}}': 0.2,
              f'{STAGE}_sum{{stage="ec.get.d2h_wait"}}': 0.5,
              "seaweedfs_tpu_ec_reconstruct_intervals_total": 300.0}
    assert reader(name)(as_run(parent)) is None
    assert reader(name)(as_run({})) is None
    born = counters({w: (0, 0) for w in (16384, 131072)}, 0, 0.0, 0.0)
    assert reader(name)(as_run(born)) is None


def test_a_size_class_of_the_driver_pads_to_one_width():
    """Under the server's rounding (a power of two of 16 KiB tiles) and
    under any other to a power of two: one GET a class warms every width
    (below the least width there are classes to spare)."""
    driver = harness.load_module("drivers", "open_loop_get_large")
    tile = 16384
    for size in [1, 2, 40, 16384, 16385, 28000, 32768, 36000, 65536,
                 65576, 131072, 131073, 1 << 20]:
        width = tile
        while width < size:
            width *= 2
        cls = driver.size_class(size)
        assert (1 << cls) // 2 < size <= 1 << cls
        assert max(tile, 1 << cls) == width


# --- the cell through run.py, without a chip ---

def run_cell(control: bool = False, seed: int = 2 ** 31 + 34):
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    out = harness.run_cell(bench, CELL, seed, 3.0, trace=False,
                           rehearsal=True, control=control)
    json.dumps(out)  # the result line has to serialise
    return out


def checks(out: dict) -> dict:
    return {c["name"]: c["value"] for c in out["checks"]}


def test_sound_run_is_correct():
    out = run_cell()
    assert out["correct"] and out["failed"] == 0, out["checks"]
    facts = out["facts"]
    assert facts["gets_on_lost_shards"] > 0
    # a record of 65,576 bytes crosses a block now and then: more
    # intervals than GETs on lost shards is two parts of one needle
    assert facts["intervals_expected"] >= facts["gets_on_lost_shards"]
    # the host coder says nothing of a warm-up: nothing was waited for
    assert facts["server_warm"] == [] and facts["warm_wait_s"] < 5
    # a whole record's interval is of the class above 64 KiB
    assert "17" in facts["classes_warmed"]
    assert set(checks(out)) == {"gets_wrong", "warmup_gets_wrong",
                                "deleted_needles_back",
                                "ecx_files_differing",
                                "server_warmups_not_done"}
    assert out["compiled_in_window"] == {"backend_compile_duration": 0,
                                         "jaxpr_to_mlir_module_duration": 0}


def test_control_is_not_correct():
    out = run_cell(control=True)
    assert not out["correct"], out["checks"]
    got = checks(out)
    assert got["gets_wrong"] == out["facts"]["control_gets_wrong"] > 0
    assert {k for k, v in got.items() if v} == {"gets_wrong"}


def test_broken_reconstruction_is_not_correct(monkeypatch):
    """The last byte of a reconstructed interval altered where it is
    produced."""
    from seaweedfs_tpu.ec.ec_volume import EcVolume
    real = EcVolume._reconstruct_interval

    def reconstruct(self, *a, **kw):
        data = bytearray(real(self, *a, **kw))
        data[len(data) // 2] ^= 0x5A
        return bytes(data)
    monkeypatch.setattr(EcVolume, "_reconstruct_interval", reconstruct)
    out = run_cell()
    assert not out["correct"], out["checks"]
    assert checks(out)["gets_wrong"] > 0


def test_a_warm_up_that_failed_is_not_correct(monkeypatch):
    driver = harness.load_module("drivers", "open_loop_get_large")
    state = {"server_warm": {"waited_s": 0.1, "reported": [
        {"state": "failed: XlaRuntimeError: no", "widths": [16384]}]},
        "warmed": {17: 5}}
    monkeypatch.setattr(driver.base, "verify", lambda *a: {
        "facts": {}, "checks": [], "attempted": 1, "failed": 0})
    out = driver.verify(None, state, {})
    assert out["checks"] == [{"name": "server_warmups_not_done",
                              "value": 1, "limit": 0}]
    assert out["facts"]["classes_warmed"] == {"17": 5}


def test_rehearsal_exits_3_with_no_result_line():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 5), "--seconds", "2", "--trace", "0",
         "--rehearsal"], cwd=ROOT, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "check server_warmups_not_done: 0 (limit 0)" in p.stderr
    assert "check gets_wrong: 0 (limit 0)" in p.stderr


def test_the_cell_is_declared_as_data_the_harness_can_resolve():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, config, traffic = harness.resolve_cell(bench, CELL)
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and len(cfg["source"]) <= 200
    assert len(cell["why"]) <= 200 and "degraded_p50_ms" in cell["why"]
    assert "Haystack" in cfg["source"] and "OSDI 2010" in cfg["source"]
    assert sorted(cfg["reduced"]) == sorted(config["reduced"]) \
        == ["volume_bytes", "volume_count", "volume_servers"]
    assert os.path.isfile(os.path.join(BENCH, "drivers",
                                       traffic["driver"] + ".py"))
    # every key of the configuration it stands on but the object: the
    # two cells differ by the object alone
    old_cfg = harness.load_json(os.path.join(
        BENCH, "configs", "seaweed-rs10-4.json"))
    assert set(config) == set(old_cfg)
    for key in ("geometry", "geometry_policy", "collection",
                "large_block_bytes", "small_block_bytes", "volume_servers",
                "guarantees"):
        assert config[key] == old_cfg[key], key
    assert config["object_bytes"] == 65536
    # the .dat and its seven tombstones stay under the driver's 1 GiB cap
    import datagen
    dat = datagen.dat_bytes(config["objects_per_volume"], 65536)
    assert dat == 983_640_008 and dat + 7 * 64 < 1 << 30
    old = harness.load_json(os.path.join(BENCH, "traffic",
                                         "degraded-get-zipf.json"))
    assert traffic["population"] == old["population"]
    assert {k: v for k, v in traffic["load"].items() if k != "rate_per_s"} \
        == {k: v for k, v in old["load"].items() if k != "rate_per_s"}
    assert traffic["load"]["rate_per_s"] % 10 == 0
    reported = [m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [])]
    for name in reported:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py")), name
    assert set(NEW) <= set(reported)
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "get_p50_ms"
    assert {"device.idle_pct.get", "gf_apply_roofline.get",
            "kernel.busy_ms_per_interval.get",
            "reconstruct.intervals_per_get.get",
            "reconstruct.host_ms_per_interval.get",
            "reconstruct.dispatch_ms_per_interval.get",
            "reconstruct.d2h_wait_ms_per_interval.get",
            "loadgen.late_p95_ms.get"} <= set(reported)
    assert sum(n.startswith("ec_get.") for n in reported) == 7
    assert not [n for n in reported
                if n.startswith(("remote.", "warmdown.", "gf_encode",
                                 "gf_reconstruct"))]
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "get_p50_ms")["workloads"]
