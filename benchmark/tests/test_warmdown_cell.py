"""What `fg-under-warmdown` adds beside the harness: the plain reference
of a warm-down against `reference.py` at a small size, its control, the
six readers on a recorded trace and hand-made counter deltas (the value;
None where the program has no such counter, as the parent commit), and
the cell through `run.py` without a chip: correct as it stands, not
correct under its control by the GET check and by the shard-file check,
not correct with the encode broken underneath it."""

import gzip
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import reduce
import reference
import reference_warmdown
import run as harness
from conftest import ROOT

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "fg-under-warmdown"
K, M, LARGE, SMALL = 10, 4, 1 << 16, 1 << 10
STAGE = "seaweedfs_tpu_ec_stage_seconds"
ENCODED = "seaweedfs_tpu_ec_encode_input_bytes_total"
BATCHES = "seaweedfs_tpu_ec_encode_batches_total"
TRACE = os.path.join(BENCH, "tests", "data", CELL + ".trace.json.gz")


def reader(name: str):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --- the plain reference ---

@pytest.fixture(scope="module")
def volume(tmp_path_factory):
    """A `.dat` of one large row, some small rows and a ragged tail, an
    `.idx` with an overwrite and a tombstone, and the reference's files
    written out by `reference.py`'s own encoder."""
    d = tmp_path_factory.mktemp("ref")
    base = str(d / "7")
    rng = np.random.default_rng(32)
    dat = rng.integers(0, 256, K * LARGE + 3 * K * SMALL + 777,
                       dtype=np.uint8).tobytes()
    with open(base + ".dat", "wb") as f:
        f.write(dat)
    idx = np.zeros(5, dtype=reference.IDX_DTYPE)
    idx["key"] = [9, 3, 5, 3, 5]
    idx["offset"] = [1, 20, 40, 60, 0]
    idx["size"] = [100, 100, 100, 120, reference.TOMBSTONE]
    with open(base + ".idx", "wb") as f:
        f.write(idx.tobytes())

    def write(out_base: str, matrix=None):
        files = [open(out_base + ext, "wb")
                 for ext in reference_warmdown.exts(K, M)[:-1]]
        for _, rows in reference.iter_shard_chunks(
                base + ".dat", K, M, LARGE, SMALL, matrix=matrix):
            for f, row in zip(files, rows):
                f.write(row.tobytes())
        for f in files:
            f.close()
        with open(out_base + ".ecx", "wb") as f:
            f.write(reference.sorted_ecx(idx.tobytes()))
    write(str(d / "good"))
    write(str(d / "cauchy"), reference_warmdown.cauchy_matrix(K, M))
    return base, str(d / "good"), str(d / "cauchy")


PARITY = [f".ec{i:02d}" for i in range(K, K + M)]


def test_walk_agrees_with_reference_files(volume):
    base, good, _ = volume
    got = reference_warmdown.walk(base, K, M, LARGE, SMALL, against=good)
    assert got["differing"] == []
    assert got["shard_bytes"] == os.path.getsize(good + ".ec00") \
        == reference.shard_size(os.path.getsize(base + ".dat"), K, LARGE,
                                SMALL)
    for ext in reference_warmdown.exts(K, M):
        with open(good + ext, "rb") as f:
            assert got["hashes"][ext] == reference_warmdown.digest(
                f.read()), ext
    # the same without files to compare
    assert reference_warmdown.walk(base, K, M, LARGE, SMALL)["hashes"] \
        == got["hashes"]


def test_control_differs_in_every_parity_file_and_no_other(volume):
    base, _, cauchy = volume
    got = reference_warmdown.walk(base, K, M, LARGE, SMALL, against=cauchy)
    assert got["differing"] == PARITY
    assert sorted(e for e in got["hashes"]
                  if got["hashes"][e] != got["control_hashes"][e]) == PARITY
    assert reference_warmdown.files_differing(
        got["hashes"], got["control_hashes"]) == M
    for ext in PARITY:  # the control's hashes are of the control's files
        with open(cauchy + ext, "rb") as f:
            assert got["control_hashes"][ext] \
                == reference_warmdown.digest(f.read())


def test_cauchy_control_is_a_code_of_its_own():
    """Any k rows of it are invertible (MDS), and its parity rows are
    not the encoding matrix's."""
    c = reference_warmdown.cauchy_matrix(K, M)
    assert c[:K] == reference.encoding_matrix(K, M)[:K]
    assert all(a != b for a, b in zip(c[K:],
                                      reference.encoding_matrix(K, M)[K:]))
    for rows in ([0, 1, 2, 3, 4, 5, 10, 11, 12, 13],
                 [4, 5, 6, 7, 8, 9, 10, 11, 12, 13], list(range(3, 13))):
        reference.mat_inv([c[r] for r in rows])  # raises when singular


@pytest.mark.parametrize("damage", ["flip", "truncate", "missing", "ecx"])
def test_walk_sees_a_damaged_file(volume, tmp_path, damage):
    base, good, _ = volume
    mine = str(tmp_path / "x")
    for ext in reference_warmdown.exts(K, M):
        with open(good + ext, "rb") as src, open(mine + ext, "wb") as dst:
            dst.write(src.read())
    target = ".ecx" if damage == "ecx" else ".ec03"
    if damage in ("flip", "ecx"):
        with open(mine + target, "r+b") as f:
            f.seek(os.path.getsize(mine + target) - 1)
            last = f.read(1)
            f.seek(-1, 1)
            f.write(bytes([last[0] ^ 1]))
    elif damage == "truncate":
        os.truncate(mine + target, os.path.getsize(mine + target) - 1)
    else:
        os.remove(mine + target)
    got = reference_warmdown.walk(base, K, M, LARGE, SMALL, against=mine)
    assert got["differing"] == [target]
    assert reference_warmdown.files_differing(
        got["hashes"], {**got["hashes"], target: "0" * 32}) == 1
    assert reference_warmdown.files_differing(got["hashes"], {}) == K + M + 1


# --- the readers ---

# a window of 20 passes of two 80 MiB batches, 1,000 GETs, 400 intervals
PASS_BYTES = 167772160
FULL = {ENCODED: 20.0 * PASS_BYTES, BATCHES: 40.0,
        f'{STAGE}_sum{{stage="ec.generate"}}': 36.0,
        f'{STAGE}_sum{{stage="ec.dispatch"}}': 6.0,
        f'{STAGE}_sum{{stage="ec.kernel"}}': 2.0,
        f'{STAGE}_sum{{stage="ec.read"}}': 5.0,
        f'{STAGE}_sum{{stage="ec.write"}}': 1.0,
        f'{STAGE}_sum{{stage="ec.digest"}}': 3.0,
        f'{STAGE}_sum{{stage="ec.fsync"}}': 3.0}
WANT = {"warmdown.input_gbps.bg": 20 * PASS_BYTES / 40.0 / 1e9,
        "warmdown.in_flight_pct.bg": 90.0,
        "warmdown.link_ms_per_batch.bg": 200.0,
        "warmdown.disk_ms_per_batch.bg": 300.0}


def run_of(counters: dict, window_s: float = 40.0) -> dict:
    return {"counters": counters, "facts": {},
            "trace": {"window_s": window_s, "device_ops": {}}}


@pytest.mark.parametrize("name", sorted(WANT))
def test_counter_reader_value(name):
    assert reader(name)(run_of(FULL)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_counter_reader_none_without_the_counters(name):
    # the parent commit: the pipeline's stages count, the two counters
    # and `ec.generate` do not exist
    parent = {k: v for k, v in FULL.items()
              if k not in (ENCODED, BATCHES) and "ec.generate" not in k}
    assert reader(name)(run_of(parent)) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_counter_reader_none_on_a_divisor_of_zero(name):
    assert reader(name)(run_of({**FULL, BATCHES: 0.0}, window_s=0.0)) is None


def recorded() -> dict:
    with gzip.open(TRACE, "rt") as f:
        return json.load(f)


def test_rooflines_split_the_recorded_trace_by_operation():
    """The recorded window (the cell's traced run on one TPU v5 lite,
    its host planes dropped; the facts and counters beside it are that
    run's) holds encode batches, four rows out, and reconstructed
    intervals, one row out, on one device. Each roofline divides by its
    own operations' device time and by no other's."""
    rec = recorded()
    summary = reduce.summarize(rec["trace"])
    ops = summary["device_ops"]
    import warmdown_readers
    enc = warmdown_readers.kernel_seconds(ops, M)
    rec_s = warmdown_readers.kernel_seconds(ops, 1)
    assert enc > 0 and rec_s > 0
    # together they are the apply kernel's whole device time
    kernel = sum(s for name, (_, s) in ops.items() if "gf_apply" in name)
    assert enc + rec_s == pytest.approx(kernel)
    assert enc + rec_s <= summary["busy_s"] * (1 + 1e-9)
    run = {"trace": summary, "facts": rec["facts"],
           "counters": rec["counters"], "config": rec["config"],
           "device_kind": rec["device_kind"], "chips": 1}
    for name, rows, columns, secs in (
            ("gf_encode_roofline.bg", M, rec["facts"]["encode_columns"], enc),
            ("gf_reconstruct_roofline.fg", 1, rec["facts"]["columns_coded"],
             rec_s)):
        got = reader(name)(run)
        least, bound = reduce.least_seconds(
            reduce.gf_apply_work(K, rows, columns), rec["device_kind"])
        assert bound == "hbm"
        assert got == pytest.approx(100.0 * least / secs)
        assert 0.0 < got < 100.0
        assert got == pytest.approx(rec["reported"][name])
    # the reconstruct's share, read from its own operations, is no
    # smaller than what all device-busy time would give
    assert reader("gf_reconstruct_roofline.fg")(run) \
        >= reduce.roofline_pct(run)
    for name in WANT:
        assert reader(name)(run) == pytest.approx(rec["reported"][name])


@pytest.mark.parametrize("name", ["gf_encode_roofline.bg",
                                  "gf_reconstruct_roofline.fg"])
def test_roofline_none_where_its_operations_did_not_run(name):
    rec = recorded()
    summary = reduce.summarize(rec["trace"])
    rows = "[4," if name.startswith("gf_encode") else "[1,"
    summary["device_ops"] = {n: v for n, v in summary["device_ops"].items()
                             if "= u8" + rows not in n}
    run = {"trace": summary, "facts": rec["facts"], "counters": {},
           "config": rec["config"], "device_kind": rec["device_kind"],
           "chips": 1}
    assert reader(name)(run) is None
    # nor where the driver saw nothing coded
    run["trace"] = reduce.summarize(rec["trace"])
    run["facts"] = {**rec["facts"], "encode_columns": 0, "columns_coded": 0}
    assert reader(name)(run) is None


# --- the cell through run.py, without a chip ---

def run_cell(control: bool = False, seed: int = 2 ** 31 + 32):
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
    assert facts["passes_in_window"] >= 2 and facts["warm"]["passes"] >= 2
    assert facts["passes_verified"] >= facts["passes_in_window"] + 2
    assert facts["encode_input_bytes_from"] == "program_counter"
    # the program's counter and the driver's own count of passes agree
    # to within the passes that straddle the window's ends
    assert facts["encode_input_bytes"] == pytest.approx(
        facts["encode_input_bytes_by_passes"], rel=0.5)
    assert 0.0 <= facts["gap_share"] <= 0.2
    assert facts["control_pass_files_differing"] == M
    assert out["compiled_in_window"] == {"backend_compile_duration": 0,
                                         "jaxpr_to_mlir_module_duration": 0}


def test_control_is_not_correct_by_either_check():
    out = run_cell(control=True)
    assert not out["correct"], out["checks"]
    got = checks(out)
    assert got["gets_wrong"] > 0
    assert got["pass_files_differing"] \
        == M * out["facts"]["passes_verified"] > 0
    assert got["final_files_differing"] == M
    # nothing else is at fault
    assert {k for k, v in got.items() if v and k != "gap_share"} \
        == {"gets_wrong", "pass_files_differing", "final_files_differing"}


def test_broken_encode_is_not_correct(monkeypatch):
    """A parity row altered where the pipeline hands it to its writers."""
    from seaweedfs_tpu.ec import pipeline
    real = pipeline._FanOut.put_rows

    def put_rows(self, rows, on_done=None):
        rows = list(rows)
        if len(rows) == K + M:
            bad = np.array(rows[K + 1], copy=True)
            bad[0] ^= 0x5A
            rows[K + 1] = bad
        return real(self, iter(rows), on_done=on_done)
    monkeypatch.setattr(pipeline._FanOut, "put_rows", put_rows)
    out = run_cell()
    assert not out["correct"], out["checks"]
    got = checks(out)
    assert got["pass_files_differing"] == out["facts"]["passes_verified"]
    assert got["final_files_differing"] == 1


def test_rehearsal_exits_3_with_no_result_line():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 5), "--seconds", "2", "--trace", "0",
         "--rehearsal"], cwd=ROOT, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "check pass_files_differing: 0 (limit 0)" in p.stderr


def test_the_cell_is_declared_as_data_the_harness_can_resolve():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, config, traffic = harness.resolve_cell(bench, CELL)
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and len(cfg["source"]) <= 200
    assert "scaffold.go" in cfg["source"] \
        and "[master.maintenance]" in cfg["source"]
    assert sorted(cfg["reduced"]) == sorted(config["reduced"])
    assert os.path.isfile(os.path.join(BENCH, "drivers",
                                       traffic["driver"] + ".py"))
    # every key of the configuration it stands on, and the old cell's
    # requests: the two cells differ by the background alone
    old_cfg = harness.load_json(os.path.join(
        BENCH, "configs", "seaweed-rs10-4.json"))
    for key in ("geometry", "geometry_policy", "collection",
                "large_block_bytes", "small_block_bytes", "object_bytes",
                "objects_per_volume", "volume_servers"):
        assert config[key] == old_cfg[key], key
    assert config["guarantees"][:4] == old_cfg["guarantees"]
    old = harness.load_json(os.path.join(BENCH, "traffic",
                                         "degraded-get-zipf.json"))
    assert {**traffic["population"], "volumes": 1} == old["population"]
    assert traffic["population"]["volumes"] == 2
    assert {k: v for k, v in traffic["load"].items() if k != "rate_per_s"} \
        == {k: v for k, v in old["load"].items() if k != "rate_per_s"}
    assert traffic["load"]["rate_per_s"] <= old["load"]["rate_per_s"]
    assert traffic["load"]["rate_per_s"] % 10 == 0
    reported = [m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [])]
    for name in reported:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py")), name
    assert {"gf_encode_roofline.bg", "gf_reconstruct_roofline.fg",
            "warmdown.input_gbps.bg", "warmdown.in_flight_pct.bg",
            "warmdown.link_ms_per_batch.bg",
            "warmdown.disk_ms_per_batch.bg"} <= set(reported)
    # both divide all device-busy time by the GET side's work
    assert not {"gf_apply_roofline.get",
                "kernel.busy_ms_per_interval.get"} & set(reported)
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "get_p50_ms")["workloads"]
