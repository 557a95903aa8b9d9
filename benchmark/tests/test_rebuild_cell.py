"""What `fg-under-rebuild-4srv` adds beside the harness: the five readers
of the background rebuild on hand-made counter deltas and on a recorded
trace (the value; None where the program has no such counter, as the
parent commit), the declaration, and the cell through `run.py` without a
chip: correct as it stands, not correct under its control by the GET
check and by the rebuilt-file check, not correct with the rebuild broken
underneath it. `tests/test_rebuild_reference.py` holds the plain
reference to `reference.py` and to the program's rebuild."""

import gzip
import importlib.util
import json
import os

import numpy as np
import pytest

import reduce
import run as harness
from conftest import ROOT

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "fg-under-rebuild-4srv"
K, M = 10, 4
STAGE = "seaweedfs_tpu_ec_stage_seconds"
REBUILT = "seaweedfs_tpu_ec_rebuild_input_bytes_total"
BATCHES = "seaweedfs_tpu_ec_rebuild_batches_total"
# a trace of the apply kernel with m rows out (the warm-down cell's; the
# rebuild runs the same program with the decode rows as its operand)
TRACE = os.path.join(BENCH, "tests", "data",
                     "fg-under-warmdown.trace.json.gz")
NEW = ("rebuild.in_flight_pct.bg", "rebuild.input_gbps.bg",
       "rebuild.copy_ms_per_pass.bg", "rebuild.link_ms_per_batch.bg",
       "gf_rebuild_roofline.bg")


def reader(name: str):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --- the readers ---

# a window of 25 passes, each of two [10, 8 MiB] batches and six 16 MiB
# survivor files pulled
PASS_BYTES = K * 16 * (1 << 20)
FULL = {REBUILT: 25.0 * PASS_BYTES, BATCHES: 50.0,
        f'{STAGE}_sum{{stage="ec.rebuild"}}': 10.0,
        f'{STAGE}_count{{stage="ec.rebuild"}}': 25.0,
        f'{STAGE}_sum{{stage="ec.copy"}}': 20.0,
        f'{STAGE}_count{{stage="ec.copy"}}': 150.0,
        f'{STAGE}_sum{{stage="ec.dispatch"}}': 0.5,
        f'{STAGE}_sum{{stage="ec.kernel"}}': 1.5}
WANT = {"rebuild.in_flight_pct.bg": 75.0,
        "rebuild.input_gbps.bg": 25 * PASS_BYTES / 40.0 / 1e9,
        "rebuild.copy_ms_per_pass.bg": 800.0,
        "rebuild.link_ms_per_batch.bg": 40.0}


def run_of(counters: dict, window_s: float = 40.0) -> dict:
    return {"counters": counters, "facts": {"rebuild_rows_out": M},
            "trace": {"window_s": window_s, "device_ops": {}}}


@pytest.mark.parametrize("name", sorted(WANT))
def test_counter_reader_value(name):
    assert reader(name)(run_of(FULL)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_none_without_the_counters(name):
    # the parent commit: the pipeline's stages count, the two counters,
    # `ec.rebuild` and `ec.copy` do not exist
    parent = {k: v for k, v in FULL.items()
              if k not in (REBUILT, BATCHES) and "ec.rebuild" not in k
              and "ec.copy" not in k}
    run = run_of(parent)
    run.update(config={"geometry": "10+4"}, device_kind="TPU v5 lite",
               chips=1)
    assert reader(name)(run) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_counter_reader_none_on_a_divisor_of_zero(name):
    got = reader(name)(run_of({
        **FULL, BATCHES: 0.0, f'{STAGE}_count{{stage="ec.rebuild"}}': 0.0},
        window_s=0.0))
    assert got is None


def recorded() -> dict:
    with gzip.open(TRACE, "rt") as f:
        return json.load(f)


def test_roofline_reads_its_own_operations_and_the_counter():
    """Least time for the columns the counter says were coded, k in and m
    out, over the device time of the operations with m rows out and of
    no other."""
    rec = recorded()
    summary = reduce.summarize(rec["trace"])
    import warmdown_readers
    secs = warmdown_readers.kernel_seconds(summary["device_ops"], M)
    assert secs > 0
    columns = rec["facts"]["encode_columns"]
    run = {"trace": summary, "facts": {"rebuild_rows_out": M},
           "counters": {REBUILT: K * columns}, "config": rec["config"],
           "device_kind": rec["device_kind"], "chips": 1}
    got = reader("gf_rebuild_roofline.bg")(run)
    least, bound = reduce.least_seconds(reduce.gf_apply_work(K, M, columns),
                                        rec["device_kind"])
    assert bound == "hbm"
    assert got == pytest.approx(100.0 * least / secs)
    assert 0.0 < got < 100.0
    # the same arithmetic as the encode's share
    assert got == pytest.approx(rec["reported"]["gf_encode_roofline.bg"])
    # nothing coded, or no operation of m rows out: nothing to read
    run["counters"] = {REBUILT: 0.0}
    assert reader("gf_rebuild_roofline.bg")(run) is None
    run["counters"] = {REBUILT: K * columns}
    run["trace"] = {**summary, "device_ops": {
        n: v for n, v in summary["device_ops"].items() if "= u8[4," not in n}}
    assert reader("gf_rebuild_roofline.bg")(run) is None


# --- the declaration ---

def test_the_cell_is_declared_as_data_the_harness_can_resolve():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, config, traffic = harness.resolve_cell(bench, CELL)
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and len(cfg["source"]) <= 200
    assert "ec.rebuild -force" in cfg["source"]
    assert sorted(cfg["reduced"]) == sorted(config["reduced"])
    assert os.path.isfile(os.path.join(BENCH, "drivers",
                                       traffic["driver"] + ".py"))
    # the four-server configuration letter for letter, two guarantees on
    old_cfg = harness.load_json(os.path.join(
        BENCH, "configs", "seaweed-4srv-rs10-4.json"))
    for key in ("geometry", "geometry_policy", "collection",
                "large_block_bytes", "small_block_bytes", "object_bytes",
                "objects_per_volume", "volume_servers", "shards_per_server"):
        assert config[key] == old_cfg[key], key
    assert config["guarantees"][:5] == old_cfg["guarantees"]
    assert len(config["guarantees"]) == 7
    # and its requests: the two cells differ by the background alone
    old = harness.load_json(os.path.join(BENCH, "traffic",
                                         "degraded-get-zipf-4srv.json"))
    assert {**traffic["population"], "volumes": 1} == old["population"]
    assert {k: v for k, v in traffic["load"].items() if k != "rate_per_s"} \
        == {k: v for k, v in old["load"].items() if k != "rate_per_s"}
    assert traffic["background"]["max_gap_share"] == 0.2
    reported = [m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [])]
    for name in reported:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py")), name
    assert set(NEW) <= set(reported)
    assert bench["per_layer"][-len(NEW):] == [
        m for m in bench["per_layer"] if m["name"] in NEW]
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "get_p50_ms"
    assert sum(n.startswith("ec_get.") for n in reported) == 10
    assert sum(n.startswith("remote.") for n in reported) == 4
    # these would charge the rebuild's work to the GETs
    assert not {"server.cpu_ms_per_get.get",
                "reconstruct.host_ms_per_interval.get",
                "gf_apply_roofline.get",
                "kernel.busy_ms_per_interval.get"} & set(reported)
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "get_p50_ms")["workloads"]


# --- the cell through run.py, without a chip ---

def run_cell(control: bool = False, seed: int = 2 ** 31 + 39):
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    out = harness.run_cell(bench, CELL, seed, 4.0, trace=False,
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
    assert facts["rebuild_input_bytes_from"] == "program_counter"
    # the program's counter and the driver's own count of passes agree
    # to within the passes that straddle the window's ends
    assert facts["rebuild_input_bytes"] == pytest.approx(
        facts["rebuild_input_bytes_by_passes"], rel=0.5)
    assert facts["copy_s_a_pass"] > 0 and facts["rebuild_s_a_pass"] > 0
    assert facts["admin_requests"] > 0 and facts["rebuild_rows_out"] == M
    assert out["compiled_in_window"] == {"backend_compile_duration": 0,
                                         "jaxpr_to_mlir_module_duration": 0}


def test_control_is_not_correct_by_either_check():
    out = run_cell(control=True)
    assert not out["correct"], out["checks"]
    got = checks(out)
    assert got["gets_wrong"] > 0
    assert got["pass_files_differing"] \
        == out["facts"]["control_pass_files_differing"] \
        * out["facts"]["passes_verified"] > 0
    # nothing else is at fault
    assert {k for k, v in got.items() if v and k != "gap_share"} \
        == {"gets_wrong", "pass_files_differing"}


def test_broken_rebuild_is_not_correct(monkeypatch):
    """A rebuilt row altered where the rebuild hands it to its writers."""
    from seaweedfs_tpu.ec import pipeline
    real = pipeline._FanOut.put_rows

    def put_rows(self, rows, on_done=None):
        rows = list(rows)
        if len(rows) == M:  # the rebuild's; an encode's has k + m
            bad = np.array(rows[1], copy=True)
            bad[0] ^= 0x5A
            rows[1] = bad
        return real(self, iter(rows), on_done=on_done)
    monkeypatch.setattr(pipeline._FanOut, "put_rows", put_rows)
    out = run_cell()
    assert not out["correct"], out["checks"]
    assert checks(out)["pass_files_differing"] \
        == out["facts"]["passes_verified"]
