"""The reader of PR 33's counter (`remote.call_reuse_pct.get`) on hand-made
runs: the percentage; None where the program has no such counter (a
parent commit) and where no remote read was made; and its entry in
`BENCHMARK.json` names a reader and a cell that exist."""

import importlib.util
import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "remote.call_reuse_pct.get"
CALLS = "seaweedfs_tpu_volume_ec_peer_call_total"
READS = "seaweedfs_tpu_volume_ec_remote_shard_reads_total"


def reader():
    path = os.path.join(BENCH, "layer_metrics", NAME + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_of(counters: dict) -> dict:
    return {"counters": counters, "facts": {"gets_completed": 1592}}


def calls(reused, built) -> dict:
    return {f'{CALLS}{{result="reused"}}': reused,
            f'{CALLS}{{result="built"}}': built}


@pytest.mark.parametrize("counters,want", [
    (calls(3401.0, 0.0), 100.0),   # the stubs were built in set-up
    (calls(3399.0, 2.0), 100.0 * 3399 / 3401),
    (calls(1.0, 1.0), 50.0),
    # a window in which every read dialled is a 0, not an absence
    (calls(0.0, 2.0), 0.0),
])
def test_value(counters, want):
    assert reader()(run_of(counters)) == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    {},                                       # a parent commit
    {f'{READS}{{via="grpc"}}': 3401.0},       # reads, and no such family
    {f'{CALLS}{{result="reused"}}': 5.0},     # one label set alone
    calls(0.0, 0.0),                          # a cell with no remote read
])
def test_none_where_there_is_nothing_to_read(counters):
    assert reader()(run_of(counters)) is None


def test_benchmark_json_names_a_reader_and_a_cell_that_exist():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert bench["per_layer"][-1] == entry  # appended, not inserted
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "remote shard read (server/volume_server.py)",
        "moves": "get_p50_ms", "workloads": ["degraded-get-4srv-rs10-4"]}
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                       NAME + ".py"))
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells
    # the layer is one the benchmark already names, letter for letter
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"][:-1]}
