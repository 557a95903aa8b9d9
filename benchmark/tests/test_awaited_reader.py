"""The reader of the loop's awaited peer reads, on hand-made runs: the
value; None where the program has no such counter (a parent commit) or
the window declined nothing."""

import importlib.util
import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "remote.awaited_pct.get"
DECLINED = "seaweedfs_tpu_volume_ec_read_declined_total"


def read(counters: dict):
    path = os.path.join(BENCH, "layer_metrics", NAME + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read({"counters": counters,
                     "facts": {"gets_completed": 1000}})


def declined(awaited, handed_on) -> dict:
    return {f'{DECLINED}{{then="awaited"}}': awaited,
            f'{DECLINED}{{then="executor"}}': handed_on}


@pytest.mark.parametrize("counters,want", [
    (declined(781.0, 440.0), 100.0 * 781 / 1221),
    # a window in which every declined read was handed on is a 0
    (declined(0.0, 440.0), 0.0),
    (declined(12.0, 0.0), 100.0),
])
def test_value(counters, want):
    assert read(counters) == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    {},  # a program without the counter, as a parent
    {'seaweedfs_tpu_volume_ec_read_nowait_total{result="declined"}': 9.0},
    {f'{DECLINED}{{then="awaited"}}': 5.0},  # one label set alone
    declined(0.0, 0.0),  # nothing declined in the window
])
def test_none_where_there_is_nothing_to_read(counters):
    assert read(counters) is None


def test_benchmark_json_lists_the_reader_in_the_two_cluster_cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    entry = bench["per_layer"][names.index(NAME)]
    # appended after the entries that were there, not inserted
    assert names.index(NAME) > names.index("gf_rebuild_roofline.bg")
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "remote shard read (server/volume_server.py)",
        "moves": "get_p50_ms",
        "workloads": ["degraded-get-4srv-rs10-4", "fg-under-rebuild-4srv"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells
