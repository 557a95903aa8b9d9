"""The readers of what PR 31 added to an EC GET, on hand-made runs: the
value; None where the program has no such counter (a parent commit);
None on a divisor of zero."""

import importlib.util
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = "seaweedfs_tpu_ec_stage_seconds"
NOWAIT = "seaweedfs_tpu_volume_ec_read_nowait_total"


def reader(name: str):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_of(counters: dict, gets: int = 1000) -> dict:
    return {"counters": counters, "facts": {"gets_completed": gets}}


def nowait(served, declined) -> dict:
    return {f'{NOWAIT}{{result="served"}}': served,
            f'{NOWAIT}{{result="declined"}}': declined}


RESUME = {f'{FAMILY}_sum{{stage="ec.get.resume"}}': 0.25}


@pytest.mark.parametrize("name,counters,want", [
    ("ec_get.resume_ms_per_get.get", RESUME, 0.25),
    ("ec_get.on_loop_pct.get", nowait(657.0, 343.0), 65.7),
    # a window in which every read was handed on is a 0, not an absence
    ("ec_get.on_loop_pct.get", nowait(0.0, 1000.0), 0.0),
    ("ec_get.on_loop_pct.get", nowait(1000.0, 0.0), 100.0),
])
def test_value(name, counters, want):
    assert reader(name)(run_of(counters)) == pytest.approx(want)


@pytest.mark.parametrize("name,counters,gets", [
    # a program without the stage family or the counter, as a parent
    ("ec_get.resume_ms_per_get.get", {}, 1000),
    ("ec_get.on_loop_pct.get", {}, 1000),
    ("ec_get.on_loop_pct.get", RESUME, 1000),
    # one label set alone is not the counter this reader knows
    ("ec_get.on_loop_pct.get",
     {f'{NOWAIT}{{result="served"}}': 5.0}, 1000),
    # a divisor of zero
    ("ec_get.resume_ms_per_get.get", RESUME, 0),
    ("ec_get.on_loop_pct.get", nowait(0.0, 0.0), 1000),
])
def test_none_where_there_is_nothing_to_read(name, counters, gets):
    assert reader(name)(run_of(counters, gets)) is None


@pytest.mark.parametrize("name", ["ec_get.resume_ms_per_get.get",
                                  "ec_get.on_loop_pct.get"])
def test_benchmark_json_lists_the_reader_in_both_cells(name):
    import json
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["layer"] == "EC read, host path"
    assert entry["moves"] == "get_p50_ms"
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    assert bench["per_layer"].index(entry) >= 16  # appended, not inserted
