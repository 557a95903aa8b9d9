"""The readers of the program's stage counters, on hand-made runs: the
values, None where a counter is absent (a parent commit has none), None
where the divisor is 0."""

import importlib.util
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = "seaweedfs_tpu_ec_stage_seconds"
INTERVALS = "seaweedfs_tpu_ec_reconstruct_intervals_total"

# seconds over a window of 1,000 GETs, 400 of them reconstructing
SECONDS = {"ec.get": 8.0, "ec.get.handler": 5.0, "ec.get.queue": 0.5,
           "ec.get.ecx": 0.2, "ec.get.shard_read": 0.1,
           "ec.get.parse": 0.3, "ec.get.resume": 0.6,
           "ec.get.peer_fetch": 0.8,
           "ec.get.survivors": 0.4, "ec.get.stack_pad": 0.2,
           "ec.get.dispatch": 0.6, "ec.get.d2h_wait": 1.0}

WANT = {
    "ec_get.server_ms_per_get.get": 8.0,
    "ec_get.second_plane_ms_per_get.get": 3.0,
    "ec_get.queue_ms_per_get.get": 0.5,
    "ec_get.read_parse_ms_per_get.get": 0.6,
    "reconstruct.host_ms_per_interval.get": 3.5,
    "reconstruct.dispatch_ms_per_interval.get": 1.5,
    "reconstruct.d2h_wait_ms_per_interval.get": 2.5,
    # handler 5.0 less the 4.7 the exclusive stages name, over 8.0
    "ec_get.unnamed_pct.get": 100 * 0.3 / 8.0,
}


def reader(name: str):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_of(seconds: dict, gets: int = 1000, intervals: float = 400.0):
    counters = {f'{FAMILY}_sum{{stage="{k}"}}': v
                for k, v in seconds.items()}
    counters[INTERVALS] = intervals
    return {"counters": counters, "facts": {"gets_completed": gets}}


@pytest.mark.parametrize("name", sorted(WANT))
def test_value(name):
    assert reader(name)(run_of(SECONDS)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_none_without_the_counters(name):
    # a program that has no stage family, as the parent commit
    run = {"counters": {INTERVALS: 400.0},
           "facts": {"gets_completed": 1000}}
    assert reader(name)(run) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_none_on_a_divisor_of_zero(name):
    run = run_of(SECONDS, gets=0, intervals=0.0)
    if name == "ec_get.unnamed_pct.get":  # its divisor is S(ec.get)
        run = run_of({**SECONDS, "ec.get": 0.0})
    assert reader(name)(run) is None


def test_stages_a_window_may_lack_count_as_zero():
    # nobody rode a flight, no peer was asked: those families are unborn
    lean = {k: v for k, v in SECONDS.items()
            if k not in ("ec.get.flight_wait", "ec.get.peer_fetch")}
    assert reader("reconstruct.host_ms_per_interval.get")(run_of(lean)) \
        == pytest.approx(1.5)
    assert reader("ec_get.unnamed_pct.get")(run_of(lean)) \
        == pytest.approx(100 * 1.1 / 8.0)
    # but not the ones every EC GET takes
    bare = {k: v for k, v in SECONDS.items() if k != "ec.get.ecx"}
    assert reader("ec_get.unnamed_pct.get")(run_of(bare)) is None
    assert reader("ec_get.read_parse_ms_per_get.get")(run_of(bare)) is None
