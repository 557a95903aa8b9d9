"""The program's stages on the device trace's own clock, on a second
trace recorded on the chip (`run.py --workload degraded-get-rs10-4 --seed
3000000251 --seconds 10 --trace 1 --keep-trace ...`, TPU v5 lite, PR 25:
2,000 GETs, 548 of them on a lost shard, one of those riding another's
reconstruction: 547 launches), beside that window's deltas of the stage
counters on /metrics: one instrument, two sinks."""

import json
import os

import pytest

import reduce

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "degraded-get-rs10-4.stages.trace.json.gz")
COUNTERS = os.path.join(DATA, "degraded-get-rs10-4.stages.counters.json")

PER_GET = ("ec.get", "ec.get.handler", "ec.get.ecx", "ec.get.parse")
PER_INTERVAL = ("ec.get.peer_fetch", "ec.get.survivors", "ec.get.dispatch",
                "ec.get.d2h_wait")


@pytest.fixture(scope="module")
def summary():
    return reduce.summarize(reduce.load_recorded(RECORDED))


@pytest.fixture(scope="module")
def counters():
    with open(COUNTERS) as f:
        return json.load(f)


def test_stages_are_host_events_of_the_trace(summary, counters):
    (_, (launches, _)), = summary["device_ops"].items()
    assert launches == 547
    assert launches == counters[
        "seaweedfs_tpu_ec_reconstruct_intervals_total"]
    assert launches + counters['seaweedfs_tpu_ec_stage_seconds_count'
                               '{stage="ec.get.flight_wait"}'] == 548
    for stage in PER_GET:
        assert summary["host"][stage][0] == 2000, stage
    for stage in PER_INTERVAL:
        assert summary["host"][stage][0] == launches, stage
    # the stack, then the pad to the 16 KiB bucket: two copies a launch
    assert summary["host"]["ec.get.stack_pad"][0] == 2 * launches
    assert summary["host"]["ec.get.shard_read"][0] == 2000 - 548
    # these begin on one thread and end on another, or are known for
    # what they were only when they are over: the record form, which
    # reaches the ring and the counters and not the profiler
    for stage in ("ec.get.queue", "ec.get.resume", "ec.get.flight_wait"):
        assert stage not in summary["host"]


def test_most_idle_time_is_labelled_by_a_stage(summary):
    gaps = summary["idle_gaps"]
    idle = summary["window_s"] - summary["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle)
    by_stage = sum(s for name, s in gaps.items()
                   if name.startswith("ec.get"))
    assert max(gaps, key=gaps.get) == "ec.get"
    assert by_stage > 0.7 * idle
    assert gaps["no annotation"] < 0.3 * summary["window_s"]


def test_stages_sit_above_the_launch_they_make(summary):
    # per launch PJRT's own events lie inside the stage that made them
    host = summary["host"]
    assert host["ec.get.dispatch"][1] >= host["PjitFunction(apply_fn)"][1]
    assert host["ec.get.d2h_wait"][1] >= host["np.asarray(jax.Array)"][1]
    assert host["ec.get.d2h_wait"][1] < 1.25 * host[
        "np.asarray(jax.Array)"][1]


def seconds(counters: dict, stage: str) -> float:
    return counters[f'seaweedfs_tpu_ec_stage_seconds_sum{{stage="{stage}"}}']


@pytest.mark.parametrize("stage", [
    "ec.get.ecx", "ec.get.peer_fetch", "ec.get.stack_pad",
    "ec.get.dispatch", "ec.get.d2h_wait"])
def test_counter_and_trace_agree(summary, counters, stage):
    """Stages of a worker thread, a tenth of a millisecond and up: the
    sum on /metrics and the trace's seconds are one pair of clock reads
    and the annotation around it."""
    assert summary["host"][stage][1] == pytest.approx(
        seconds(counters, stage), rel=0.01)
    count = counters[
        f'seaweedfs_tpu_ec_stage_seconds_count{{stage="{stage}"}}']
    assert summary["host"][stage][0] == count


def test_where_the_two_sinks_differ_and_why(summary, counters):
    host = summary["host"]
    # a stage of a few microseconds: the annotation opens before and
    # closes after the clock pair, ~1.5 us more a stage
    for stage in ("ec.get.shard_read", "ec.get.parse"):
        extra = (host[stage][1] - seconds(counters, stage)) / host[stage][0]
        assert 0 < extra < 3e-6, (stage, extra)
    # the enclosing stages live on the event loop's thread, where
    # concurrent GETs overlap: `summarize` counts a name's overlapping
    # events on one thread once, the counter sums every GET's residence
    for stage in ("ec.get", "ec.get.handler"):
        assert host[stage][1] < 0.7 * seconds(counters, stage)
    assert host["ec.get"][1] < summary["window_s"]
