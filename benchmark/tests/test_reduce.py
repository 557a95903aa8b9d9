"""The reduction from a trace to numbers, on a trace recorded on the chip
(`run.py --workload degraded-get-rs10-4 --seed 3000000025 --seconds 10
--trace 1 --keep-trace ...`, TPU v5 lite, PR 24: 2,000 GETs, 548 of them
on a lost shard)."""

import os

import pytest

import reduce

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "degraded-get-rs10-4.trace.json.gz")


@pytest.fixture(scope="module")
def summary():
    return reduce.summarize(reduce.load_recorded(RECORDED))


def test_window_busy_and_ops(summary):
    assert summary["window_s"] == pytest.approx(10.312723712)
    assert summary["busy_s"] == pytest.approx(0.001824508)
    assert summary["devices_traced"] == 1
    (name, (count, seconds)), = summary["device_ops"].items()
    # two of the 548 reads shared another's flight
    assert "gf_apply" in name and count == 546
    assert seconds == pytest.approx(summary["busy_s"])


def test_host_spans_are_read(summary):
    assert summary["host"]["np.asarray(jax.Array)"] == [
        546, pytest.approx(0.591707042)]
    assert summary["host"]["DevicePut"] == [546, pytest.approx(0.12158705)]


def test_idle_gaps_cover_the_whole_idle_time(summary):
    idle = summary["window_s"] - summary["busy_s"]
    assert sum(summary["idle_gaps"].values()) == pytest.approx(idle)
    assert max(summary["idle_gaps"], key=summary["idle_gaps"].get) \
        == "no annotation"


def test_layer_readers_on_the_recorded_run(summary):
    # what run.py hands a reader, with the facts of the recorded run
    run = {"trace": summary, "device_kind": "TPU v5 lite", "chips": 1,
           "config": {"geometry": "10+4"}, "counters": {},
           "facts": {"columns_coded": 561152, "rows_out": 1}}
    assert reduce.idle_pct(run) == pytest.approx(99.9823081850057)
    # 11 bytes a column over 819 GB/s against the device-busy time
    assert reduce.roofline_pct(run) == pytest.approx(
        100 * 561152 * 11 / 819e9 / 0.001824508)
    empty = dict(run, trace=dict(summary, busy_s=0.0, devices_traced=0))
    assert reduce.roofline_pct(empty) is None  # never 0 for a share
    assert reduce.idle_pct(empty) is None


def test_breakdown_is_short_and_named(summary):
    b = reduce.breakdown(summary)
    assert b["device_ops"][0][0] == "gf_apply.1 u8[1,16384]"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_roofline_arithmetic():
    work = reduce.gf_apply_work(10, 4, 1000)
    assert work == {"hbm_bytes": 14000, "int8_ops": 2 * 64 * 10 * 4 * 1000}
    least, bound = reduce.least_seconds(work, "TPU v5 lite")
    assert bound == "hbm" and least == pytest.approx(14000 / 819e9)
    with pytest.raises(KeyError):
        reduce.peaks("TPU v9 imaginary")


def test_union_and_overlap():
    merged = reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [(0, 3), (5, 8)]
    assert reduce.overlap(2, 6, merged) == 2
    assert reduce.overlap(3, 5, merged) == 0
    assert reduce.overlap(-1, 9, merged) == 6


def test_extract_reads_a_fresh_trace(tmp_path):
    """`extract` on a trace made here (CPU): the annotation is found and
    the session's own start and stop give the window."""
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("ec_pipeline_dispatch"):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    s = reduce.summarize(reduce.find_xplane(str(tmp_path)))
    assert s["window_s"] > 0 and s["devices_traced"] == 0
    assert s["host"]["ec_pipeline_dispatch"][0] == 1
