"""What the four-server cell adds beside the harness: the plain
reference of the spread and of the seed's layout, and the readers of the
remote-read counters on hand-made runs (the value; None where the
program has no such counter, as the parent commit; None on a divisor of
zero)."""

import importlib.util
import json
import os

import pytest

import datagen
import reference_cluster

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "degraded-get-4srv-rs10-4"
READS = "seaweedfs_tpu_volume_ec_remote_shard_reads_total"
LOOKUPS = "seaweedfs_tpu_volume_ec_shard_location_lookups_total"
STAGE = "seaweedfs_tpu_ec_stage_seconds"


def reader(name: str):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_balanced_distribution_is_round_robin_over_free_servers():
    assert reference_cluster.balanced_distribution([8] * 4, 14) == [
        [0, 4, 8, 12], [1, 5, 9, 13], [2, 6, 10], [3, 7, 11]]
    # a server without a free slot is passed over
    assert reference_cluster.balanced_distribution([8, 0, 8], 5) == [
        [0, 2, 4], [], [1, 3]]
    # one that runs out stops taking
    assert reference_cluster.balanced_distribution([1, 8], 4) == [
        [0], [1, 2, 3]]
    with pytest.raises(ValueError):
        reference_cluster.balanced_distribution([0, 0], 14)


@pytest.mark.parametrize("seed", [0, 24, 3100000211, 2**31 + 17])
def test_seed_layout_partitions_the_shards_and_kills_the_seeds_loss(seed):
    perm = datagen.shard_permutation(seed, 0, 10).tolist()
    lost = datagen.lost_shards(seed, 0, 10, 4, 3, 1)
    layout = reference_cluster.seed_layout(perm, lost, 10, 4)
    assert layout["doomed"] == lost
    assert [len(layout[r]) for r in ("doomed", "chip", "peer_a",
                                     "peer_b")] == [4, 4, 3, 3]
    assert sorted(s for sids in layout.values() for s in sids) \
        == list(range(14))
    assert sorted(layout["chip"][:3]) == sorted(perm[3:6])
    assert all(s >= 10 for s in (layout["chip"][3], layout["peer_a"][2],
                                 layout["peer_b"][2]))


def test_seed_layout_refuses_another_loss():
    perm = list(range(10))
    with pytest.raises(ValueError):  # not perm's first data shards
        reference_cluster.seed_layout(perm, [1, 2, 3, 10], 10, 4)
    with pytest.raises(ValueError):  # two parity shards
        reference_cluster.seed_layout(perm, [0, 1, 10, 11], 10, 4)


def test_misplaced_counts_both_ways():
    want = {"a": [0, 1], "b": [2]}
    assert reference_cluster.misplaced({"a": [0, 1], "b": [2]}, want) == 0
    assert reference_cluster.misplaced({"a": [0], "b": [1, 2]}, want) == 2
    assert reference_cluster.misplaced({"a": [0, 1], "b": [2],
                                        "dead": [3]}, want) == 1


def run_of(counters: dict, gets: int = 1000) -> dict:
    return {"counters": counters, "facts": {"gets_completed": gets}}


FULL = {f'{READS}{{via="grpc"}}': 2300.0, f'{READS}{{via="http"}}': 100.0,
        f'{LOOKUPS}{{result="none"}}': 1200.0,
        f'{LOOKUPS}{{result="holder"}}': 0.0,
        f'{STAGE}_sum{{stage="ec.get.remote_read"}}': 4.8,
        f'{STAGE}_count{{stage="ec.get.remote_read"}}': 2400.0}
WANT = {"remote.reads_per_get.get": 2.4,
        "remote.lookups_per_get.get": 1.2,
        "remote.read_ms_per_read.get": 2.0}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_value(name):
    assert reader(name)(run_of(FULL)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_none_without_the_counters(name):
    assert reader(name)(run_of({"some_other_total": 5.0})) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_none_on_a_divisor_of_zero(name):
    run = run_of({**FULL,
                  f'{STAGE}_count{{stage="ec.get.remote_read"}}': 0.0},
                 gets=0)
    assert reader(name)(run) is None


def test_the_cell_is_declared_as_data_the_harness_can_resolve():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert cell["chips"] == 1 and config["volume_servers"] == 4
    assert "volume_servers" not in config["reduced"]
    assert sorted(cfg["reduced"]) == sorted(config["reduced"])
    assert os.path.isfile(os.path.join(BENCH, "drivers",
                                       traffic["driver"] + ".py"))
    # the old cell's objects, population and connections
    with open(os.path.join(BENCH, "traffic",
                           "degraded-get-zipf.json")) as f:
        old = json.load(f)
    assert traffic["population"] == old["population"]
    assert {k: v for k, v in traffic["load"].items() if k != "rate_per_s"} \
        == {k: v for k, v in old["load"].items() if k != "rate_per_s"}
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert os.path.isfile(os.path.join(
                BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "get_p50_ms")["workloads"]
