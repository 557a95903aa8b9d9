"""bench.py per-phase incremental checkpointing (VERDICT r5: a timed-out
phase nulled the whole BENCH_DETAIL.json record two rounds running — now
each phase lands on disk the moment it completes)."""

import json
import os
import sys


def _bench():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench
    return bench


def test_checkpoint_writes_partial_record(tmp_path):
    bench = _bench()
    path = str(tmp_path / "BENCH_DETAIL.json")
    detail = {"incomplete": True,
              "fused_compact_gzip_rs": {"gbps": 1.5}}
    bench._checkpoint(detail, path=path)
    got = json.load(open(path))
    assert got["fused_compact_gzip_rs"]["gbps"] == 1.5
    assert got["incomplete"] is True

    # a later phase extends the same record; earlier numbers survive
    detail["multichip"] = {"scaling": {}}
    bench._checkpoint(detail, path=path)
    got = json.load(open(path))
    assert got["fused_compact_gzip_rs"]["gbps"] == 1.5
    assert got["multichip"]["scaling"] == {}


def test_checkpoint_is_atomic(tmp_path):
    """The write goes through a tmp file + os.replace: a reader never
    sees a torn record, and a failed write leaves the old one intact."""
    bench = _bench()
    path = str(tmp_path / "BENCH_DETAIL.json")
    bench._checkpoint({"phase": 1}, path=path)
    # unwritable tmp target: the old record must survive
    bench._checkpoint({"phase": 2},
                      path=str(tmp_path / "nodir" / "x.json"))
    assert json.load(open(path)) == {"phase": 1}
    assert not os.path.exists(path + ".tmp")


def test_main_checkpoints_every_phase(monkeypatch, tmp_path, capsys):
    """Drive bench.main() with every phase stubbed: each phase completes
    -> the on-disk record already contains it (and a phase that 'hangs'
    forever would still leave all earlier phases on disk)."""
    bench = _bench()
    path = str(tmp_path / "BENCH_DETAIL.json")
    monkeypatch.setattr(bench, "DETAIL_PATH", path)
    snapshots = []

    def fake_phase(name, work, timeout_s):
        if os.path.exists(path):
            snapshots.append(set(json.load(open(path))))
        return {"gbps": 1.0, "phase_wall_s": 0.1}

    monkeypatch.setattr(bench, "_run_phase", fake_phase)
    monkeypatch.setattr(bench, "bench_system",
                        lambda w: {"write": {"req_s": 1},
                                   "read": {"req_s": 1}})
    monkeypatch.setattr(bench, "bench_needle_map", lambda w: {})
    monkeypatch.setattr(bench, "phase_saturation",
                        lambda w, **k: {"host_cores": 1, "shards": 2})
    monkeypatch.setattr(bench, "HARD_BUDGET_S", 10_000.0)
    bench.main()

    # the subprocess phases are fused, then multichip: multichip saw
    # the checkpoint of fused and of every inline phase between them
    assert len(snapshots) == 1
    assert {"fused_compact_gzip_rs", "system_req_s", "saturation",
            "georepl"} <= snapshots[0]
    final = json.load(open(path))
    assert "incomplete" not in final
    for key in ("fused_compact_gzip_rs", "system_req_s", "saturation",
                "multichip", "disk_needle_map"):
        assert key in final, key
    for key in ("encode", "kernel_phase", "rebuild", "volume_bytes"):
        assert key not in final, key
    # the last printed line names what ran, and carries no GB/s headline
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "fused_compact_gzip_rs" in last["phases"]
    assert "value" not in last and "metric" not in last
