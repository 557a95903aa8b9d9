"""Prometheus exposition correctness goldens (utils/metrics.py):
label escaping, histogram bucket monotonicity, label support on
histograms, and # TYPE lines appearing exactly once per metric family
with the family's samples contiguous.
"""

import re

from seaweedfs_tpu.utils.metrics import _BUCKETS, Registry


def test_label_escaping_golden():
    r = Registry("gold")
    r.count("reads", labels={"collection": 'we"ird\\name\nx'})
    text = r.render()
    assert ('seaweedfs_tpu_gold_reads_total'
            '{collection="we\\"ird\\\\name\\nx"} 1.0') in text


def test_histogram_bucket_monotonicity_and_count():
    r = Registry("gold")
    samples = [0.00005, 0.0005, 0.005, 0.05, 0.5, 5.0, 50.0, 0.05, 0.05]
    for s in samples:
        r.observe("lat", s)
    text = r.render()
    bucket_counts = [
        int(m.group(1)) for m in re.finditer(
            r'seaweedfs_tpu_gold_lat_seconds_bucket\{le="[^"]+"\} (\d+)',
            text)]
    assert len(bucket_counts) == len(_BUCKETS) + 1  # finite buckets + +Inf
    assert bucket_counts == sorted(bucket_counts)  # cumulative
    assert bucket_counts[-1] == len(samples)  # +Inf == count
    assert (f"seaweedfs_tpu_gold_lat_seconds_count {len(samples)}"
            in text)
    total = float(re.search(
        r"seaweedfs_tpu_gold_lat_seconds_sum ([0-9.]+)", text).group(1))
    assert abs(total - sum(samples)) < 1e-9


def test_labeled_histograms_render_with_le_merged():
    r = Registry("gold")
    r.observe("read", 0.002, labels={"collection": "photos"})
    r.observe("read", 0.02, labels={"collection": "photos"})
    r.observe("read", 0.2, labels={"collection": "docs"})
    r.observe("read", 0.2)  # unlabeled family member
    with r.timed("read", labels={"collection": "photos"}):
        pass
    text = r.render()
    assert ('seaweedfs_tpu_gold_read_seconds_bucket'
            '{collection="photos",le="+Inf"} 3') in text
    assert ('seaweedfs_tpu_gold_read_seconds_bucket'
            '{collection="docs",le="+Inf"} 1') in text
    assert ('seaweedfs_tpu_gold_read_seconds_bucket{le="+Inf"} 1'
            in text)
    assert ('seaweedfs_tpu_gold_read_seconds_count{collection="docs"} 1'
            in text)
    # per-label-set counts stay separate
    assert ('seaweedfs_tpu_gold_read_seconds_count'
            '{collection="photos"} 3') in text


def test_type_lines_once_per_family_and_contiguous():
    r = Registry("gold")
    # interleaving-prone names: 'read' + labels sorts around 'read2'
    r.count("read")
    r.count("read", labels={"collection": "z"})
    r.count("read2")
    r.gauge("read", 1.0)  # same name, different kind: its own TYPE line
    r.observe("read", 0.01)
    r.observe("read", 0.01, labels={"collection": "z"})
    r.observe("read2", 0.01)
    text = r.render()
    type_lines = [ln for ln in text.splitlines()
                  if ln.startswith("# TYPE")]
    assert len(type_lines) == len(set(type_lines))
    assert text.count("# TYPE seaweedfs_tpu_gold_read_total counter") == 1
    assert text.count("# TYPE seaweedfs_tpu_gold_read gauge") == 1
    assert (text.count("# TYPE seaweedfs_tpu_gold_read_seconds histogram")
            == 1)
    # samples of one family must be contiguous: every sample line belongs
    # to the family named by the most recent # TYPE line
    current = None
    for ln in text.splitlines():
        if not ln:
            continue
        if ln.startswith("# TYPE"):
            current = ln.split()[2]
            continue
        name = ln.split("{")[0].split(" ")[0]
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix):
                name = name[:-len(suffix)]
                break
        assert name == current, f"sample {ln!r} outside family {current}"


def test_seconds_family_is_a_count_and_a_sum_a_label_value():
    """add_seconds: any number of durations under one lock, rendered as
    a summary without quantiles; longer entries are read by their first
    two fields."""
    r = Registry("t")
    assert r.is_empty()
    r.add_seconds("stage", "stage", [("a.b", 0.25), ("a.c", 1.0, "more"),
                                     ("a.b", 0.5)])
    r.add_seconds("stage", "stage", (('q"x', 2.0),))
    assert not r.is_empty()
    text = r.render()
    assert text.count("# TYPE seaweedfs_tpu_t_stage_seconds summary") == 1
    assert 'seaweedfs_tpu_t_stage_seconds_sum{stage="a.b"} 0.75' in text
    assert 'seaweedfs_tpu_t_stage_seconds_count{stage="a.b"} 2' in text
    assert 'seaweedfs_tpu_t_stage_seconds_count{stage="a.c"} 1' in text
    assert 'seaweedfs_tpu_t_stage_seconds_sum{stage="q\\"x"} 2.0' in text
    assert "_bucket" not in text
