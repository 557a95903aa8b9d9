"""The xorsched formulation end-to-end (ISSUE 20 tentpole).

Four contracts, each load-bearing for the headline claim:

- static op-count: the compiled-HLO element-ops per input byte of the
  packed bit-plane-resident encode program is <= 0.5x the bitplane
  program at RS(10,4) — the no-chip stand-in for chip GB/s, same
  idiom as MeshCoder.encode_is_collective_free;
- the rec/dyn-matrix window path stays ONE executable per
  (n_batches, shape) under xorsched (rebuild windows never recompile);
- the governor's formulation axis explores bitplane vs xorsched per
  geometry, exploits the measured argmax, and yields to the
  WEED_EC_FORMULATION pin;
- governed stream_encode steers an unpinned JaxCoder through the axis
  while staying byte-identical to striping.write_ec_files, and the
  ec.stage.pack fault point fails the stage loudly instead of silently
  falling back to byte staging.
"""

import hashlib
import os

import numpy as np
import pytest

from seaweedfs_tpu.ec import governor, pipeline, striping
from seaweedfs_tpu.ec.coder import JaxCoder, get_coder
from seaweedfs_tpu.ec.geometry import Geometry, to_ext
from seaweedfs_tpu.ops import rs_jax, xor_schedule

GEO = Geometry(10, 4, large_block_size=10000, small_block_size=100)


@pytest.fixture(autouse=True)
def fresh_governor():
    governor.reset()
    yield
    governor.reset()


def _sha(path: str) -> str:
    return hashlib.sha256(open(path, "rb").read()).hexdigest()


def _write_dat(tmp_path, name: str, size: int, seed: int) -> str:
    rng = np.random.default_rng(seed)
    base = os.path.join(str(tmp_path), name)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
    return base


# ------------------------------------------------ static op-count claim

def test_hlo_ops_per_byte_at_least_halved():
    """Acceptance: compiled-HLO element-ops per input byte for the
    xorsched RS(10,4) encode program (the packed bit-plane-resident
    per-batch program the windowed path launches) must be <= 0.5x the
    bitplane program's. The CSE reduction behind it is logged."""
    b = rs_jax.encode_hlo_ops_per_byte(10, 4, method="bitplane")
    x = rs_jax.encode_hlo_ops_per_byte(10, 4, method="xorsched")
    sched = xor_schedule.schedule_for_matrix(
        __import__("seaweedfs_tpu.ops.gf256", fromlist=["gf256"])
        .parity_matrix(10, 4))
    print(f"hlo elem-ops/byte: bitplane {b:.2f}, xorsched {x:.2f} "
          f"(ratio {x / b:.3f}); schedule: {sched.dense_xors} dense "
          f"XORs -> {sched.sched_xors} after CSE "
          f"({1 - sched.sched_xors / sched.dense_xors:.1%} saved)")
    assert sched.sched_xors < sched.dense_xors
    assert x <= 0.5 * b, (x, b)


# ------------------------------------- rec windows: one executable/shape

def test_rec_window_single_executable_per_shape():
    """Encode window + two different reconstruction patterns of the same
    batch shape must share ONE packed dyn executable (the matrix rides
    as data; zero-padded rec matrices reuse the encode program) — the
    'rebuild windows don't recompile' contract under xorsched."""
    rng = np.random.default_rng(0)
    k, m = 10, 4
    c = JaxCoder(k, m, method="xorsched")
    cn = get_coder("numpy", k, m)
    batches = [rng.integers(0, 256, (k, 1024), dtype=np.uint8)
               for _ in range(3)]
    staged = [c.stage_async(b) for b in batches]

    acc = np.asarray(c.encode_digest_window_async(staged))
    want = np.zeros(m, dtype=np.uint32)
    for b in batches:
        want = (want + cn.encode(b).astype(np.uint64).sum(axis=1)
                ).astype(np.uint32)
    assert np.array_equal(acc, want)

    c.rec_digest_window_async(tuple(range(2, 14)), (0, 1), staged)
    c.rec_digest_window_async(tuple(range(0, 12)), (12, 13), staged)
    packed_keys = [key for key in c._wcache() if key[0] == "dynwp"]
    assert len(packed_keys) == 1, packed_keys

    # and the warm path compiles the SAME key dispatch will use
    c2 = JaxCoder(k, m, method="xorsched")
    c2.warm_encode_digest_window(3, (k, 1024))
    acc2 = np.asarray(c2.encode_digest_window_async(
        [c2.stage_async(b) for b in batches]))
    assert np.array_equal(acc2, want)
    assert len([key for key in c2._wcache()
                if key[0] == "dynwp"]) == 1, c2._wcache().keys()


def test_staged_batches_are_packed_and_footprint_equal():
    """stage_async under xorsched emits uint32 bit-plane words whose
    footprint equals the byte input (residency, not 8x expansion)."""
    c = JaxCoder(10, 4, method="xorsched")
    b = np.arange(10 * 1024, dtype=np.uint8).reshape(10, 1024)
    h = c.stage_async(b)
    assert h.dtype == np.uint32 and h.shape == (80, 32)
    assert h.nbytes == b.nbytes
    assert np.array_equal(np.asarray(xor_schedule.unpack_planes(h, 1024)),
                          b)


# ------------------------------------------------- governor formulation

def test_governor_formulation_axis_explore_then_exploit():
    gov = governor.get()
    k = 10
    first = gov.plan(1 << 20, k).formulation
    assert first == "bitplane"  # candidate order is deterministic
    gov.form_gbps[(k, "bitplane")] = 1.0
    second = gov.plan(1 << 20, k).formulation
    assert second == "xorsched"  # second candidate still unexplored
    gov.form_gbps[(k, "xorsched")] = 3.0
    assert gov.plan(1 << 20, k).formulation == "xorsched"  # argmax
    gov.form_gbps[(k, "xorsched")] = 0.5
    assert gov.plan(1 << 20, k).formulation == "bitplane"
    # the axis is per-geometry: a fresh k starts exploring again
    assert gov.plan(1 << 20, 20).formulation == "bitplane"


def test_governor_formulation_env_pin(monkeypatch):
    monkeypatch.setenv("WEED_EC_FORMULATION", "xorsched")
    governor.reset()
    gov = governor.get()
    gov.form_gbps[(10, "bitplane")] = 99.0
    gov.form_gbps[(10, "xorsched")] = 0.1
    assert gov.plan(1 << 20, 10).formulation == "xorsched"


def test_formulation_env_rejects_unknown(monkeypatch):
    monkeypatch.setenv("WEED_EC_FORMULATION", "turbo")
    with pytest.raises(ValueError, match="turbo"):
        rs_jax.formulation_env()


# ------------------------------------------- governed pipeline steering

def test_stream_encode_steers_formulation_and_stays_identical(tmp_path):
    """Two governed encodes through one unpinned JaxCoder: the governor
    explores bitplane then xorsched, finish_run feeds the formulation
    model, and every shard file matches the reference writer both
    times."""
    size = 35_555
    ref = _write_dat(tmp_path, "ref", size, seed=3)
    striping.write_ec_files(ref, get_coder("numpy", 10, 4), GEO,
                            buffer_size=50)
    c = JaxCoder(10, 4)
    assert not c._method_pinned
    for name in ("v1", "v2"):
        base = _write_dat(tmp_path, name, size, seed=3)
        pipeline.stream_encode(base, c, GEO)
        for i in range(14):
            assert _sha(base + to_ext(i)) == _sha(ref + to_ext(i)), \
                (name, i)
    gov = governor.get()
    assert (10, "bitplane") in gov.form_gbps
    assert (10, "xorsched") in gov.form_gbps
    assert c.method in ("bitplane", "xorsched")


def test_pinned_coder_reports_actual_formulation():
    """A pinned coder ignores the governor's plan and the steered op
    carries what actually ran, so the model never cross-attributes."""
    op = governor.get().plan(1 << 20, 10)
    c = JaxCoder(10, 4, method="xorsched")
    steered = pipeline._steer_formulation(c, op)
    assert steered.formulation == "xorsched"
    # coders without the hook opt out entirely
    cn = get_coder("numpy", 10, 4)
    assert pipeline._steer_formulation(cn, op).formulation == ""


# ------------------------------------------------------ fault injection

def test_stage_pack_fault_fails_stage_loudly():
    from seaweedfs_tpu import faults

    assert "ec.stage.pack" in faults.KNOWN_POINTS
    c = JaxCoder(10, 4, method="xorsched")
    faults.clear()
    faults.set_fault("ec.stage.pack", "drop")
    try:
        with pytest.raises(faults.FaultError, match="ec.stage.pack"):
            c.stage_async(np.zeros((10, 64), dtype=np.uint8))
    finally:
        faults.clear()
