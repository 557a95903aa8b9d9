#!/usr/bin/env python3
"""Compile every selectable EC kernel on the chip at the served shapes.

chip_smoke.py proves the served path end to end at the governor's start
point; this checks the rest of what the product can select or plan:

  PallasCoder (what `-coder auto` is on a TPU) and JaxCoder
  (`-coder jax`), each at the governor's batch widths from 1 to 64 MiB per
  row plus a ragged batch, rows = 1..4 for rebuild, 1 KiB..1 MiB intervals
  for the degraded read (rows = 1, two loss patterns), and the shipped
  policy geometries beside RS(10,4).

Every result is compared byte for byte with the native host coder. One
process, which owns the chip; needs a TPU (JAX_PLATFORMS=tpu) and fails
without one. Run through the chip tool from the repo root:
    python scripts/chip_kernels.py
Prints one JSON line per case and exits non-zero if any case failed.
"""

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "tpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import jax  # noqa: E402

from seaweedfs_tpu.ec.coder import JaxCoder, PallasCoder  # noqa: E402
from seaweedfs_tpu.ops import gf256, native  # noqa: E402

MB = 1 << 20
FAILED = []


def case(name: str, fn) -> None:
    t0 = time.perf_counter()
    try:
        rec = fn()
    except Exception as e:  # noqa: BLE001 - the refusal IS the result
        rec = {"ok": False, "error": f"{type(e).__name__}: {str(e)[:600]}"}
    rec = {"case": name, **rec, "wall_s": round(time.perf_counter() - t0, 3)}
    if not rec.get("ok"):
        FAILED.append(name)
    print(json.dumps(rec), flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, round(time.perf_counter() - t0, 4)


def encode_case(coder, data):
    def run():
        want = native.gf_matrix_apply(
            gf256.parity_matrix(coder.k, coder.m), data)
        got, first = timed(
            lambda: coder.materialize(coder.encode_async(data)))
        _, second = timed(
            lambda: coder.materialize(coder.encode_async(data)))
        return {"ok": bool(np.array_equal(got, want)), "first_s": first,
                "second_s": second}
    return run


def rebuild_case(coder, data, parity, missing):
    def run():
        shards = [*data, *parity]
        present = tuple(i for i in range(coder.k + coder.m)
                        if i not in missing)[:coder.k]
        survivors = np.stack([shards[i] for i in present])
        fn = coder.rec_apply_async(present, tuple(missing))
        got, first = timed(lambda: coder.materialize(fn(survivors)))
        ok = all(np.array_equal(got[r], shards[t])
                 for r, t in enumerate(missing))
        return {"ok": ok, "missing": list(missing), "first_s": first}
    return run


def interval_case(coder, data, parity, size, lost):
    """EcVolume._reconstruct_interval's call: one target row, k survivors
    of `size` bytes; then the same size under another loss pattern."""
    def run():
        shards = [*data, *parity]
        out = {"ok": True, "size": size}
        other = [(p + 1) % (coder.k + coder.m) for p in lost]
        for tag, pattern in (("first", lost), ("other_pattern", other)):
            holed = [None if i in pattern else s[:size]
                     for i, s in enumerate(shards)]
            target = min(p for p in pattern if p < coder.k)
            got, secs = timed(lambda: coder.reconstruct(
                holed, targets=(target,))[target])
            _, again = timed(lambda: coder.reconstruct(
                holed, targets=(target,))[target])
            out["ok"] &= bool(np.array_equal(got, shards[target][:size]))
            out[f"{tag}_s"], out[f"{tag}_again_s"] = secs, again
        return out
    return run


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, have {dev.platform}")
    print(json.dumps({"case": "device", "platform": dev.platform,
                      "kind": dev.device_kind, "count": len(jax.devices()),
                      "jax": jax.__version__}), flush=True)
    if not native.available():
        raise SystemExit("cannot build the native host coder")
    rng = np.random.default_rng(21)
    big = rng.integers(0, 256, (20, 64 * MB), dtype=np.uint8)
    data8 = big[:10, :8 * MB]
    parity8 = native.gf_matrix_apply(gf256.parity_matrix(10, 4), data8)

    for name, coder in (("pallas", PallasCoder(10, 4)),
                        ("jax_bitplane", JaxCoder(10, 4))):
        print(json.dumps({"case": f"{name}/coder", **coder.describe()}))
        for w in (1, 2, 4, 7, 8, 16, 32, 64):
            case(f"{name}/encode/{w}MiB",
                 encode_case(coder, big[:10, :w * MB]))
        for missing in ([3], [0, 12], [1, 5, 11], [0, 3, 7, 12]):
            case(f"{name}/rebuild/rows{len(missing)}",
                 rebuild_case(coder, data8, parity8, missing))
        for size in (1024, 4096, 100_000, 333_333, MB):
            case(f"{name}/interval/{size}",
                 interval_case(coder, data8, parity8, size, [2, 6, 9, 13]))
    for k, m in ((20, 4), (6, 3)):
        case(f"pallas/encode/rs{k}+{m}/8MiB",
             encode_case(PallasCoder(k, m), big[:k, :8 * MB]))
        case(f"jax_bitplane/encode/rs{k}+{m}/8MiB",
             encode_case(JaxCoder(k, m), big[:k, :8 * MB]))
    print(json.dumps({"case": "done", "failed": FAILED}))
    raise SystemExit(1 if FAILED else 0)


if __name__ == "__main__":
    main()
