"""JAX Reed-Solomon bulk kernels (TPU-first, CPU-portable).

The reference system's RS hot loop (seaweedfs
weed/storage/erasure_coding/ec_encoder.go:120-231, backed by SIMD assembly in
klauspost/reedsolomon) is re-thought here for TPU rather than translated:

GF(2^8) multiplication by a *constant* is linear over GF(2), so an entire
(rows x cols) GF coefficient matrix expands to a (rows*8 x cols*8) binary
matrix acting on the bit-planes of the input bytes. Applying the code then
becomes ONE integer matmul on the MXU followed by a mod-2 and a bit repack —
exactly the shape of work TPUs are built for — instead of the
per-constant table lookups CPUs use.

Three formulations of the same math (the FORMULATIONS registry):

- `gf_apply_bitplane(matrix)`: bit-plane expansion + `jax.lax.dot_general`
  (MXU path; the Pallas kernel in rs_pallas.py is the hand-tiled version).
- `gf_apply_lut(matrix)`: split each byte into nibbles and gather from
  16-entry product tables (VPU path; also the clearest correctness
  reference).
- `gf_apply_xorsched(matrix)`: precomputed XOR schedule with greedy
  shared-pair CSE executed over uint32-packed bit-plane words
  (ops/xor_schedule.py) — no lane expansion, no dot_general; the windowed
  coder path keeps batches bit-plane-resident so the pack/unpack
  transpose is paid at stage time, not per kernel.

All are bit-exact vs. the numpy coder in gf256.py, which is itself
matrix-compatible with the reference coder.

Shapes: shards are `[num_shards, n]` uint8; `n` is the stripe width. The
functions are jit-friendly (static matrix baked in via closure).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from . import gf256


def bitplane_matrix(matrix: np.ndarray) -> np.ndarray:
    """Expand a GF(2^8) coefficient matrix [R, C] to binary [R*8, C*8].

    W[r*8+i, c*8+j] = bit i of (matrix[r,c] * 2^j in GF(2^8)); then for
    byte-vectors x: bits(out[r]) = sum_j W[r*8+i, c*8+j] * bits(x[c])_j mod 2.
    """
    r, c = matrix.shape
    w = np.zeros((r * 8, c * 8), dtype=np.int8)
    for rr in range(r):
        for cc in range(c):
            coeff = int(matrix[rr, cc])
            for j in range(8):
                prod = gf256.gf_mul(coeff, 1 << j)
                for i in range(8):
                    w[rr * 8 + i, cc * 8 + j] = (prod >> i) & 1
    return w


def nibble_tables(matrix: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per-coefficient 16-entry product tables for low/high nibbles.

    lo[r, c, x] = matrix[r,c] * x        (x in 0..15)
    hi[r, c, x] = matrix[r,c] * (x<<4)
    so matrix[r,c] * b == lo[r,c,b&15] ^ hi[r,c,b>>4].
    """
    mul = gf256.mul_table()
    r, c = matrix.shape
    lo = np.zeros((r, c, 16), dtype=np.uint8)
    hi = np.zeros((r, c, 16), dtype=np.uint8)
    for rr in range(r):
        for cc in range(c):
            coeff = int(matrix[rr, cc])
            lo[rr, cc] = mul[coeff, np.arange(16)]
            hi[rr, cc] = mul[coeff, np.arange(16) << 4]
    return lo, hi


def _unpack_bits(x: jnp.ndarray) -> jnp.ndarray:
    """[C, n] uint8 -> [C*8, n] int8 bit-planes (bit j of byte c at row c*8+j)."""
    c, n = x.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (x[:, None, :] >> shifts[None, :, None]) & jnp.uint8(1)
    return bits.reshape(c * 8, n).astype(jnp.int8)


def _pack_bits(bits: jnp.ndarray, rows: int) -> jnp.ndarray:
    """[R*8, n] int (0/1) -> [R, n] uint8."""
    n = bits.shape[1]
    b = bits.reshape(rows, 8, n).astype(jnp.uint8)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    # XOR-free pack: planes are disjoint bit positions, sum == or
    return jnp.sum(b * weights[None, :, None], axis=1, dtype=jnp.uint8)


def gf_apply_bitplane(matrix: np.ndarray):
    """Return a jittable fn: shards [C, n] uint8 -> [R, n] uint8 via MXU.

    The contraction runs in int8 with int32 accumulation: every MAC is a
    0/1 product, the row sums are < C*8 <= 2^10, then mod 2 recovers XOR.
    """
    w = jnp.asarray(bitplane_matrix(matrix))  # [R8, C8] int8
    rows = matrix.shape[0]

    def apply_fn(shards: jnp.ndarray) -> jnp.ndarray:
        bits = _unpack_bits(shards)
        acc = jax.lax.dot_general(
            w, bits,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return _pack_bits(acc & 1, rows)

    return apply_fn


def gf_apply_bitplane_dyn(w: jnp.ndarray, shards: jnp.ndarray) -> jnp.ndarray:
    """gf_apply_bitplane with the EXPANDED binary matrix as a runtime
    input instead of a baked constant: one compiled executable serves
    ANY coefficient matrix of the same [R, C] shape.

    This is what lets the reconstruction window reuse the encode-warmed
    program — a rec matrix for len(missing) <= m victims zero-pads to the
    parity matrix's [m, k] shape (zero rows produce zero output rows,
    ec/coder.py slices them off) — instead of paying its own compile per
    loss pattern.  The bitplane contraction is already matrix-generic on
    the MXU, so nothing is lost by not constant-folding W.
    """
    rows = w.shape[0] // 8
    bits = _unpack_bits(shards)
    acc = jax.lax.dot_general(
        w, bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return _pack_bits(acc & 1, rows)


def gf_apply_lut(matrix: np.ndarray):
    """Return a jittable fn: shards [C, n] uint8 -> [R, n] uint8 via nibble LUTs."""
    lo_np, hi_np = nibble_tables(matrix)
    lo = jnp.asarray(lo_np)
    hi = jnp.asarray(hi_np)
    r, c = matrix.shape

    def apply_fn(shards: jnp.ndarray) -> jnp.ndarray:
        lo_nib = shards & jnp.uint8(0x0F)   # [C, n]
        hi_nib = shards >> jnp.uint8(4)     # [C, n]
        out = jnp.zeros((r, shards.shape[1]), dtype=jnp.uint8)
        for cc in range(c):  # static python loop: c is small (<=32)
            out = out ^ jnp.take(lo[:, cc, :], lo_nib[cc], axis=1)
            out = out ^ jnp.take(hi[:, cc, :], hi_nib[cc], axis=1)
        return out

    return apply_fn


def gf_apply_xorsched(matrix: np.ndarray):
    """Return a jittable fn: shards [C, n] uint8 -> [R, n] uint8 via the
    packed-word XOR schedule (ops/xor_schedule.py).

    The schedule (greedy shared-pair CSE over the expanded binary matrix)
    is built once per matrix and baked in as straight-line uint32 XORs;
    this full-fidelity form packs/unpacks around it for the plain
    encode/reconstruct API. The windowed coder path skips both
    transposes: batches arrive already bit-plane-resident
    (JaxCoder.stage_async) and only the digest repack touches bytes.
    """
    from . import xor_schedule
    sched = xor_schedule.schedule_for_matrix(matrix)

    def apply_fn(shards: jnp.ndarray) -> jnp.ndarray:
        n = shards.shape[1]
        planes = xor_schedule.pack_planes(shards)
        out = xor_schedule.run_schedule(sched, planes)
        return xor_schedule.unpack_planes(out, n)

    return apply_fn


def gf_apply_planes_dyn(w: jnp.ndarray, planes: jnp.ndarray) -> jnp.ndarray:
    """gf_apply_bitplane_dyn's packed-word twin: the EXPANDED binary
    matrix rides in as runtime data and the inputs/outputs are
    uint32-packed bit-plane rows ([C*8, nw] -> [R*8, nw]).

    out[i] = XOR over j of (planes[j] AND broadcast(w[i, j])) — each
    matrix bit becomes an all-ones/all-zero word mask, so one compiled
    executable serves ANY coefficient matrix of the same shape, exactly
    like the byte-domain dyn program. This is what keeps the xorsched
    rebuild windows on the one-executable-per-shape contract: rec
    matrices zero-pad to [m, k] and reuse the encode window's program
    instead of building + compiling a fresh XOR schedule per failure
    pattern.
    """
    masks = (-(w.astype(jnp.int32))).astype(jnp.uint32)  # 1 -> 0xFFFFFFFF
    out = jnp.zeros((w.shape[0], planes.shape[1]), dtype=jnp.uint32)
    for j in range(int(planes.shape[0])):  # static: C*8 <= 256
        out = out ^ (masks[:, j][:, None] & planes[j][None, :])
    return out


# the formulation registry: every named GF kernel formulation the coder,
# mesh, governor, and bench layers can select (WEED_EC_FORMULATION)
FORMULATIONS = {
    "lut": gf_apply_lut,
    "bitplane": gf_apply_bitplane,
    "xorsched": gf_apply_xorsched,
}


def gf_apply(method: str, matrix: np.ndarray):
    """Build the apply fn for a registered formulation."""
    try:
        build = FORMULATIONS[method]
    except KeyError:
        raise ValueError(f"unknown GF formulation {method!r}; "
                         f"have {sorted(FORMULATIONS)}") from None
    return build(matrix)


def formulation_env() -> str | None:
    """The WEED_EC_FORMULATION pin (lut|bitplane|xorsched), or None when
    unset. An unknown value raises rather than silently no-oping the
    operator's intent."""
    raw = os.environ.get("WEED_EC_FORMULATION", "").strip().lower()
    if not raw:
        return None
    if raw not in FORMULATIONS:
        raise ValueError(f"WEED_EC_FORMULATION={raw!r}: valid values are "
                         f"{sorted(FORMULATIONS)}")
    return raw


# instruction kinds that carry no element work: parameters/constants are
# inputs, tuples/GTEs are plumbing, fusion wrappers re-state their root
_HLO_SKIP_OPS = frozenset({"parameter", "constant", "tuple",
                           "get-tuple-element", "fusion"})


def hlo_elem_ops(hlo_text: str) -> int:
    """Static element-op count of a compiled HLO module: for every
    instruction (including inside fused computations) the product of its
    output shape dims. The same static-inspection trick as the mesh
    coder's collective-free assertion — a property of the compiled
    program, checkable with no TPU attached."""
    import re
    pat = re.compile(r"=\s*[a-z0-9]+\[([0-9,]*)\][^\s]*\s+([a-z0-9_\-]+)\(")
    total = 0
    for m in pat.finditer(hlo_text):
        if m.group(2) in _HLO_SKIP_OPS:
            continue
        elems = 1
        dims = m.group(1)
        if dims:
            for d in dims.split(","):
                elems *= int(d)
        total += elems
    return total


def encode_program_hlo(data_shards: int, parity_shards: int, method: str,
                       width: int = 65536) -> str:
    """Compiled HLO of the PER-BATCH encode program for a formulation at
    a [k, width] stripe batch.

    For lut/bitplane that is the byte-domain program (their expand/repack
    runs per batch by construction). For xorsched it is the packed
    bit-plane-resident program ([k*8, width/32] uint32 -> parity planes)
    — the program the windowed path launches per batch, with the
    pack/unpack transpose hoisted to stage/write time. Both consume
    exactly k*width input bytes, so op-count-per-byte comparisons are
    apples to apples."""
    pm = gf256.parity_matrix(data_shards, parity_shards)
    if method == "xorsched":
        from . import xor_schedule
        if width % 32:
            raise ValueError("xorsched program width must be a multiple "
                             f"of 32, got {width}")
        sched = xor_schedule.schedule_for_matrix(pm)
        fn = jax.jit(lambda planes: xor_schedule.run_schedule(sched,
                                                              planes))
        sds = jax.ShapeDtypeStruct((data_shards * 8, width // 32),
                                   jnp.uint32)
    else:
        fn = jax.jit(gf_apply(method, pm))
        sds = jax.ShapeDtypeStruct((data_shards, width), jnp.uint8)
    return fn.lower(sds).compile().as_text()


def encode_hlo_ops_per_byte(data_shards: int, parity_shards: int,
                            method: str, width: int = 65536) -> float:
    """Static element-ops per input byte of the per-batch encode program
    — the container-checkable stand-in for the chip-side op/byte bound
    (see encode_program_hlo for which program each formulation runs per
    batch)."""
    text = encode_program_hlo(data_shards, parity_shards, method, width)
    return hlo_elem_ops(text) / float(data_shards * width)


@functools.lru_cache(maxsize=64)
def _encode_fn(data_shards: int, parity_shards: int, method: str):
    pm = gf256.parity_matrix(data_shards, parity_shards)
    return jax.jit(gf_apply(method, pm))


def encode_parity(data: jnp.ndarray, parity_shards: int,
                  method: str = "bitplane") -> jnp.ndarray:
    """data [k, n] uint8 -> parity [m, n] uint8 (jitted, cached per geometry)."""
    return _encode_fn(int(data.shape[0]), parity_shards, method)(data)


@functools.lru_cache(maxsize=256)
def _reconstruct_fn(data_shards: int, parity_shards: int,
                    present: tuple[int, ...], missing: tuple[int, ...],
                    method: str):
    """Jitted fn: survivors [k, n] (first k present, ascending) -> missing rows."""
    rec_matrix = gf256.reconstruction_matrix(data_shards, parity_shards,
                                             present, missing)
    return jax.jit(gf_apply(method, rec_matrix))


def reconstruct(shards: list[jnp.ndarray | None], data_shards: int,
                parity_shards: int, method: str = "bitplane",
                data_only: bool = False) -> list[jnp.ndarray]:
    """Fill None entries from any k survivors (same semantics as gf256.reconstruct)."""
    total = data_shards + parity_shards
    assert len(shards) == total
    present = tuple(i for i, s in enumerate(shards) if s is not None)
    missing = tuple(i for i, s in enumerate(shards) if s is None
                    and (not data_only or i < data_shards))
    if not missing:
        return list(shards)  # type: ignore[arg-type]
    if len(present) < data_shards:
        raise ValueError("too few shards to reconstruct")
    fn = _reconstruct_fn(data_shards, parity_shards, present[:data_shards],
                         missing, method)
    survivors = jnp.stack([shards[i] for i in present[:data_shards]])
    rebuilt = fn(survivors)
    out = list(shards)
    for row, tgt in enumerate(missing):
        out[tgt] = rebuilt[row]
    return out  # type: ignore[return-value]
