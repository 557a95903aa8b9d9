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

Two formulations of the same math:

- `gf_apply_bitplane(matrix)`: bit-plane expansion + `jax.lax.dot_general`
  (MXU path; the kernel of the XLA coder, and the Pallas kernel in
  rs_pallas.py is the hand-tiled version).
- `gf_apply_lut(matrix)`: split each byte into nibbles and gather from
  16-entry product tables. No coder runs it: it stays as the op-level
  reference tests/test_rs_jax.py compares the bit-plane kernel against
  (`encode_parity(method=)` / `reconstruct(method=)` exist for that).

Both are bit-exact vs. the numpy coder in gf256.py, which is itself
matrix-compatible with the reference coder.

Shapes: shards are `[num_shards, n]` uint8; `n` is the stripe width. The
functions are jit-friendly (static matrix baked in via closure).
"""

from __future__ import annotations

import functools

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


# "lut" is the tests' op-level reference, not a selectable kernel
_APPLY = {"bitplane": gf_apply_bitplane, "lut": gf_apply_lut}


@functools.lru_cache(maxsize=64)
def _encode_fn(data_shards: int, parity_shards: int, method: str):
    pm = gf256.parity_matrix(data_shards, parity_shards)
    return jax.jit(_APPLY[method](pm))


def encode_parity(data: jnp.ndarray, parity_shards: int,
                  method: str = "bitplane") -> jnp.ndarray:
    """data [k, n] uint8 -> parity [m, n] uint8 (jitted, cached per geometry)."""
    return _encode_fn(int(data.shape[0]), parity_shards, method)(data)


@functools.lru_cache(maxsize=256)
def _reconstruct_fn(data_shards: int, parity_shards: int,
                    present: tuple[int, ...], missing: tuple[int, ...],
                    method: str = "bitplane"):
    """Jitted fn: survivors [k, n] (first k present, ascending) -> missing rows."""
    rec_matrix = gf256.reconstruction_matrix(data_shards, parity_shards,
                                             present, missing)
    return jax.jit(_APPLY[method](rec_matrix))


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
