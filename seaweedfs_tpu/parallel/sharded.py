"""Multi-chip EC kernels: pjit/shard_map over a device mesh.

The reference scales encode/rebuild by spreading work across volume servers
over gRPC (weed/shell/command_ec_encode.go:160-263, parallel shard fetch in
weed/storage/store_ec.go:322-376). The TPU-native equivalent keeps that
inter-node fabric, and *inside* a host scales across chips with a
jax.sharding.Mesh:

- axis "batch": stripe-row batches are data-parallel — each chip encodes its
  slice of the row batch with the fused Pallas kernel. No collectives on the
  encode path (the code is systematic), so throughput scales linearly over
  ICI-attached chips.
- rebuild: surviving shards live sharded across chips (axis "shard"); the
  reconstruction is an all_gather of the k needed survivor rows over ICI
  followed by the same GF matmul kernel — the ICI analog of the reference's
  parallel goroutine fetch from 10 peer nodes.

Everything is jit-compiled once per (geometry, mesh) and uses static shapes.
This module is the [B, k, n]-batched kernel surface (and the shape the
MULTICHIP dryruns measure); the production EC plane drives the same
shard_map machinery through parallel/mesh_coder.py's MeshCoder, which
implements the ErasureCoder interface over the pipeline's [k, B] batches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import gf256, rs_jax, rs_pallas


def make_mesh(n_devices: int | None = None,
              axis_name: str = "batch") -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    if len(devs) < n:
        raise ValueError(f"need {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]), (axis_name,))


def _apply_fn(matrix: np.ndarray, use_pallas: bool, interpret: bool):
    if use_pallas:
        return rs_pallas.gf_apply_pallas(matrix, interpret=interpret)
    return rs_jax.gf_apply_bitplane(matrix)


# The compiled-fn caches key on the Mesh itself (hashable by device ids +
# axis names), so the lru_cache IS the registry: bounded at maxsize
# entries, evicted LRU, nothing module-global pinning every mesh ever
# built. (The previous _MESHES dict grew monotonically and kept evicted
# entries' meshes alive forever.)

@functools.lru_cache(maxsize=32)
def _sharded_encode_fn(k: int, m: int, mesh: Mesh, use_pallas: bool,
                       interpret: bool = False):
    pm = gf256.parity_matrix(k, m)
    apply_fn = _apply_fn(pm, use_pallas, interpret)

    def step(data):  # [b_local, k, n] uint8 per device
        b, kk, n = data.shape
        # fold the local batch into the stripe width: one wide kernel call
        flat = jnp.transpose(data, (1, 0, 2)).reshape(kk, b * n)
        parity = apply_fn(flat)
        return jnp.transpose(parity.reshape(-1, b, n), (1, 0, 2))

    # check_vma off: pallas_call outputs carry no vma metadata
    shard_step = jax.shard_map(step, mesh=mesh,
                               in_specs=P("batch", None, None),
                               out_specs=P("batch", None, None),
                               check_vma=False)
    return jax.jit(shard_step)


def sharded_encode(mesh: Mesh, data, parity_shards: int = 4,
                   use_pallas: bool | None = None,
                   interpret: bool = False):
    """data [B, k, n] uint8 (B divisible by mesh size) -> parity [B, m, n].

    B is sharded over the mesh "batch" axis; each chip runs the fused kernel
    on its local rows. use_pallas defaults to the platform (the Pallas
    kernel on a TPU, the XLA program elsewhere); interpret=True runs the
    Pallas kernel on the CPU test mesh.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    b, k, n = data.shape
    assert b % mesh.devices.size == 0, (b, mesh.devices.size)
    fn = _sharded_encode_fn(k, parity_shards, mesh, use_pallas, interpret)
    spec = NamedSharding(mesh, P("batch", None, None))
    data = jax.device_put(data, spec)
    return fn(data)


@functools.lru_cache(maxsize=32)
def _sharded_rebuild_fn(k: int, m: int, present: tuple[int, ...],
                        missing: tuple[int, ...], mesh: Mesh,
                        use_pallas: bool, interpret: bool = False):
    """Survivor shards sharded over chips; all_gather + GF matmul rebuild."""
    rec = gf256.reconstruction_matrix(k, m, present, missing)
    apply_fn = _apply_fn(rec, use_pallas, interpret)
    n_dev = mesh.devices.size

    def step(survivors):  # [k_padded, n] rows sharded over "batch"
        # ICI collective: every chip needs all k survivor rows
        full = jax.lax.all_gather(survivors, "batch", axis=0, tiled=True)
        full = full[:k]  # drop mesh-size padding rows
        # each chip rebuilds a slice of the column space
        n = full.shape[1]
        cols = n // n_dev
        idx = jax.lax.axis_index("batch")
        local = jax.lax.dynamic_slice(full, (0, idx * cols), (k, cols))
        return apply_fn(local)

    shard_step = jax.shard_map(step, mesh=mesh, in_specs=P("batch", None),
                               out_specs=P(None, "batch"), check_vma=False)
    return jax.jit(shard_step)


def sharded_rebuild(mesh: Mesh, shards: list, k: int, m: int,
                    use_pallas: bool | None = None,
                    interpret: bool = False):
    """Rebuild missing shards with survivors distributed across the mesh.

    shards: length k+m list with None for missing. Survivor rows are laid out
    sharded over the "batch" axis (pad to mesh size), all-gathered over ICI,
    and each chip computes the missing rows for its column slice.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    present = tuple(i for i, s in enumerate(shards) if s is not None)
    missing = tuple(i for i, s in enumerate(shards) if s is None)
    if len(present) < k:
        raise ValueError("too few shards")
    basis = present[:k]
    survivors = np.stack([np.asarray(shards[i], dtype=np.uint8)
                          for i in basis])
    n_dev = mesh.devices.size
    n = survivors.shape[1]
    pad_rows = (-survivors.shape[0]) % n_dev
    pad_cols = (-n) % n_dev  # each chip rebuilds an equal column slice
    if pad_rows or pad_cols:
        survivors = np.pad(survivors, ((0, pad_rows), (0, pad_cols)))
    fn = _sharded_rebuild_fn(k, m, basis, missing, mesh, use_pallas,
                             interpret)
    spec = NamedSharding(mesh, P("batch", None))
    out = fn(jax.device_put(jnp.asarray(survivors), spec))
    result = list(shards)
    rebuilt = np.asarray(out)[:, :n]
    for row, tgt in enumerate(missing):
        result[tgt] = rebuilt[row]
    return result
