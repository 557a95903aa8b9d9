"""MeshCoder — the production ErasureCoder over a jax.sharding.Mesh.

`parallel/sharded.py` proved the kernel shape (MULTICHIP_r05: the encode
HLO is collective-free, linear weak scaling over an 8-device mesh); this
module is the production face: an `ErasureCoder` the streaming pipeline
(ec/pipeline.py) and the store's `ec_generate`/`ec_rebuild` drive
unchanged, with every [k, B] batch's B axis sharded over the mesh so ONE
governed host feed saturates N chips.

Sharding shape (the pipeline's batches are [k, B] — k shard rows of a
B-byte stripe batch):

- encode: columns are independent under RS (parity[:, j] depends only on
  data[:, j]), so the batch axis shards as P(None, "batch") and each chip
  runs the same GF kernel on its B/n column slice. No collectives — the
  property the MULTICHIP dryruns verify — so aggregate throughput is
  n * per-chip throughput on ICI-attached chips.
- rebuild: the same shape with the reconstruction matrix — the host feed
  already holds all k survivor rows of a batch, so they column-shard
  exactly like encode input and each chip reconstructs the missing rows
  of its own column slice, collective-free. (Until PR 21 survivors went
  up row-sharded and were all_gather'd over ICI; on four real v5e chips
  XLA did not finish compiling that uint8 all_gather of a [12, 4 MiB]
  batch in 90 s, and ec.rebuild timed out.)

Batch widths not divisible by the mesh size zero-pad to the next multiple
(GF parity of zero columns is zero, so padding never changes real bytes;
materialize slices the pad off). Output is byte-identical to the
single-chip JaxCoder and to striping.write_ec_files at every geometry —
tests/test_mesh_coder.py proves it at odd widths and RS(20,4).

Staging is per-chip: `_stage_cols` splits a host batch into per-device
column slices and device_puts each one separately (transfers overlap),
emitting an `ec.stage.chip` span and per-chip byte/second counters into
the shared "ec" metrics registry next to the governor's gauges.

One of two kernels runs inside the shard_map step, fixed at
construction: `bitplane` (rs_jax's XLA matmul, what the CPU test mesh
and `-coder jax` use) or `pallas` (the hand-tiled TPU kernel, what a TPU
host's `auto` resolves to; `Store._maybe_mesh` picks it from the class
of the single-chip coder).

`WEED_EC_MESH_DEVICES` selects the mesh: unset/"0"/"1" means no mesh
(production paths keep the proven single-chip JaxCoder), "all" takes
every local device, N clamps to what the host has. A 1-device request
degenerates to a plain JaxCoder — `coder()` never returns a MeshCoder
wrapping one chip.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from typing import Optional, Sequence

import numpy as np

from .. import observe
from ..ec.coder import JaxCoder
from ..ops import gf256, rs_jax
from ..utils import metrics as metrics_mod


def mesh_device_count() -> int:
    """Devices WEED_EC_MESH_DEVICES asks for: 0 = mesh disabled (the
    default — virtual CPU test meshes must not silently reroute every
    production encode), "all" = every local device, N clamps to the
    host. Values <= 1 read as disabled: a 1-chip mesh IS the JaxCoder
    path."""
    raw = os.environ.get("WEED_EC_MESH_DEVICES", "").strip().lower()
    if not raw or raw in ("0", "1", "no", "false"):
        return 0
    import jax
    have = len(jax.devices())
    if raw == "all":
        return have if have > 1 else 0
    try:
        n = int(raw)
    except ValueError:
        return 0
    n = min(n, have)
    return n if n > 1 else 0


def coder(data_shards: int, parity_shards: int,
          n_devices: Optional[int] = None):
    """The mesh-or-single factory: a MeshCoder over n_devices (default:
    WEED_EC_MESH_DEVICES, then all local devices) when that resolves to
    more than one chip, else the single-chip JaxCoder."""
    if n_devices is None:
        if os.environ.get("WEED_EC_MESH_DEVICES", "").strip():
            n_devices = mesh_device_count() or 1
        else:
            import jax
            n_devices = len(jax.devices())
    if n_devices <= 1:
        return JaxCoder(data_shards, parity_shards)
    return MeshCoder(data_shards, parity_shards, n_devices=n_devices)


class _MeshHandle:
    """In-flight sharded result + the valid (pre-padding) width."""

    __slots__ = ("arr", "width")

    def __init__(self, arr, width: int):
        self.arr = arr
        self.width = width

    def copy_to_host_async(self) -> None:
        start = getattr(self.arr, "copy_to_host_async", None)
        if start is not None:
            start()


class MeshCoder(JaxCoder):
    """ErasureCoder over a jax.sharding.Mesh (axis "batch" = the stripe
    batch's column axis). See the module docstring for the sharding
    shape."""

    def __init__(self, data_shards: int, parity_shards: int,
                 n_devices: Optional[int] = None,
                 method: str = "bitplane", interpret: bool = False):
        if method not in ("bitplane", "pallas"):
            raise ValueError(f"unknown mesh coder method {method!r}")
        self.method = method
        # method="pallas" in Pallas interpret mode: the CPU test mesh only
        self._interpret = interpret
        super().__init__(data_shards, parity_shards)
        import jax
        from jax.sharding import Mesh
        devs = jax.devices()
        n = n_devices or len(devs)
        if n < 2:
            raise ValueError("MeshCoder needs >= 2 devices; use JaxCoder "
                             "(or parallel.mesh_coder.coder) for one chip")
        if len(devs) < n:
            raise ValueError(f"need {n} devices, have {len(devs)}")
        self.mesh = Mesh(np.array(devs[:n]), ("batch",))
        self.mesh_devices = n
        self._devices = list(devs[:n])
        self._enc_sharded = None
        self._rec_sharded: dict = {}
        self._lock = threading.Lock()
        metrics_mod.shared("ec").gauge("feed_mesh_devices", n)

    def describe(self) -> dict:
        out = {**super().describe(), "formulation": self.method,
               "mesh_devices": self.mesh_devices}
        if self.method == "pallas":
            out["warm"] = self._host_state().status()
        return out

    def _host_state(self):
        """The degraded read's program (`_rec_apply_sync`), one row out."""
        from ..ops import rs_pallas
        return rs_pallas.host_state(1, self.k, interpret=self._interpret)

    def warm_widths(self) -> None:
        if self.method == "pallas":
            self._host_state().warm()

    # --- staging: per-chip sub-batches ---

    def _pad_cols(self, arr: np.ndarray) -> np.ndarray:
        pad = (-arr.shape[-1]) % self.mesh_devices
        if pad:
            width = [(0, 0)] * (arr.ndim - 1) + [(0, pad)]
            arr = np.pad(arr, width)
        return arr

    def _col_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P(None, "batch"))

    def _stage_cols(self, arr: np.ndarray):
        """device_put one per-chip column slice per device and assemble
        the sharded array — transfers overlap (device_put is async), and
        each chip's H2D is visible as its own ec.stage.chip span plus
        feed_chip_staged_bytes / feed_chip_stage_seconds counters."""
        import jax
        n = self.mesh_devices
        cols = arr.shape[1] // n
        ctx = observe.ensure_ctx("ec")
        reg = metrics_mod.shared("ec")
        shards = []
        for i, dev in enumerate(self._devices):
            start_us = int(time.time() * 1e6)
            t0 = time.perf_counter()
            piece = np.ascontiguousarray(arr[:, i * cols:(i + 1) * cols])
            shards.append(jax.device_put(piece, dev))
            dur = time.perf_counter() - t0
            observe.record_span("ec.stage.chip", ctx, start_us,
                                int(dur * 1e6),
                                tags={"chip": i, "bytes": piece.nbytes})
            reg.count("feed_chip_staged_bytes", value=piece.nbytes,
                      labels={"chip": str(i)})
            reg.count("feed_chip_stage_seconds", value=round(dur, 6),
                      labels={"chip": str(i)})
        return jax.make_array_from_single_device_arrays(
            arr.shape, self._col_sharding(), shards)

    # --- encode: shard_map over the batch axis, collective-free ---

    def _apply_matrix_fn(self, matrix: np.ndarray):
        """The per-chip GF kernel for this coder's method — pallas keeps
        the hand-tiled TPU kernel inside the shard_map step (the demo's
        _apply_fn shape), bitplane is rs_jax's XLA matmul."""
        if self.method == "pallas":
            from ..ops import rs_pallas
            return rs_pallas.gf_apply_pallas(matrix,
                                             interpret=self._interpret)
        return rs_jax.gf_apply_bitplane(matrix)

    def _rec_apply_sync(self, present, missing, stage=""):
        # a degraded read's interval: one chip, host-side pad/slice and
        # bucketed widths, exactly as PallasCoder does it
        if self.method != "pallas":
            return super()._rec_apply_sync(present, missing, stage)
        key = ("sync", present, missing)
        with self._lock:
            fn = self._rec_sharded.get(key)
            if fn is None:
                from ..ops import rs_pallas
                fn = self._rec_sharded[key] = rs_pallas.gf_apply_pallas_host(
                    gf256.reconstruction_matrix(self.k, self.m, present,
                                                missing),
                    interpret=self._interpret)
            return functools.partial(fn, stage=stage) if stage else fn

    def _sharded(self, matrix: np.ndarray):
        """jit(shard_map) of the per-chip kernel for `matrix`, columns
        sharded in and out — the one program shape encode and rebuild
        share. check_vma off: pallas_call outputs carry no vma metadata."""
        import jax
        from jax.sharding import PartitionSpec as P
        return jax.jit(jax.shard_map(
            self._apply_matrix_fn(matrix), mesh=self.mesh,
            in_specs=P(None, "batch"), out_specs=P(None, "batch"),
            check_vma=False))

    def _enc_fn(self):
        with self._lock:
            if self._enc_sharded is None:
                self._enc_sharded = self._sharded(
                    gf256.parity_matrix(self.k, self.m))
            return self._enc_sharded

    def encode_async(self, data: np.ndarray):
        width = int(data.shape[1])
        arr = self._pad_cols(np.asarray(data, dtype=np.uint8))
        return _MeshHandle(self._enc_fn()(self._stage_cols(arr)), width)

    def encode(self, data: np.ndarray) -> np.ndarray:
        return self.materialize(self.encode_async(data))

    def materialize(self, handle) -> np.ndarray:
        if isinstance(handle, _MeshHandle):
            out = np.asarray(handle.arr)
            return out[..., :handle.width]
        return super().materialize(handle)

    def encode_hlo_text(self, width: Optional[int] = None) -> str:
        """Compiled HLO of the sharded encode at `width` (default: one
        tile per chip) — what the multichip bench and tests inspect for
        the collective-free property."""
        import jax
        import jax.numpy as jnp
        w = width or 1024 * self.mesh_devices
        sds = jax.ShapeDtypeStruct((self.k, w), jnp.uint8)
        return self._enc_fn().lower(sds).compile().as_text()

    def encode_is_collective_free(self,
                                  width: Optional[int] = None) -> bool:
        text = self.encode_hlo_text(width).lower()
        return not any(tok in text for tok in
                       ("all-reduce", "all-gather", "collective-permute",
                        "all-to-all"))

    # --- rebuild: column-sharded survivors, collective-free ---

    def _rec_fn(self, present: tuple, missing: tuple):
        key = (present, missing)
        with self._lock:
            fn = self._rec_sharded.get(key)
            if fn is None:
                fn = self._rec_sharded[key] = self._sharded(
                    gf256.reconstruction_matrix(self.k, self.m, present,
                                                missing))
            return fn

    def rec_apply_async(self, present, missing):
        present, missing = tuple(present), tuple(missing)
        fn = self._rec_fn(present, missing)

        def run(survivors: np.ndarray):
            width = int(survivors.shape[1])
            arr = self._pad_cols(np.asarray(survivors, dtype=np.uint8))
            return _MeshHandle(fn(self._stage_cols(arr)), width)

        return run


def mesh_status() -> dict:
    """Per-process mesh/EC-feed status for /admin/ec/mesh_status and the
    ec.mesh.status shell command: the configured mesh, the devices jax
    actually sees (enumerated only when the operator opted into a mesh
    or one is already live — a status probe on a mesh-less server must
    not pay jax backend init), and the per-chip staging + governor
    state from the shared "ec" registry."""
    reg = metrics_mod.shared("ec")
    feed = reg.snapshot(prefix="feed_")
    chips: dict[str, dict] = {}
    for key, value in sorted(feed.items()):
        if key.startswith("feed_chip_") and '{chip="' in key:
            name, _, rest = key.partition("{")
            chip = rest.split('"')[1]
            field = name[len("feed_chip_"):]
            chips.setdefault(chip, {})[field] = value
    out = {
        "requested": os.environ.get("WEED_EC_MESH_DEVICES", ""),
        "mesh_devices": int(feed.get("feed_mesh_devices", 0) or 0),
        "chips": chips,
        "feed": {k: v for k, v in feed.items()
                 if not k.startswith("feed_chip_")},
    }
    if out["mesh_devices"] > 0 or out["requested"].strip():
        import jax
        out["devices"] = [{"id": d.id, "platform": d.platform}
                          for d in jax.devices()]
        out["backend"] = jax.default_backend()
    else:
        out["devices"] = None  # no mesh configured: skip backend init
    return out
