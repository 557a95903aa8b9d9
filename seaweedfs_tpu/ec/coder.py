"""Pluggable ErasureCoder interface — the seam where TPU meets storage.

The reference binds directly to klauspost/reedsolomon
(weed/storage/erasure_coding/ec_encoder.go:8); this build routes all RS math
through one interface with interchangeable backends:

- NumpyCoder   — pure-python/numpy reference (always available, slow)
- JaxCoder     — jit'd XLA bit-plane matmul (rs_jax.py; CPU or TPU)
- PallasCoder  — hand-tiled TPU kernel (rs_pallas.py)
- CppCoder     — native C++ table coder (native/, klauspost-equivalent CPU path)

All backends produce bit-identical shards (enforced by tests), so the choice
is purely a placement/performance decision, and each backend has one kernel.

"auto" never hides the device: on a TPU backend it is the PallasCoder and
a failure to build or compile it propagates; the host chain is taken only
when the backend really is the CPU (get_coder).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Callable, Optional, Sequence

import numpy as np

from .. import observe
from ..ops import gf256, rs_jax

# fn(survivors [k, n] uint8) -> rebuilt rows [len(missing), n] uint8
ApplyFn = Callable[[np.ndarray], np.ndarray]

# the widths at which the Pallas coder dispatches a degraded read's
# interval (`ops/rs_pallas.host_widths()` at the served tile), for who
# labels by them and must not import the kernel (a server's /metrics)
DISPATCH_WIDTHS = tuple(16384 << i for i in range(7))


class ErasureCoder:
    """Encode/reconstruct fixed-width stripes of k data + m parity shards."""

    def __init__(self, data_shards: int, parity_shards: int):
        self.k = data_shards
        self.m = parity_shards

    def encode(self, data: np.ndarray) -> np.ndarray:
        """data [k, n] uint8 -> parity [m, n] uint8."""
        raise NotImplementedError

    def _rec_apply(self, present: tuple, missing: tuple) -> ApplyFn:
        """Backend hook: build the survivors->missing transform."""
        raise NotImplementedError

    # --- async pipeline hooks (ec/pipeline.py) ---
    # CPU backends compute synchronously — the streaming pipeline still
    # overlaps their compute with disk read/write via its worker threads.
    # JAX backends override these to return in-flight device computations.

    def encode_async(self, data: np.ndarray):
        """Dispatch an encode; returns a handle for materialize()."""
        return self.encode(data)

    def rec_apply_async(self, present: tuple, missing: tuple) -> ApplyFn:
        """Like _rec_apply but the returned fn may defer computation."""
        return self._rec_apply(present, missing)

    def _rec_apply_sync(self, present: tuple, missing: tuple,
                        stage: str = "") -> ApplyFn:
        """Like _rec_apply for reconstruct(), whose caller blocks on the
        answer (a degraded read): backends may trade the in-flight result
        for fewer device programs, and time their steps as observe
        stages under the caller's `stage` prefix, if it gives one."""
        return self._rec_apply(present, missing)

    def materialize(self, handle) -> np.ndarray:
        """Block until a handle from encode_async/rec_apply_async is real."""
        return np.asarray(handle)

    def warm_widths(self) -> None:
        """Called when a store generates or mounts EC shards of this
        geometry (never at boot for a server that holds none): a backend
        that compiles a program a width of a degraded read's interval
        starts compiling them now, on a thread of its own, and says how
        far it is in `describe()["warm"]`. Returns at once; a host
        backend has nothing to compile."""

    def reconstruct(self, shards: Sequence[Optional[np.ndarray]],
                    data_only: bool = False,
                    targets: Optional[Sequence[int]] = None,
                    stage: str = ""
                    ) -> list[Optional[np.ndarray]]:
        """Fill missing (None) entries from any k survivors.

        targets: rebuild only these shard ids (all must be absent); default
        rebuilds every absent shard (all of them, or data shards only with
        data_only=True) — matching the reference coder's
        Reconstruct/ReconstructData split.

        stage: a caller that names its steps (a degraded read passes
        "ec.get") has the host copies before the device timed as the
        observe stage `<stage>.stack_pad` and, where the backend's
        synchronous apply has them, its dispatch and its wait.
        """
        total = self.k + self.m
        assert len(shards) == total
        present = tuple(i for i, s in enumerate(shards) if s is not None)
        if targets is not None:
            missing = tuple(targets)
            assert all(shards[i] is None for i in missing), missing
        else:
            missing = tuple(i for i, s in enumerate(shards) if s is None
                            and (not data_only or i < self.k))
        if not missing:
            return list(shards)
        if len(present) < self.k:
            raise ValueError("too few shards to reconstruct")
        with (observe.stage(stage + ".stack_pad") if stage
              else contextlib.nullcontext()):
            survivors = np.stack([np.asarray(shards[i], dtype=np.uint8)
                                  for i in present[:self.k]])
        rebuilt = np.asarray(
            self._rec_apply_sync(present[:self.k], missing, stage)(survivors))
        out = list(shards)
        for row, tgt in enumerate(missing):
            out[tgt] = rebuilt[row]
        return out

    def verify(self, shards: Sequence[np.ndarray]) -> bool:
        data = np.stack(shards[:self.k])
        parity = np.stack(shards[self.k:])
        return bool(np.array_equal(self.encode(data), parity))

    def describe(self) -> dict:
        """What runs the RS math and where, for the volume server's boot
        log and status surface. device None = the host CPU, no JAX."""
        return {"coder": type(self).__name__,
                "geometry": f"{self.k}+{self.m}", "device": None}


class NumpyCoder(ErasureCoder):
    def encode(self, data: np.ndarray) -> np.ndarray:
        return gf256.encode_parity(np.asarray(data, dtype=np.uint8), self.m)

    def _rec_apply(self, present, missing):
        rec = gf256.reconstruction_matrix(self.k, self.m, present, missing)
        mul = gf256.mul_table()

        def apply_fn(survivors: np.ndarray) -> np.ndarray:
            out = np.zeros((len(missing), survivors.shape[1]), dtype=np.uint8)
            for r in range(rec.shape[0]):
                for c in range(rec.shape[1]):
                    out[r] ^= mul[rec[r, c]][survivors[c]]
            return out

        return apply_fn


def _jax_stage(data: np.ndarray):
    import jax
    return jax.device_put(np.asarray(data, dtype=np.uint8))


def _device_info() -> dict:
    """The device a JAX-backed coder computes on, as JAX reports it."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "count": len(devs)}


class JaxCoder(ErasureCoder):
    """The XLA coder: rs_jax's bit-plane matmul under jit, on whatever
    backend JAX initialised."""

    def __init__(self, data_shards: int, parity_shards: int):
        super().__init__(data_shards, parity_shards)
        from ..utils import compile_cache
        compile_cache.configure()

    def describe(self) -> dict:
        return {**super().describe(), "formulation": "bitplane",
                "device": _device_info()}

    def encode(self, data: np.ndarray) -> np.ndarray:
        return np.asarray(self.encode_async(data))

    def _rec_apply(self, present, missing):
        return rs_jax._reconstruct_fn(self.k, self.m, present, missing)

    def encode_async(self, data: np.ndarray):
        return rs_jax.encode_parity(_jax_stage(data), self.m)

    def rec_apply_async(self, present, missing):
        fn = self._rec_apply(present, missing)
        return lambda survivors: fn(_jax_stage(survivors))


class PallasCoder(ErasureCoder):
    """Fused TPU kernel path (rs_pallas.py) at ONE tile and ONE VMEM
    limit (rs_pallas.TILE / VMEM_LIMIT_BYTES). A compile refusal raises:
    nothing here retries at another tile or on another backend.
    interpret=True is for the CPU test mesh only."""

    def __init__(self, data_shards: int, parity_shards: int,
                 tile: int | None = None, interpret: bool = False):
        super().__init__(data_shards, parity_shards)
        from ..ops import rs_pallas
        from ..utils import compile_cache
        if not interpret:
            import jax
            if jax.default_backend() != "tpu":
                raise RuntimeError(
                    "the Pallas coder needs a TPU backend, have "
                    f"{jax.default_backend()!r}")
        compile_cache.configure()
        self._mod = rs_pallas
        self.tile = tile or rs_pallas.TILE
        self.vmem_limit_bytes = rs_pallas.VMEM_LIMIT_BYTES
        self.interpret = interpret
        pm = gf256.parity_matrix(data_shards, parity_shards)
        self._encode = self._apply(pm)
        self._encode_host = self._apply(pm, host=True)
        self._rec_cache: dict = {}

    def _apply(self, matrix: np.ndarray, host: bool = False):
        build = (self._mod.gf_apply_pallas_host if host
                 else self._mod.gf_apply_pallas)
        return build(matrix, tile=self.tile, interpret=self.interpret,
                     vmem_limit_bytes=self.vmem_limit_bytes)

    def _host_state(self):
        """The degraded read's program, one row out (rs_pallas)."""
        return self._mod.host_state(1, self.k, self.tile, self.interpret,
                                    self.vmem_limit_bytes)

    def warm_widths(self) -> None:
        self._host_state().warm()

    def describe(self) -> dict:
        return {**super().describe(), "tile": self.tile,
                "vmem_limit_bytes": self.vmem_limit_bytes,
                "interpret": self.interpret, "device": _device_info(),
                "warm": self._host_state().status()}

    def encode(self, data: np.ndarray) -> np.ndarray:
        return self._encode_host(np.asarray(data, dtype=np.uint8))

    def _rec_apply(self, present, missing, host: bool = False):
        key = (present, missing, host)
        fn = self._rec_cache.get(key)
        if fn is None:
            fn = self._rec_cache[key] = self._apply(
                gf256.reconstruction_matrix(self.k, self.m, present,
                                            missing), host=host)
        return fn

    def _rec_apply_sync(self, present, missing, stage=""):
        fn = self._rec_apply(present, missing, host=True)
        return functools.partial(fn, stage=stage) if stage else fn

    def encode_async(self, data: np.ndarray):
        return self._encode(_jax_stage(data))

    def rec_apply_async(self, present, missing):
        fn = self._rec_apply(present, missing)
        return lambda survivors: fn(_jax_stage(survivors))


class CppCoder(ErasureCoder):
    """Native C++ table kernel (native/rs_core.cpp) — the CPU production
    path, equivalent in role to the reference's klauspost/reedsolomon."""

    def __init__(self, data_shards: int, parity_shards: int):
        super().__init__(data_shards, parity_shards)
        from ..ops import native
        if not native.available():
            raise RuntimeError("native core unavailable")
        self._native = native
        self._pm = gf256.parity_matrix(data_shards, parity_shards)

    def encode(self, data: np.ndarray) -> np.ndarray:
        return self._native.gf_matrix_apply(self._pm, data)

    def _rec_apply(self, present, missing):
        rec = gf256.reconstruction_matrix(self.k, self.m, present, missing)
        return lambda survivors: self._native.gf_matrix_apply(rec, survivors)


_REGISTRY = {}


def register_coder(name: str, factory) -> None:
    _REGISTRY[name] = factory


def _mesh_factory(data_shards: int, parity_shards: int) -> ErasureCoder:
    """Mesh-or-single factory (parallel/mesh_coder.py): a MeshCoder over
    WEED_EC_MESH_DEVICES (default: every local device), degenerating to
    the plain JaxCoder on a 1-chip host. Imported lazily — the parallel
    package must not load for processes that never pick this backend."""
    from ..parallel import mesh_coder as mesh_mod
    return mesh_mod.coder(data_shards, parity_shards)


register_coder("numpy", NumpyCoder)
register_coder("jax", JaxCoder)
register_coder("pallas", PallasCoder)
register_coder("cpp", CppCoder)
register_coder("mesh", _mesh_factory)


def _auto_coder(data_shards: int, parity_shards: int) -> ErasureCoder:
    """What "auto" means, decided from the backend JAX initialised.

    tpu: the PallasCoder, and its failure is the caller's failure — a TPU
    host must never end up encoding on the host unannounced. cpu: the
    native coder, else the XLA coder. A cpu backend that JAX fell back to
    because a TPU runtime is installed but could not be initialised
    (chip busy, or owned by another process; JAX only logs that at INFO
    when JAX_PLATFORMS is unset) is refused: the operator says which one
    they meant with JAX_PLATFORMS=cpu or by freeing the chip."""
    import jax
    backend = jax.default_backend()
    if backend == "tpu":
        return PallasCoder(data_shards, parity_shards)
    if backend == "cpu":
        try:
            jax.devices("tpu")
        except RuntimeError as e:
            if "failed to initialize" in str(e):
                raise RuntimeError(
                    "EC coder 'auto': JAX fell back to the CPU because the "
                    f"TPU backend could not be initialised ({e}). Set "
                    "JAX_PLATFORMS=cpu to run the EC tier on the host, or "
                    "free the chip.") from e
    try:
        return CppCoder(data_shards, parity_shards)
    except RuntimeError:  # native core unavailable (no toolchain)
        return JaxCoder(data_shards, parity_shards)


def get_coder(name: str, data_shards: int, parity_shards: int) -> ErasureCoder:
    if name == "auto":
        return _auto_coder(data_shards, parity_shards)
    if name not in _REGISTRY:
        raise KeyError(f"unknown coder {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name](data_shards, parity_shards)
