"""Pluggable ErasureCoder interface — the seam where TPU meets storage.

The reference binds directly to klauspost/reedsolomon
(weed/storage/erasure_coding/ec_encoder.go:8); this build routes all RS math
through one interface with interchangeable backends:

- NumpyCoder   — pure-python/numpy reference (always available, slow)
- JaxCoder     — jit'd XLA (CPU or TPU; bitplane-MXU, nibble-LUT, or
                 packed-word xorsched formulation — rs_jax.FORMULATIONS)
- PallasCoder  — hand-tiled TPU kernel (rs_pallas.py)
- CppCoder     — native C++ table coder (native/, klauspost-equivalent CPU path)

All backends produce bit-identical shards (enforced by tests), so the choice
is purely a placement/performance decision. WEED_EC_FORMULATION pins the
JaxCoder kernel formulation; unset, the JaxCoder defaults to bitplane and
lets the feed governor's formulation axis retune it between runs from
measured kernel spans (retune_formulation).

"auto" never hides the device: on a TPU backend it is the PallasCoder and
a failure to build or compile it propagates; the host chain is taken only
when the backend really is the CPU (get_coder).
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Callable, Optional, Sequence

import numpy as np

from .. import observe
from ..ops import gf256, rs_jax

# fn(survivors [k, n] uint8) -> rebuilt rows [len(missing), n] uint8
ApplyFn = Callable[[np.ndarray], np.ndarray]
# (present_k, missing) -> ApplyFn
ApplyBuilder = Callable[[tuple, tuple], ApplyFn]


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

    def encode_digest_async(self, data: np.ndarray, acc=None):
        """Dispatch encode + on-device parity digest; handle materializes to
        [m] uint32 — per parity row, the wrapping byte sum mod 2^32,
        folded into `acc` when given (so a streaming caller chains ONE
        executable per batch instead of alternating digest and add
        programs).

        Device backends fuse the reduction into the encode jit so only 4*m
        bytes ever cross device->host (pipeline.stream_encode_device_sink).
        Digests combine across batches by wrapping addition, and
        zero-padding contributes nothing (parity of zeros is zeros).
        """
        parity = self.encode(data)
        digest = np.sum(parity, axis=1, dtype=np.uint32)
        if acc is not None:
            digest = (np.asarray(acc, dtype=np.uint32) + digest)
        return digest

    # --- staged-window hooks (pipeline.stream_encode_device_sink) ---
    # The window schedule separates "move bytes" (stage_async) from "run
    # kernels" (one *_window_async dispatch per staged window), so launch
    # overhead is paid once per window, not once per batch.

    def stage_async(self, data: np.ndarray):
        """Move one batch toward the device WITHOUT running any kernel.
        CPU backends return the array unchanged."""
        return np.asarray(data, dtype=np.uint8)

    def encode_digest_window_async(self, staged: Sequence, acc=None):
        """Digest a whole staged window; device backends dispatch ONE
        multi-input executable. All staged batches must share a shape."""
        for b in staged:
            acc = self.encode_digest_async(b, acc)
        return acc

    def rec_digest_window_async(self, present: tuple, missing: tuple,
                                staged: Sequence, acc=None):
        """Like encode_digest_window_async but digesting RECONSTRUCTED
        shards: staged batches are [k, n] survivor stripes; the digest is
        the [len(missing)] uint32 wrapping byte sum of the rebuilt rows."""
        apply_fn = self._rec_apply(present, missing)
        for b in staged:
            rebuilt = np.asarray(apply_fn(np.asarray(b, dtype=np.uint8)))
            d = np.sum(rebuilt, axis=1, dtype=np.uint32)
            acc = d if acc is None else np.asarray(acc, np.uint32) + d
        return acc

    def warm_encode_digest_window(self, n_batches: int,
                                  shape: tuple) -> None:
        """Ahead-of-time compile the window executable WITHOUT executing
        anything on device, so the compile is not billed to the first
        window. CPU backends have nothing to compile."""

    def warm_rec_digest_window(self, present: tuple, missing: tuple,
                               n_batches: int, shape: tuple) -> None:
        """AOT-compile the reconstruction window executable (see
        warm_encode_digest_window)."""

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


def _fused_digest(encode_fn):
    """jit((data, acc) -> acc + per-row uint32 byte sum): parity stays on
    device and the running digest accumulates inside the SAME executable,
    so a streaming caller repeats one program per batch."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fn(data, acc):
        parity = encode_fn(data)
        return acc + jnp.sum(parity.astype(jnp.uint32), axis=1,
                             dtype=jnp.uint32)

    return fn


def _rec_window_cap() -> int:
    """Max batches per RECONSTRUCTION window executable
    (WEED_EC_REC_WINDOW_BATCHES, default 8). Capping the window bounds
    the program size, and with the shared dynamic-matrix executable a cap
    >= the encode window's batch count means rebuild compiles NOTHING new.
    """
    try:
        cap = int(os.environ.get("WEED_EC_REC_WINDOW_BATCHES", "8"))
    except ValueError:
        return 8
    return cap if cap > 0 else 8


def _chunks(seq: Sequence, cap: int):
    for i in range(0, len(seq), cap):
        yield seq[i:i + cap]


def _fused_digest_multi(apply_fn):
    """jit((acc, *batches) -> acc + sum of per-batch row digests): ONE
    executable covers a whole staged window, so launch overhead is paid
    once per window instead of once per batch."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fn(acc, *batches):
        for b in batches:
            rows = apply_fn(b)
            acc = acc + jnp.sum(rows.astype(jnp.uint32), axis=1,
                                dtype=jnp.uint32)
        return acc

    return fn


def _fused_digest_multi_dyn():
    """One executable, ANY coefficient matrix: fn(acc, w, *batches)
    applies the expanded binary matrix w (rs_jax.gf_apply_bitplane_dyn)
    to every batch and folds the per-row uint32 byte sums into acc.

    Compiled once per (n_batches, batch shape) — the encode window and
    every reconstruction window share the program (the zero-padded rec
    matrix rides in as data), so a rebuild in a process (or persistent
    compilation cache) that has encoded never compiles anything."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fn(acc, w, *batches):
        for b in batches:
            rows = rs_jax.gf_apply_bitplane_dyn(w, b)
            acc = acc + jnp.sum(rows.astype(jnp.uint32), axis=1,
                                dtype=jnp.uint32)
        return acc

    return fn


def _fused_digest_multi_dyn_packed():
    """_fused_digest_multi_dyn over uint32-packed bit-plane batches
    (method="xorsched"): fn(acc, w, *planes) applies the expanded binary
    matrix as word masks (rs_jax.gf_apply_planes_dyn) — batches arrive
    already bit-plane-resident from stage_async, so the per-batch program
    contains NO expand transpose, and the only byte repack is the m
    output rows feeding the digest sum.

    Same one-executable-per-shape contract as the byte-domain dyn
    program: the matrix is runtime data, so the encode window and every
    zero-padded rec matrix share one compiled program per
    (n_batches, packed shape) and rebuild windows never recompile."""
    import jax
    import jax.numpy as jnp
    from ..ops import xor_schedule

    @jax.jit
    def fn(acc, w, *planes):
        for p in planes:
            out = rs_jax.gf_apply_planes_dyn(w, p)
            rows = xor_schedule.unpack_planes(out, int(p.shape[1]) * 32)
            acc = acc + jnp.sum(rows.astype(jnp.uint32), axis=1,
                                dtype=jnp.uint32)
        return acc

    return fn


def _aot_compile_window_dyn_packed(m_rows: int, k: int, n_batches: int,
                                   shape: tuple):
    """AOT-compile the packed dynamic-matrix window executable from the
    BYTE batch shape callers plan with (the packed staged shape is
    derived here). compiled(acc, w, *planes)."""
    import jax
    import jax.numpy as jnp
    from ..ops import xor_schedule
    jfn = _fused_digest_multi_dyn_packed()
    sds = jax.ShapeDtypeStruct(
        (int(shape[0]) * 8, xor_schedule.packed_width(int(shape[1]))),
        jnp.uint32)
    w_sds = jax.ShapeDtypeStruct((m_rows * 8, k * 8), jnp.int8)
    acc_sds = jax.ShapeDtypeStruct((m_rows,), jnp.uint32)
    return jfn.lower(acc_sds, w_sds, *([sds] * n_batches)).compile()


def _aot_compile_window_dyn(m_rows: int, k: int, n_batches: int,
                            shape: tuple):
    """AOT-compile the dynamic-matrix window executable (abstract shapes
    only — no bytes move, nothing executes). compiled(acc, w, *batches)."""
    import jax
    import jax.numpy as jnp
    jfn = _fused_digest_multi_dyn()
    sds = jax.ShapeDtypeStruct(tuple(shape), jnp.uint8)
    w_sds = jax.ShapeDtypeStruct((m_rows * 8, k * 8), jnp.int8)
    acc_sds = jax.ShapeDtypeStruct((m_rows,), jnp.uint32)
    return jfn.lower(acc_sds, w_sds, *([sds] * n_batches)).compile()


def _jax_stage(data: np.ndarray):
    import jax
    return jax.device_put(np.asarray(data, dtype=np.uint8))


def _device_info() -> dict:
    """The device a JAX-backed coder computes on, as JAX reports it."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "count": len(devs)}


def _aot_compile_window(apply_fn, m_rows: int, n_batches: int,
                        shape: tuple):
    """Lower + compile the multi-batch digest executable from abstract
    shapes only — no bytes move, no kernel runs. The returned compiled
    object is called exactly like the jit fn: compiled(acc, *batches)."""
    import jax
    import jax.numpy as jnp
    jfn = _fused_digest_multi(apply_fn)
    sds = jax.ShapeDtypeStruct(tuple(shape), jnp.uint8)
    acc_sds = jax.ShapeDtypeStruct((m_rows,), jnp.uint32)
    return jfn.lower(acc_sds, *([sds] * n_batches)).compile()


class JaxCoder(ErasureCoder):
    # subclasses may accept extra kernel backends (MeshCoder: "pallas")
    _VALID_METHODS = frozenset(rs_jax.FORMULATIONS)
    # What may be selected on a TPU backend: the programs that compiled
    # on the v5e at every batch width the governor can plan (1-64 MiB per
    # row). Chip run of PR 21 at RS(10,4): lut needs 20 GB of HBM at an
    # 8 MiB row (0.19 GB/s at 1 MiB); xorsched runs 0.95 GB/s at 8 MiB
    # against bitplane's 39 and its pack transpose needs 21 GB at 64 MiB.
    # Both stay CPU-selectable (lut is a test reference); ROADMAP A4/C2.
    _TPU_METHODS = frozenset({"bitplane"})

    def __init__(self, data_shards: int, parity_shards: int,
                 method: str | None = None):
        super().__init__(data_shards, parity_shards)
        from ..utils import compile_cache
        compile_cache.configure()
        env = rs_jax.formulation_env()
        # an explicit method or the env var pins the formulation; only an
        # unpinned coder lets the governor's formulation axis retune it
        self._method_pinned = method is not None or env is not None
        self.method = method or env or "bitplane"
        if self.method not in self._VALID_METHODS:
            raise ValueError(f"unknown formulation {self.method!r}; "
                             f"have {sorted(self._VALID_METHODS)}")
        if not self._selectable(self.method):
            raise ValueError(
                f"formulation {self.method!r} does not compile on a TPU at "
                "the served batch widths (see JaxCoder._TPU_METHODS); "
                f"on this backend choose from {sorted(self._TPU_METHODS)}")

    def _selectable(self, method: str) -> bool:
        import jax
        return jax.default_backend() != "tpu" or method in self._TPU_METHODS

    def describe(self) -> dict:
        return {**super().describe(), "formulation": self.method,
                "device": _device_info()}

    def retune_formulation(self, method: str) -> str:
        """Governor hook (pipeline._steer_formulation): switch the kernel
        formulation BETWEEN runs. Pinned coders (explicit method or
        WEED_EC_FORMULATION) ignore the request; returns the method
        actually in use so finish_run attributes kernel spans to what
        ran. The cached fused digest fn is method-bound and dropped on a
        switch; window caches key by method (or are method-generic)."""
        if (not self._method_pinned and method != self.method
                and method in rs_jax.FORMULATIONS
                and self._selectable(method)):
            self.method = method
            self._digest_fn = None
        return self.method

    def encode(self, data: np.ndarray) -> np.ndarray:
        out = rs_jax.encode_parity(np.asarray(data, dtype=np.uint8), self.m,
                                   method=self.method)
        return np.asarray(out)

    def _rec_apply(self, present, missing):
        return rs_jax._reconstruct_fn(self.k, self.m, present, missing,
                                      self.method)

    def encode_async(self, data: np.ndarray):
        import jax
        return rs_jax.encode_parity(
            jax.device_put(np.asarray(data, dtype=np.uint8)), self.m,
            method=self.method)

    def rec_apply_async(self, present, missing):
        import jax
        fn = self._rec_apply(present, missing)
        return lambda survivors: fn(
            jax.device_put(np.asarray(survivors, dtype=np.uint8)))

    def encode_digest_async(self, data: np.ndarray, acc=None):
        import jax
        import jax.numpy as jnp
        fn = getattr(self, "_digest_fn", None)
        if fn is None:
            # via the _encode_fn hook so subclasses' kernel choice
            # (MeshCoder's pallas/lut methods) holds on this path too
            fn = self._digest_fn = _fused_digest(self._encode_fn())
        if acc is None:
            acc = jnp.zeros(self.m, dtype=jnp.uint32)
        return fn(jax.device_put(np.asarray(data, dtype=np.uint8)), acc)

    def stage_async(self, data: np.ndarray):
        """H2D staging; under method="xorsched" the batch is ALSO
        transposed to uint32-packed bit-plane rows here — once per batch
        on the stager pool, fused with the H2D put — so every window
        kernel (encode, digests, rebuild) consumes the resident layout
        and the expand/repack cost amortizes from per-kernel to
        per-window. The packed form is the same total bytes as the
        input (no 8x lane expansion)."""
        if self.method != "xorsched":
            return _jax_stage(data)
        from .. import faults, observe
        if faults.fire("ec.stage.pack"):
            # a dropped pack has no silent fallback: the window kernels
            # need the resident layout, so failing the stage is the
            # honest degradation (the sink's error path surfaces it)
            raise faults.FaultError("dropped at ec.stage.pack")
        import jax
        with observe.span("ec.stage.pack"):
            arr = jax.device_put(np.asarray(data, dtype=np.uint8))
            return self._pack_fn()(arr)

    def _pack_fn(self):
        fn = getattr(self, "_pack_jit", None)
        if fn is None:
            import jax
            from ..ops import xor_schedule
            fn = self._pack_jit = jax.jit(xor_schedule.pack_planes)
        return fn

    def _encode_fn(self):
        return lambda d: rs_jax.encode_parity(d, self.m, method=self.method)

    def _wcache(self) -> dict:
        cache = getattr(self, "_window_cache", None)
        if cache is None:
            cache = self._window_cache = {}
        return cache

    # --- dynamic-matrix window path (bitplane method) ---
    # The window executable takes the expanded binary matrix as DATA, so
    # encode and every reconstruction share one program per
    # (n_batches, shape): warming the encode window warms every rebuild.

    def _dyn_w(self, key, build):
        cache = getattr(self, "_dyn_mats", None)
        if cache is None:
            cache = self._dyn_mats = {}
        w = cache.get(key)
        if w is None:
            import jax.numpy as jnp
            w = cache[key] = jnp.asarray(rs_jax.bitplane_matrix(build()))
        return w

    def _dyn_w_enc(self):
        return self._dyn_w(
            "enc", lambda: gf256.parity_matrix(self.k, self.m))

    def _dyn_w_rec(self, present: tuple, missing: tuple):
        def build() -> np.ndarray:
            rec = gf256.reconstruction_matrix(self.k, self.m, present,
                                              missing)
            if rec.shape[0] < self.m:
                # zero rows reconstruct zeros (digest 0): padding to the
                # parity matrix's shape is what lets the rec window reuse
                # the encode executable; callers slice the pad rows off
                rec = np.vstack([
                    rec, np.zeros((self.m - rec.shape[0], self.k),
                                  dtype=rec.dtype)])
            return rec
        return self._dyn_w(("rec", present, missing), build)

    def _dyn_window_fn(self, n_batches: int, shape: tuple):
        cache = self._wcache()
        key = ("dynw", n_batches, tuple(shape))
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = _fused_digest_multi_dyn()
        return fn

    def _packed_shape(self, shape: tuple) -> tuple:
        from ..ops import xor_schedule
        return (shape[0] * 8, xor_schedule.packed_width(shape[1]))

    def _dyn_window_fn_packed(self, n_batches: int, shape: tuple):
        # shape is the PACKED per-batch shape (staged batches are already
        # bit-plane words under xorsched); keyed separately from "dynw"
        # so byte- and packed-domain programs never collide
        cache = self._wcache()
        key = ("dynwp", n_batches, tuple(shape))
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = _fused_digest_multi_dyn_packed()
        return fn

    def _dyn_window_builder(self):
        """The matrix-as-data window builder for this formulation, or
        None when the formulation has no dyn path (lut): bitplane windows
        consume byte batches, xorsched windows consume the bit-plane-
        resident batches stage_async produces. Either way encode and
        every rebuild share ONE executable per (n_batches, shape)."""
        if self.method == "bitplane":
            return self._dyn_window_fn
        if self.method == "xorsched":
            return self._dyn_window_fn_packed
        return None

    def encode_digest_window_async(self, staged, acc=None):
        import jax.numpy as jnp
        if acc is None:
            acc = jnp.zeros(self.m, dtype=jnp.uint32)
        dyn = self._dyn_window_builder()
        if dyn is not None:
            fn = dyn(len(staged), staged[0].shape)
            return fn(acc, self._dyn_w_enc(), *staged)
        cache = self._wcache()
        key = ("enc", self.method, len(staged), tuple(staged[0].shape))
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = _fused_digest_multi(self._encode_fn())
        return fn(acc, *staged)

    def rec_digest_window_async(self, present, missing, staged, acc=None):
        import jax.numpy as jnp
        present, missing = tuple(present), tuple(missing)
        cap = _rec_window_cap()
        dyn = self._dyn_window_builder()
        if dyn is not None:
            n_missing = len(missing)
            if acc is None:
                full = jnp.zeros(self.m, dtype=jnp.uint32)
            elif n_missing == self.m:
                full = jnp.asarray(acc, dtype=jnp.uint32)
            else:
                full = jnp.pad(jnp.asarray(acc, dtype=jnp.uint32),
                               (0, self.m - n_missing))
            w = self._dyn_w_rec(present, missing)
            for chunk in _chunks(list(staged), cap):
                fn = dyn(len(chunk), chunk[0].shape)
                full = fn(full, w, *chunk)
            return full if n_missing == self.m else full[:n_missing]
        if acc is None:
            acc = jnp.zeros(len(missing), dtype=jnp.uint32)
        cache = self._wcache()
        for chunk in _chunks(list(staged), cap):
            key = ("rec", self.method, present, missing, len(chunk),
                   tuple(chunk[0].shape))
            fn = cache.get(key)
            if fn is None:
                fn = cache[key] = _fused_digest_multi(
                    self._rec_apply(present, missing))
            acc = fn(acc, *chunk)
        return acc

    def warm_encode_digest_window(self, n_batches, shape):
        if self.method == "bitplane":
            key = ("dynw", n_batches, tuple(shape))
            self._wcache()[key] = _aot_compile_window_dyn(
                self.m, self.k, n_batches, shape)
            return
        if self.method == "xorsched":
            # warm takes the BYTE batch shape (what the pipeline knows);
            # the packed shape it compiles for is what stage_async emits
            key = ("dynwp", n_batches, self._packed_shape(tuple(shape)))
            self._wcache()[key] = _aot_compile_window_dyn_packed(
                self.m, self.k, n_batches, shape)
            return
        key = ("enc", self.method, n_batches, tuple(shape))
        self._wcache()[key] = _aot_compile_window(
            self._encode_fn(), self.m, n_batches, shape)

    def warm_rec_digest_window(self, present, missing, n_batches, shape):
        cap = _rec_window_cap()
        sizes = {min(cap, n_batches)}
        if n_batches > cap and n_batches % cap:
            sizes.add(n_batches % cap)
        if self.method == "bitplane":
            for n in sizes:
                key = ("dynw", n, tuple(shape))
                if key not in self._wcache():
                    self._wcache()[key] = _aot_compile_window_dyn(
                        self.m, self.k, n, shape)
            return
        if self.method == "xorsched":
            for n in sizes:
                key = ("dynwp", n, self._packed_shape(tuple(shape)))
                if key not in self._wcache():
                    self._wcache()[key] = _aot_compile_window_dyn_packed(
                        self.m, self.k, n, shape)
            return
        present, missing = tuple(present), tuple(missing)
        for n in sizes:
            key = ("rec", self.method, present, missing, n, tuple(shape))
            self._wcache()[key] = _aot_compile_window(
                self._rec_apply(present, missing), len(missing), n, shape)


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
        env = rs_jax.formulation_env()
        if env not in (None, "bitplane"):
            # an operator's pin is never dropped silently: the Pallas
            # xorsched twin was withdrawn in PR 21 (VMEM refusal at this
            # tile, 4.8 GB/s at tile 2048 against 45 on the v5e) and lut
            # never had one
            raise ValueError(
                f"WEED_EC_FORMULATION={env} pins an XLA formulation; the "
                "Pallas coder has one kernel (bitplane). Unset it, or "
                "select the XLA coder with -coder jax.")
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
        self._digest_cache: dict = {}

    def _apply(self, matrix: np.ndarray, host: bool = False):
        build = (self._mod.gf_apply_pallas_host if host
                 else self._mod.gf_apply_pallas)
        return build(matrix, tile=self.tile, interpret=self.interpret,
                     vmem_limit_bytes=self.vmem_limit_bytes)

    def describe(self) -> dict:
        return {**super().describe(), "tile": self.tile,
                "vmem_limit_bytes": self.vmem_limit_bytes,
                "interpret": self.interpret, "device": _device_info()}

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

    def encode_digest_async(self, data: np.ndarray, acc=None):
        import jax.numpy as jnp
        if acc is None:
            acc = jnp.zeros(self.m, dtype=jnp.uint32)
        fn = self._digest_cache.get("batch")
        if fn is None:
            fn = self._digest_cache["batch"] = _fused_digest(self._encode)
        return fn(_jax_stage(data), acc)

    stage_async = staticmethod(_jax_stage)

    def encode_digest_window_async(self, staged, acc=None):
        import jax.numpy as jnp
        if acc is None:
            acc = jnp.zeros(self.m, dtype=jnp.uint32)
        key = ("enc", len(staged), tuple(staged[0].shape))
        fn = self._digest_cache.get(key)
        if fn is None:
            fn = self._digest_cache[key] = _fused_digest_multi(self._encode)
        return fn(acc, *staged)

    def rec_digest_window_async(self, present, missing, staged, acc=None):
        import jax.numpy as jnp
        if acc is None:
            acc = jnp.zeros(len(missing), dtype=jnp.uint32)
        # capped like the Jax path: a bounded rec program per chunk
        # instead of one giant window executable (see _rec_window_cap)
        for chunk in _chunks(list(staged), _rec_window_cap()):
            key = ("rec", present, missing, len(chunk),
                   tuple(chunk[0].shape))
            fn = self._digest_cache.get(key)
            if fn is None:
                fn = self._digest_cache[key] = _fused_digest_multi(
                    self._rec_apply(present, missing))
            acc = fn(acc, *chunk)
        return acc

    def warm_encode_digest_window(self, n_batches, shape):
        key = ("enc", n_batches, tuple(shape))
        self._digest_cache[key] = _aot_compile_window(
            self._encode, self.m, n_batches, shape)

    def warm_rec_digest_window(self, present, missing, n_batches, shape):
        key = ("rec", present, missing, n_batches, tuple(shape))
        self._digest_cache[key] = _aot_compile_window(
            self._rec_apply(present, missing), len(missing), n_batches,
            shape)


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
register_coder("jax_lut", lambda k, m: JaxCoder(k, m, method="lut"))
register_coder("jax_xorsched",
               lambda k, m: JaxCoder(k, m, method="xorsched"))
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
