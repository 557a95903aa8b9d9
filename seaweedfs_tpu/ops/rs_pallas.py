"""Hand-tiled Pallas TPU kernel for bulk GF(2^8) matrix application.

The XLA path in rs_jax.py materializes the 8x bit-plane expansion and the
int32 accumulator in HBM (~25x the input traffic). This kernel keeps the
whole expand -> MXU matmul -> mod-2 -> repack chain inside VMEM per tile,
so HBM sees only the 10 input bytes and 4 parity bytes per column — the
hot loop the reference runs on CPU SIMD
(seaweedfs weed/storage/erasure_coding/ec_encoder.go:162-192 via
klauspost/reedsolomon assembly), rebuilt for the TPU memory hierarchy.

Bit-plane layouts are pre-permuted so the kernel only does cheap sublane
concatenation / static row slices:
  input rows:  plane-major  j*C + c  == bit j of input byte c
  output rows: plane-major  i*R + r  == bit i of output byte r

The coefficient matrix is a runtime operand of the compiled program, so
one executable per (rows, cols, width) serves encode and every
reconstruction pattern — a degraded read with a new loss pattern reuses
the program (and the persistent compile cache entry) of the last one.
"""

from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import observe
from ..utils import glog
from ..utils import metrics as metrics_mod
from . import gf256
from .rs_jax import bitplane_matrix

# Columns per grid step, and the scoped-VMEM limit every pallas_call here
# states. Found on a v5e (TPU v5 lite, jax 0.9.0 / libtpu 0.0.34, PR 21):
# at RS(10,4) over an [10, 8 MiB] batch every tile from 4096 to 262144
# compiled under this limit and ran at 43.0-45.8 GB/s of input, the spread
# of one tile's own repeats, while the first call (compile) grew from 0.5 s
# at 16384 to 2.4 s at 65536 and 13.7 s at 262144. The served path meets a
# new width with every ragged last batch and every degraded-read bucket,
# so the tile is the largest one that still compiles in half a second.
# VMEM at T = 16384, uint8 blocks padded to 32 sublanes: in 32*T and out
# 32*T, each double-buffered = 2 MiB; the body's values if fully live
# (int32 data 16*T*4, int8 bit rows 96*T, int32 accumulator 32*T*4, int32
# output 8*T*4) = 5 MiB more. 32 MiB leaves room for RS(20,4) (compiled)
# and is a quarter of the chip's 128 MiB. The same kernel also compiled
# with no limit stated at 16384, 65536 and 262144.
TILE = 16384
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def _plane_major_matrix(matrix: np.ndarray) -> np.ndarray:
    """bitplane_matrix with rows/cols permuted to plane-major order."""
    r, c = matrix.shape
    w = bitplane_matrix(matrix)  # rows r*8+i, cols c*8+j
    row_perm = [rr * 8 + i for i in range(8) for rr in range(r)]
    col_perm = [cc * 8 + j for j in range(8) for cc in range(c)]
    return w[np.ix_(row_perm, col_perm)]


def _gf_kernel(w_ref, data_ref, out_ref, *, rows: int):
    # widen to int32 for the bit extraction: Mosaic has no uint8 shift
    # (arith.shrui) or uint8 elementwise lowering; VPU lanes are 32-bit
    # anyway so the widening is layout-only
    data = data_ref[:].astype(jnp.int32)  # [C, T]
    # expand to plane-major bit rows [8*C, T] without leaving VMEM
    planes = [((data >> j) & 1).astype(jnp.int8) for j in range(8)]
    bits = jnp.concatenate(planes, axis=0)
    acc = jax.lax.dot_general(
        w_ref[:], bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,  # Mosaic matmul acc must be 32-bit
    )  # [8*R, T] plane-major
    out = jnp.zeros((rows, acc.shape[1]), jnp.int32)
    for i in range(8):
        out = out | ((acc[i * rows:(i + 1) * rows, :] & 1) << i)
    out_ref[:] = out.astype(jnp.uint8)


@functools.lru_cache(maxsize=None)
def _build_apply(rows: int, cols: int, tile: int, interpret: bool,
                 vmem_limit_bytes: int):
    """jit fn (w [8R, 8C] int8, data [C, n] uint8) -> [R, n] uint8, n a
    multiple of tile."""
    kernel = functools.partial(_gf_kernel, rows=rows)

    @jax.jit
    def apply_fn(w: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
        n = data.shape[1]
        assert n % tile == 0, (n, tile)
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((rows, n), jnp.uint8),
            grid=(n // tile,),
            in_specs=[
                pl.BlockSpec((8 * rows, 8 * cols), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((cols, tile), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((rows, tile), lambda i: (0, i),
                                   memory_space=pltpu.VMEM),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=vmem_limit_bytes),
            interpret=interpret,
            name="gf_apply",
        )(w, data)

    return apply_fn


def _bind(matrix: np.ndarray, tile: int, interpret: bool,
          vmem_limit_bytes: int):
    """(kernel fn over whole-tile widths with `matrix` bound, cols)."""
    matrix = np.asarray(matrix, dtype=np.uint8)
    rows, cols = matrix.shape
    raw = _build_apply(rows, cols, tile, interpret, vmem_limit_bytes)
    w = jnp.asarray(_plane_major_matrix(matrix))  # [8R, 8C] int8
    return (lambda data: raw(w, data)), cols


def gf_apply_pallas(matrix: np.ndarray, tile: int = TILE,
                    interpret: bool = False,
                    vmem_limit_bytes: int = VMEM_LIMIT_BYTES):
    """Return fn: data [C, n] uint8 -> [R, n] uint8 ON THE DEVICE; n is
    padded to whole tiles and sliced back inside (two small XLA programs
    per distinct n beside the kernel's one — right for a pipeline that
    dispatches a few batch shapes and keeps results in flight).

    interpret is the caller's decision (tests pass True to run on the CPU
    mesh); nothing here infers it from the backend, so a process that
    lost its TPU fails in the compiler instead of interpreting."""
    kernel, _ = _bind(matrix, tile, interpret, vmem_limit_bytes)

    def apply_fn(data: jnp.ndarray) -> jnp.ndarray:
        n = data.shape[1]
        pad = (-n) % tile
        if pad:
            data = jnp.pad(data, ((0, 0), (0, pad)))
        out = kernel(data)
        return out[:, :n] if pad else out

    return apply_fn


# below this many tiles a host-side call runs at the next power of two
_BUCKET_TILES = 64


def host_widths(tile: int = TILE) -> tuple[int, ...]:
    """The bucketed widths of a host call, smallest first: 1, 2, 4 ...
    `_BUCKET_TILES` tiles (seven: 16 KiB ... 1 MiB at the served tile)."""
    return tuple(tile << i for i in range(_BUCKET_TILES.bit_length()))


def host_width(n: int, tile: int = TILE) -> int:
    """The width at which a host call of `n` columns is dispatched."""
    tiles = -(-n // tile)
    if tiles < _BUCKET_TILES:
        tiles = 1 << (tiles - 1).bit_length()
    return tiles * tile


class _HostWidths:
    """What one host-call program (`_build_apply`'s arguments name it:
    the matrix is an operand) has run at in this process, and the thread
    that compiles its bucketed widths before a read needs them."""

    def __init__(self, rows: int, cols: int, tile: int, interpret: bool,
                 vmem_limit_bytes: int):
        self.rows, self.cols, self.tile = rows, cols, tile
        self.raw = _build_apply(rows, cols, tile, interpret,
                                vmem_limit_bytes)
        self.done: set[int] = set()  # widths a call has returned from
        self.state = "idle"  # -> "running" -> "done" | "failed: ..."
        self._lock = threading.Lock()
        # a dispatch's labels, made once a width
        self._labels: dict[tuple[int, bool], dict] = {}

    def warm(self) -> None:
        """Start the warm-up, once; returns at once."""
        with self._lock:
            if self.state != "idle":
                return
            self.state = "running"
        threading.Thread(target=self._run, name="ec-warm-widths",
                         daemon=True).start()

    def _run(self) -> None:
        # under no request: a context of its own, and `enclosing` keeps
        # it out of any wide event (as `ec.generate` is)
        try:
            with observe.stage("ec.warm_widths", observe.capture(),
                               enclosing=True):
                # any matrix will do, it is an operand: put as `_bind`
                # puts a real one, so a read finds the same program
                w = jnp.asarray(np.zeros((8 * self.rows, 8 * self.cols),
                                         dtype=np.int8))
                for width in host_widths(self.tile):
                    if width not in self.done:
                        np.asarray(self.raw(w, np.zeros(
                            (self.cols, width), dtype=np.uint8)))
                        self.done.add(width)
            self.state = "done"
        except Exception as e:  # reads go on compiling what they meet
            self.state = f"failed: {type(e).__name__}: {e}"
            glog.error("warm-up of the degraded read's widths failed "
                       "(%s)", self.state)

    def status(self) -> dict:
        return {"state": self.state, "widths": sorted(self.done)}

    def count(self, n: int, width: int) -> None:
        """One dispatch of an interval of `n` columns at `width`, on the
        shared `ec` registry; `warm="no"`: no call at this width had
        returned yet, so this one compiles or waits for the compile."""
        warm = width in self.done
        labels = self._labels.get((width, warm))
        if labels is None:
            # an interval of a large block can be wider than any bucket
            # and of any width: one label for them all
            bucket = width <= _BUCKET_TILES * self.tile
            labels = self._labels[(width, warm)] = {
                "width": str(width) if bucket else "wider",
                "warm": "yes" if warm else "no"}
        reg = metrics_mod.shared("ec")
        reg.count("reconstruct_interval_bytes", value=n)
        reg.count("reconstruct_padded_bytes", value=width)
        reg.count("reconstruct_dispatch", labels=labels)


_host_states: dict[tuple, _HostWidths] = {}


def host_state(rows: int, cols: int, tile: int = TILE,
               interpret: bool = False,
               vmem_limit_bytes: int = VMEM_LIMIT_BYTES) -> _HostWidths:
    """The one `_HostWidths` of a program in this process."""
    key = (rows, cols, tile, interpret, vmem_limit_bytes)
    state = _host_states.get(key)
    if state is None:
        state = _host_states.setdefault(key, _HostWidths(*key))
    return state


def gf_apply_pallas_host(matrix: np.ndarray, tile: int = TILE,
                         interpret: bool = False,
                         vmem_limit_bytes: int = VMEM_LIMIT_BYTES):
    """Return fn: numpy [C, n] -> numpy [R, n], for callers that block on
    the answer anyway (a degraded read's interval, a synchronous encode).

    Pads and slices on the HOST, so the kernel is the only device program,
    and widths under 64 tiles round up to a power of two of tiles: reads
    of any size up to 1 MiB share seven executables instead of compiling
    one per size inside the GET (chip run of PR 21: ~1 s per degraded GET
    before, each new interval length a fresh compile). `host_state` of
    the same arguments compiles the seven ahead of the reads."""
    kernel, cols = _bind(matrix, tile, interpret, vmem_limit_bytes)
    state = host_state(len(matrix), cols, tile, interpret, vmem_limit_bytes)

    def apply_fn(data: np.ndarray, stage: str = "") -> np.ndarray:
        """`stage`: the prefix under which a caller that has one (a
        degraded read: "ec.get") wants the three steps of this call
        timed as observe stages (`<stage>.stack_pad`, `.dispatch`,
        `.d2h_wait`) and its dispatch counted; without one nothing is
        timed or counted."""
        def timed(step: str):
            return (observe.stage(stage + step) if stage
                    else contextlib.nullcontext())

        n = data.shape[1]
        width = host_width(n, tile)
        if width != n:
            with timed(".stack_pad"):
                padded = np.zeros((cols, width), dtype=np.uint8)
                padded[:, :n] = data
                data = padded
        with timed(".dispatch"):
            if stage:
                state.count(n, width)
            out = kernel(data)  # H2D + launch; returns before the device
        with timed(".d2h_wait"):
            out = np.asarray(out)  # the kernel, D2H, delinearize
        state.done.add(width)
        return out[:, :n]

    return apply_fn


@functools.lru_cache(maxsize=64)
def _encode_fn(data_shards: int, parity_shards: int, tile: int,
               interpret: bool):
    pm = gf256.parity_matrix(data_shards, parity_shards)
    return gf_apply_pallas(pm, tile=tile, interpret=interpret)


def encode_parity(data: jnp.ndarray, parity_shards: int,
                  tile: int = TILE, interpret: bool = False) -> jnp.ndarray:
    """data [k, n] uint8 -> parity [m, n] uint8 via the fused TPU kernel."""
    return _encode_fn(int(data.shape[0]), parity_shards, tile,
                      interpret)(data)
