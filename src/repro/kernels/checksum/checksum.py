"""On-device data-integrity checksum + fused QA statistics (paper §2.3/§2.1).

The paper checksums every storage<->compute transfer on the host, and runs a
fast visual-QA pass over every ingested volume. Both are single-read
reductions over the same bytes, so we fuse them: ONE device pass over a
volume emits

    s1 = sum_i w_i            (mod 2^32, int32 wrap-around)      \\ transfer
    s2 = sum_i (i mod M) w_i  (mod 2^32),  M = 65521             /  checksum
    min, max, sum             over finite float values            \\ fast QA
    finite_count                                                  /

replacing ~5 separate numpy passes (isfinite, std, mean, checksum, ...) in
``core.ingest._fast_qa`` with a single ``pallas_call``. A batched variant
grids over the leading dim so a whole shape-bucket of volumes is verified in
one launch. ``ref.py`` defines the identical functions in numpy; kernel and
oracle agree bit-exactly — float sums use a fixed power-of-two halving tree
on both sides (elementwise IEEE adds, no reassociation, subnormals flushed
to zero), integer checksums wrap mod 2^32.

TPU layout. Operands are lane-dense ``(rows, 128)`` tiles. One QA block of
:data:`~.ref.BLK` = 1024 values is exactly one ``(8, 128)`` float32 vreg, so
the block's halving tree is a butterfly of sublane rolls (4, 2, 1) then lane
rolls (64 … 1). Each roll is by half the current span, which makes the
butterfly independent of the roll direction, and it leaves the block sum in
every element. Every carry — the checksum, count, min and max partials and
the running sum — is a whole ``(8, 128)`` tile, so the kernels never store a
scalar to VMEM. The partial tiles are reduced to scalars by XLA after the
call: integer sums wrap and min/max are exact, so that order is free.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import BLK, EXP_MASK, M_POS, ftz_f32, qa_block_size, tree_sum_f32

LANES = 128
SUBLANES = BLK // LANES       # 8: one QA block == one (8, 128) f32 tile
STEP_BLOCKS = 64              # QA blocks per grid step (256 KiB of f32)
CHECKSUM_STEP_ROWS = 512      # word rows per step of the plain checksum


class StreamSizeError(ValueError):
    """The bytes fed to a :class:`QAChecksumAccumulator` disagree with the
    array size it was declared for (overrun or truncated transfer)."""


def _auto_interpret(interpret):
    """Pallas kernels compile only on TPU; elsewhere run interpreted."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


# ---------------------------------------------------------------------------
# in-kernel helpers (each operates on one (8, 128) tile)
# ---------------------------------------------------------------------------

def _tile_pos():
    """Flat element index of each position of an (8, 128) tile."""
    row = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)
    return row * LANES + lane


def _ftz(x):
    """Flush float32 subnormals (and -0.0) to +0.0 — ``ref.ftz_f32``."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where((bits & EXP_MASK) == 0, jnp.float32(0.0), x)


def _tree_sum_f32(v):
    """``ref.tree_sum_f32`` over one 1024-value block held as an (8, 128)
    tile, as a roll butterfly: after the roll by half of span ``s`` every
    element holds the tree node ``T[i mod s/2]``, so the block sum ends up
    in every element whichever way the hardware rotates."""
    for shift in (4, 2, 1):
        v = _ftz(v + pltpu.roll(v, shift, 0))
    for shift in (64, 32, 16, 8, 4, 2, 1):
        v = _ftz(v + pltpu.roll(v, shift, 1))
    return v


def _fold_words(w, base, pos0, s1, s2):
    """Add one (8, 128) tile of words whose first word has global index
    ``base`` into the (s1, s2) partial tiles. Zero words (padding) add
    nothing, so they need no mask."""
    pos = base % M_POS + pos0
    pos = jnp.where(pos >= M_POS, pos - M_POS, pos)
    return s1 + w, s2 + w * pos


def _rows(ref, j):
    return ref[pl.ds(pl.multiple_of(j * SUBLANES, SUBLANES), SUBLANES), :]


# ---------------------------------------------------------------------------
# plain checksum (kept: the transfer-only fast path)
# ---------------------------------------------------------------------------

def _checksum_kernel(w_ref, o_ref, *, rows: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    pos0 = _tile_pos()

    def body(j, s):
        return _fold_words(_rows(w_ref, j), (i * rows + j * SUBLANES) * LANES,
                           pos0, *s)

    s1, s2 = jax.lax.fori_loop(0, rows // SUBLANES, body,
                               (o_ref[0], o_ref[1]))
    o_ref[0] = s1
    o_ref[1] = s2


def _to_words(x) -> jnp.ndarray:
    """Little-endian int32 word view of an array's bytes (zero-padded)."""
    x = x.reshape(-1)
    if x.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    if x.dtype.itemsize < 4:
        per = 4 // x.dtype.itemsize
        x = jnp.pad(x, (0, (-x.size) % per)).reshape(-1, per)
    return jax.lax.bitcast_convert_type(x, jnp.int32).reshape(-1)


def _pad_rows(words, rows: int):
    """Zero-pad a (..., n) int32 word stream to (..., rows, 128)."""
    pad = rows * LANES - words.shape[-1]
    if pad:
        widths = [(0, 0)] * (words.ndim - 1) + [(0, pad)]
        words = jnp.pad(words, widths)
    return words.reshape(words.shape[:-1] + (rows, LANES))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _device_checksum(x, *, interpret: bool):
    words = _to_words(x).reshape(-1)
    rows = max(_round_up(-(-words.size // LANES), SUBLANES), SUBLANES)
    step = min(CHECKSUM_STEP_ROWS, rows)
    rows = _round_up(rows, step)
    out = pl.pallas_call(
        functools.partial(_checksum_kernel, rows=step),
        grid=(rows // step,),
        in_specs=[pl.BlockSpec((step, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((2, SUBLANES, LANES), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((2, SUBLANES, LANES), jnp.int32),
        interpret=interpret,
    )(_pad_rows(words, rows))
    return jnp.sum(out, axis=(1, 2))


def device_checksum(x, *, interpret=None):
    """x: any array. Returns int32[2] = (s1, s2) over its uint32 word view."""
    return _device_checksum(jnp.asarray(x),
                            interpret=_auto_interpret(interpret))


# ---------------------------------------------------------------------------
# fused QA + checksum
# ---------------------------------------------------------------------------
# One kernel serves the one-shot, batched and chunk-accumulating entry
# points. It folds a grid of (volume g, step i) over a carry that enters as
# an operand: the one-shot call passes the empty carry, a stream chunk
# passes the previous chunk's result. ``off`` (SMEM) holds the global word
# and value offsets of the first element and the end of the valid values,
# so a chunk's arithmetic is operation for operation the one-shot kernel's
# — bit-exact for any chunking. Float32 volumes pass their words only (the
# values are the same bits); other dtypes also pass their values widened to
# float32, one block per (8, 128) tile.

def _qa_kernel(off_ref, *refs, k_blocks: int, blk_v: int, w_rows: int,
               conv: bool):
    if conv:
        w_ref, v_ref, ci_ref, cf_ref, oi_ref, of_ref = refs
    else:
        w_ref, ci_ref, cf_ref, oi_ref, of_ref = refs
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        oi_ref[...] = ci_ref[...]
        of_ref[...] = cf_ref[...]

    w0, v0, v_end = off_ref[0], off_ref[1], off_ref[2]
    pos0 = _tile_pos()

    def words(j, w, s):
        return _fold_words(w, w0 + (i * w_rows + j * SUBLANES) * LANES,
                           pos0, *s)

    def values(k, bits, q):
        cnt, vmin, vmax, vsum = q
        vidx = v0 + (i * k_blocks + k) * blk_v + pos0
        valid = vidx < v_end
        if blk_v < BLK:                       # one short, zero-padded block
            valid = valid & (pos0 < blk_v)
        exp = bits & EXP_MASK
        finite = valid & (exp != EXP_MASK)
        v = jnp.where(finite & (exp != 0),
                      jax.lax.bitcast_convert_type(bits, jnp.float32),
                      jnp.float32(0.0))
        return (cnt + finite.astype(jnp.int32),
                jnp.minimum(vmin, jnp.where(finite, v, jnp.inf)),
                jnp.maximum(vmax, jnp.where(finite, v, -jnp.inf)),
                _ftz(vsum + _tree_sum_f32(v)))

    s = (oi_ref[0], oi_ref[1])
    q = (oi_ref[2], of_ref[0], of_ref[1], of_ref[2])
    if conv:
        s = jax.lax.fori_loop(
            0, w_rows // SUBLANES, lambda j, s: words(j, _rows(w_ref, j), s),
            s)
        q = jax.lax.fori_loop(
            0, k_blocks, lambda k, q: values(
                k, jax.lax.bitcast_convert_type(_rows(v_ref, k), jnp.int32),
                q), q)
    else:
        def body(k, sq):
            w = _rows(w_ref, k)
            return words(k, w, sq[0]), values(k, w, sq[1])
        s, q = jax.lax.fori_loop(0, k_blocks, body, (s, q))
    oi_ref[0], oi_ref[1] = s
    oi_ref[2], of_ref[0], of_ref[1], of_ref[2] = q


@functools.partial(jax.jit, static_argnames=("k_blocks", "blk_v",
                                             "interpret"))
def _qa_call(off, words, vals, ci, cf, *, k_blocks: int, blk_v: int,
             interpret: bool):
    """Run the fused kernel. ``words``: (G, w_rows_total, 128) int32;
    ``vals``: (G, v_rows_total, 128) float32, or None for float32 volumes;
    ``off``: int32[3] = (first word index, first value index, value end);
    ``ci``/``cf``: the (G, 3, 8, 128) int32/float32 carry. Returns the new
    carry."""
    G = words.shape[0]
    conv = vals is not None
    nsteps = (vals if conv else words).shape[1] // (SUBLANES * k_blocks)
    w_rows = words.shape[1] // nsteps
    tile = lambda rows: pl.BlockSpec((None, rows, LANES),
                                     lambda g, i: (g, i, 0))
    carry = pl.BlockSpec((None, 3, SUBLANES, LANES),
                         lambda g, i: (g, 0, 0, 0))
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), tile(w_rows)]
    operands = [off, words]
    if conv:
        in_specs.append(tile(SUBLANES * k_blocks))
        operands.append(vals)
    return pl.pallas_call(
        functools.partial(_qa_kernel, k_blocks=k_blocks, blk_v=blk_v,
                          w_rows=w_rows, conv=conv),
        grid=(G, nsteps),
        in_specs=in_specs + [carry, carry],
        out_specs=(carry, carry),
        out_shape=(jax.ShapeDtypeStruct(ci.shape, jnp.int32),
                   jax.ShapeDtypeStruct(cf.shape, jnp.float32)),
        interpret=interpret,
    )(*operands, ci, cf)


def _empty_carry(G: int):
    tile = jnp.ones((G, 1, SUBLANES, LANES), jnp.float32)
    return (jnp.zeros((G, 3, SUBLANES, LANES), jnp.int32),
            jnp.concatenate([tile * jnp.inf, tile * -jnp.inf, tile * 0.0],
                            axis=1))


def _finish(ci, cf):
    """Carry tiles -> (sums int32 (G,2), qa f32 (G,3), cnt int32 (G,1))."""
    sums = jnp.sum(ci[:, :2], axis=(2, 3))            # int32 wrap-around
    cnt = jnp.sum(ci[:, 2], axis=(1, 2))[:, None]
    qa = jnp.stack([jnp.min(cf[:, 0], axis=(1, 2)),
                    jnp.max(cf[:, 1], axis=(1, 2)),
                    cf[:, 2, 0, 0]], axis=1)
    return sums, qa, cnt


def _launch_shape(nblocks: int, blk_v: int, itemsize: int):
    """(k_blocks, padded block count, word rows) of a launch over
    ``nblocks`` QA blocks. A launch of fewer than :data:`STEP_BLOCKS`
    blocks is one grid step; a longer one steps 64 full blocks at a time,
    whose words fill whole (8, 128) tiles for every itemsize."""
    nblocks = max(nblocks, 1)
    k_blocks = min(STEP_BLOCKS, nblocks)
    nb_pad = _round_up(nblocks, k_blocks)
    words = nb_pad * blk_v * itemsize // 4
    if itemsize == 4 or nb_pad > k_blocks:
        w_rows = max(-(-words // LANES), SUBLANES)
    else:
        w_rows = _round_up(max(-(-words // LANES), 1), SUBLANES)
    return k_blocks, nb_pad, w_rows


@functools.partial(jax.jit, static_argnames=("interpret",))
def _qa_checksum_2d(vals, *, interpret: bool):
    """Core batched op. vals: (G, nv) in the original dtype. Returns
    (sums int32 (G,2), qa f32 (G,3), cnt int32 (G,1))."""
    G, nv = vals.shape
    itemsize = vals.dtype.itemsize
    blk_v = qa_block_size(nv, itemsize)
    k_blocks, nb_pad, w_rows = _launch_shape(-(-nv // blk_v), blk_v,
                                             itemsize)
    # pad each ROW's byte extent to a word boundary before packing, so words
    # never straddle volume boundaries (matches the per-row oracle padding)
    row_pad = 0
    while (nv + row_pad) * itemsize % 4:
        row_pad += 1
    wvals = jnp.pad(vals, ((0, 0), (0, row_pad))) if row_pad else vals
    words = _pad_rows(_to_words(wvals).reshape(G, -1), w_rows)
    fvals = None
    if vals.dtype != jnp.float32:
        fvals = _pad_rows(vals.astype(jnp.float32), nb_pad * SUBLANES)
    off = jnp.array([0, 0, nv], jnp.int32)
    return _finish(*_qa_call(off, words, fvals, *_empty_carry(G),
                             k_blocks=k_blocks, blk_v=blk_v,
                             interpret=interpret))


def qa_checksum_batched(x, *, interpret=None):
    """Fused QA+checksum over a shape-bucket: ``x`` is (N, ...) — N volumes
    verified in ONE ``pallas_call`` (grid over the leading dim). Returns
    (int32 (N,2) checksums, float32 (N,3) [min,max,sum], int32 (N,1) counts).
    """
    x = jnp.asarray(x)
    return _qa_checksum_2d(x.reshape(x.shape[0], -1),
                           interpret=_auto_interpret(interpret))


def qa_checksum(x, *, interpret=None):
    """Unbatched fused QA+checksum: one device pass over ``x`` emitting
    ``(s1, s2)``, ``(min, max, sum)`` over finite values, and finite_count.
    Returns (int32[2], float32[3], int32[1]); see :func:`qa_stats` for a
    friendly view."""
    x = jnp.asarray(x)
    sums, qa, cnt = _qa_checksum_2d(x.reshape(1, -1),
                                    interpret=_auto_interpret(interpret))
    return sums[0], qa[0], cnt[0]


# ---------------------------------------------------------------------------
# chunk-accumulating variant (streaming ingest, repro.core.stream)
# ---------------------------------------------------------------------------
# The one-shot call wants the whole volume resident before it can launch —
# which is exactly the host-side pass the streaming ingest path exists to
# kill. The accumulator below folds the SAME per-block reduction over
# arbitrary byte chunks as they arrive off the wire: each launch starts
# from the previous launch's carry tiles at the chunk's global word/value
# offsets, so the arithmetic executed across all launches is
# operation-for-operation the one-shot kernel's sequence (it re-buffers to
# block alignment so callers may feed arbitrary chunk sizes, including one
# chunk bigger than the volume). Launch sizes are rounded up to a power of
# two of blocks, so a stream of fixed-size chunks compiles once.

# dtypes both backends fold identically: little-endian native numerics that
# jnp.asarray round-trips losslessly (f64/i64 would silently downcast under
# default-x64-off jax, so they are excluded rather than wrong)
ACCUMULATOR_DTYPES = ("float16", "float32", "int8", "uint8", "int16",
                      "uint16", "int32", "uint32")


class QAChecksumAccumulator:
    """Fold one logical array's bytes through the fused QA+checksum pass,
    chunk by chunk, bit-exact with one-shot :func:`qa_stats` on the whole
    array.

    Feed arbitrary byte chunks via :meth:`update` (internal re-buffering
    aligns launches to the shared kernel/oracle block size, so chunk
    boundaries never have to respect it) and call :meth:`finalize` when the
    last byte is in — the :class:`QAStats` verdict is available the moment
    the transfer completes, with no second pass over the bytes.

    ``backend="device"`` launches the Pallas kernel per fold (each
    :meth:`update` stages its chunk host→device and dispatches
    asynchronously; only :meth:`finalize` blocks). ``backend="host"`` runs a
    vectorized numpy fold with the identical block tree — bit-exact with the
    kernel — for hosts without an accelerator. The default picks ``device``
    on TPU and ``host`` elsewhere (interpret-mode Pallas is for tests, not
    data-plane throughput). ``backend`` and ``interpret`` hold what was
    actually used, for the stream report. A kernel that fails to compile or
    run raises out of :meth:`update`/:meth:`finalize`; only a byte count
    that disagrees with the declared size raises :class:`StreamSizeError`.
    """

    def __init__(self, n_vals: int, dtype, *, interpret=None,
                 backend: str = "auto"):
        self.dtype = np.dtype(dtype)
        if self.dtype.name not in ACCUMULATOR_DTYPES:
            raise ValueError(
                f"unsupported streaming-QA dtype {self.dtype} "
                f"(supported: {', '.join(ACCUMULATOR_DTYPES)})")
        if n_vals < 0:
            raise ValueError(f"negative n_vals {n_vals}")
        self.n_vals = int(n_vals)
        self.itemsize = self.dtype.itemsize
        self.blk_v = qa_block_size(self.n_vals, self.itemsize)
        self.blk_w = self.blk_v * self.itemsize // 4
        self.align_bytes = self.blk_v * self.itemsize
        self.total_blocks = max(-(-self.n_vals // self.blk_v), 1)
        if backend == "auto":
            backend = "device" if jax.default_backend() == "tpu" else "host"
        if backend not in ("device", "host"):
            raise ValueError(f"unknown backend {backend!r}")
        self.backend = backend
        self.interpret = (_auto_interpret(interpret) if backend == "device"
                          else None)
        self.device_seconds = 0.0      # staging + fold dispatch + final sync
        self._buf = bytearray()
        self._blocks_done = 0
        self._bytes_seen = 0
        self._stats: Optional[QAStats] = None
        if backend == "device":
            self._carry = _empty_carry(1)
        else:
            self._s1 = np.uint32(0)
            self._s2 = np.uint32(0)
            self._vmin = np.float32(np.inf)
            self._vmax = np.float32(-np.inf)
            self._vsum = np.float32(0.0)
            self._cnt = 0

    # -- per-launch plumbing -------------------------------------------------

    def _fold_device(self, chunk: bytes, nblocks: int, w0: int, v0: int):
        """Stage one aligned chunk as lane-dense tiles, zero-padded to a
        power-of-two block count, and fold it into the device carry."""
        t0 = time.perf_counter()
        bucket = 1 << (nblocks - 1).bit_length()
        k_blocks, nb_pad, w_rows = _launch_shape(bucket, self.blk_v,
                                                 self.itemsize)
        words = np.zeros(w_rows * LANES, np.uint32)
        raw = np.frombuffer(bytes(chunk) + b"\0" * ((-len(chunk)) % 4),
                            "<u4")
        words[:raw.size] = raw
        vals = None
        if self.dtype != np.float32:
            vals = np.zeros(nb_pad * BLK, np.float32)
            vals[:len(chunk) // self.itemsize] = np.frombuffer(
                chunk, self.dtype)
            vals = jnp.asarray(vals.reshape(1, -1, LANES))
        v_end = min(self.n_vals, v0 + nblocks * self.blk_v)
        off = np.array([w0, v0, v_end], np.int32)
        self._carry = _qa_call(
            jnp.asarray(off), jnp.asarray(words.view(np.int32)
                                          .reshape(1, -1, LANES)),
            vals, *self._carry, k_blocks=k_blocks, blk_v=self.blk_v,
            interpret=self.interpret)
        self.device_seconds += time.perf_counter() - t0

    def _fold_host(self, chunk: bytes, nblocks: int, w0: int, v0: int):
        """Vectorized numpy twin of the kernel. Integer checksums are
        associative mod 2^32, so whole-chunk sums match the kernel's
        per-tile folds bit-for-bit; the float sum keeps the kernel's exact
        shape — per-block halving tree, then one sequential scalar add per
        block in order, subnormals flushed throughout."""
        t0 = time.perf_counter()
        w = np.frombuffer(bytes(chunk) + b"\0" * ((-len(chunk)) % 4), "<u4")
        with np.errstate(over="ignore"):
            pos = ((w0 + np.arange(w.size, dtype=np.int64)) % M_POS
                   ).astype(np.uint32)
            self._s1 = np.uint32(self._s1 + np.sum(w, dtype=np.uint32))
            self._s2 = np.uint32(self._s2 + np.sum(w * pos, dtype=np.uint32))
        v = np.zeros(nblocks * self.blk_v, np.float32)
        with np.errstate(over="ignore", invalid="ignore"):
            v[:len(chunk) // self.itemsize] = np.frombuffer(chunk, self.dtype)
        v = ftz_f32(v).reshape(nblocks, self.blk_v)
        vidx = (v0 + np.arange(v.size)).reshape(nblocks, self.blk_v)
        finite = np.isfinite(v) & (vidx < self.n_vals)
        self._cnt += int(np.sum(finite))
        self._vmin = np.minimum(self._vmin,
                                np.float32(np.min(np.where(finite, v, np.inf))))
        self._vmax = np.maximum(self._vmax,
                                np.float32(np.max(np.where(finite, v,
                                                           -np.inf))))
        for t in tree_sum_f32(np.where(finite, v, np.float32(0.0))):
            self._vsum = np.float32(ftz_f32(self._vsum + t))
        self.device_seconds += time.perf_counter() - t0

    def _process(self, chunk: bytes, nblocks: int):
        w0 = self._blocks_done * self.blk_w
        v0 = self._blocks_done * self.blk_v
        if self.backend == "device":
            self._fold_device(chunk, nblocks, w0, v0)
        else:
            self._fold_host(chunk, nblocks, w0, v0)
        self._blocks_done += nblocks

    # -- public surface ------------------------------------------------------

    def update(self, data: bytes):
        """Fold the next ``data`` bytes of the array's buffer. Whole blocks
        launch immediately (async on device); a sub-block tail is carried to
        the next update/finalize."""
        if self._stats is not None:
            raise RuntimeError("accumulator already finalized")
        self._bytes_seen += len(data)
        if self._bytes_seen > self.n_vals * self.itemsize:
            raise StreamSizeError(
                f"stream overrun: fed {self._bytes_seen} bytes for a "
                f"{self.n_vals * self.itemsize}-byte array")
        self._buf += data
        nblocks = len(self._buf) // self.align_bytes
        if nblocks:
            cut = nblocks * self.align_bytes
            self._process(bytes(self._buf[:cut]), nblocks)
            del self._buf[:cut]

    def finalize(self) -> QAStats:
        """Fold the carried tail (zero-padded + masked exactly like the
        one-shot kernel's final block) and return the whole-array
        :class:`QAStats`. Raises :class:`StreamSizeError` if the byte count
        fed does not match the declared array size — a truncated transfer
        must fail verification, not silently pass QA on a prefix."""
        if self._stats is not None:
            return self._stats
        if self._bytes_seen != self.n_vals * self.itemsize:
            raise StreamSizeError(
                f"stream truncated: fed {self._bytes_seen} of "
                f"{self.n_vals * self.itemsize} bytes")
        remaining = self.total_blocks - self._blocks_done
        if remaining:
            self._process(bytes(self._buf), remaining)
            self._buf.clear()
        if self.backend == "device":
            t0 = time.perf_counter()
            sums, qa, cnt = (np.asarray(a)[0] for a in _finish(*self._carry))
            sums = sums.view(np.uint32)
            self.device_seconds += time.perf_counter() - t0
            self._stats = QAStats(int(sums[0]), int(sums[1]), float(qa[0]),
                                  float(qa[1]), float(qa[2]), int(cnt[0]))
        else:
            self._stats = QAStats(int(self._s1), int(self._s2),
                                  float(self._vmin), float(self._vmax),
                                  float(self._vsum), self._cnt)
        return self._stats


@dataclasses.dataclass(frozen=True)
class QAStats:
    """Host-side view of one volume's fused QA+checksum pass."""
    s1: int
    s2: int
    vmin: float
    vmax: float
    vsum: float
    finite_count: int

    @property
    def checksum(self) -> int:
        return ((self.s2 & 0xFFFFFFFF) << 32) | (self.s1 & 0xFFFFFFFF)


def qa_stats(x, *, interpret=None) -> QAStats:
    """Run :func:`qa_checksum` and pull the scalars to the host."""
    sums, qa, cnt = qa_checksum(x, interpret=interpret)
    sums = np.asarray(sums).view(np.uint32)
    qa = np.asarray(qa)
    return QAStats(int(sums[0]), int(sums[1]), float(qa[0]), float(qa[1]),
                   float(qa[2]), int(np.asarray(cnt)[0]))
