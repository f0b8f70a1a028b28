"""Device bit-unpacking: the core decode primitive (jnp + Pallas).

Replaces the CPU `unpack8*` function tables for the device path.  The
formulation is chosen for TPU vector units: for a static width ``w``, a
block of 32 consecutive values occupies exactly ``w`` u32 words of the
packed stream, and the (word-index, bit-shift) pattern of the 32 values
within those words depends only on ``w`` — so the decode is

    words:  (n_blocks, w) u32
    lo    = words[:, WIDX[w]]            # static fancy index
    hi    = words[:, WIDX2[w]]
    out   = ((lo >> SHIFT[w]) | (hi << (32 - SHIFT[w]))) & mask

with zero data-dependent gathers — pure reshapes, static selects and
shifts, which XLA vectorizes onto the VPU and which is equally valid
inside a Pallas kernel.  Widths 1..32 are supported (dict indices, levels
and delta miniblocks never exceed 32; 64-bit lanes decode as two passes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["unpack_u32", "unpack_u64", "unpack_u32_pallas",
           "pad_to_words", "plan_tables"]


@functools.lru_cache(maxsize=None)
def plan_tables(width: int):
    """Static (word_idx, word_idx2, shift) tables for one width."""
    i = np.arange(32)
    bit = i * width
    widx = bit // 32
    shift = bit % 32
    # The value's high bits live in the next word when shift + width > 32.
    widx2 = np.minimum(widx + 1, width - 1)
    return (
        tuple(widx.tolist()),
        tuple(widx2.tolist()),
        tuple(shift.tolist()),
    )


def pad_to_words(data: bytes | np.ndarray, width: int, count: int) -> np.ndarray:
    """Host-side staging: pad the packed byte stream so it covers whole
    32-value blocks, and return it as little-endian u32 words."""
    buf = np.frombuffer(data, dtype=np.uint8) if not isinstance(
        data, np.ndarray
    ) else data
    n_blocks = (count + 31) // 32
    need_bytes = n_blocks * width * 4
    if len(buf) < need_bytes:
        padded = np.zeros(need_bytes, dtype=np.uint8)
        padded[: len(buf)] = buf
        buf = padded
    else:
        buf = buf[:need_bytes]
    return buf.view("<u4").reshape(n_blocks, width)


def _unpack_block_math(words, width: int):
    """(n_blocks, width) u32 -> (n_blocks, 32) u32.  Shared by the jnp and
    Pallas implementations."""
    if width == 32:
        return words
    widx, widx2, shift = plan_tables(width)
    widx = jnp.asarray(widx, dtype=jnp.int32)
    widx2 = jnp.asarray(widx2, dtype=jnp.int32)
    shift = jnp.asarray(shift, dtype=jnp.uint32)
    lo = words[:, widx]
    hi = words[:, widx2]
    mask = jnp.uint32((1 << width) - 1)
    # hi contributes only when the value straddles a word boundary;
    # (32 - shift) == 32 is UB, so gate it with where().
    straddle = (shift + width) > 32
    hi_part = jnp.where(
        straddle,
        hi << jnp.where(straddle, 32 - shift.astype(jnp.int32), 0).astype(
            jnp.uint32
        ),
        jnp.uint32(0),
    )
    return ((lo >> shift) | hi_part) & mask


@functools.partial(jax.jit, static_argnames=("width", "count"))
def unpack_u32(words: jax.Array, width: int, count: int) -> jax.Array:
    """Unpack LSB-first ``width``-bit values (device, jnp path).

    ``words``: u32 words from :func:`pad_to_words` — either the 2-D
    (n_blocks, width) matrix or its FLAT 1-D form.  Ship flat: a 2-D
    u32 array with a <=32 minor dim tiles to 128 lanes on TPU (up to
    128/width x transient HBM); the reshape here happens inside the jit
    and fuses into the column gathers.  Returns (count,) u32."""
    if width == 0:
        return jnp.zeros((count,), dtype=jnp.uint32)
    if words.ndim == 1:
        words = words.reshape(-1, width)
    out = _unpack_block_math(words.astype(jnp.uint32), width)
    return out.reshape(-1)[:count]


@functools.lru_cache(maxsize=None)
def plan_tables64(width: int):
    """Static (widx, widx2, widx3, shift) tables for widths up to 64.

    A 32-value block of ``width``-bit values spans exactly ``width`` u32
    words; value i starts at bit i*width, so its 64 bits live in up to
    three consecutive words (two 32-bit chunks at a per-lane shift)."""
    i = np.arange(32)
    bit = i * width
    widx = bit // 32
    shift = bit % 32
    widx2 = np.minimum(widx + 1, width - 1)
    widx3 = np.minimum(widx + 2, width - 1)
    return (
        tuple(widx.tolist()),
        tuple(widx2.tolist()),
        tuple(widx3.tolist()),
        tuple(shift.tolist()),
    )


def _chunk32(w_lo, w_hi, shift):
    """32 bits starting ``shift`` bits into ``w_lo`` (vector shifts;
    shift==0 gated to avoid the undefined <<32)."""
    nonzero = shift > 0
    hi_part = jnp.where(
        nonzero,
        w_hi << jnp.where(nonzero, 32 - shift.astype(jnp.int32), 0).astype(
            jnp.uint32
        ),
        jnp.uint32(0),
    )
    return (w_lo >> shift) | hi_part


@functools.partial(jax.jit, static_argnames=("width", "count"))
def unpack_u64(words: jax.Array, width: int, count: int):
    """Unpack LSB-first ``width``-bit values (width 0..64) into two u32
    lanes: returns ``(lo, hi)`` arrays of shape (count,).

    The 64-bit twin of :func:`unpack_u32` — one formulation instead of
    the reference's generated per-width unpack tables
    (``bitpacking64.go``, 3383 generated LoC)."""
    if width == 0:
        z = jnp.zeros((count,), dtype=jnp.uint32)
        return z, z
    if width <= 32:
        lo = unpack_u32(words, width, count)
        return lo, jnp.zeros((count,), dtype=jnp.uint32)
    if words.ndim == 1:
        words = words.reshape(-1, width)
    words = words.astype(jnp.uint32)
    widx, widx2, widx3, shift = plan_tables64(width)
    shift = jnp.asarray(shift, dtype=jnp.uint32)
    w1 = words[:, jnp.asarray(widx, dtype=jnp.int32)]
    w2 = words[:, jnp.asarray(widx2, dtype=jnp.int32)]
    w3 = words[:, jnp.asarray(widx3, dtype=jnp.int32)]
    lo = _chunk32(w1, w2, shift)
    hi = _chunk32(w2, w3, shift)
    if width < 64:
        hi = hi & jnp.uint32((1 << (width - 32)) - 1)
    return lo.reshape(-1)[:count], hi.reshape(-1)[:count]


def _unpack_block_unrolled(words, width: int):
    """Same math as :func:`_unpack_block_math` but with the per-lane index
    tables unrolled into static Python ints — Pallas kernels may not
    capture array constants, and 32 static shift/or ops map straight onto
    the VPU anyway.

    The word-straddle contribution uses ``hi * 2^k`` instead of
    ``hi << k``: Mosaic (TPU v5e, measured on hardware 2026-07)
    miscompiles the ``(lo >> sh) | (hi << (32 - sh))`` pattern — every
    width >= 17 data-dependently corrupts high bits of the straddle
    contribution, while widths <= 16 (including their straddle lanes,
    e.g. sh=30 at width 3) decode clean and interpret mode is bit-exact
    at every width, so the precise codegen trigger lives in Mosaic.
    The u32-wraparound multiply is the same value for every straddle
    lane and compiles correctly at every width (verified by an on-chip
    sweep vs the CPU oracle, widths 1..32)."""
    if width == 32:
        return words
    widx, widx2, shift = plan_tables(width)
    mask = np.uint32((1 << width) - 1)
    cols = []
    for i in range(32):
        sh = shift[i]
        lo = words[:, widx[i]] >> np.uint32(sh)
        if sh + width > 32:
            lo = lo | (words[:, widx2[i]]
                       * np.uint32((1 << (32 - sh)) & 0xFFFFFFFF))
        cols.append(lo & mask)
    return jnp.stack(cols, axis=1)


def _unpack_kernel(words_ref, out_ref, *, width: int):
    out_ref[:] = _unpack_block_unrolled(words_ref[:], width)


@functools.partial(jax.jit, static_argnames=("width", "count",
                                             "block_rows", "interpret"))
def unpack_u32_pallas(words: jax.Array, width: int, count: int,
                      block_rows: int = 512, interpret: bool = False):
    """Pallas version: grid over row-blocks of the words matrix, VPU
    shift/mask math in VMEM.  Semantics identical to :func:`unpack_u32`.

    Jitted so eager callers (and the A/B harness) don't pay a re-trace
    + re-lower of the pallas_call per invocation; inside the fused page
    kernels the enclosing jit makes this a no-op.

    ``interpret`` is the caller's choice: the default lowers through
    Mosaic, which only a TPU target compiles; ``interpret=True`` runs
    the Pallas interpreter on any backend (the CPU tests)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if width == 0:
        return jnp.zeros((count,), dtype=jnp.uint32)
    if words.ndim == 1:
        words = words.reshape(-1, width)
    n_blocks = words.shape[0]
    rows = min(block_rows, max(n_blocks, 1))
    grid = (pl.cdiv(n_blocks, rows),)
    padded_blocks = grid[0] * rows
    if padded_blocks != n_blocks:
        words = jnp.pad(words, ((0, padded_blocks - n_blocks), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_unpack_kernel, width=width),
        out_shape=jax.ShapeDtypeStruct((padded_blocks, 32), jnp.uint32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, width), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rows, 32), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(words.astype(jnp.uint32))
    return out.reshape(-1)[:count]
