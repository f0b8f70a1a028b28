"""Device value-decode kernels: PLAIN staging, levels→validity, dictionary
gather (fixed and variable width), BYTE_STREAM_SPLIT, and
DELTA_BINARY_PACKED int32/int64.

All kernels follow the same shape discipline: hosts stage *padded,
fixed-shape* buffers (page bytes as u32 words, run/plan tables as arrays)
and devices run pure vectorized expansion under ``jit`` — no
data-dependent Python control flow crosses the boundary (SURVEY.md §7).
Dynamic output sizes (variable-length gathers) are padded to power-of-two
buckets so XLA compiles one kernel per bucket, not per page.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .bitunpack import pad_to_words, unpack_u32

__all__ = [
    "stage_u32",
    "bss_to_lanes",
    "plain_fixed_to_lanes",
    "levels_to_validity",
    "scatter_to_dense",
    "dict_gather_fixed",
    "dict_gather_bytes",
    "plan_delta_i32",
    "expand_delta_i32",
    "plan_delta_i64",
    "expand_delta_i64",
    "bucket",
]


def bucket(n: int) -> int:
    """Round up to a power-of-two bucket (min 32) to bound recompilation."""
    b = 32
    while b < n:
        b <<= 1
    return b


def stage_u32(data, n_words: int) -> np.ndarray:
    """Host staging: raw little-endian bytes -> padded u32 word array."""
    buf = np.frombuffer(data, dtype=np.uint8) if not isinstance(
        data, np.ndarray
    ) else data
    need = n_words * 4
    if len(buf) < need:
        out = np.zeros(need, dtype=np.uint8)
        out[: len(buf)] = buf[:need]
        buf = out
    return buf[:need].view("<u4")


@functools.partial(jax.jit, static_argnames=("n_words",))
def u8_to_u32_words(b: jax.Array, n_words: int):
    """Device-resident little-endian byte stream -> (n_words,) u32.

    The device twin of :func:`stage_u32` for bytes that never visit the
    host (e.g. the device snappy decompressor's output)."""
    w = b[: n_words * 4].astype(jnp.uint32).reshape(-1, 4)
    return w[:, 0] | (w[:, 1] << 8) | (w[:, 2] << 16) | (w[:, 3] << 24)


@functools.partial(jax.jit, static_argnames=("n_words",))
def u8_to_u32_words_at(b: jax.Array, off, n_words: int):
    """Like :func:`u8_to_u32_words` but reading from byte offset ``off``
    (a traced scalar, so one compiled kernel serves every page of a
    chunk regardless of how many level bytes precede its values
    segment)."""
    w = jax.lax.dynamic_slice(b, (off,), (n_words * 4,))
    w = w.astype(jnp.uint32).reshape(-1, 4)
    return w[:, 0] | (w[:, 1] << 8) | (w[:, 2] << 16) | (w[:, 3] << 24)


@functools.partial(jax.jit, static_argnames=("count", "k", "lanes"))
def bss_to_lanes(raw: jax.Array, count: int, k: int, lanes: int):
    """BYTE_STREAM_SPLIT decode on device: ``k`` byte streams of
    ``count`` bytes each -> flat (count*lanes,) u32 little-endian lane
    words.  The scatter of value bytes across streams
    (``cpu/bss.py``) inverts to one transpose — ideal device work:
    no sequential structure at all."""
    streams = raw[: k * count].reshape(k, count)
    rows = streams.T                                  # (count, k) u8
    if k != lanes * 4:
        rows = jnp.pad(rows, ((0, 0), (0, lanes * 4 - k)))
    b = rows.reshape(count, lanes, 4).astype(jnp.uint32)
    words = (b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16)
             | (b[..., 3] << 24))
    return words.reshape(-1)


@functools.partial(jax.jit, static_argnames=("count", "type_length"))
def flba_bytes_to_lanes(raw: jax.Array, count: int, type_length: int):
    """Device-resident FLBA byte rows -> flat (count*lanes,) u32 lane
    words (rows zero-padded to whole little-endian u32 lanes — the
    DeviceColumn FLBA layout of ``_stage_byte_rows_np``).  Lets a
    device expansion (e.g. DELTA_BYTE_ARRAY front coding) feed a fixed
    column without a host round trip."""
    L = type_length
    lanes = (L + 3) // 4
    rows = raw[: count * L].reshape(count, L)
    if L != lanes * 4:
        rows = jnp.pad(rows, ((0, 0), (0, lanes * 4 - L)))
    b = rows.reshape(count, lanes, 4).astype(jnp.uint32)
    words = (b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16)
             | (b[..., 3] << 24))
    return words.reshape(-1)


def _rle_expand(ends: jax.Array, vals: jax.Array, start: int, n_runs: int,
                count: int):
    """Run table slice -> per-position values (searchsorted expand)."""
    e = ends[start : start + n_runs]
    i = jnp.arange(count, dtype=jnp.int32)
    idx = jnp.searchsorted(e, i, side="right").astype(jnp.int32)
    idx = jnp.minimum(idx, n_runs - 1)
    return vals[start + idx]


@functools.partial(jax.jit, static_argnames=("spec", "count", "lanes",
                                             "stride"))
def planes_to_words(raw32: jax.Array, rle32_ends: jax.Array,
                    rle32_vals: jax.Array, raw8: jax.Array,
                    rle8_ends: jax.Array, rle8_vals: jax.Array,
                    spec: tuple, count: int, lanes: int, stride: int):
    """Lane/byte-plane wire transport -> flat u32 lane words.

    The host ships each of the value's u32 lanes one of three ways —
    whole-lane run-length coding (``("rle32", start, n_runs)``: numeric
    data's high words are runs), raw (``("raw32", slab)``), or
    descended to its four byte planes (``("bytes", e0, e1, e2, e3)``
    with per-plane ``("raw8", slab)`` / ``("rle8", start, n_runs)``
    entries: catches constant upper bytes INSIDE an otherwise-random
    lane, e.g. values < 2^16 in an int64).  Raw slab ``j`` starts at
    ``j * stride``.  Only genuinely random bytes pay full wire;
    reconstruction (searchsorted expands + shift combine) is pure
    parallel device work."""
    return _planes_words(raw32, rle32_ends, rle32_vals, raw8, rle8_ends,
                         rle8_vals, spec, count, lanes, stride)


def _planes_words(raw32, rle32_ends, rle32_vals, raw8, rle8_ends,
                  rle8_vals, spec, count: int, lanes: int, stride: int):
    words = []
    for entry in spec:
        kind = entry[0]
        if kind == "raw32":
            j = entry[1]
            words.append(raw32[j * stride : j * stride + count])
        elif kind == "rle32":
            words.append(_rle_expand(rle32_ends, rle32_vals,
                                     entry[1], entry[2], count))
        else:  # "bytes": four byte-plane sub-entries
            b = []
            for sub in entry[1:]:
                if sub[0] == "raw8":
                    j = sub[1]
                    b.append(raw8[j * stride : j * stride + count]
                             .astype(jnp.uint32))
                else:
                    b.append(_rle_expand(rle8_ends, rle8_vals,
                                         sub[1], sub[2], count)
                             .astype(jnp.uint32))
            words.append(b[0] | (b[1] << 8) | (b[2] << 16)
                         | (b[3] << 24))
    if lanes == 1:
        return words[0]
    return jnp.stack(words, axis=1).reshape(-1)


@functools.partial(jax.jit, static_argnames=("count", "lanes"))
def plain_fixed_to_lanes(words: jax.Array, count: int, lanes: int):
    """PLAIN fixed-width values staged as u32 words -> flat u32 lanes.

    lanes=1: int32/float32; lanes=2: int64/double (lo, hi); lanes=3: int96.
    The 'decode' of PLAIN on device is a reinterpret — the point is that
    the bytes are already in HBM and never round-trip through host.

    Value buffers stay FLAT 1-D at every jit boundary: TPU tiles a 2-D
    ``u32[n, lanes]`` output as T(8,128), padding the minor dim to 128
    lanes — 64x HBM waste for int64, 128x for int32 (measured: a 400 MB
    ``u32[50M,2]`` column would allocate 25.6 GB and OOM the chip)."""
    return words[: count * lanes]


@functools.partial(jax.jit, static_argnames=("max_def",))
def levels_to_validity(def_levels: jax.Array, max_def: int):
    """Def levels -> (validity mask, packed-value position per slot).

    The fused kernel of SURVEY §2.8: mask = (def == max_def), and
    positions[i] = how many non-null values precede slot i — the gather
    index used to inflate packed values to record slots."""
    mask = def_levels == jnp.int32(max_def)
    positions = _running_count(mask.astype(jnp.int32)) - 1
    return mask, jnp.maximum(positions, 0)


def _running_count(x, block: int = 1024):
    """``jnp.cumsum`` of a 1-D int32 array, in blocks: a cumsum within
    each block of ``block``, plus each block's exclusive carry.  The
    same integers; the TPU compiler took about 29 s on one cumsum over
    a million values and a quarter of a second on this form (AOT
    compile for a described v5e)."""
    n = x.shape[0]
    rows = jnp.pad(x, (0, -n % block)).reshape(-1, block)
    inner = jnp.cumsum(rows, axis=1)
    ends = inner[:, -1]
    carry = jnp.cumsum(ends) - ends
    return (inner + carry[:, None]).reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("lanes",))
def scatter_to_dense(packed: jax.Array, mask: jax.Array,
                     positions: jax.Array, lanes: int = 1):
    """Inflate packed non-null values to one-per-slot dense form (null
    slots get 0).  ``packed`` is flat 1-D with ``lanes`` u32 words per
    value (the DeviceColumn layout); 2-D (n, lanes) inputs are also
    accepted for synthetic callers (output stays 2-D then)."""
    if packed.shape[0] == 0:
        # all slots null (zero packed values): nothing to gather — an
        # empty-buffer gather is out-of-range at any index
        n = mask.shape[0]
        shape = ((n,) + packed.shape[1:] if packed.ndim > 1
                 else (n * lanes,))
        return jnp.zeros(shape, dtype=packed.dtype)
    if packed.ndim > 1:
        gathered = packed[positions]
        return jnp.where(mask[:, None], gathered,
                         jnp.zeros_like(gathered))
    if lanes == 1:
        return jnp.where(mask, packed[positions],
                         jnp.zeros((), dtype=packed.dtype))
    rows = _take_rows(packed, positions, lanes)
    return jnp.where(mask[:, None], rows,
                     jnp.zeros((), dtype=packed.dtype)).reshape(-1)


def _take_rows(flat, idx, lanes: int):
    """Rows ``idx`` of a flat (n*lanes,) lane buffer viewed as
    (n, lanes).  A row gather, not a gather of flat per-lane word
    indices: at 1M values the TPU compiler (libtpu 0.0.34) took ~4
    minutes on the flat-index form and under a second on this one, for
    the same program and temp bytes (AOT compile for a described v5e,
    PR 21).  Indices are in range by construction; ``clip`` is the
    clamping a plain ``x[idx]`` gather does.  A bucket-padded buffer
    may end in a partial row, which no index selects: it is dropped."""
    whole = flat.shape[0] // lanes * lanes
    return jnp.take(flat[:whole].reshape(-1, lanes), idx, axis=0,
                    mode="clip")


@functools.partial(jax.jit, static_argnames=("lanes",))
def dict_gather_fixed(dictionary: jax.Array, indices: jax.Array,
                      lanes: int = 1):
    """Fixed-width dictionary gather over a FLAT (D*lanes,) u32 dict."""
    return _dict_gather_flat(dictionary, indices, lanes)


def _dict_gather_flat(dictionary, indices, lanes: int):
    if lanes == 1:
        return dictionary[indices]
    return _take_rows(dictionary, indices, lanes).reshape(-1)


# ----------------------------------------------------------------------
# Fused per-page kernels: one dispatch per data page.  Decoding a page is
# index-expand + gather (+ level expand); issuing them as one jit lets
# XLA fuse everything and collapses N dispatches into one.
# ----------------------------------------------------------------------

def _expand_core(bp, ends, rle, val, start, cnt: int, w: int, nbp: int):
    from .hybrid import expand_hybrid_core

    idx = jnp.arange(cnt, dtype=jnp.int32)
    return expand_hybrid_core(bp, ends, rle, val, start, idx, w, nbp)


def _expand_tbl(bp, table, cnt: int, w: int, nbp: int):
    """Expand from a packed (4, R) u32 run table (see hybrid.pack_plan)."""
    return _expand_core(
        bp, table[0].astype(jnp.int32), table[1] != 0, table[2],
        table[3].astype(jnp.int32), cnt, w, nbp,
    )


def _expand_stream(bp, table, cnt: int, w: int, nbp: int, single: bool):
    """Stream expansion with a static fast path: a single bit-packed run
    (what our encoder and most writers emit for levels and dict indices)
    needs no run search at all — it is a pure tiled bit-unpack.
    ``single`` is decided on host and is part of the jit key.

    The Pallas formulation of this unpack (``bitunpack.unpack_u32_pallas``,
    with the documented Mosaic width>=17 straddle-shift workaround) was
    A/B'd jitted on TPU v5e across widths 1..32 and lost or tied XLA at
    every width, so the production path is XLA-only; the kernel remains
    validated by tests (interpret mode) and measurable via
    ``tools/bench_pallas.py`` should a future Mosaic change the verdict."""
    if single and w:
        from .bitunpack import unpack_u32

        return unpack_u32(bp, w, cnt)
    return _expand_tbl(bp, table, cnt, w, nbp)


@functools.partial(jax.jit, static_argnames=("cnt", "w", "nbp", "single"))
def expand_tbl(bp, table, cnt: int, w: int, nbp: int,
               single: bool = False):
    return _expand_stream(bp, table, cnt, w, nbp, single)


@functools.partial(jax.jit, static_argnames=(
    "dcnt", "dw", "dnbp", "icnt", "iw", "inbp", "lanes", "dsingle",
    "isingle"))
def page_dict_fixed_levels_tbl(dictionary, d_bp, d_tbl, i_bp, i_tbl,
                               dcnt: int, dw: int, dnbp: int,
                               icnt: int, iw: int, inbp: int,
                               lanes: int = 1,
                               dsingle: bool = False,
                               isingle: bool = False):
    """Fused dict-page decode from packed run tables (one dispatch).
    ``dictionary`` is flat (D*lanes,) u32; returns flat values."""
    dl = _expand_stream(d_bp, d_tbl, dcnt, dw, dnbp,
                        dsingle).astype(jnp.int32)
    idx = _expand_stream(i_bp, i_tbl, icnt, iw, inbp,
                         isingle).astype(jnp.int32)
    n_dict = dictionary.shape[0] // lanes
    vals = _dict_gather_flat(dictionary, jnp.minimum(idx, n_dict - 1),
                             lanes)
    return vals, dl


@functools.partial(jax.jit, static_argnames=("icnt", "iw", "inbp", "lanes",
                                             "isingle"))
def page_dict_fixed_tbl(dictionary, i_bp, i_tbl,
                        icnt: int, iw: int, inbp: int, lanes: int = 1,
                        isingle: bool = False):
    idx = _expand_stream(i_bp, i_tbl, icnt, iw, inbp,
                         isingle).astype(jnp.int32)
    n_dict = dictionary.shape[0] // lanes
    return _dict_gather_flat(dictionary, jnp.minimum(idx, n_dict - 1),
                             lanes)


@functools.partial(jax.jit, static_argnames=(
    "count", "lanes", "dcnt", "dw", "dnbp", "dsingle"))
def page_plain_fixed_levels_tbl(words, d_bp, d_tbl, count: int, lanes: int,
                                dcnt: int, dw: int, dnbp: int,
                                dsingle: bool = False):
    dl = _expand_stream(d_bp, d_tbl, dcnt, dw, dnbp,
                        dsingle).astype(jnp.int32)
    return words[: count * lanes], dl


@functools.partial(jax.jit, static_argnames=(
    "icnt", "iw", "inbp", "total_bytes", "has_idx", "isingle", "width"))
def page_dict_bytes_tbl(dict_offsets, dict_data, i_bp, i_tbl, non_null,
                        icnt: int, iw: int, inbp: int, total_bytes: int,
                        has_idx: bool = True, isingle: bool = False,
                        width: int = 0):
    """Fused dict BYTE_ARRAY page decode: expand indices, derive the
    output offsets ON DEVICE (value lengths are just the dictionary
    offset diffs), then the byte-granular gather
    (:func:`_dict_bytes_gather`).  Shipping the offsets cost 4 bytes
    per value — more wire than the dict indices themselves for
    short-string columns; now only the run tables ship."""
    if has_idx:
        idx = _expand_stream(i_bp, i_tbl, icnt, iw, inbp,
                             isingle).astype(jnp.int32)
    else:
        idx = jnp.zeros((icnt,), jnp.int32)
    return _dict_bytes_gather(dict_offsets, dict_data, idx, non_null,
                              total_bytes, width)


def _dict_bytes_gather(dict_offsets, dict_data, idx, non_null,
                       total_bytes: int, width: int = 0):
    """One page's bytes: the first ``non_null`` values of the
    dictionary indices ``idx``, padded to ``total_bytes``.  ``width``
    > 0: the planner found every dictionary entry ``width`` bytes long,
    so the offsets step by ``width`` from 0 and value ``v`` is row
    ``idx[v]`` of the dictionary viewed as ``(D, width)``: one row
    gather, no offsets.  Otherwise the output offsets are a running
    count of the valid values' lengths, for :func:`dict_gather_bytes`."""
    if width:
        out = _take_rows(dict_data, idx, width).reshape(-1)[:total_bytes]
        return jnp.pad(out, (0, total_bytes - out.shape[0]))
    n_dict = dict_offsets.shape[0] - 1
    idx = jnp.clip(idx, 0, max(n_dict - 1, 0))
    lens = dict_offsets[1:] - dict_offsets[:-1]
    valid = jnp.arange(idx.shape[0], dtype=jnp.int32) < non_null
    contrib = jnp.where(valid, lens[idx], 0)
    out_offsets = jnp.concatenate([jnp.zeros((1,), dict_offsets.dtype),
                                   _running_count(contrib)])
    return dict_gather_bytes(dict_offsets, dict_data, idx, out_offsets,
                             total_bytes)


# ----------------------------------------------------------------------
# Chunk program: every data page of one column chunk in one dispatch.
# Pages that share a kernel's statics and input shapes form a group and
# decode together from their stacked inputs, which the host stages as
# one buffer per group and role; each page's valid prefix is then
# written into the chunk's output at its running offset.  Offsets and
# counts are runtime int32 data (``meta``), and a group's page count is
# padded to a bucket (padding slots repeat the group's first page and
# write nothing), so the compile key holds bucketed shapes, kernel
# kinds and page statics, never exact counts.
# ----------------------------------------------------------------------

def _place(out, pages, rows):
    """Write ``pages[i][:rows[i, 1]]`` into ``out`` at ``rows[i, 0]``.
    Each write keeps ``out`` beyond the page's valid prefix, so the
    order of writes does not matter and padding slots (length 0) write
    nothing.  ``out`` must reach past every offset by a page's width."""
    width = pages.shape[1]
    lane = jnp.arange(width, dtype=jnp.int32)

    def put(i, acc):
        off, n = rows[i, 0], rows[i, 1]
        cur = jax.lax.dynamic_slice(acc, (off,), (width,))
        new = jnp.where(lane < n, pages[i], cur)
        return jax.lax.dynamic_update_slice(acc, new, (off,))

    return jax.lax.fori_loop(0, pages.shape[0], put, out)


def _expand_pages(bp, tbl, cnt: int, w: int, nbp: int, single: bool):
    """:func:`_expand_stream` of ``g`` pages: stacked ``(g, words)``
    bit-packed words and ``(g, 4, R)`` run tables -> ``(g, cnt)`` u32.
    A single bit-packed run is a pure unpack, of all pages at once.
    Otherwise each page looks up its own run table, one page after
    another (``lax.map``).  For 56 pages of def levels on a TPU v5e
    this took 12.6 ms, as the 56 page kernels did (12.5 ms); one
    lookup over the pages' tables joined took 212 ms, and a ``vmap``
    of the page kernel 179 ms."""
    if single and w:
        return unpack_u32(bp.reshape(-1), w, bp.size // w * 32).reshape(
            bp.shape[0], -1)[:, :cnt]
    return jax.lax.map(lambda page: _expand_tbl(page[0], page[1], cnt, w,
                                                nbp), (bp, tbl))


def _chunk_values(kind, statics, group, shared, rows, lanes):
    """One value group (its stacked arrays, one per role) ->
    ``(pages, width)``, in output units."""
    if kind in ("plain", "plain_bytes"):
        return group[0]
    if kind == "planes":
        spec, stride = statics
        return jax.lax.map(lambda page: _planes_words(
            *page, spec, stride, lanes, stride), group)
    if kind == "delta":
        first = jax.lax.bitcast_convert_type(rows[:, 3:5], jnp.uint32)
        return jax.lax.map(lambda page: _delta_page(
            page[0], page[1], *statics), (group, first))
    icnt, iw, inbp, isingle = statics[:4]
    idx = _expand_pages(group[0], group[1], icnt, iw, inbp,
                        isingle).astype(jnp.int32)
    if kind == "dict":
        # one 1-D gather per lane, interleaved per page: a row gather
        # (_take_rows) of a whole chunk's indices took the TPU compiler
        # about a minute (AOT compile for a described v5e)
        dictionary = shared[0]
        n_dict = dictionary.shape[0] // lanes
        idx = jnp.minimum(idx, n_dict - 1)
        words = [dictionary[k::lanes][idx] for k in range(lanes)]
        return jnp.stack(words, axis=-1).reshape(idx.shape[0], -1)
    return jax.lax.map(lambda page: _dict_bytes_gather(
        shared[0], shared[1], page[0], page[1], *statics[4:]),
        (idx, rows[:, 2]))


def _delta_page(arrs, first, n_vals: int, w: int, wide: bool):
    """One page of the delta-lane transport -> its values as flat u32
    lanes, ``n_vals + 1`` of them, of which the page's own count lead.
    ``arrs``: the packed words of the one width class (none at width
    0), then the page's min_delta as one-value lo (and, for 64 bits,
    hi) arrays; ``first``: the first value's (lo, hi) words.  The same
    arithmetic as :func:`expand_delta_i32` / :func:`expand_delta_i64`
    on a plan with one contiguous group, with the count and the first
    value as runtime data."""
    words = arrs[0][: n_vals // 32 * w] if w else None
    md = arrs[1:] if w else arrs
    if not wide:
        d = (unpack_u32(words, w, n_vals) if w
             else jnp.zeros((n_vals,), jnp.uint32))
        full = d + md[0][0]  # u32 wraparound == two's complement
        return jnp.concatenate([first[:1],
                                first[0] + _running_count(full)])
    from .bitunpack import unpack_u64

    if w:
        lo, hi = unpack_u64(words, w, n_vals)
    else:
        lo = hi = jnp.zeros((n_vals,), jnp.uint32)
    flo, fhi = _add64((lo, hi), (md[0][0], md[1][0]))
    lo, hi = jax.lax.associative_scan(
        _add64, (jnp.concatenate([first[:1], flo]),
                 jnp.concatenate([first[1:], fhi])))
    return jnp.stack([lo, hi], axis=1).reshape(-1)


@functools.partial(jax.jit, static_argnames=("sig",))
def chunk_program(shared, lev_groups, val_groups, meta, sig):
    """Decode one column chunk's pages in one program.

    ``sig`` = ``(lev_sig, val_sig, lev_len, val_len, lanes, max_def)``:
    per level group its ``(cnt, w, nbp, single)``; per value group its
    kind and statics: ``"dict"`` and ``"dict_bytes"`` the indices'
    ``(icnt, iw, inbp, isingle)``, plus the byte ``cap`` and the
    dictionary's one entry length (0 if they differ) for
    ``"dict_bytes"``; ``"plain"`` and ``"plain_bytes"`` none;
    ``"planes"`` the lane ``spec`` and slab ``stride``
    (:func:`planes_to_words`); ``"delta"`` ``(n_vals, w, wide)``
    (:func:`_delta_page`).  Then the bucketed level and value totals;
    u32 lanes per value; the column's max definition level.
    ``lev_groups`` and ``val_groups`` hold each group's arrays, one
    per role, each the group's page slots stacked on a leading axis
    (level and index streams as ``(bp_words, table)``, PLAIN values
    and bytes as ``(words,)``, the transports' as they are staged);
    ``shared`` the chunk's dictionary arrays.  ``meta`` has
    one int32 row ``(offset, length, non_null, aux0, aux1)`` per page
    slot, level groups' slots first, then the value groups', in group
    order; ``aux`` holds a delta page's first value as (lo, hi) words.

    Returns bucket-padded ``(def_levels, values, mask, positions)``;
    levels, mask and positions are None for a required column."""
    lev_sig, val_sig, lev_len, val_len, lanes, max_def = sig
    lev, vals, slot = [], [], 0
    for (cnt, w, nbp, single), (bp, tbl) in zip(lev_sig, lev_groups):
        lev.append((_expand_pages(bp, tbl, cnt, w, nbp,
                                  single).astype(jnp.int32),
                    meta[slot : slot + bp.shape[0]]))
        slot += bp.shape[0]
    for (kind, *statics), group in zip(val_sig, val_groups):
        rows = meta[slot : slot + group[0].shape[0]]
        vals.append((_chunk_values(kind, statics, group, shared, rows,
                                   lanes), rows))
        slot += group[0].shape[0]
    if not lev:
        return None, _assemble(vals, val_len), None, None
    dl = _assemble(lev, lev_len)
    mask, positions = levels_to_validity(dl, max_def)
    return dl, _assemble(vals, val_len), mask, positions


def _assemble(groups, length):
    """Each group's ``(pages, meta rows)`` placed into one buffer of
    ``length`` plus the widest page, so no write runs off its end."""
    width = max(pages.shape[1] for pages, _ in groups)
    out = jnp.zeros((length + width,), groups[0][0].dtype)
    for pages, rows in groups:
        out = _place(out, pages, rows)
    return out


@functools.partial(jax.jit, static_argnames=("total_bytes",))
def plain_bytes_from_blob(blob: jax.Array, out_offsets: jax.Array, pos,
                          total_bytes: int):
    """PLAIN BYTE_ARRAY values gathered out of a device-resident page
    blob (e.g. the snappy expansion), skipping each value's 4-byte
    length prefix: value ``v``'s bytes start at
    ``pos + out_offsets[v] + 4*(v+1)`` in the blob — pure arithmetic
    from the output offsets, no extra source table on the wire."""
    if blob.shape[0] == 0:
        return jnp.zeros((total_bytes,), dtype=jnp.uint8)
    b = jnp.arange(total_bytes, dtype=jnp.int32)
    val = jnp.searchsorted(out_offsets[1:], b, side="right").astype(
        jnp.int32)
    val = jnp.minimum(val, out_offsets.shape[0] - 2)
    src = pos + out_offsets[val] + 4 * (val + 1) + (b - out_offsets[val])
    src = jnp.clip(src, 0, blob.shape[0] - 1)
    return blob[src]


@functools.partial(jax.jit, static_argnames=("total_bytes",))
def dict_gather_bytes(dict_offsets: jax.Array, dict_data: jax.Array,
                      indices: jax.Array, out_offsets: jax.Array,
                      total_bytes: int):
    """Variable-length dictionary gather -> the values' bytes, padded
    to ``total_bytes``: the device analogue of the reference's
    per-value dict gather (``type_dict.go:39-59``), vectorized at byte
    granularity.

    Byte ``b`` of value ``v`` reads ``b + dict_offsets[indices[v]] -
    out_offsets[v]`` of the blob.  That shift changes only where a
    value starts, so each value's change of shift is added at its
    output offset (one scatter of ``n`` values; empty values that share
    an offset telescope to the last one's shift), and a blocked running
    count (:func:`_running_count`) gives every byte its shift: one
    linear pass over the bytes, no search.  Bytes past the last value
    are padding, of no fixed content.

    A dictionary of all-empty strings has a zero-length blob (legal:
    ``type_bytearray.go:24-55`` decodes it with no special case); every
    gathered value is empty, so the output is pure padding — a gather
    over ``uint8[0]`` would be out of range, so short-circuit it."""
    if dict_data.shape[0] == 0:
        return jnp.zeros((total_bytes,), dtype=dict_data.dtype)
    starts = out_offsets[:-1]
    shift = dict_offsets[indices] - starts
    step = jnp.diff(shift, prepend=jnp.zeros((1,), shift.dtype))
    marks = jnp.zeros((total_bytes,), shift.dtype).at[starts].add(
        step, mode="drop")
    src = jnp.arange(total_bytes, dtype=shift.dtype) + _running_count(marks)
    return dict_data[jnp.clip(src, 0, dict_data.shape[0] - 1)]


# ----------------------------------------------------------------------
# DELTA_BINARY_PACKED (int32) — host plan + device expand
# ----------------------------------------------------------------------

class DeltaPlan:
    __slots__ = (
        # list of 7-tuples (width, words, starts, takes,
        # n_vals, start, n_take); starts/takes are None for a
        # contiguous group, whose deltas land in the destination slice
        # [start, start + n_take) (the common single-width stream) —
        # otherwise per-MINIBLOCK scatter starts/take counts that the
        # device expands into the per-value grid (_scatter_grid)
        "groups",
        # per-BLOCK min_delta as u32 (lo, hi) lanes — the device repeats
        # them by block_size; shipping the per-delta expansion would be
        # 8 wire bytes per value (more than the raw column)
        "md_lo", "md_hi",
        "block_size", "first", "total",
    )

    def __init__(self, groups, md_lo, md_hi, block_size, first, total):
        self.groups = groups
        self.md_lo = md_lo
        self.md_hi = md_hi
        self.block_size = block_size
        self.first = first
        self.total = total


def _plan_delta(data, pos: int, max_width: int) -> DeltaPlan:
    """Parse DELTA_BINARY_PACKED headers; group miniblock payloads by bit
    width so the device unpacks each width class in one static-shape
    call.  Shared by the 32- and 64-bit planners (``max_width`` is the
    column's physical width — a wider miniblock is malformed).

    The structure pass (validation + per-miniblock bookkeeping) is the
    CPU oracle's own ``scan_delta_structure`` — one implementation of
    the parsing rules for both paths."""
    from ..cpu.delta import scan_delta_structure

    st = scan_delta_structure(data, pos, max_width=max_width)
    mb_size = st.mb_size
    buf = (data if isinstance(data, np.ndarray)
           else np.frombuffer(data, dtype=np.uint8))
    md_u = np.asarray(st.md_blocks, dtype=np.int64).view(np.uint64)
    md_lo = (md_u & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    md_hi = (md_u >> np.uint64(32)).astype(np.uint32)
    groups = []
    for w, src_contig, p_w, s_w, t_w, dst_contig in st.grouped():
        nbytes = mb_size * w // 8
        k = len(p_w)
        if src_contig:
            packed = buf[p_w[0] : p_w[0] + nbytes * k]
        else:
            from ..native import delta_native

            nat = delta_native()
            packed = (nat.gather_segments(buf, p_w, nbytes)
                      if nat is not None else None)
            if packed is None:  # one Python slice per miniblock
                packed = np.concatenate(
                    [buf[p : p + nbytes] for p in p_w])
        n_vals = mb_size * k
        # flat: a 2-D (n_blocks, w) device buffer tiles to 128 lanes
        words = pad_to_words(packed, w, n_vals).reshape(-1)
        if dst_contig:
            # contiguous destination slice: only the globally-last
            # miniblock can be partial.  positions/keep stay None and
            # the expanders use a cheap dynamic-slice update.
            groups.append((w, words, None, None, n_vals,
                           int(s_w[0]), int(t_w.sum())))
        else:
            # scattered destinations ship per-MINIBLOCK starts/takes
            # (8 bytes each); the device rebuilds the per-value scatter
            # grid — per-value position arrays would cost more wire
            # than the packed deltas themselves
            groups.append((w, words, s_w.astype(np.int32),
                           t_w.astype(np.int32), n_vals, 0, 0))
    return DeltaPlan(groups, md_lo, md_hi, st.block_size, st.first,
                     st.total)


def plan_delta_i32(data, pos: int = 0) -> DeltaPlan:
    return _plan_delta(data, pos, 32)


def _scatter_grid(starts, takes, n_vals: int, out_len: int) -> jax.Array:
    """Per-value scatter targets for a width class with non-contiguous
    miniblock destinations, built ON DEVICE from per-miniblock starts
    and take counts (the wire carries 8 bytes per miniblock, not per
    value).  Positions past a miniblock's take count map out of bounds,
    which ``.at[].set(mode="drop")`` discards."""
    starts = jnp.asarray(starts)
    takes = jnp.asarray(takes)
    k = starts.shape[0]
    mb = n_vals // max(k, 1)
    lane = jnp.arange(mb, dtype=jnp.int32)[None, :]
    pos = starts[:, None] + lane
    pos = jnp.where(lane < takes[:, None], pos, out_len)
    return pos.reshape(-1)


def _repeat_md(md_blocks, block_size: int, n_deltas: int) -> jax.Array:
    """Per-delta min_delta lane from the per-BLOCK table (device-side
    repeat — a (n_blocks, 1) broadcast, so only 4 bytes per 128-value
    block ever cross the wire)."""
    mdb = jnp.asarray(md_blocks)
    n_blocks = mdb.shape[0]
    # a broadcast, not jnp.repeat: same values, and the TPU compiler
    # takes ~20 s on the repeat at 1M deltas (AOT compile, PR 21)
    return jnp.broadcast_to(mdb[:, None], (n_blocks, block_size)
                            ).reshape(-1)[:n_deltas]


def expand_delta_i32(plan: DeltaPlan) -> jax.Array:
    """Device: unpack each width class, scatter into the delta stream, add
    min_delta, prefix-sum (int32 two's-complement wrap)."""
    n_deltas = max(plan.total - 1, 0)
    deltas = jnp.zeros((max(n_deltas, 1),), dtype=jnp.uint32)
    for w, words, starts, takes, n_vals, start, n_take in plan.groups:
        vals = unpack_u32(jnp.asarray(words), w, n_vals)
        if starts is None:  # contiguous destination slice
            deltas = jax.lax.dynamic_update_slice(
                deltas, vals[:n_take], (start,))
        else:
            pos = _scatter_grid(starts, takes, n_vals, deltas.shape[0])
            deltas = deltas.at[pos].set(vals[:n_vals], mode="drop")
    if plan.total == 0:
        return jnp.zeros((0,), dtype=jnp.uint32)
    first = jnp.asarray(np.uint32(plan.first & 0xFFFFFFFF))
    if n_deltas == 0:
        return first[None]
    md = _repeat_md(plan.md_lo, plan.block_size, n_deltas)
    full = deltas[:n_deltas] + md  # u32 wraparound == two's complement
    return jnp.concatenate([first[None], first + jnp.cumsum(full)])


# ----------------------------------------------------------------------
# DELTA_BINARY_PACKED (int64) — the 64-bit twin, with every 64-bit
# quantity carried as (lo, hi) u32 lanes (TPUs have no native int64;
# the reference instead duplicates its whole decoder per width,
# deltabp_decoder.go:10-12).
# ----------------------------------------------------------------------


def plan_delta_i64(data, pos: int = 0) -> DeltaPlan:
    """Parse a 64-bit DELTA_BINARY_PACKED stream (widths 0..64); same
    width-grouped miniblock layout as :func:`plan_delta_i32`."""
    return _plan_delta(data, pos, 64)


def _add64(a, b):
    """(lo, hi) u32-lane 64-bit add — associative, carried via the
    unsigned-wraparound compare."""
    lo = a[0] + b[0]
    carry = (lo < b[0]).astype(jnp.uint32)
    return lo, a[1] + b[1] + carry


@jax.jit
def _scan64_interleaved(slo, shi):
    """Inclusive 64-bit prefix sum -> flat interleaved (lo, hi) u32.
    One jit so the (n, 2) stack fuses away instead of materializing
    with a 64x-padded TPU tile layout."""
    lo, hi = jax.lax.associative_scan(_add64, (slo, shi))
    return jnp.stack([lo, hi], axis=1).reshape(-1)


def expand_delta_i64(plan: DeltaPlan) -> jax.Array:
    """Device: unpack each width class to (lo, hi) lanes, scatter into
    the delta stream, add min_delta (64-bit lane add), then an inclusive
    64-bit prefix sum via ``lax.associative_scan``.  Returns flat
    (total*2,) u32 — the interleaved (lo, hi) little-endian lane layout
    of DeviceColumn INT64."""
    from .bitunpack import unpack_u64

    if plan.total == 0:
        return jnp.zeros((0,), dtype=jnp.uint32)
    n_deltas = plan.total - 1
    first_u = plan.first & 0xFFFFFFFFFFFFFFFF
    first = jnp.asarray(
        [[np.uint32(first_u & 0xFFFFFFFF), np.uint32(first_u >> 32)]],
        dtype=jnp.uint32,
    )
    if n_deltas == 0:
        return first.reshape(-1)
    dlo = jnp.zeros((n_deltas,), dtype=jnp.uint32)
    dhi = jnp.zeros((n_deltas,), dtype=jnp.uint32)
    for w, words, starts, takes, n_vals, start, n_take in plan.groups:
        lo, hi = unpack_u64(jnp.asarray(words), w, n_vals)
        if starts is None:  # contiguous destination slice
            dlo = jax.lax.dynamic_update_slice(dlo, lo[:n_take], (start,))
            dhi = jax.lax.dynamic_update_slice(dhi, hi[:n_take], (start,))
        else:
            pos = _scatter_grid(starts, takes, n_vals, n_deltas)
            dlo = dlo.at[pos].set(lo[:n_vals], mode="drop")
            dhi = dhi.at[pos].set(hi[:n_vals], mode="drop")
    md_lo = _repeat_md(plan.md_lo, plan.block_size, n_deltas)
    md_hi = _repeat_md(plan.md_hi, plan.block_size, n_deltas)
    flo, fhi = _add64((dlo, dhi), (md_lo, md_hi))
    slo = jnp.concatenate([first[:, 0], flo])
    shi = jnp.concatenate([first[:, 1], fhi])
    return _scan64_interleaved(slo, shi)
