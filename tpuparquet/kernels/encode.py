"""Device encode kernels: the write-side twins of the decode set.

SURVEY.md §7 stage 7 ("writer TPU path — encode kernels mirror
decode").  The use case is columns that already live in HBM after TPU
compute: encoding on device ships *encoded* bytes over the narrow
host link instead of raw values (a sorted int64 timestamp column
delta-packs to ~1/3 of its PLAIN bytes; dict indices to width/64).

Same shape discipline as decode (``kernels/decode.py``): static
widths, flat 1-D u32 buffers at every jit boundary, all dynamic
decisions (per-miniblock widths) made on host between two device
phases.  Every kernel is byte-exact with its NumPy twin in
``cpu/bitpack.py`` / ``cpu/delta.py`` — the tests assert identical
wire bytes, not just round-trip equality.

Reference analogues (CPU-only, value-at-a-time there): the generated
pack tables ``bitbacking32.go``/``bitpacking64.go`` (one vectorized
formulation replaces ~4.6k generated LoC, as on the decode side), the
delta encoder ``deltabp_encoder.go`` (block 128 / 4 miniblocks per its
call sites, ``type_bytearray.go:176-180``), and the writer encode
dispatch ``chunk_writer.go:99-159``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "pack_u32_device",
    "pack_u64_device",
    "bss_encode_device",
    "delta_encode_device",
    "DeviceValues",
]


@functools.lru_cache(maxsize=None)
def _pack_tables(width: int):
    """Static per-word contribution tables for one width.

    A 32-value block occupies exactly ``width`` u32 words; word j's 32
    bits [32j, 32j+32) overlap value i's bits [i*w, i*w+w).  Each entry
    is (value_lane, p) where p = 32j - i*w is the bit offset into the
    value whose 32-bit window lands in this word (p < 0: the value
    starts -p bits into the word)."""
    out = []
    for j in range(width):
        lo_bit, hi_bit = 32 * j, 32 * j + 32
        contribs = []
        for i in range(32):
            b = i * width
            if b < hi_bit and b + width > lo_bit:
                contribs.append((i, lo_bit - b))
        out.append(tuple(contribs))
    return tuple(out)


def _pack_block_math(vlo, vhi, width: int):
    """(n_blocks, 32) u32 lane pair -> (n_blocks, width) u32 words.

    ``vhi`` is None for the 32-bit case.  Values MUST already fit in
    ``width`` bits (the delta planner guarantees it; raw callers mask).
    Static shifts only; the straddle uses the same multiply-instead-of-
    shift trick as the decode side (Mosaic miscompiles the shift form
    for sh >= 16 — see bitunpack._unpack_block_unrolled)."""
    words = []
    for contribs in _pack_tables(width):
        acc = None
        for i, p in contribs:
            if p < 0:
                # value starts -p bits into this word: low bits shift up
                c = vlo[:, i] * np.uint32((1 << (-p)) & 0xFFFFFFFF)
            elif p == 0:
                c = vlo[:, i]
            elif p < 32:
                c = vlo[:, i] >> np.uint32(p)
                if vhi is not None:
                    c = c | (vhi[:, i]
                             * np.uint32((1 << (32 - p)) & 0xFFFFFFFF))
            else:
                if vhi is None:
                    continue
                c = vhi[:, i] >> np.uint32(p - 32)
            acc = c if acc is None else (acc | c)
        words.append(acc if acc is not None
                     else jnp.zeros_like(vlo[:, 0]))
    return jnp.stack(words, axis=1)


@functools.partial(jax.jit, static_argnames=("width", "count"))
def pack_u32_device(values: jax.Array, width: int, count: int) -> jax.Array:
    """LSB-first bit-pack of ``count`` u32 values (< 2^width) into flat
    u32 words — the inverse of :func:`bitunpack.unpack_u32`; byte-exact
    with ``cpu.bitpack.pack``.  Input may be longer (padded); the tail
    past ``count`` is zeroed so padding never leaks into the stream."""
    n_blocks = (count + 31) // 32
    if width == 0 or n_blocks == 0:
        return jnp.zeros((0,), dtype=jnp.uint32)
    v = values[: n_blocks * 32]
    if v.shape[0] < n_blocks * 32:
        v = jnp.pad(v, (0, n_blocks * 32 - v.shape[0]))
    idx = jnp.arange(n_blocks * 32, dtype=jnp.int32)
    v = jnp.where(idx < count, v, 0).reshape(n_blocks, 32)
    mask = jnp.uint32(((1 << width) - 1) & 0xFFFFFFFF)
    return _pack_block_math(v & mask, None, width).reshape(-1)


@functools.partial(jax.jit, static_argnames=("width", "count"))
def pack_u64_device(lo: jax.Array, hi: jax.Array, width: int,
                    count: int) -> jax.Array:
    """64-bit twin of :func:`pack_u32_device` for widths 33..64: values
    arrive as (lo, hi) u32 lanes, already < 2^width."""
    n_blocks = (count + 31) // 32
    if width == 0 or n_blocks == 0:
        return jnp.zeros((0,), dtype=jnp.uint32)

    def prep(x):
        x = x[: n_blocks * 32]
        if x.shape[0] < n_blocks * 32:
            x = jnp.pad(x, (0, n_blocks * 32 - x.shape[0]))
        idx = jnp.arange(n_blocks * 32, dtype=jnp.int32)
        return jnp.where(idx < count, x, 0).reshape(n_blocks, 32)

    vlo, vhi = prep(lo), prep(hi)
    if width <= 32:
        mask = jnp.uint32(((1 << width) - 1) & 0xFFFFFFFF)
        return _pack_block_math(vlo & mask, None, width).reshape(-1)
    himask = jnp.uint32(((1 << (width - 32)) - 1) & 0xFFFFFFFF)
    return _pack_block_math(vlo, vhi & himask, width).reshape(-1)


@functools.partial(jax.jit, static_argnames=("count", "k", "lanes"))
def bss_encode_device(flat: jax.Array, count: int, k: int,
                      lanes: int) -> jax.Array:
    """BYTE_STREAM_SPLIT encode: flat (count*lanes,) u32 lane words ->
    (k*count,) u8 stream bytes.  Inverse of ``decode.bss_to_lanes``;
    byte-exact with ``cpu.bss.encode_byte_stream_split``."""
    w = flat[: count * lanes].reshape(count, lanes)
    b = jnp.stack([(w >> (8 * s)) & 0xFF for s in range(4)], axis=2)
    rows = b.reshape(count, lanes * 4)[:, :k].astype(jnp.uint8)
    return rows.T.reshape(-1)


# ----------------------------------------------------------------------
# DELTA_BINARY_PACKED encode: two device phases around one host width
# decision, mirroring the decode planner's width-grouped miniblocks.
# ----------------------------------------------------------------------

_BLOCK = 128
_MINIBLOCKS = 4
_MB = _BLOCK // _MINIBLOCKS


def _sub64(alo, ahi, blo, bhi):
    """(a - b) on u32 lanes with borrow."""
    lo = alo - blo
    borrow = (alo < blo).astype(jnp.uint32)
    return lo, ahi - bhi - borrow


def _bucket_blocks(count: int) -> int:
    """Power-of-two block count covering ``count`` values (min 32).

    Phase-1/phase-2 jits key on SHAPES with the true count traced, so
    arbitrary per-page value counts compile O(log) kernel variants, not
    one per count (a writer streaming variable pages would otherwise
    recompile per page)."""
    from .decode import bucket

    need = max((max(count - 1, 1) + _BLOCK - 1) // _BLOCK, 1)
    return bucket(need)


def _pad_flat(flat, lanes: int, nb: int):
    want = (nb * _BLOCK + 1) * lanes
    if flat.shape[0] < want:
        flat = jnp.pad(flat, (0, want - flat.shape[0]))
    return flat[:want]


@jax.jit
def _delta_phase1_i64(flat: jax.Array, valid):
    """Flat (2*(NB*128+1),) u32 interleaved i64 lanes (bucket-padded,
    true count ``valid`` traced) -> per-block min_delta lanes,
    per-miniblock adjusted maxima lanes, and the adjusted delta stream
    (device-resident for phase 2)."""
    c = flat.shape[0] // 2
    v = flat.reshape(c, 2)
    lo, hi = v[:, 0], v[:, 1]
    dlo, dhi = _sub64(lo[1:], hi[1:], lo[:-1], hi[:-1])
    nd = c - 1                      # == NB * _BLOCK
    nb = nd // _BLOCK
    idx = jnp.arange(nd, dtype=jnp.int32)
    live = idx < (valid - 1)
    # dead lanes become i64 max so they never win the min
    dlo = jnp.where(live, dlo, jnp.uint32(0xFFFFFFFF))
    dhi = jnp.where(live, dhi, jnp.uint32(0x7FFFFFFF))
    blo = dlo.reshape(nb, _BLOCK)
    bhi = dhi.reshape(nb, _BLOCK)
    # signed i64 min per block via lexicographic (hi signed, lo unsigned)
    shi = bhi.astype(jnp.int32)

    def min_pair(a, b):
        alo, ahi = a
        blo_, bhi_ = b
        a_less = (ahi < bhi_) | ((ahi == bhi_) & (alo < blo_))
        return (jnp.where(a_less, alo, blo_),
                jnp.where(a_less, ahi, bhi_))

    mlo, mhi = blo, shi
    k = _BLOCK
    while k > 1:
        k //= 2
        mlo, mhi = min_pair(
            (mlo[:, :k], mhi[:, :k]), (mlo[:, k:2 * k], mhi[:, k:2 * k]))
    min_lo, min_hi = mlo[:, 0], mhi[:, 0].astype(jnp.uint32)
    # adjusted = delta - min_delta (u64 lanes), dead lanes forced to 0
    alo, ahi = _sub64(blo.reshape(-1), bhi.reshape(-1),
                      jnp.repeat(min_lo, _BLOCK),
                      jnp.repeat(min_hi, _BLOCK))
    alo = jnp.where(live, alo, 0)
    ahi = jnp.where(live, ahi, 0)
    # per-miniblock max (u64): lexicographic on (hi unsigned, lo)
    xlo = alo.reshape(nb * _MINIBLOCKS, _MB)
    xhi = ahi.reshape(nb * _MINIBLOCKS, _MB)

    def max_pair(a, b):
        alo_, ahi_ = a
        blo_, bhi_ = b
        a_more = (ahi_ > bhi_) | ((ahi_ == bhi_) & (alo_ > blo_))
        return (jnp.where(a_more, alo_, blo_),
                jnp.where(a_more, ahi_, bhi_))

    qlo, qhi = xlo, xhi
    k = _MB
    while k > 1:
        k //= 2
        qlo, qhi = max_pair(
            (qlo[:, :k], qhi[:, :k]), (qlo[:, k:2 * k], qhi[:, k:2 * k]))
    return (min_lo, min_hi, qlo[:, 0], qhi[:, 0], alo, ahi)


@jax.jit
def _delta_phase1_i32(flat: jax.Array, valid):
    """32-bit twin of :func:`_delta_phase1_i64`: single-lane u32 math
    (the host is32 path wraps deltas at 32 bits, cpu/delta.py)."""
    c = flat.shape[0]
    v = flat
    d = v[1:] - v[:-1]  # u32 wraparound == two's-complement i32 delta
    nd = c - 1
    nb = nd // _BLOCK
    idx = jnp.arange(nd, dtype=jnp.int32)
    live = idx < (valid - 1)
    # dead lanes become i32 max so they never win the signed min
    d = jnp.where(live, d, jnp.uint32(0x7FFFFFFF))
    b = d.reshape(nb, _BLOCK)
    mins = jnp.min(b.astype(jnp.int32), axis=1)
    # adjusted = delta - min in [0, 2^32): u32 wrap equals the host's
    # 64-bit subtraction of values within the i32 range
    adj = b - mins.astype(jnp.uint32)[:, None]
    adj = jnp.where(live.reshape(nb, _BLOCK), adj, 0)
    mx = jnp.max(adj.reshape(nb * _MINIBLOCKS, _MB), axis=1)
    return mins, mx, adj.reshape(-1)


@functools.partial(jax.jit, static_argnames=("width",))
def _pack_masked32(values: jax.Array, valid, width: int) -> jax.Array:
    """Bucket-shaped pack: ``values`` length is a padded multiple of 32
    (jit keys on the bucket shape), the true count ``valid`` is traced,
    dead lanes zeroed before packing."""
    idx = jnp.arange(values.shape[0], dtype=jnp.int32)
    v = jnp.where(idx < valid, values, 0).reshape(-1, 32)
    mask = jnp.uint32(((1 << width) - 1) & 0xFFFFFFFF)
    return _pack_block_math(v & mask, None, width).reshape(-1)


@functools.partial(jax.jit, static_argnames=("width",))
def _pack_masked64(lo: jax.Array, hi: jax.Array, valid,
                   width: int) -> jax.Array:
    idx = jnp.arange(lo.shape[0], dtype=jnp.int32)
    vlo = jnp.where(idx < valid, lo, 0).reshape(-1, 32)
    vhi = jnp.where(idx < valid, hi, 0).reshape(-1, 32)
    himask = jnp.uint32(((1 << (width - 32)) - 1) & 0xFFFFFFFF)
    return _pack_block_math(vlo, vhi & himask, width).reshape(-1)


def delta_encode_device(flat, count: int, is32: bool = False) -> bytes:
    """DELTA_BINARY_PACKED encode with the deltas, minima, maxima and
    miniblock packing computed ON DEVICE; byte-identical to
    ``cpu.delta.encode_delta_binary_packed`` (block 128, 4 miniblocks).

    ``flat``: device (or host) flat u32 lanes — (count*2,) interleaved
    (lo, hi) for int64, (count,) for int32 (``is32=True``, which wraps
    deltas at 32 bits exactly like the host encoder).  Only the packed
    miniblock words, per-block minima and per-miniblock maxima cross
    back to the host; for a sorted timestamp column that is ~1/3 of the
    PLAIN bytes."""
    from ..varint import write_uvarint, write_zigzag

    from ..cpu.delta import widths_from_max
    from .decode import bucket

    flat2 = jnp.asarray(flat)
    lanes = 1 if is32 else 2
    out = bytearray()
    write_uvarint(out, _BLOCK)
    write_uvarint(out, _MINIBLOCKS)
    write_uvarint(out, count)
    if count == 0:
        write_zigzag(out, 0)
        return bytes(out)
    first_lanes = np.asarray(flat2[:lanes])  # one transfer
    if is32:
        v0 = int(first_lanes[0])
        first = v0 - (1 << 32) if v0 >= (1 << 31) else v0
    else:
        v0 = int(first_lanes[0]) | (int(first_lanes[1]) << 32)
        first = v0 - (1 << 64) if v0 >= (1 << 63) else v0
    write_zigzag(out, first)
    if count == 1:
        return bytes(out)

    nb_bucket = _bucket_blocks(count)
    padded = _pad_flat(flat2, lanes, nb_bucket)
    nb = (count - 1 + _BLOCK - 1) // _BLOCK  # true block count
    if is32:
        mins, mx, alo = _delta_phase1_i32(padded, count)
        minima = np.asarray(mins)[:nb].astype(np.int64)
        mb_max = np.asarray(mx)[: nb * _MINIBLOCKS].astype(np.uint64)
        ahi = None
    else:
        min_lo, min_hi, mx_lo, mx_hi, alo, ahi = _delta_phase1_i64(
            padded, count)
        minima = (np.asarray(min_lo)[:nb].astype(np.uint64)
                  | (np.asarray(min_hi)[:nb].astype(np.uint64)
                     << np.uint64(32))).view(np.int64)
        mb_max = (np.asarray(mx_lo)[: nb * _MINIBLOCKS].astype(np.uint64)
                  | (np.asarray(mx_hi)[: nb * _MINIBLOCKS].astype(np.uint64)
                     << np.uint64(32)))
    widths = widths_from_max(mb_max)

    # phase 2: pack all miniblocks of one width in one device call.
    # The gather/pack shapes bucket so the jit cache stays O(widths x
    # log(size)), not one entry per data-dependent miniblock count.
    payloads: list[bytes] = [b""] * len(widths)
    for w in np.unique(widths):
        w = int(w)
        if w == 0:
            continue
        idx = np.nonzero(widths == w)[0]
        cnt = len(idx) * _MB
        cap = bucket(cnt)
        sel = np.zeros(cap, dtype=np.int32)
        sel[:cnt] = (idx[:, None] * _MB
                     + np.arange(_MB)[None, :]).reshape(-1)
        sel_dev = jnp.asarray(sel)
        if w <= 32:
            words = _pack_masked32(alo[sel_dev], cnt, w)
        else:
            words = _pack_masked64(alo[sel_dev], ahi[sel_dev], cnt, w)
        raw = np.asarray(words).tobytes()
        step = _MB * w // 8
        for j, i in enumerate(idx):
            payloads[i] = raw[j * step : (j + 1) * step]

    widths_b = widths.astype(np.uint8).tobytes()
    for b in range(nb):
        write_zigzag(out, int(minima[b]))
        out.extend(widths_b[b * _MINIBLOCKS : (b + 1) * _MINIBLOCKS])
        for p in payloads[b * _MINIBLOCKS : (b + 1) * _MINIBLOCKS]:
            out.extend(p)
    return bytes(out)


class DeviceValues:
    """Device-resident fixed-width column values for the columnar write
    path (``FileWriter.write_columns``): the values stay in HBM through
    validation and statistics, and DELTA_BINARY_PACKED (int64),
    BYTE_STREAM_SPLIT and PLAIN pages encode on device — only encoded
    bytes and two stat scalars cross the host link.  Small-range
    integer columns dictionary-encode via a DEVICE-side intern
    (:func:`device_dict_build`): the index stream crosses at 4 bytes
    per value instead of the unpacked column, and the file matches the
    host path byte for byte.

    ``flat``: flat u32 lane words (the DeviceColumn layout: lanes
    interleaved little-endian, ``itemsize//4`` words per value);
    ``dtype``: the logical dtype — int32/int64/float32/float64.
    Combine with ``column_encodings`` to force DELTA or BSS.
    """

    __slots__ = ("flat", "dtype")

    def __init__(self, flat, dtype):
        self.dtype = np.dtype(dtype)
        if self.dtype not in (np.dtype(np.int32), np.dtype(np.int64),
                              np.dtype(np.float32), np.dtype(np.float64)):
            raise TypeError(
                f"DeviceValues supports int32/int64/float32/float64, "
                f"got {self.dtype}")
        self.flat = jnp.asarray(flat)
        if self.flat.dtype != jnp.uint32 or self.flat.ndim != 1:
            raise TypeError("flat must be a 1-D uint32 lane array")
        if self.flat.shape[0] % self.lanes:
            raise ValueError(
                f"lane array length {self.flat.shape[0]} not a multiple "
                f"of {self.lanes}")

    @property
    def lanes(self) -> int:
        return self.dtype.itemsize // 4

    @property
    def count(self) -> int:
        """Derived from the lane buffer (never stored), so tree
        transforms that reshape the leaf can't desync it."""
        return self.flat.shape[0] // self.lanes

    def __len__(self) -> int:
        return self.count

    def to_numpy(self) -> np.ndarray:
        return np.asarray(self.flat).view(self.dtype)

    def min_max(self, unsigned: bool = False):
        """(min, max) as numpy scalars of the storage dtype — computed on
        device, only two scalars cross to host.  Mirrors
        ``io.values.Handler.min_max``: NaNs excluded, (None, None) when
        empty or all-NaN; ``unsigned`` orders integers as u32/u64 but
        returns signed-storage values."""
        if self.count == 0:
            return None, None
        with jax.enable_x64(True):
            v = self.flat
            if self.lanes == 2:
                v = jax.lax.bitcast_convert_type(
                    v.reshape(-1, 2),
                    jnp.uint64 if unsigned else
                    (jnp.float64 if self.dtype.kind == "f" else jnp.int64))
            elif self.dtype.kind == "f":
                v = jax.lax.bitcast_convert_type(v, jnp.float32)
            elif unsigned:
                pass  # u32 order is the lane dtype's own
            else:
                v = jax.lax.bitcast_convert_type(v, jnp.int32)
            if self.dtype.kind == "f":
                mn, mx = jnp.nanmin(v), jnp.nanmax(v)
            else:
                mn, mx = jnp.min(v), jnp.max(v)
            mn, mx = np.asarray(mn)[()], np.asarray(mx)[()]
        if self.dtype.kind == "f":
            if np.isnan(mn):
                return None, None
            return self.dtype.type(mn), self.dtype.type(mx)
        if unsigned:
            store = np.int32 if self.dtype.itemsize == 4 else np.int64
            return (np.asarray(mn).view(store)[()],
                    np.asarray(mx).view(store)[()])
        return self.dtype.type(mn), self.dtype.type(mx)

    def encode(self, ptype, encoding) -> bytes:
        """Encode one page's values on device; returns the wire bytes."""
        from ..format.metadata import Encoding, Type
        from ..stats import current_stats

        st = current_stats()
        if st is not None:
            st.pages_device_encoded += 1
        if encoding == Encoding.PLAIN:
            # PLAIN little-endian value bytes == the LE lane words' bytes
            return np.asarray(self.flat).tobytes()
        if encoding == Encoding.DELTA_BINARY_PACKED:
            return delta_encode_device(self.flat, self.count,
                                       is32=(ptype == Type.INT32))
        if encoding == Encoding.BYTE_STREAM_SPLIT:
            out = bss_encode_device(self.flat, self.count,
                                    self.dtype.itemsize, self.lanes)
            return np.asarray(out).tobytes()
        raise ValueError(
            f"DeviceValues cannot encode {encoding!r}; supported: PLAIN, "
            "DELTA_BINARY_PACKED, BYTE_STREAM_SPLIT")


def device_dict_build(dv: "DeviceValues"):
    """Device-side dictionary interning for small-range integer
    ``DeviceValues`` columns: the range table, first-occurrence order
    and per-value indices all compute in HBM, and only the int32 index
    stream plus the tiny order table cross to the host (4 wire bytes
    per value instead of the unpacked column).

    Returns ``(dictionary ndarray, pull)`` where ``pull()`` fetches
    the int32 index stream — deferred so the caller's dictionary-size
    gates run BEFORE the only per-value transfer.  The order is
    EXACTLY the host interner's first-occurrence order
    (``cpu/dictionary._build_int_dictionary_smallrange``), so for
    small-RANGE columns the written file is byte-identical to encoding
    the same values from a numpy array.  None when the range gate
    rejects; a KNOWN divergence from the host path: wide-range but
    few-distinct columns (host np.unique still dict-encodes them)
    stay on the non-dict device encodes — interning them would need a
    device sort over 64-bit lanes."""
    if dv.dtype.kind != "i":
        return None
    n = dv.count
    if n == 0:
        return None
    lo, hi = dv.min_max()
    rng = int(hi) - int(lo) + 1  # Python ints: no wraparound
    if rng > 4 * n or rng > 1 << 24:
        return None  # same gate as the host interner
    # (value - lo) < 2**24 fits the LOW lane's u32 wraparound exactly,
    # so the high lane of int64 columns never participates
    lo_lane = np.uint32(int(lo) & 0xFFFFFFFF)
    vals_lo = dv.flat[:: dv.lanes] if dv.lanes > 1 else dv.flat
    off = (vals_lo - lo_lane).astype(jnp.int32)
    first = jnp.full(rng, n, dtype=jnp.int32).at[off].min(
        jnp.arange(n, dtype=jnp.int32))
    # present entries (first < n) sort before absent ones, in
    # first-occurrence order; ties are impossible
    order_full = jnp.argsort(first)
    dsize = int(jnp.sum(first < n))
    order = order_full[:dsize]
    rank = jnp.zeros(rng, dtype=jnp.int32).at[order].set(
        jnp.arange(dsize, dtype=jnp.int32))
    indices = rank[off]
    dict_np = (np.asarray(order).astype(np.int64) + int(lo)).astype(
        dv.dtype)
    return dict_np, lambda: np.asarray(indices)


def _devicevalues_unflatten(aux, leaves):
    # bypass __init__: pytree unflattening may pass dummy leaves while
    # manipulating tree structure, which must not be validated
    obj = DeviceValues.__new__(DeviceValues)
    (obj.dtype,) = aux
    obj.flat = leaves[0]
    return obj


# DeviceValues is a JAX pytree (lane buffer is the leaf; dtype static
# aux — count derives from the leaf, so leaf-reshaping transforms stay
# consistent): jitted producers can return one directly, and it feeds
# write_columns without leaving the device.
jax.tree_util.register_pytree_node(
    DeviceValues,
    lambda v: ((v.flat,), (v.dtype,)),
    _devicevalues_unflatten,
)
