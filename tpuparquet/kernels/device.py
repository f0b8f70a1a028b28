"""Device decode orchestration: column chunks -> device-resident columns.

The cuDF-style batch-decode backend of BASELINE.json: raw page bytes are
staged to device memory and decoded by vectorized kernels; the host only
parses headers and builds plan tables.  Output is Arrow-layout
:class:`DeviceColumn` objects (packed values + validity + levels), which
``to_numpy()`` materializes in exactly the CPU oracle's representation for
bit-exact parity checks.

Device coverage — every value encoding the format defines:

* PLAIN int32/int64/float/double/int96/FLBA (reinterpret staging)
* PLAIN boolean (width-1 unpack) and RLE boolean (run-table expand)
* RLE_DICTIONARY indices (run-table expand) + dictionary gather,
  fixed-width and variable-width (byte-level gather)
* definition/repetition levels (run-table expand) + validity fusion
* DELTA_BINARY_PACKED int32 and int64 (two-u32-lane arithmetic)
* BYTE_STREAM_SPLIT int32/int64/float/double/FLBA (device transpose)
* DELTA_LENGTH_BYTE_ARRAY (host length scan, zero-copy payload staging)
* DELTA_BYTE_ARRAY (front coding = the snappy kernel's copy graph;
  non-expanding pages assemble on host, chosen per page because it
  ships STRICTLY fewer bytes, not for lack of a kernel — wire-neutral
  pages take the device kernel.  The golden exception list
  ``HOST_ASSEMBLY_EXCEPTIONS`` in ``tests/test_fallback_matrix.py``
  pins exactly which (type, encoding) combinations may do this, and
  its wire-number pin asserts every host-assembled page really
  shipped fewer bytes than the compact wire form)
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from ..compress import decompress_block, decompress_block_into
from ..cpu import decode_plain
from ..errors import CorruptChunkError, CorruptPageError, \
    DeviceDispatchError, ScanError
from ..faults import backoff_delays, fault_point, filter_bytes
from ..native import plane_native
from ..obs import recorder as _flightrec
from ..obs import trace as _trace
from ..obs.recorder import flight
from ..obs.trace import emit_span
from .arena import HostArena, discard_thread_arena, lease_arena, \
    return_arena, thread_arena, trim_arena_pool
from ..cpu.plain import ByteArrayColumn
from ..format.compact import CompactReader
from ..format.metadata import (
    ColumnMetaData,
    CompressionCodec,
    Encoding,
    PageHeader,
    PageType,
    Type,
    decode_struct,
)
from ..format.schema import SchemaNode
from .bitunpack import pad_to_words, unpack_u32
from .decode import (
    bucket,
    dict_gather_bytes,
    dict_gather_fixed,
    expand_delta_i32,
    expand_delta_i64,
    levels_to_validity,
    plain_fixed_to_lanes,
    plan_delta_i32,
    plan_delta_i64,
    stage_u32,
    u8_to_u32_words,
)

__all__ = ["DeviceColumn", "decode_chunk_device", "read_row_group_device",
           "read_row_groups_device", "read_row_group_device_resilient",
           "cpu_fallback_values"]


# ----------------------------------------------------------------------
# Graceful degradation: forced-host value decode.
#
# When device dispatch fails (simulated via the fault harness, or a
# real accelerator error surfacing as DeviceDispatchError /
# RuntimeError), the resilient read path re-plans the unit under this
# thread-local flag: every page's VALUES decode on the bit-exact CPU
# oracle and only finished buffers cross to the device — no device
# decode kernels, no wire transports.  Pages planned this way report
# transport "host-degraded" and count DecodeStats.pages_degraded.
# ----------------------------------------------------------------------

_degrade_tls = threading.local()


def _host_values_only() -> bool:
    return getattr(_degrade_tls, "host_only", False)


@contextlib.contextmanager
def cpu_fallback_values():
    """Scope (this thread) forcing every page's values onto the CPU
    oracle decode — the device→host graceful-degradation mode."""
    prev = getattr(_degrade_tls, "host_only", False)
    _degrade_tls.host_only = True
    try:
        yield
    finally:
        _degrade_tls.host_only = prev

_LANES = {
    Type.INT32: 1, Type.FLOAT: 1, Type.INT64: 2, Type.DOUBLE: 2,
    Type.INT96: 3,
}


def _lanes_for(ptype: Type, type_length) -> int:
    """u32 words per value in the flat device layout.

    Value buffers are FLAT 1-D u32 at every jit boundary: a 2-D
    ``u32[n, lanes]`` TPU output is tiled T(8,128) with the minor dim
    padded to 128 — 64x HBM waste for int64 (measured: a 50M-value
    int64 chunk would allocate 25.6 GB and OOM)."""
    if ptype == Type.BOOLEAN:
        return 1
    if ptype == Type.FIXED_LEN_BYTE_ARRAY:
        return _flba_lanes(type_length)
    return _LANES[ptype]

_DICT_ENCODINGS = (Encoding.PLAIN_DICTIONARY, Encoding.RLE_DICTIONARY)

# competition winner -> event-log transport label (obs.TRANSPORT_COUNTER
# maps these back to the DecodeStats counter each increments)
_CHOSEN_TRANSPORT = {"planes": "planes", "delta": "delta-lanes",
                     "snappy": "snappy-tokens"}

# Device-side snappy decompression of PLAIN fixed-width value segments
# (tokens + literals ship instead of the decompressed bytes).  Engages
# only for genuinely-compressed blocks — single-literal blocks keep the
# zero-copy host view, which is strictly cheaper.
def _DEVICE_SNAPPY() -> bool:
    """Read per plan (not import) so same-process A/B runs can flip it."""
    if _host_values_only():
        return False
    return os.environ.get("TPQ_DEVICE_SNAPPY", "1") != "0"

# Byte-plane RLE wire transport for PLAIN fixed-width segments (any
# codec, including UNCOMPRESSED): upper byte planes of numeric data are
# nearly constant and ship as runs.  Gated per page by measured wire
# size — pages whose planes are all random ship raw as before.
def _DEVICE_DELTA_LANES() -> bool:
    if _host_values_only():
        return False
    return os.environ.get("TPQ_DEVICE_DELTA", "1") != "0"


def _padded_u32_bytes(n_words: int) -> int:
    """POST-split staged bytes of an (n_words,) u32 array, so wire
    estimates don't materialize throwaway arrays."""
    return sum(_piece_rows(n_words, 4)) * 4


def _plan_delta_lane_words(seg, count: int, ptype: Type, params=None):
    """Plan the delta-lane transport for one PLAIN int32/int64 values
    segment: re-encode values as (first, per-page min_delta, packed
    delta offsets) on the host and rebuild them with the EXISTING
    delta expand kernels on device.

    ``params`` is the plan cache's remembered ``(min_delta, width)``
    for this page: the O(window) entropy rejection and the full
    min/max pass are skipped, and a single max-reduce re-validates the
    cached width against the actual deltas (a stale hint falls back to
    the full computation rather than corrupting — hints stay
    performance-only).

    Sorted/clustered columns (timestamps, counters, row ids) pack their
    deltas into a few bits per value where even the byte planes ship
    half the raw words — the round-4 notes measured lanes at 0.505x of
    raw vs 0.35x for deltas on a pyarrow timestamp file, but rejected
    the transport because numpy pack cost 680 ms per 10M values.  The
    C word-writer pack (native/pack.c, 54 ms) changes that math; this
    planner only engages when the native is present.

    All arithmetic is modular (uint64/uint32 wrap), matching the
    expand kernels' lane adds and prefix scan — random pages reject on
    width, never corrupt.  Returns (exact_wire_bytes, commit) or None;
    ``commit(stager)`` stages the plan and returns ``get_words(staged)``
    producing the flat u32 lane layout PLAIN consumers slice, and the
    page's ``(kind, handles, statics, aux)`` for a :class:`_PageOp`."""
    from ..native import pack_native

    if count < 1024 or pack_native() is None:
        return None
    lanes = _LANES[ptype]
    nbytes = count * lanes * 4
    buf = (seg.reshape(-1) if isinstance(seg, np.ndarray)
           else np.frombuffer(seg, dtype=np.uint8))
    if buf.size < nbytes:
        raise ValueError("PLAIN: input too short")
    if lanes == 2:
        v = np.ascontiguousarray(buf[:nbytes]).view("<u8")
    else:
        v = np.ascontiguousarray(buf[:nbytes]).view("<u4")
    n_deltas = count - 1

    def _width(dd):
        lo = int(dd.min())
        hi = int(dd.max())
        span = int(np.uint64(hi - lo)) if lanes == 2 \
            else int(np.uint32(hi - lo))
        return lo, span.bit_length()

    if params is None:
        # O(window) entropy rejection before any full pass (the adjacent
        # plane planner samples for the same reason): the sample's delta
        # span lower-bounds the full span, so a window that already needs
        # full width proves the page rejects
        win = 16384
        if count > win:
            _, w_s = _width((v[1 : win + 1] - v[:win]).view(
                np.int64 if lanes == 2 else np.int32))
            if w_s >= 32 * lanes:
                return None
    # wrap-consistent deltas: the device rebuild adds mod 2^(32*lanes)
    d = (v[1:] - v[:-1]).view(np.int64 if lanes == 2 else np.int32)
    if params is not None:
        # cached (min_delta, width): re-validate with ONE reduce over
        # the offsets instead of the two-pass min/max — a stale hint
        # (changed bytes under an unchanged footer) recomputes honestly
        md, w = params
        mask = (1 << (32 * lanes)) - 1
        off_c = ((d.astype(np.int64) - md).astype(np.uint64)
                 & np.uint64(mask)) if lanes == 1 \
            else (d - np.int64(md)).view(np.uint64)
        fits = (w < 32 * lanes
                and (off_c.size == 0
                     or int(off_c.max()).bit_length() <= w))
        if not fits:
            md, w = _width(d)
            off_c = None
    else:
        md, w = _width(d)
        off_c = None
    if w >= 32 * lanes:
        return None
    # Advertise the POST-SPLIT staged cost, not the packed byte count:
    # the stager pads the words array's tail piece to a power-of-two
    # (_split_rows), and a first cut of this planner that compared
    # pre-pad wire flipped pages to delta that staged MORE after
    # padding than the planes they displaced.  (Competitors advertise
    # pre-pad wire, so this pessimizes delta — it engages only when
    # clearly better.)
    # Quantize the padded delta count to 32k multiples: the expand jit
    # compiles per (n_vals, w) shape, and exact per-page sizes would
    # recompile on every distinct page length for <3% wire savings.
    n_pad32 = (n_deltas + 32767) // 32768 * 32768
    n_words = n_pad32 // 32 * w
    wire = _padded_u32_bytes(n_words) + 32 if w else 32
    if wire + 4096 >= nbytes:
        return None  # must clear the same savings floor as the planes

    def commit(stager, _i64=(lanes == 2)):
        # pack deferred to here: the planner only charged the cheap
        # diff/min/max pass while the plane transport could still win
        from .bitunpack import pad_to_words
        from .decode import DeltaPlan

        mask = (1 << (32 * lanes)) - 1
        if off_c is not None:  # hint path already built the offsets
            off = off_c
        else:
            off = ((d.astype(np.int64) - md).astype(np.uint64)
                   & np.uint64(mask)) if lanes == 1 \
                else (d - md).view(np.uint64)
        n_pad = n_pad32
        if n_pad != n_deltas:
            off = np.concatenate(
                [off, np.zeros(n_pad - n_deltas, dtype=np.uint64)])
        packed = pack_native().pack(off, w) if w \
            else np.empty(0, np.uint8)
        words = pad_to_words(packed, w, n_pad).reshape(-1) if w else None
        md_u = np.uint64(md & mask)
        md_lo = np.asarray([md_u & np.uint64(0xFFFFFFFF)],
                           dtype=np.uint32)
        md_hi = np.asarray([md_u >> np.uint64(32)], dtype=np.uint32)
        groups = ([(w, words, None, None, n_pad, 0, n_deltas)]
                  if w else [])
        first = int(v[0])
        plan = DeltaPlan(groups, md_lo, md_hi, n_deltas, first, count)
        build, hs = _stage_delta_plan(plan, stager, need_hi=_i64)

        def get_words(s, _b=build):
            from .decode import expand_delta_i32, expand_delta_i64

            return (expand_delta_i64(_b(s)) if _i64
                    else expand_delta_i32(_b(s)))

        return get_words, ("delta", hs, (n_pad, w, _i64),
                           (first & 0xFFFFFFFF, first >> 32))

    return wire, commit, (md, w)


def _DEVICE_PLANES() -> bool:
    if _host_values_only():
        return False
    return os.environ.get("TPQ_DEVICE_PLANES", "1") != "0"


def _plan_token_expansion(payload, expected_size: int):
    """Shared prologue of the token-shipping planners: single-literal /
    no-native-scanner / int32-overflow checks, then the token plan.
    Returns ``(te, ts, lp, out_cap, steps, out_len, wire)`` or None;
    ``wire`` is what the token tables cost on the wire (padded sizes —
    the padding ships)."""
    from ..compress import snappy_single_literal_view

    if snappy_single_literal_view(payload) is not None:
        return None
    from ..native import snappy_native

    nat = snappy_native()
    if nat is None or getattr(nat, "_scan_tokens_fn", None) is None:
        return None
    from .snappy import plan_tokens

    plan = plan_tokens(payload, expected_size)
    if plan is None:
        return None  # int32 token table would wrap
    te, ts, lp = plan[:3]
    return (*plan, te.nbytes + ts.nbytes + lp.nbytes)


def _stage_token_expansion(plan, stager: "_Stager"):
    """Stage a token plan; returns ``blob(staged) -> u8[out_cap]``."""
    te, ts, lp, out_cap, steps = plan[:5]
    hs = stager.add_many([te, ts, lp], pad=False)

    def blob(staged, _hs=hs, _cap=out_cap, _steps=steps):
        from .snappy import expand_tokens

        return expand_tokens(staged[_hs[0]], staged[_hs[1]],
                             staged[_hs[2]], _cap, _steps)

    return blob


def _plan_device_snappy_blob(payload, expected_size: int,
                             wire_budget: float, stager: "_Stager"):
    """Like :func:`_plan_device_snappy_words` but returning
    ``(wire, blob)`` with the raw u8 page expansion (for byte-granular
    consumers), engaged only when the token tables fit ``wire_budget``
    bytes."""
    plan = _plan_token_expansion(payload, expected_size)
    if plan is None or plan[6] > wire_budget:
        return None
    return plan[6], _stage_token_expansion(plan, stager)


def _rle_table(plane: np.ndarray, count: int, val_dtype, bucket,
               max_runs: int | None = None):
    """(bucket-padded ends, vals, cap) run tables for one plane/lane, or
    None when the plane has more than ``max_runs`` runs (the table could
    not beat shipping the plane raw, so don't finish building it).

    ``plane`` may be a strided view — the native path scans it in one C
    pass with no bool temp or materialized copy."""
    nat = plane_native()
    if nat is not None and plane.ndim == 1:
        res = nat.run_scan(
            plane, count if max_runs is None else min(max_runs, count))
        if res is None:
            return None
        ends_r, vals_r = res
        cap = bucket(len(ends_r))
        ends = np.full(cap, count, dtype=np.int32)
        ends[: len(ends_r)] = ends_r
        vals = np.zeros(cap, dtype=val_dtype)
        vals[: len(vals_r)] = vals_r
        return ends, vals, cap
    change = np.flatnonzero(plane[1:] != plane[:-1]).astype(np.int32) + 1
    if max_runs is not None and len(change) + 1 > max_runs:
        return None
    cap = bucket(len(change) + 1)
    ends = np.full(cap, count, dtype=np.int32)
    ends[: len(change)] = change
    ends[len(change)] = count
    vals = np.zeros(cap, dtype=val_dtype)
    vals[: len(change) + 1] = plane[np.concatenate(
        ([0], change)).astype(np.int64)]
    return ends, vals, cap


def _lane_contig(plane: np.ndarray) -> np.ndarray:
    """Contiguous copy of a (possibly strided) lane/plane view."""
    nat = plane_native()
    if nat is not None and plane.ndim == 1 and not plane.flags.c_contiguous:
        return nat.gather(plane)
    return np.ascontiguousarray(plane)


def _plan_plane_words(seg, count: int, lanes: int, stager: "_Stager",
                      budget: int | None = None, lane_plans=None):
    """Plan the lane/byte-plane RLE transport for one PLAIN fixed-width
    values segment (``count`` values of ``lanes`` u32 words each).

    Decisions are made PER U32 LANE from a contiguous sample window, so
    a full-entropy page rejects in O(window) and an engaged page only
    ever touches the lanes/planes that pay:

    * ``rle32`` — the lane is runs as a whole (high words of
      timestamps/counters; zero high lanes of small-range values);
      one strided compare + flatnonzero, 8 wire bytes per run.
    * ``bytes`` — the lane is random as a word but has constant upper
      byte planes (e.g. int32s < 2^16); only the random byte planes
      ship raw.
    * ``raw32`` — genuinely random lane: one contiguous u32 slab.

    Host cost matters as much as wire here (the planner runs on the
    pipeline's plan thread): everything below is one strided-view pass
    per engaged lane, no full-page 2-D materialization.

    ``budget``, when given, is a competing transport's exact wire cost
    (snappy tokens): the planes engage only if they beat it.
    ``lane_plans`` is the plan cache's remembered per-lane verdict list
    for this page: the sample windows and the estimate pre-gate are
    skipped and the tables build directly — the actual-cost gate below
    still re-checks what the BUILT tables weigh, so a stale hint ships
    raw rather than a losing transport.

    Each raw slab is staged ``stride`` values long, the count rounded up
    to a multiple of 32, so pages whose counts differ by less than that
    share a chunk program's group.

    Returns ``(wire, words_closure, lane_plans, val)`` — the wire cost
    recomputed from the BUILT tables (what the gate actually accepted;
    the event log reports it), and ``val``, the page's ``(kind, handles,
    statics, aux)`` for a :class:`_PageOp` — or None when the page
    rejects."""
    from .decode import bucket

    if count < 1024:
        return None  # can't clear the 4 KiB savings gate
    nbytes = count * lanes * 4
    buf = (seg.reshape(-1) if isinstance(seg, np.ndarray)
           else np.frombuffer(seg, dtype=np.uint8, count=nbytes))
    if buf.size < nbytes:
        raise ValueError("PLAIN values segment shorter than value count")
    words_v = buf[:nbytes].view("<u4")  # value-interleaved lanes
    wire_cap = (0.75 * nbytes if budget is None
                else min(0.75 * nbytes, budget))
    if lane_plans is not None and len(lane_plans) == lanes:
        plans = lane_plans
    else:
        win_n = min(count, 1 << 14)
        mid = (count - win_n) // 2

        plans = []  # per lane: ("raw32",) | ("rle32", est) | ("bytes", keep)
        wire = 0
        for lane in range(lanes):
            lw = np.ascontiguousarray(
                words_v[mid * lanes + lane
                        : (mid + win_n) * lanes : lanes])
            r32 = float((lw[1:] != lw[:-1]).mean()) if win_n > 1 else 1.0
            est32 = 8 * bucket(int(r32 * count) + 1)
            if est32 < 4 * count:  # beats the 4-bytes-per-value raw lane
                plans.append(("rle32", est32))
                wire += est32
                continue
            wb = lw.view(np.uint8).reshape(win_n, 4)
            r8 = (wb[1:] != wb[:-1]).mean(axis=0)
            cost8 = np.minimum(5 * np.array(
                [bucket(int(r * count) + 1) for r in r8]), count)
            if cost8.sum() < 0.75 * 4 * count:
                plans.append(("bytes", cost8))
                wire += int(cost8.sum())
            else:
                plans.append(("raw32",))
                wire += 4 * count
        # engage only on a solid win: the plan thread pays real host
        # time per engaged lane, so marginal pages keep the raw path
        if wire > wire_cap or nbytes - wire < 4096:
            return None

    raw32_parts, raw8_parts = [], []
    e32_parts, v32_parts, e8_parts, v8_parts = [], [], [], []
    s32 = s8 = 0
    spec = []
    actual = 0  # wire recomputed from BUILT tables (samples can lie)
    # each raw slab stages a multiple of 32 values long
    stride = -(-count // 32) * 32

    def slab(col):
        if stride == count:
            return col
        out = np.zeros(stride, col.dtype)
        out[:count] = col
        return out

    def raw32(lane_v):
        nonlocal actual
        spec.append(("raw32", len(raw32_parts)))
        raw32_parts.append(slab(_lane_contig(lane_v)))
        actual += 4 * count

    def raw8(col):
        nonlocal actual
        raw8_parts.append(slab(col))
        actual += count
        return ("raw8", len(raw8_parts) - 1)

    for lane, plan in enumerate(plans):
        lane_v = words_v[lane::lanes]  # strided view, len == count
        if plan[0] == "rle32":
            # beyond count/2 runs the 8 B/run table cannot beat the raw
            # 4 B/value lane, so the scan aborts there (tab is None)
            tab = _rle_table(lane_v, count, np.uint32, bucket,
                             max_runs=count // 2 + 1)
            if tab is None or 8 * tab[2] >= 4 * count:
                # the sample under-estimated (heterogeneous page):
                # the built table would out-weigh the raw lane
                raw32(lane_v)
                continue
            ends, vals, cap = tab
            e32_parts.append(ends)
            v32_parts.append(vals)
            spec.append(("rle32", s32, cap))
            s32 += cap
            actual += 8 * cap
        elif plan[0] == "raw32":
            raw32(lane_v)
        else:
            cost8 = plan[1]
            subs = []
            for t in range(4):
                # strided view of byte plane t of this lane (LE words:
                # byte t of value i lives at i*4*lanes + 4*lane + t)
                col = buf[4 * lane + t : nbytes : 4 * lanes]
                if cost8[t] >= count:
                    subs.append(raw8(_lane_contig(col)))
                    continue
                tab = _rle_table(col, count, np.uint8, bucket,
                                 max_runs=count // 5 + 1)
                if tab is None or 5 * tab[2] >= count:
                    # sample under-estimated
                    subs.append(raw8(_lane_contig(col)))
                    continue
                ends, vals, cap = tab
                e8_parts.append(ends)
                v8_parts.append(vals)
                subs.append(("rle8", s8, cap))
                s8 += cap
                actual += 5 * cap
            spec.append(("bytes", *subs))
    # re-apply the gate on what the tables actually cost: a page whose
    # sample window misrepresented it should ship raw, not an engaged
    # transport that saves nothing (nothing is staged until below, so
    # bailing here is free)
    if actual > wire_cap or nbytes - actual < 4096:
        return None

    def cat(parts, dtype):
        if not parts:
            return np.zeros(1, dtype=dtype)
        if len(parts) == 1:  # already contiguous: don't re-copy 10s of MB
            return parts[0]
        return np.concatenate(parts)

    hs = stager.add_many(
        [cat(raw32_parts, np.uint32), cat(e32_parts, np.int32),
         cat(v32_parts, np.uint32), cat(raw8_parts, np.uint8),
         cat(e8_parts, np.int32), cat(v8_parts, np.uint8)],
        pad=False)
    spec = tuple(spec)

    def words(staged, _hs=hs, _spec=spec, _count=count, _lanes=lanes,
              _stride=stride):
        from .decode import planes_to_words

        return planes_to_words(
            staged[_hs[0]], staged[_hs[1]], staged[_hs[2]],
            staged[_hs[3]], staged[_hs[4]], staged[_hs[5]],
            _spec, _count, _lanes, _stride)

    return (actual, words, plans,
            ("planes", tuple(hs), (spec, stride), (0, 0)))


def _stage_delta_plan(plan, stager: "_Stager", need_hi: bool):
    """Route a DeltaPlan's device buffers through the batched stager
    (wave-chunked transfer + bytes_staged accounting — these previously
    shipped as implicit device_puts at dispatch, uncounted).

    The packed width-class words ride the padded path (the build slices
    them back to exact length before unpack's reshape); per-miniblock
    scatter starts/takes and the per-block min_delta lanes ship exact —
    padding would corrupt scatter targets and the repeat length.
    ``need_hi`` is False for i32 plans: ``expand_delta_i32`` never
    reads the hi lane, so it stays host-side.  Returns the build and
    the staged handles: each group's words (and its starts and takes),
    then the min_delta lanes staged."""
    from .decode import DeltaPlan

    specs = []
    h0 = len(stager.arrays)
    for w, words, starts, takes, n_vals, start, n_take in plan.groups:
        wh = stager.add(words)
        if starts is None:
            specs.append((w, wh, words.size, None, None,
                          n_vals, start, n_take))
        else:
            sh = stager.add(starts, pad=False)
            th = stager.add(takes, pad=False)
            specs.append((w, wh, words.size, sh, th, n_vals, 0, 0))
    has_md = plan.md_lo.size > 0
    lo_h = stager.add(plan.md_lo, pad=False) if has_md else None
    hi_h = stager.add(plan.md_hi, pad=False) if has_md and need_hi \
        else None
    hs = tuple(range(h0, len(stager.arrays)))
    # captured by value: holding the plan object itself would keep the
    # just-staged host words/starts/takes arrays alive through dispatch
    lo_host = None if has_md else plan.md_lo
    hi_host = plan.md_hi if hi_h is None else None
    meta = (plan.block_size, plan.first, plan.total)

    def build(s, _specs=tuple(specs), _lo=lo_h, _hi=hi_h,
              _lo_host=lo_host, _hi_host=hi_host, _meta=meta):
        groups = []
        for w, wh, nw, sh, th, n_vals, start, n_take in _specs:
            groups.append((
                w, s[wh][:nw],
                None if sh is None else s[sh],
                None if th is None else s[th],
                n_vals, start, n_take,
            ))
        return DeltaPlan(
            groups,
            _lo_host if _lo is None else s[_lo],
            _hi_host if _hi is None else s[_hi],
            *_meta,
        )

    return build, hs


def _plan_device_snappy_words(payload, expected_size: int, n_words: int,
                              offset: int = 0):
    """Plan device-side snappy decompression of one values segment.

    Returns ``(wire, commit)`` when the segment could decompress on
    device (multi-token block, native scanner available): ``wire`` is
    the exact transfer cost, and ``commit(stager)`` stages the plan and
    returns ``words(staged) -> (n_words,) u32``.  Returns None when the
    host path applies (single literal -> zero-copy view; no native
    scanner; int32 overflow risk; tokens would not shrink the
    transfer).  Staging is deferred so the dispatcher can pit the token
    wire against the lane/byte-plane transport and ship the cheaper.
    Wire format work happens in ``native/snappy.c
    tpq_snappy_scan_tokens``; copy resolution is
    :func:`tpuparquet.kernels.snappy.expand_tokens` (pointer doubling).
    Reference analogue of the block being replaced:
    ``compress.go:102-122`` (the hot decompress in the read loop).

    ``offset`` (bytes into the decompressed block) serves V1 pages whose
    level streams precede the values: the host scans levels from its own
    decompressed copy, but the WIRE ships the compressed tokens and the
    device slices the values segment out of its own expansion — level
    run tables are tiny; the values bytes are the transfer wall."""
    plan = _plan_token_expansion(payload, expected_size)
    if plan is None:
        return None
    out_len, wire = plan[5], plan[6]
    if out_len < offset + n_words * 4:
        raise ValueError("PLAIN values segment shorter than value count")
    # the wire gate: short-match-heavy blocks (numeric data under
    # min_match=4) cost more as 8-byte-per-token tables than as raw
    # bytes — ship tokens only when they actually shrink the transfer
    if wire >= 0.9 * (n_words * 4):
        return None

    def commit(stager, _plan=plan, _nw=n_words, _off=offset):
        blob = _stage_token_expansion(_plan, stager)

        def words(staged, _blob=blob, _nw=_nw, _off=_off):
            from .decode import u8_to_u32_words_at

            out = _blob(staged)
            if _off == 0:
                return u8_to_u32_words(out, _nw)
            return u8_to_u32_words_at(out, jnp.int32(_off), _nw)

        return words

    return wire, commit


class DeviceColumn:
    """Device-resident decoded column (Arrow layout).

    ``data``: flat (n_non_null * lanes,) u32 for fixed-width types
    (``lanes`` little-endian words per value — see :func:`_lanes_for`
    for why the buffer is 1-D), or u8 bytes with ``offsets`` for
    BYTE_ARRAY.  ``mask``/``positions`` map record
    slots to packed values; ``rep_levels``/``def_levels`` preserve nesting.

    Buffers are stored *bucket-padded* (the shape the chunk program and
    the fused page kernels emit) with logical lengths ``num_values``
    (record slots) and ``n_packed`` (non-null values); the public
    accessors slice lazily and materialize implicit streams (all-zero
    levels, all-valid masks) on demand, so the common flat-required case
    costs zero extra dispatches.
    """

    __slots__ = ("ptype", "type_length", "offsets", "num_values",
                 "n_packed", "n_bytes", "_data_p", "_mask_p", "_pos_p",
                 "_rep_p", "_def_p", "_cache")

    def __init__(self, ptype, type_length, data, offsets, mask, positions,
                 rep_levels, def_levels, num_values, n_packed=None,
                 n_bytes=None):
        self.ptype = ptype
        self.type_length = type_length
        self._data_p = data
        self.offsets = offsets
        self._mask_p = mask
        self._pos_p = positions
        self._rep_p = rep_levels
        self._def_p = def_levels
        self.num_values = num_values
        self.n_packed = (
            n_packed if n_packed is not None
            else (None if data is None
                  else data.shape[0] // (self.lanes or 1))
        )
        self.n_bytes = n_bytes  # BYTE_ARRAY only: logical data length
        self._cache = {}

    @property
    def lanes(self):
        """u32 words per value (fixed-width types; None for BYTE_ARRAY)."""
        if self.offsets is not None:
            return None
        return _lanes_for(self.ptype, self.type_length)

    # -- lazy exact-shape accessors ---------------------------------------

    def _sliced(self, key, padded, n, fill):
        got = self._cache.get(key)
        if got is None:
            if padded is None:
                got = fill()
            elif padded.shape[0] == n:
                got = padded
            else:
                got = padded[:n]
            self._cache[key] = got
        return got

    @property
    def data(self):
        if self.offsets is not None:
            # BYTE_ARRAY: the buffer axis is bytes, not values
            return self._sliced(
                "data", self._data_p, self.n_bytes,
                lambda: jnp.zeros((0,), dtype=jnp.uint8))
        return self._sliced(
            "data", self._data_p, (self.n_packed or 0) * self.lanes,
            lambda: jnp.zeros((0,), dtype=jnp.uint32))

    @property
    def mask(self):
        return self._sliced(
            "mask", self._mask_p, self.num_values,
            lambda: jnp.ones((self.num_values,), dtype=bool))

    @property
    def positions(self):
        return self._sliced(
            "pos", self._pos_p, self.num_values,
            lambda: jnp.arange(self.num_values, dtype=jnp.int32))

    @property
    def rep_levels(self):
        return self._sliced(
            "rep", self._rep_p, self.num_values,
            lambda: jnp.zeros((self.num_values,), dtype=jnp.int32))

    @property
    def def_levels(self):
        return self._sliced(
            "def", self._def_p, self.num_values,
            lambda: jnp.zeros((self.num_values,), dtype=jnp.int32))

    def _buffers(self):
        """Every live device buffer (the single source of truth for
        batched syncs — block_until_ready AND _finish_unit fence
        through this, so a new slot added here is fenced everywhere)."""
        return [
            x for x in (self._data_p, self.offsets, self._mask_p,
                        self._pos_p, self._rep_p, self._def_p)
            if x is not None
        ]

    def block_until_ready(self):
        # one batched sync rather than one per buffer
        jax.block_until_ready(self._buffers())
        return self

    def to_numpy(self, limit: int | None = None):
        """Materialize to the CPU oracle's chunk representation:
        (values, rep_levels, def_levels).  Slices padding host-side.

        ``limit`` bounds the materialization to the first ``limit``
        record slots (values keep their packed non-null order) —
        device buffers are sliced BEFORE the pull, so a bounded check
        of a huge chunk never streams the whole buffer over a narrow
        host link."""
        n = self.num_values
        if limit is not None and limit < n:
            n = max(limit, 0)
            rep = (np.zeros(n, dtype=np.int32) if self._rep_p is None
                   else np.asarray(self._rep_p[:n], dtype=np.int32))
            dl = (np.zeros(n, dtype=np.int32) if self._def_p is None
                  else np.asarray(self._def_p[:n], dtype=np.int32))
            nn = (n if self._mask_p is None
                  else int(np.asarray(self.mask[:n]).sum()))
            if self.offsets is not None:
                offs = np.asarray(self.offsets[: nn + 1], dtype=np.int64)
                data = np.asarray(self.data[: int(offs[-1])],
                                  dtype=np.uint8)
                return ByteArrayColumn(offs, data), rep, dl
            lanes = self.lanes
            flat = np.asarray(self.data[: nn * lanes],
                              dtype=np.uint32)
            return self._flat_to_typed(flat, lanes), rep, dl
        rep = (np.zeros(n, dtype=np.int32) if self._rep_p is None
               else np.asarray(self._rep_p, dtype=np.int32)[:n])
        dl = (np.zeros(n, dtype=np.int32) if self._def_p is None
              else np.asarray(self._def_p, dtype=np.int32)[:n])
        if self.offsets is not None:
            offs = np.asarray(self.offsets, dtype=np.int64)
            data = np.asarray(self.data, dtype=np.uint8)[: int(offs[-1])]
            return ByteArrayColumn(offs, data), rep, dl
        lanes = self.lanes
        flat = np.asarray(self.data, dtype=np.uint32)
        return self._flat_to_typed(flat, lanes), rep, dl

    def as_values(self):
        """Repackage the packed values for ``FileWriter.write_columns``
        (a :class:`tpuparquet.kernels.encode.DeviceValues` — it shares
        this column's flat u32 lane layout, so no data moves).

        Fixed-width int32/int64/float/double columns only, and the
        column must be all-non-null (``write_columns`` takes validity
        separately via ``masks=``; the packed buffer is exactly the
        non-null stream either way)."""
        from ..cpu.plain import PHYSICAL_DTYPES
        from .encode import DeviceValues

        dt = (None if self.offsets is not None or self.ptype == Type.BOOLEAN
              else PHYSICAL_DTYPES.get(self.ptype))
        if dt is None:
            raise TypeError(
                f"as_values supports int32/int64/float/double columns, "
                f"not {self.ptype.name}")
        return DeviceValues(self.data, dt)

    def _flat_to_typed(self, flat: np.ndarray, lanes: int):
        """Flat little-endian u32 lane words -> the oracle's value
        array (the single home of the lane-layout contract)."""
        if self.ptype == Type.BOOLEAN:
            return flat.astype(bool)
        if self.ptype == Type.INT32:
            return flat.view(np.int32)
        if self.ptype == Type.FLOAT:
            return flat.view(np.float32)
        if self.ptype == Type.INT64:
            return flat.view(np.uint8).view("<i8")
        if self.ptype == Type.DOUBLE:
            return flat.view(np.uint8).view("<f8")
        if self.ptype == Type.INT96:
            return flat.reshape(-1, 3)
        if self.ptype == Type.FIXED_LEN_BYTE_ARRAY:
            n = self.type_length
            return flat.view(np.uint8).reshape(-1, 4 * lanes)[:, :n]
        raise TypeError(f"unsupported type {self.ptype}")


def _devicecolumn_flatten(col: DeviceColumn):
    leaves = (col._data_p, col.offsets, col._mask_p, col._pos_p,
              col._rep_p, col._def_p)
    aux = (col.ptype, col.type_length, col.num_values, col.n_packed,
           col.n_bytes)
    return leaves, aux


def _devicecolumn_unflatten(aux, leaves):
    data, offsets, mask, positions, rep, dl = leaves
    ptype, type_length, num_values, n_packed, n_bytes = aux
    return DeviceColumn(ptype, type_length, data, offsets, mask,
                        positions, rep, dl, num_values,
                        n_packed=n_packed, n_bytes=n_bytes)


# DeviceColumn is a JAX pytree: decoded columns pass straight through
# jit/vmap/transform boundaries (buffers are the leaves; shape metadata
# is static aux), so `jax.jit(fn)(read_row_group_device(...)['x'])`
# just works — the decode output is a first-class device value.
jax.tree_util.register_pytree_node(
    DeviceColumn, _devicecolumn_flatten, _devicecolumn_unflatten)


def _stage_fixed_plain(raw: bytes, count: int, ptype: Type,
                       type_length) -> jax.Array:
    if ptype == Type.BOOLEAN:
        words = pad_to_words(np.frombuffer(raw, np.uint8), 1, count)
        return unpack_u32(jnp.asarray(words.reshape(-1)), 1, count)
    if ptype == Type.FIXED_LEN_BYTE_ARRAY:
        return _stage_byte_rows(
            np.frombuffer(raw, np.uint8, count * type_length).reshape(
                count, type_length
            )
        )
    lanes = _LANES[ptype]
    words = stage_u32(raw, count * lanes)
    return plain_fixed_to_lanes(jnp.asarray(words), count, lanes)


def _flba_lanes(type_length: int) -> int:
    return (type_length + 3) // 4


def _stage_byte_rows_np(arr: np.ndarray) -> np.ndarray:
    """(N, L) u8 rows -> flat (N*lanes,) u32, zero-padding each row to
    whole little-endian u32 lanes (shared FLBA/int96 staging)."""
    if arr.shape[0] == 0:  # all-null page: zero rows, width still known
        return np.zeros((0,), dtype=np.uint32)
    rows = arr.view(np.uint8).reshape(arr.shape[0], -1)
    lanes = _flba_lanes(rows.shape[1])
    padded = np.zeros((rows.shape[0], lanes * 4), dtype=np.uint8)
    padded[:, : rows.shape[1]] = rows
    return padded.view("<u4").reshape(-1)


def _stage_byte_rows(arr: np.ndarray) -> jax.Array:
    return jnp.asarray(_stage_byte_rows_np(arr))


def _check_dict_indices(i_sc, width: int, non_null: int, dict_len: int,
                        idx_np=None) -> None:
    """Reject out-of-range dictionary indices host-side.

    The device gather clamps indices (its padding lanes must stay in
    range), so a corrupt file's oversized index would silently decode to
    the last dictionary entry; the CPU oracle raises instead.  Precise
    scan maxing is only needed when the bit width can express an index
    beyond the dictionary — the writer-aligned case costs nothing."""
    if non_null == 0:
        return
    if dict_len <= 0:
        raise ValueError("dict-encoded page with empty dictionary")
    if idx_np is not None:
        mx = int(idx_np.max()) if idx_np.size else -1
    elif i_sc is None:
        mx = 0  # width 0: every index decodes to 0
    elif (1 << width) <= dict_len:
        return
    else:
        from .hybrid import max_scan_value

        mx = max_scan_value(i_sc, width)
    if mx >= dict_len:
        raise ValueError(
            f"dictionary index {mx} out of range "
            f"(dictionary has {dict_len} entries)"
        )


# Transfer geometry.  A fused chunk's page arrays ship inside one
# stacked buffer per group and role (``_Stager.stack``), whole, one
# ``device_put`` piece each: on a TPU v5e a piece cost about 0.2 ms
# whatever its size (9.7-28 KB average pieces moved 46-135 MB/s).  Any
# other array splits into power-of-two-row pieces of at most
# _PIECE_BYTES, which the device concatenates back.  Pieces ship in
# waves of at most _WAVE_BYTES in flight, blocking between waves.
_PIECE_BYTES = 16 << 20   # split unit for large arrays
# Below this, pieces zero-pad to a power-of-two bucket.  The floor
# trades padding waste (tail bucket up to 2x a sub-floor array) against
# the number of distinct piece shapes (each split array's concatenate
# compiles once per shape): a 1 MB floor cost staged wire 10-22% in
# tail padding (a byte count) across many mid-sized level/word arrays;
# 128 KB adds at most three more power-of-two shapes per dtype.
_MIN_PIECE_BYTES = 128 << 10
_WAVE_BYTES = 96 << 20    # max bytes in flight per wave


def _piece_rows(n: int, row_bytes: int) -> list[int]:
    """Row counts of the pieces :func:`_split_rows` cuts an ``n``-row
    array into: 16 MB pieces, then descending powers of two down to
    ``_MIN_PIECE_BYTES``, then one tail bucket of at most
    ``_MIN_PIECE_BYTES`` (the only piece that holds zero padding)."""
    from .decode import bucket

    max_rows = max(1, 1 << max(0, (_PIECE_BYTES // row_bytes)
                               .bit_length() - 1))
    min_rows = max(1, 1 << max(0, (_MIN_PIECE_BYTES // row_bytes)
                               .bit_length() - 1))
    rows = [max_rows] * (n // max_rows)
    left = n - len(rows) * max_rows
    while left >= min_rows:
        p = 1 << (left.bit_length() - 1)
        rows.append(p)
        left -= p
    if left:
        rows.append(bucket(left))  # <= min_rows (bucket() floors at 32)
    return rows


def _split_rows(a: np.ndarray):
    """Decompose an array into leading-dim pieces with power-of-two row
    counts (descending), zero-padding only the final piece.  Keeps the
    universe of transferred shapes small — each distinct (shape, dtype)
    costs a one-time transfer-program compile — without bucket-padding
    whole multi-hundred-MB buffers.  Zero-copy slices but for the
    tail: the host copies at most _MIN_PIECE_BYTES per array, and the
    reassembled total is deterministic in n (bounded jit keys)."""
    if a.ndim == 0 or a.shape[0] == 0:
        return [a]
    n = a.shape[0]
    pieces = []
    pos = 0
    for rows in _piece_rows(n, a.nbytes // n):
        if pos + rows <= n:
            pieces.append(a[pos : pos + rows])
        else:
            tail = np.zeros((rows,) + a.shape[1:], a.dtype)
            tail[: n - pos] = a[pos:]
            pieces.append(tail)
        pos += rows
    return pieces


def _staged_shape(stager: "_Stager", h: int) -> tuple:
    """The shape ``stager.put()`` gives array ``h`` on the device."""
    a = stager.arrays[h]
    if h in stager.no_pad or a.ndim == 0 or a.shape[0] == 0:
        return a.shape
    return (sum(_piece_rows(a.shape[0], a.nbytes // a.shape[0])),) \
        + a.shape[1:]


class _Stager:
    """Collects host arrays across chunks for batched wave transfers.

    ``put()`` ships every registered array in waves of at most
    ``_WAVE_BYTES`` — blocking between waves, which bounds the bytes in
    flight at once.  Padded arrays are cut into pieces
    (``_split_rows``, zero padding) and reassembled with a device-side
    concatenate; ``pad=False`` arrays ship with their exact shape,
    unsplit — for buffers whose tail padding would corrupt device
    semantics (e.g. the monotonic offset arrays fed to searchsorted),
    and for the stacked buffers of :meth:`stack`.  ``put()`` returns
    only after every transfer has completed, so host buffers (arena
    slabs included) are immediately reusable.

    A fused chunk's pages ship stacked: :meth:`stack` packs a group's
    page arrays into one buffer per role and takes the page arrays
    themselves off the wire (their handles stage to None); ``stacked``
    counts them."""

    __slots__ = ("arrays", "no_pad", "stacked")

    def __init__(self):
        self.arrays = []
        self.no_pad = set()
        self.stacked = 0

    def add(self, arr, pad: bool = True) -> int:
        a = arr if isinstance(arr, np.ndarray) else np.asarray(arr)
        self.arrays.append(np.ascontiguousarray(a))
        if not pad:
            self.no_pad.add(len(self.arrays) - 1)
        return len(self.arrays) - 1

    def add_many(self, arrs, pad: bool = True) -> list[int]:
        return [self.add(a, pad=pad) for a in arrs]

    def stack(self, pages, slots: int, arena: HostArena) -> tuple:
        """Register a group of ``pages`` (each a tuple of handles, one
        per role) as one ``(slots, *staged page shape)`` buffer per
        role, borrowed from ``arena``: each slot holds its page as
        ``put()`` would stage it (zero tail padding included), and the
        slots past the pages repeat the first page.  The pages' own
        arrays no longer ship.  Returns the buffers' handles, one per
        role."""
        out = []
        for role in zip(*pages):
            first = self.arrays[role[0]]
            shape = (slots,) + _staged_shape(self, role[0])
            buf = arena.borrow(
                int(np.prod(shape)) * first.dtype.itemsize
            ).view(first.dtype).reshape(shape)
            for slot, h in zip(buf, role):
                a = self.arrays[h]
                if a.ndim:
                    slot[: a.shape[0]] = a
                    slot[a.shape[0]:] = 0
                else:
                    slot[...] = a
            buf[len(role):] = buf[0]
            out.append(self.add(buf, pad=False))
        for h in (h for page in pages for h in page):
            self.arrays[h] = None
            self.stacked += 1
        return tuple(out)

    def put(self):
        return _put_all([self])[0]


def _put_all(stagers):
    """One batched wave transfer across SEVERAL stagers (the per-column
    stagers of one unit); returns each stager's staged list, None for
    a dropped handle.

    A fused chunk's stacked buffers ship whole, one piece each; every
    other array ships as ``_split_rows`` cuts it.  Pieces ship in
    column order, so the wave composition is independent of how many
    plan threads built the stagers — the parity pin's staged-bytes
    guarantee."""
    specs = []
    pieces = []
    for stg in stagers:
        sp = []
        for i, a in enumerate(stg.arrays):
            if a is None:
                sp.append(None)
                continue
            ps = [a] if i in stg.no_pad else _split_rows(a)
            sp.append((len(pieces), len(ps)))
            pieces.extend(ps)
        specs.append(sp)
    if not pieces:
        return [[] for _ in stagers]
    from ..stats import current_stats

    _cs = current_stats()
    _whist = None
    if _cs is not None:
        # counted at transfer time, post-split/padding: the pieces
        # ARE the wire
        _cs.bytes_staged += sum(p.nbytes for p in pieces)
        _cs.pieces_staged += len(pieces)
        _cs.stacked_arrays += sum(stg.stacked for stg in stagers)
        # per-wave transfer wall (put -> the block that fences it):
        # the link-health observable — a congested link shows as
        # the wave histogram's tail exploding while bytes_staged
        # stays flat
        _whist = _cs.hist("stager_wave_us")
    dev = [None] * len(pieces)
    prev = None
    t_wave = 0.0
    i = 0
    while i < len(pieces):
        wave, wave_bytes = [], 0
        while i < len(pieces) and (
            not wave or wave_bytes + pieces[i].nbytes <= _WAVE_BYTES
        ):
            wave.append(i)
            wave_bytes += pieces[i].nbytes
            i += 1
        if prev is not None:
            jax.block_until_ready(prev)
            if _whist is not None:
                _whist.record((time.perf_counter() - t_wave) * 1e6)
        if _whist is not None:
            t_wave = time.perf_counter()
        out = jax.device_put([pieces[j] for j in wave])
        for j, d in zip(wave, out):
            dev[j] = d
        prev = out
    jax.block_until_ready(prev)
    if _whist is not None and prev is not None:
        _whist.record((time.perf_counter() - t_wave) * 1e6)
    return [
        [None if ref is None
         else dev[ref[0]] if ref[1] == 1
         else jnp.concatenate(dev[ref[0] : ref[0] + ref[1]])
         for ref in sp]
        for sp in specs
    ]


def decode_chunk_device(blob, cm: ColumnMetaData, node: SchemaNode,
                        base: int = 0) -> DeviceColumn:
    """Decode one column chunk to a DeviceColumn (standalone wrapper; the
    row-group path batches staging across chunks)."""
    arena = thread_arena()
    try:
        st = _Stager()
        finish = plan_chunk_device(blob, cm, node, base, st, arena)
        col = finish(st.put())  # put() blocks until transfers complete
        # finish() itself stages some paths (CPU fallbacks, delta,
        # FLBA/boolean) straight from arena-backed views, outside the
        # stager — those transfers must land before slabs recycle
        col.block_until_ready()
    except BaseException:
        discard_thread_arena()  # in-flight transfers may read the slabs
        raise
    arena.release_all()
    return col


def plan_chunk_device(blob, cm: ColumnMetaData, node: SchemaNode,
                      base: int, stager: _Stager,
                      arena: HostArena | None = None,
                      verify_crc: bool | None = None,
                      cache_key=None, cache_state=None):
    """Phase 1 (host): page-header walk, block decompression, run-table
    scans, staging-plan registration.  Returns ``finish(staged)`` which
    issues the fused device dispatches and assembles the DeviceColumn.

    ``blob`` holds the chunk's byte range; offsets in ``cm`` are absolute
    minus ``base``.  ``verify_crc`` gates page CRC32 verification when
    headers carry one (None = env default) — same semantics as the CPU
    path in ``io/chunk.py``.

    ``cache_key`` is this chunk's plan-cache identity
    (``(footer fingerprint, rg, column)``, see ``kernels/plancache.py``):
    on a hit the per-page transport competition is skipped and only the
    remembered winner's planner runs; on a miss the verdicts are stored.
    Hints are ROUTING-ONLY — they choose which lossless transport plans,
    never what the decoded bytes are, so a stale hint degrades wire
    choice at worst.  ``cache_state`` (a list, out-param) receives
    "hit" / "miss" / "off" for span annotation.
    """
    from ..io.pages import crc_verify_default, verify_page_crc
    from ..stats import current_stats

    if arena is None:
        arena = HostArena()  # throwaway: no recycling, plain lifetime
    if verify_crc is None:
        verify_crc = crc_verify_default()
    codec = CompressionCodec(cm.codec)
    ptype = Type(node.element.type)
    _st = current_stats()
    # per-page event log (obs/): only on when the active collector was
    # opened with collect_stats(events=True) — the emission sites below
    # all gate on `_ev is not None`, so a plain collector (or none)
    # pays nothing per page
    _ev = None if _st is None else _st.events
    _col_path = ".".join(cm.path_in_schema)
    _degraded = _host_values_only()
    # footer-keyed plan cache: hints index by DATA-page ordinal
    _pc = _hints = _record = None
    if cache_key is not None and not _degraded:
        from .plancache import plan_cache

        _pc = plan_cache()
        if _pc is not None:
            _hints = _pc.lookup(cache_key)
            if _hints is None:
                _record = []
    if cache_state is not None:
        cache_state.append(
            "off" if _pc is None
            else ("hit" if _hints is not None else "miss"))
    _page_i = 0
    _walk_i = 0  # all-page ordinal (dict pages included): error coords
    if _st is not None:
        _st.chunks += 1
        _st.bytes_compressed += cm.total_compressed_size
        _st.bytes_uncompressed += cm.total_uncompressed_size or 0
        _st.values += cm.num_values
    start = cm.data_page_offset
    if cm.dictionary_page_offset is not None:
        start = min(start, cm.dictionary_page_offset)
    start -= base
    end = start + cm.total_compressed_size
    r = CompactReader(blob, start, end)

    dict_fixed_h = None    # stager handle: flat (D*lanes,) u32
    dict_offsets_h = None  # stager handles: byte-array dictionary
    dict_data_h = None
    dict_lens_np = None
    dict_len = 0
    dict_fixed = 0         # every entry's length, if they share one
    dict_pages = fixed_pages = 0  # byte-array dictionary data pages
    dict_host = None       # host copy, kept only for the degraded path

    # Deferred device work: each op is a closure (staged, parts) -> None
    # appended during the host walk and executed by finish() after the
    # one batched transfer.  parts keys: "val", "bytes", "rep", "def".
    ops = []
    values_read = 0
    total = cm.num_values
    max_def = node.max_def_level
    dwidth = max_def.bit_length()
    vlanes = (None if ptype == Type.BYTE_ARRAY
              else _lanes_for(ptype, node.element.type_length))

    while values_read < total:
        if r.pos >= end:
            raise CorruptChunkError(
                f"column chunk exhausted at {values_read}/{total} values",
                column=_col_path,
            )
        _t_pg = time.perf_counter() if _ev is not None else 0.0
        ph = decode_struct(PageHeader, r)
        # same malformed-header checks as the CPU path (io/chunk.py,
        # io/pages.py) — thrift-optional fields may arrive as None
        if ph.compressed_page_size is None or ph.compressed_page_size < 0:
            raise CorruptPageError("page header missing compressed size",
                                   column=_col_path, page=_walk_i)
        if ph.uncompressed_page_size is None or ph.uncompressed_page_size < 0:
            raise CorruptPageError("page header missing uncompressed size",
                                   column=_col_path, page=_walk_i)
        if r.pos + ph.compressed_page_size > end:
            raise CorruptPageError("page payload overruns column chunk",
                                   column=_col_path, page=_walk_i)
        # zero-copy view of the compressed bytes (the decompressors take
        # any buffer; a bytes() here would copy every page)
        payload = np.frombuffer(
            filter_bytes("kernels.device.page_payload",
                         blob[r.pos : r.pos + ph.compressed_page_size],
                         column=_col_path, page=_walk_i),
            dtype=np.uint8,
        )
        if payload.size != ph.compressed_page_size:
            raise CorruptPageError("page payload truncated",
                                   column=_col_path, page=_walk_i)
        if verify_page_crc(ph, payload, enabled=verify_crc,
                           column=_col_path, page=_walk_i):
            if _st is not None:
                _st.pages_crc_verified += 1
        r.pos += ph.compressed_page_size
        _walk_i += 1
        ptype_page = PageType(ph.type)
        if ptype_page in (PageType.DATA_PAGE, PageType.DATA_PAGE_V2) \
                and not _degraded:
            # simulated device failures land here (harness site); the
            # degraded re-plan skips it — the CPU decode it models
            # doesn't touch the device kernels
            fault_point("kernels.device.page_dispatch",
                        column=_col_path, page=_page_i)

        if ptype_page == PageType.DICTIONARY_PAGE:
            dph = ph.dictionary_page_header
            if dph is None or dph.num_values is None or dph.num_values < 0:
                raise ValueError(
                    "DICTIONARY_PAGE header missing its struct"
                )
            raw = decompress_block_into(codec, payload,
                                        ph.uncompressed_page_size, arena)
            dict_np = decode_plain(
                ptype, raw, dph.num_values,
                node.element.type_length,
            )
            if _degraded:
                # the host gather below needs the dictionary ON HOST;
                # own the bytes — `raw` is an arena view that recycles
                dict_host = (dict_np if isinstance(dict_np,
                                                   ByteArrayColumn)
                             else np.array(dict_np, copy=True))
            if isinstance(dict_np, ByteArrayColumn):
                dict_offsets_h = stager.add(
                    dict_np.offsets.astype(np.int32))
                dict_data_h = stager.add(dict_np.data)
                dict_lens_np = dict_np.lengths()
                dict_len = len(dict_lens_np)
                if dict_len and (dict_lens_np == dict_lens_np[0]).all():
                    dict_fixed = int(dict_lens_np[0])
            else:
                arr = np.asarray(dict_np)
                dict_len = arr.shape[0]
                if arr.dtype == np.bool_:
                    staged = arr.astype(np.uint32).reshape(-1)
                elif arr.dtype in (np.dtype("<i4"), np.dtype("<f4")):
                    staged = arr.view("<u4").reshape(-1)
                elif arr.dtype in (np.dtype("<i8"), np.dtype("<f8")):
                    staged = arr.view("<u4").reshape(-1)
                elif ptype == Type.INT96:
                    staged = arr.astype("<u4").reshape(-1)
                else:  # FLBA (D, L) u8
                    staged = _stage_byte_rows_np(arr)
                dict_fixed_h = stager.add(staged)
            if r.pos != cm.data_page_offset - base:
                r.pos = cm.data_page_offset - base
            continue

        bytes_comp = None  # BYTE_ARRAY PLAIN: compressed source for the
        # device page-blob gather (src, uncompressed_size, values_offset)
        if ptype_page == PageType.DATA_PAGE:
            h = ph.data_page_header
            if h is None or h.num_values is None or h.num_values < 0:
                raise ValueError("DATA_PAGE header missing data_page_header")
            n = h.num_values
            device_plain = (_DEVICE_SNAPPY()
                            and codec == CompressionCodec.SNAPPY
                            and h.encoding == Encoding.PLAIN
                            and ptype in _LANES)
            if device_plain and not node.max_rep_level and not max_def:
                # flat-required PLAIN page: the block holds no level
                # bytes, so planning needs nothing from the payload —
                # defer decompression (device tokens, or zero-copy host
                # view for single-literal blocks, decided at dispatch)
                values_comp = (payload, ph.uncompressed_page_size, 0)
                values_seg = None
                dl_scan = dl_host = None
            else:
                values_comp = None
                raw = decompress_block_into(codec, payload,
                                            ph.uncompressed_page_size, arena)
                pos = 0
                if node.max_rep_level:
                    r_scan, r_host, pos = _scan_levels_v1(
                        raw, n, node.max_rep_level, pos,
                        h.repetition_level_encoding,
                    )
                    _defer_levels(ops, stager, "rep", r_scan, r_host, n,
                                  node.max_rep_level.bit_length(),
                                  max_level=node.max_rep_level)
                dl_scan, dl_host, pos = _scan_levels_v1(
                    raw, n, max_def, pos, h.definition_level_encoding
                )
                values_seg = raw[pos:]
                if device_plain:
                    # V1 page WITH levels: host scanned them from its
                    # own copy; the wire can still ship tokens, with the
                    # device slicing values out of its expansion at
                    # ``pos`` (values_seg stays the host fallback for
                    # single-literal / no-scanner blocks)
                    values_comp = (payload, ph.uncompressed_page_size,
                                   pos)
                elif (_DEVICE_SNAPPY() and codec == CompressionCodec.SNAPPY
                        and h.encoding == Encoding.PLAIN
                        and ptype == Type.BYTE_ARRAY):
                    # BYTE_ARRAY twin: host scans lengths from its copy;
                    # the device can gather value bytes out of its own
                    # expansion (length prefixes skipped arithmetically)
                    bytes_comp = (payload, ph.uncompressed_page_size, pos)
            enc = h.encoding
        elif ptype_page == PageType.DATA_PAGE_V2:
            from ..cpu.hybrid import scan_hybrid

            h = ph.data_page_header_v2
            if h is None or h.num_values is None or h.num_values < 0:
                raise ValueError(
                    "DATA_PAGE_V2 header missing data_page_header_v2"
                )
            n = h.num_values
            rl_len = h.repetition_levels_byte_length or 0
            dl_len = h.definition_levels_byte_length or 0
            if rl_len < 0 or dl_len < 0 or rl_len + dl_len > len(payload):
                raise ValueError("V2 level lengths exceed page size")
            if node.max_rep_level:
                r_scan = scan_hybrid(
                    payload[:rl_len], n, node.max_rep_level.bit_length()
                )
                _defer_levels(ops, stager, "rep", r_scan, None, n,
                              node.max_rep_level.bit_length(),
                              max_level=node.max_rep_level)
            dl_scan, dl_host = (None, None)
            if max_def:
                dl_scan = scan_hybrid(
                    payload[rl_len : rl_len + dl_len], n, dwidth
                )
            values_seg = payload[rl_len + dl_len :]
            values_comp = None
            if h.is_compressed is not False:
                vals_size = ph.uncompressed_page_size - rl_len - dl_len
                if (_DEVICE_SNAPPY() and codec == CompressionCodec.SNAPPY
                        and h.encoding == Encoding.PLAIN
                        and ptype in _LANES):
                    # V2 keeps levels outside compression: planning only
                    # needs the level bytes, so the values block can
                    # decompress on device
                    values_comp = (values_seg, vals_size, 0)
                    values_seg = None
                else:
                    if (_DEVICE_SNAPPY()
                            and codec == CompressionCodec.SNAPPY
                            and h.encoding == Encoding.PLAIN
                            and ptype == Type.BYTE_ARRAY):
                        bytes_comp = (values_seg, vals_size, 0)
                    values_seg = decompress_block_into(
                        codec, values_seg, vals_size, arena,
                    )
            enc = h.encoding
        else:
            continue
        if _st is not None:
            _st.pages += 1
            _st.hist("page_comp_bytes").record(ph.compressed_page_size)
            _st.hist("page_uncomp_bytes").record(
                ph.uncompressed_page_size)

        if not max_def:
            non_null = n
        elif dl_scan is not None:
            # count non-nulls from the run table (RLE arithmetic + one
            # vectorized unpack) rather than syncing the device expansion
            # back — device->host round-trips serialize the page pipeline
            from .hybrid import count_eq_scan

            non_null = count_eq_scan(dl_scan, dwidth, max_def,
                                     validate_max=True)
            if (ptype_page == PageType.DATA_PAGE_V2
                    and h.num_nulls is not None
                    and n - h.num_nulls != non_null):
                # same cross-check as the CPU path (io/pages.py)
                raise ValueError(
                    f"V2 num_nulls {h.num_nulls} disagrees with def "
                    f"levels ({n - non_null} nulls)"
                )
        else:
            non_null = int((dl_host == max_def).sum())
        values_read += n

        # plan-cache hint for THIS data page (routing-only: which
        # transport planner to run; None entry = page had no cacheable
        # decision).  _rec_entry collects the miss-path verdict; every
        # data page appends exactly one entry so hint indices stay
        # aligned with the data-page ordinal across re-reads.
        _hint = (_hints[_page_i]
                 if _hints is not None and _page_i < len(_hints)
                 else None)
        _rec_entry = None

        # Resolve deferred value-segment decompression.  The device
        # transports COMPETE on wire cost: snappy tokens (no host
        # decompress) vs byte planes vs delta lanes (both need the
        # decompressed bytes — native snappy makes that cheap).  A
        # timestamp page whose tokens cost 0.76x of raw but whose lanes
        # cost 0.50x must ship lanes, not whichever planner ran first.
        # The token SCAN is itself a third of the plan wall, so it runs
        # LAZILY: the compressed payload size approximates the token
        # transport's wire (tokens re-encode the block as table +
        # literals), and a competitor already under that bound skips
        # the scan outright — trading a few percent of wire precision
        # in the crossover region for ~30% of the plan phase.
        plan_words = None
        fused_val = None  # a planes or delta page's _PageOp value kernel
        payload_bound = None
        # cached verdict for a PLAIN fixed-width page: run ONLY the
        # remembered winner's planner (or none, for a raw page) — the
        # losers' sample windows and above all the token SCAN are what a
        # warm re-read skips
        _use_hint = (isinstance(_hint, tuple) and len(_hint) >= 2
                     and _hint[0] == "plain")
        _hchoice = _hint[1] if _use_hint else None
        _hparams = (_hint[2] if _use_hint and len(_hint) > 2 else None)
        if values_comp is not None:
            payload_bound = len(values_comp[0])
            competitors = ((_DEVICE_PLANES()
                            or (_DEVICE_DELTA_LANES()
                                and ptype in (Type.INT32, Type.INT64)))
                           and non_null >= 1024)
            if _use_hint:
                competitors = _hchoice in ("planes", "delta")
            if values_seg is None and competitors:
                values_seg = decompress_block_into(
                    codec, values_comp[0], values_comp[1], arena)
        delta_cand = None
        if ((not _use_hint or _hchoice == "delta")
                and _DEVICE_DELTA_LANES() and enc == Encoding.PLAIN
                and ptype in (Type.INT32, Type.INT64)
                and values_seg is not None):
            delta_cand = _plan_delta_lane_words(
                values_seg, non_null, ptype,
                params=(_hparams if _use_hint and _hchoice == "delta"
                        else None))
        delta_wire = delta_cand[0] if delta_cand is not None else None

        planes_spec = None

        def _try_planes(budget):
            if ((not _use_hint or _hchoice == "planes")
                    and _DEVICE_PLANES() and non_null
                    and enc == Encoding.PLAIN and ptype in _LANES
                    and values_seg is not None):
                return _plan_plane_words(
                    values_seg, non_null, _LANES[ptype], stager,
                    budget=budget,
                    lane_plans=(_hparams
                                if _use_hint and _hchoice == "planes"
                                else None))
            return None

        budgets = [c for c in (delta_wire, payload_bound)
                   if c is not None]
        planes_wire = None
        _pl = _try_planes(min(budgets) if budgets else None)
        if _pl is not None:
            planes_wire, plan_words, planes_spec, fused_val = _pl
        chosen = "planes" if plan_words is not None else None
        tok = None
        tok_scanned = False
        if plan_words is None:
            run_tok = payload_bound is not None and not (
                delta_wire is not None and delta_wire < payload_bound)
            if _use_hint:
                run_tok = (_hchoice == "snappy"
                           and payload_bound is not None)
            if run_tok:
                # no competitor beats the token bound: pay the scan
                tok_scanned = True
                tok = _plan_device_snappy_words(
                    values_comp[0], values_comp[1],
                    non_null * _LANES[ptype], offset=values_comp[2],
                )
                if tok is None and not _use_hint:
                    # token transport unreachable after all: re-contest
                    # the planes without its payload bound (they may
                    # have been pruned ONLY by it)
                    _pl = _try_planes(delta_wire)
                    if _pl is not None:
                        planes_wire, plan_words, planes_spec, fused_val = _pl
                    chosen = "planes" if plan_words is not None else None
            if plan_words is None:
                if delta_cand is not None and (
                        tok is None or delta_cand[0] < tok[0]):
                    plan_words, fused_val = delta_cand[1](stager)
                    chosen = "delta"
                elif tok is not None:
                    plan_words = tok[1](stager)
                    chosen = "snappy"
                elif values_seg is None and values_comp is not None:
                    # no device transport reachable (or a cached "raw"
                    # verdict skipped the competition): the PLAIN
                    # fallback below needs the decompressed bytes
                    values_seg = decompress_block_into(
                        codec, values_comp[0], values_comp[1], arena)
        if _record is not None and enc == Encoding.PLAIN \
                and ptype in _LANES:
            _params = (planes_spec if chosen == "planes"
                       else delta_cand[2] if chosen == "delta"
                       else None)
            _rec_entry = ("plain", chosen, _params)
        chosen_wire = (planes_wire if chosen == "planes"
                       else delta_wire if chosen == "delta"
                       else tok[0] if chosen == "snappy" else None)
        if _st is not None and chosen is not None:
            if chosen == "planes":
                _st.pages_device_planes += 1
            elif chosen == "delta":
                _st.pages_device_delta_lanes += 1
            else:
                _st.pages_device_snappy += 1

        # event-log fields for this page (filled by the dispatch chain
        # below; emitted once at the end of the loop body).  The PLAIN
        # fixed-width transports are decided right here, so their
        # transport label, wire numbers and gate verdict resolve now.
        _tr = _wire_ev = _raw_ev = _gate = _reason = None
        if enc == Encoding.PLAIN and ptype in _LANES:
            _raw_ev = non_null * _LANES[ptype] * 4
            _tr = _CHOSEN_TRANSPORT.get(chosen, "raw")
            _wire_ev = chosen_wire if chosen is not None else _raw_ev
            if _st is not None and chosen is not None and _raw_ev:
                _st.hist("wire_ratio_permille").record(
                    chosen_wire * 1000 // _raw_ev)
            if _ev is not None:
                # "declined" = competed on wire cost (or in-planner
                # gates) and lost; "n/a" = never eligible for this
                # page — the distinction an operator needs when a
                # transport they expected is absent
                _gate = {"raw": _raw_ev}
                _gate["delta-lanes"] = (
                    delta_wire if delta_wire is not None
                    else "declined" if delta_cand is not None
                    or (_DEVICE_DELTA_LANES()
                        and ptype in (Type.INT32, Type.INT64)
                        and values_seg is not None)
                    else "n/a (type/flag/compressed)")
                _gate["planes"] = (
                    planes_wire if planes_wire is not None
                    else "declined" if (_DEVICE_PLANES() and non_null
                                        and values_seg is not None)
                    else "n/a (flag/empty/compressed)")
                if tok is not None:
                    _gate["snappy-tokens"] = tok[0]
                elif payload_bound is None:
                    _gate["snappy-tokens"] = "n/a (not device-snappy)"
                elif tok_scanned:
                    _gate["snappy-tokens"] = "declined"
                else:
                    _gate["snappy-tokens"] = (
                        f"not-scanned (competitor under payload bound "
                        f"{payload_bound}B)")
                if chosen is not None:
                    _reason = (f"{_tr} {chosen_wire}B beat raw "
                               f"{_raw_ev}B")
                else:
                    _reason = "no transport beat raw staging"
                if _use_hint:
                    _reason += " (plan-cache hit)"

        # Def-level plan, padded for the fused page kernels.  A page
        # whose value path can't fuse expands it standalone via
        # _defer_levels below.
        dl_ref = None  # (handles, cnt, nbp, single) when fusable
        if dl_scan is not None:
            from .hybrid import plan_stream_args

            dl_args, dl_cnt, dl_nbp, dl_sg = plan_stream_args(
                dl_scan, n, dwidth)
            dl_ref = (stager.add_many(dl_args, pad=False), dl_cnt, dl_nbp,
                      dl_sg)
        elif dl_host is not None:
            hh = stager.add(np.asarray(dl_host, dtype=np.int32))
            ops.append(lambda s, p, _h=hh, _n=n:
                       p["def"].append((s[_h], _n)))

        def _def_standalone():
            """Expand the def plan on its own (non-fused value paths)."""
            if dl_ref is not None:
                from .decode import expand_tbl

                hs, cnt, nbp = dl_ref[:3]

                def op(s, p, _hs=hs, _cnt=cnt, _nbp=nbp, _n=n,
                       _sg=dl_ref[3]):
                    dl_dev = expand_tbl(
                        s[_hs[0]], s[_hs[1]], _cnt, dwidth, _nbp,
                        single=_sg,
                    ).astype(jnp.int32)
                    p["def"].append((dl_dev, _n))

                ops.append(op)

        if _degraded:
            # Graceful degradation (cpu_fallback_values): this page's
            # VALUES decode on the bit-exact CPU oracle — the exact
            # code path `read_row_group_arrays` runs — and only the
            # finished buffers stage to the device.  No decode kernels,
            # no wire transports; level expansion still rides the
            # shared machinery above.
            _tr = "host-degraded"
            _wire_ev = _raw_ev = _gate = None
            _reason = "device dispatch degraded: CPU oracle decode"
            _def_standalone()
            if _st is not None:
                _st.pages_degraded += 1
            if enc in _DICT_ENCODINGS:
                from ..cpu import decode_dict_indices, gather

                if dict_host is None:
                    raise CorruptChunkError(
                        "dictionary-encoded page but no dictionary "
                        "page seen", column=_col_path)
                # bytes(): the oracle decoder indexes scalars out of
                # its input, and numpy-u8 scalars overflow its width
                # arithmetic
                idx = decode_dict_indices(bytes(memoryview(values_seg)),
                                          non_null)
                if idx.size and int(idx.max()) >= dict_len:
                    raise CorruptPageError(
                        f"dictionary index {int(idx.max())} out of "
                        f"range (dictionary has {dict_len})",
                        column=_col_path, page=_page_i)
                col = gather(dict_host, idx)
            else:
                col = decode_values_cpu(ptype, enc, values_seg,
                                        non_null,
                                        node.element.type_length)
            # own the bytes: the oracle decoders return VIEWS of the
            # arena-backed page buffer, and on the CPU backend staging
            # can be zero-copy — a recycled slab would silently rewrite
            # this column under a later unit's decode
            if isinstance(col, ByteArrayColumn):
                col = ByteArrayColumn(np.array(col.offsets, copy=True),
                                      np.array(col.data, copy=True))
            else:
                col = np.array(col, copy=True)
            if isinstance(col, ByteArrayColumn):
                dh = stager.add(col.data)
                ops.append(
                    lambda s, p, _dh=dh,
                    _o=col.offsets.astype(np.int32),
                    _nb=int(col.data.size):
                    p["bytes"].append((_o, s[_dh], _nb))
                )
            else:
                ops.append(
                    lambda s, p, _c=col, _nn=non_null:
                    p["val"].append((_stage_numpy_fixed(_c, ptype), _nn))
                )
        elif enc in _DICT_ENCODINGS:
            _tr = "dict"
            width = int(values_seg[0]) if len(values_seg) else 0
            if dict_fixed_h is not None:
                from ..cpu.hybrid import scan_hybrid
                from .hybrid import plan_stream_args

                i_sc = scan_hybrid(values_seg, non_null, width, pos=1) \
                    if width else None
                _check_dict_indices(i_sc, width, non_null, dict_len)
                idx_ref = None
                if i_sc is not None:
                    idx_args, i_cnt, i_nbp, i_sg = plan_stream_args(
                        i_sc, non_null, width)
                    idx_ref = (stager.add_many(idx_args, pad=False),
                               i_cnt, i_nbp, i_sg)
                if idx_ref is not None:
                    # dl_ref is None here only where the page has no
                    # def levels or host-decoded ones (a closure above)
                    ops.append(_PageOp(
                        dl_ref, dwidth, "dict", idx_ref[0],
                        (idx_ref[1], width, idx_ref[2], idx_ref[3]),
                        (dict_fixed_h,), vlanes, n, non_null))
                else:
                    _def_standalone()

                    def op(s, p, _nn=non_null, _dh=dict_fixed_h,
                           _vl=vlanes):
                        idx = jnp.zeros((_nn,), jnp.int32)
                        p["val"].append(
                            (dict_gather_fixed(s[_dh], idx,
                                               lanes=_vl), _nn)
                        )

                    ops.append(op)
            elif dict_offsets_h is not None:
                # host-side index decode (vectorized, no device sync) just
                # to size the output; the gather uses the device indices.
                # One scan serves both the host expand and the device plan.
                from ..cpu.hybrid import expand_scan, scan_hybrid
                from .decode import bucket
                from .hybrid import plan_stream_args

                if width:
                    i_sc = scan_hybrid(values_seg, non_null, width, pos=1)
                    idx_u = expand_scan(*i_sc[:6], non_null, width)
                    # validate BEFORE the int32 cast: a width-32 index
                    # like 0xFFFFFFFF would wrap negative and pass
                    _check_dict_indices(None, width, non_null, dict_len,
                                        idx_np=idx_u)
                    idx_np = idx_u.astype(np.int32)
                else:
                    i_sc = None
                    idx_np = np.zeros(non_null, np.int32)
                    _check_dict_indices(None, width, non_null, dict_len,
                                        idx_np=idx_np)
                lens = dict_lens_np[idx_np]
                out_offsets = np.zeros(non_null + 1, dtype=np.int32)
                np.cumsum(lens, out=out_offsets[1:])
                total_b = int(out_offsets[-1])
                # every dynamic input stays at its bucket size so the jit
                # cache keys on buckets, not exact per-page counts
                cap = bucket(max(total_b, 1))
                dict_pages += 1
                fixed_pages += dict_fixed > 0
                if i_sc is not None:
                    i_args, i_cnt, i_nbp, i_single = plan_stream_args(
                        i_sc, non_null, width, expanded=idx_u)
                    ops.append(_PageOp(
                        dl_ref, dwidth, "dict_bytes",
                        stager.add_many(i_args, pad=False),
                        (i_cnt, width, i_nbp, i_single, cap, dict_fixed),
                        (dict_offsets_h, dict_data_h), None, n,
                        non_null, offsets=out_offsets, nbytes=total_b))
                else:
                    _def_standalone()

                    def op(s, p, _icnt=bucket(max(non_null, 1)),
                           _cap=cap, _oo=out_offsets, _nn=non_null,
                           _tb=total_b, _doh=dict_offsets_h,
                           _ddh=dict_data_h, _fx=dict_fixed):
                        from .decode import page_dict_bytes_tbl

                        dummy = jnp.zeros((1,), jnp.uint32)
                        data = page_dict_bytes_tbl(
                            s[_doh], s[_ddh], dummy, dummy,
                            np.int32(_nn), _icnt, 0, 0, _cap,
                            has_idx=False, width=_fx,
                        )
                        p["bytes"].append((_oo, data, _tb))

                    ops.append(op)
            else:
                raise ValueError("dict-encoded page without dictionary")
        elif enc == Encoding.PLAIN:
            if ptype == Type.BYTE_ARRAY:
                col = decode_plain(ptype, values_seg, non_null)  # host scan
                offs = col.offsets.astype(np.int32)
                from .decode import bucket as _bucket

                blob_plan = None
                budget = None
                # cached "raw" verdict skips the token scan outright; a
                # cached "tokens" verdict (or no hint) pays it — the
                # tables it builds ARE the staged content
                _ba_skip = (isinstance(_hint, tuple) and len(_hint) == 2
                            and _hint[0] == "ba" and _hint[1] is False)
                if bytes_comp is not None and not _ba_skip:
                    budget = (0.9 * int(col.data.size)
                              - 4 * _bucket(non_null + 1))
                    if budget > 0:
                        blob_plan = _plan_device_snappy_blob(
                            bytes_comp[0], bytes_comp[1], budget, stager)
                if _record is not None:
                    _rec_entry = ("ba", blob_plan is not None)
                _raw_ev = int(col.data.size)
                if _ev is not None:
                    _gate = {"raw": _raw_ev,
                             "snappy-tokens": (
                                 blob_plan[0] if blob_plan is not None
                                 else "declined" if budget is not None
                                 else "n/a (not device-snappy)")}
                if blob_plan is not None:
                    # compressed tokens + padded offsets ship; the
                    # device expands the page and gathers value bytes
                    # (length prefixes skipped arithmetically)
                    from .decode import bucket, plain_bytes_from_blob

                    _def_standalone()
                    blob_wire, blob_plan = blob_plan
                    _tr = "snappy-tokens"
                    _wire_ev = blob_wire
                    if _st is not None:
                        _st.pages_device_snappy += 1
                        if _raw_ev:
                            _st.hist("wire_ratio_permille").record(
                                blob_wire * 1000 // _raw_ev)
                    if _ev is not None:
                        _reason = (f"tokens {blob_wire}B under budget "
                                   f"{int(budget)}B (raw {_raw_ev}B)")
                    nb = int(col.data.size)
                    cap = bucket(max(nb, 1))
                    ocap = bucket(non_null + 1)
                    offs_pad = np.full(ocap, nb, dtype=np.int32)
                    offs_pad[: non_null + 1] = offs
                    oh = stager.add(offs_pad, pad=False)

                    def op(s, p, _bp=blob_plan, _oh=oh, _o=offs,
                           _cap=cap, _nb=nb, _pos=bytes_comp[2]):
                        data = plain_bytes_from_blob(
                            _bp(s), s[_oh], jnp.int32(_pos), _cap)
                        p["bytes"].append((_o, data, _nb))

                    ops.append(op)
                else:
                    # the bytes stage padded to a bucket, so pages of
                    # other exact lengths share a chunk program's group
                    _tr = "raw"
                    _wire_ev = _raw_ev
                    ops.append(_PageOp(
                        dl_ref, dwidth, "plain_bytes",
                        (stager.add(col.data),), (), (), None, n,
                        non_null, offsets=offs,
                        nbytes=int(col.data.size)))
            elif ptype in _LANES:
                # zero-copy u32 view of the decompressed values rides the
                # one batched transfer (or the words come straight from
                # the device transports); 'decode' is a device reshape,
                # fused with the def-level expansion where there is one
                lanes = _LANES[ptype]
                if plan_words is None:
                    wh = stager.add(stage_u32(values_seg, non_null * lanes))
                    ops.append(_PageOp(dl_ref, dwidth, "plain", (wh,),
                                       (), (), lanes, n, non_null))
                else:
                    kind, hs, statics, aux = fused_val or (
                        "tokens", (), (), (0, 0))
                    ops.append(_PageOp(dl_ref, dwidth, kind, hs, statics,
                                       (), lanes, n, non_null, aux=aux,
                                       words=plan_words))
            else:
                _tr = "raw"
                _def_standalone()
                # values_seg stays a zero-copy view (arena lifetime runs
                # until the caller's release, after transfers complete)
                ops.append(
                    lambda s, p, _seg=values_seg, _nn=non_null:
                    p["val"].append((
                        _stage_fixed_plain(_seg, _nn, ptype,
                                           node.element.type_length),
                        _nn,
                    ))
                )
        elif enc == Encoding.BYTE_STREAM_SPLIT and ptype in (
                Type.INT32, Type.INT64, Type.FLOAT, Type.DOUBLE,
                Type.FIXED_LEN_BYTE_ARRAY):
            from .decode import bss_to_lanes

            _tr = "bss"
            _def_standalone()
            k = (node.element.type_length
                 if ptype == Type.FIXED_LEN_BYTE_ARRAY
                 else 4 * _LANES[ptype])
            raw_np = (values_seg.reshape(-1)
                      if isinstance(values_seg, np.ndarray)
                      else np.frombuffer(values_seg, dtype=np.uint8))
            if raw_np.size < non_null * k:
                raise ValueError("BYTE_STREAM_SPLIT: input too short")
            if non_null:
                rh = stager.add(raw_np[: non_null * k])
                ops.append(
                    lambda s, p, _rh=rh, _nn=non_null, _k=k, _vl=vlanes:
                    p["val"].append(
                        (bss_to_lanes(s[_rh], _nn, _k, _vl), _nn)
                    )
                )
        elif enc == Encoding.RLE and ptype == Type.BOOLEAN:
            # boolean RLE data values: a length-prefixed width-1 hybrid
            # stream — the same prefix parse and run-table deferral as
            # the V1 levels
            import struct

            _tr = "rle"
            _def_standalone()
            if len(values_seg) < 4:
                raise ValueError("boolean RLE stream missing length")
            (bsz,) = struct.unpack_from("<I", values_seg, 0)
            if 4 + bsz > len(values_seg):
                # the shared level scanner would silently truncate the
                # slice; a declared length beyond the page is corrupt
                raise ValueError("boolean RLE length exceeds page")
            if non_null:
                b_sc, _, _ = _scan_levels_v1(values_seg, non_null, 1, 0)
                _defer_levels(ops, stager, "val", b_sc, None, non_null, 1,
                              cast=None)
        elif enc == Encoding.DELTA_LENGTH_BYTE_ARRAY \
                and ptype == Type.BYTE_ARRAY:
            # lengths decode host-side (small delta stream, validation
            # shared with the CPU decoder); the byte payload ships as a
            # zero-copy view — the CPU fallback would memcpy the whole
            # string payload before staging
            from ..cpu.delta import scan_delta_length_byte_array

            _tr = "dlba"
            _def_standalone()
            offs, dpos = scan_delta_length_byte_array(values_seg,
                                                      non_null)
            dlba_bytes = int(offs[-1])
            view = np.frombuffer(values_seg, np.uint8, dlba_bytes, dpos)
            dh = stager.add(view)
            ops.append(
                lambda s, p, _dh=dh, _o=offs, _nb=dlba_bytes:
                p["bytes"].append((_o, s[_dh], _nb))
            )
        elif enc == Encoding.DELTA_BYTE_ARRAY and ptype in (
                Type.BYTE_ARRAY, Type.FIXED_LEN_BYTE_ARRAY):
            # front coding IS the LZ copy-resolution problem the snappy
            # kernel solves: each value = one copy token (its prefix,
            # read from the previous value's output start) + one literal
            # token (its suffix).  Ship compact prefixes+suffixes, expand
            # on device by pointer doubling (kernels/snappy.py).  FLBA
            # rides the same expansion; its flat output converts to lane
            # words on device (flba_bytes_to_lanes) instead of offsets.
            from ..cpu.delta import (
                assemble_delta_byte_array,
                decode_delta_binary_packed,
                scan_delta_length_byte_array,
            )

            _def_standalone()
            prefix_lens, ppos = decode_delta_binary_packed(
                values_seg, np.int64)
            if prefix_lens.size != non_null:
                raise ValueError("DELTA_BYTE_ARRAY: prefix count mismatch")
            soffs, spos = scan_delta_length_byte_array(
                values_seg, non_null, ppos)
            suffix_lens = np.diff(soffs)
            if non_null:
                if prefix_lens[0] != 0:
                    raise ValueError(
                        "DELTA_BYTE_ARRAY: first prefix must be 0")
                if (prefix_lens < 0).any():
                    raise ValueError("DELTA_BYTE_ARRAY: negative prefix")
            total_lens = prefix_lens + suffix_lens
            if non_null > 1 and (prefix_lens[1:]
                                 > total_lens[:-1]).any():
                raise ValueError(
                    "DELTA_BYTE_ARRAY: prefix longer than previous value")
            flba_len = (node.element.type_length
                        if ptype == Type.FIXED_LEN_BYTE_ARRAY else None)
            if flba_len is not None and non_null and not (
                    total_lens == flba_len).all():
                raise ValueError(
                    "DELTA_BYTE_ARRAY: FLBA value length mismatch")
            offs = np.zeros(non_null + 1, dtype=np.int64)
            np.cumsum(total_lens, out=offs[1:])
            expanded = int(offs[-1])
            n_suffix = int(soffs[-1]) if non_null else 0
            compact = n_suffix + 8 * non_null  # suffixes + token table
            if (non_null == 0 or expanded > (1 << 30)
                    or expanded < compact):
                # host assembly only when it ships STRICTLY fewer
                # bytes than the compact wire form (wire-neutral pages
                # take the copy-graph kernel below); the empty-page and
                # bucket(expanded)-past-int32 guards (cf. plan_tokens)
                # stay host for correctness.  Assembles from the
                # ALREADY-parsed streams — no re-parse.  The per-page
                # wire numbers that justify the choice ride the event
                # gate and are pinned by tests/test_fallback_matrix.py.
                _tr = "dba-host"
                if _ev is not None:
                    _wire_ev = expanded
                    _raw_ev = expanded
                    _gate = {"expanded": expanded, "compact": compact}
                    _reason = (
                        f"front coding non-expanding: host assembly "
                        f"ships {expanded}B vs compact wire {compact}B")
                suffix_view = np.frombuffer(values_seg, np.uint8,
                                            n_suffix, spos)
                col = assemble_delta_byte_array(prefix_lens, soffs,
                                                suffix_view)
                if flba_len is not None:
                    rows = np.asarray(col.data)[: non_null * flba_len] \
                        .reshape(non_null, flba_len)
                    ops.append(
                        lambda s, p, _r=rows, _nn=non_null:
                        p["val"].append((_stage_byte_rows(_r), _nn))
                    )
                else:
                    dh = stager.add(col.data)
                    ops.append(
                        lambda s, p, _dh=dh,
                        _o=col.offsets.astype(np.int64),
                        _nb=int(col.data.size):
                        p["bytes"].append((_o, s[_dh], _nb))
                    )
            else:
                from .decode import bucket as _bucket

                _tr = "dba"
                if _ev is not None:
                    _wire_ev = compact
                    _raw_ev = expanded
                    _gate = {"expanded": expanded, "compact": compact}
                    _reason = (f"copy-token expansion: {compact}B wire "
                               f"vs {expanded}B expanded")
                out_cap = _bucket(expanded)
                T = _bucket(2 * non_null)
                te = np.full(T, out_cap, dtype=np.int32)
                ts = np.full(T, -1, dtype=np.int32)
                # copy token i: output [offs[i], offs[i]+p[i]) reads
                # from the previous value's start; literal token i:
                # the suffix bytes
                te[0 : 2 * non_null : 2] = (offs[:-1]
                                            + prefix_lens).astype(np.int32)
                te[1 : 2 * non_null : 2] = offs[1:].astype(np.int32)
                prev_start = np.zeros(non_null, dtype=np.int64)
                prev_start[1:] = offs[:-2]
                ts[0 : 2 * non_null : 2] = prev_start.astype(np.int32)
                ts[1 : 2 * non_null : 2] = (-soffs[:-1] - 1).astype(
                    np.int32)
                lits = np.frombuffer(values_seg, np.uint8, n_suffix,
                                     spos)
                th = stager.add_many([te, ts], pad=False)
                lh = stager.add(lits)
                steps = max(int(np.ceil(np.log2(max(expanded, 2)))), 1)

                def op(s, p, _th=th, _lh=lh, _cap=out_cap, _st=steps,
                       _o=offs, _nb=expanded, _nn=non_null,
                       _fl=flba_len):
                    from .decode import flba_bytes_to_lanes
                    from .snappy import expand_tokens

                    out = expand_tokens(s[_th[0]], s[_th[1]], s[_lh],
                                        _cap, _st)
                    if _fl is not None:
                        p["val"].append(
                            (flba_bytes_to_lanes(out, _nn, _fl), _nn))
                    else:
                        p["bytes"].append((_o, out, _nb))

                ops.append(op)
        elif enc == Encoding.DELTA_BINARY_PACKED and ptype in (
                Type.INT32, Type.INT64):
            _tr = "delta-bp"
            _def_standalone()
            if ptype == Type.INT32:
                build, _ = _stage_delta_plan(
                    plan_delta_i32(values_seg), stager, need_hi=False)
                ops.append(
                    lambda s, p, _b=build, _nn=non_null:
                    p["val"].append(
                        (expand_delta_i32(_b(s))[:_nn], _nn)
                    )
                )
            else:
                build, _ = _stage_delta_plan(
                    plan_delta_i64(values_seg), stager, need_hi=True)
                ops.append(
                    lambda s, p, _b=build, _nn=non_null:
                    p["val"].append(
                        (expand_delta_i64(_b(s))[: _nn * 2], _nn)
                    )
                )
        else:
            # CPU fallback for the remaining encodings; stage the result.
            _tr = "host"
            if _ev is not None:
                _reason = "no device kernel for this encoding"
            _def_standalone()
            if _st is not None:
                _st.pages_host_values += 1
            col = decode_values_cpu(ptype, enc, values_seg, non_null,
                                    node.element.type_length)
            # own the bytes (see the degraded branch above): the
            # decoders may return views of the recyclable arena slab
            if isinstance(col, ByteArrayColumn):
                col = ByteArrayColumn(np.array(col.offsets, copy=True),
                                      np.array(col.data, copy=True))
            elif isinstance(col, np.ndarray):
                col = np.array(col, copy=True)
            if isinstance(col, ByteArrayColumn):
                dh = stager.add(col.data)
                ops.append(
                    lambda s, p, _dh=dh, _o=col.offsets.astype(np.int32),
                    _nb=int(col.data.size):
                    p["bytes"].append((_o, s[_dh], _nb))
                )
            else:
                ops.append(
                    lambda s, p, _c=col, _nn=non_null:
                    p["val"].append((_stage_numpy_fixed(_c, ptype), _nn))
                )

        # one event per data page: the dispatch chain above resolved
        # the transport; every branch reaches this point (dictionary
        # pages `continue` before it and are not data pages).  A
        # branch that forgot its `_tr = ...` label ships as "unknown"
        # rather than a silent null — visible in transport_counts()
        # and the profile table, so the gap can't hide.
        if _ev is not None:
            _ev.page(
                column=_col_path, page=_page_i,
                page_type=("v2" if ptype_page == PageType.DATA_PAGE_V2
                           else "v1"),
                encoding=Encoding(enc).name, codec=codec.name,
                num_values=n, non_null=non_null,
                transport=_tr if _tr is not None else "unknown",
                wire_bytes=_wire_ev, raw_bytes=_raw_ev,
                gate=_gate, reason=_reason,
                plan_s=time.perf_counter() - _t_pg,
            )
        if _record is not None:
            _record.append(_rec_entry)
        _page_i += 1

    if _record is not None and _pc is not None:
        from .plancache import plan_cache_budget

        _pc.store(cache_key, _record, plan_cache_budget())

    type_length = node.element.type_length
    chunk = _chunk_plan(ops, stager, max_def, arena)

    def finish(staged) -> DeviceColumn:
        _cs = current_stats()
        if _cs is not None:
            _cs.dict_bytes_pages += dict_pages
            _cs.dict_bytes_fixed_pages += fixed_pages
        if chunk is not None:
            if _cs is not None:
                _cs.chunks_fused += 1
                _cs.pages_fused += chunk.pages
                _cs.programs_dispatched += 1
            return _chunk_column(chunk, staged, ptype, type_length, total)
        parts = {"val": [], "bytes": [], "rep": [], "def": []}
        programs = 0
        for op in ops:
            op(staged, parts)
            programs += getattr(op, "programs", 1)

        rep, _, n_rep = _merge_parts(parts["rep"])
        dl, _, n_def = _merge_parts(parts["def"])
        programs += n_rep + n_def
        if max_def and dl is not None:
            mask, positions = levels_to_validity(dl, max_def)
            programs += 1
        else:
            mask = positions = None

        bytes_parts = parts["bytes"]
        if bytes_parts:
            if len(bytes_parts) == 1:
                offs_np, data, nbytes = bytes_parts[0]
                offsets = jnp.asarray(offs_np.astype(np.int64))
                _count_programs(_cs, programs)
                return DeviceColumn(ptype, type_length, data, offsets,
                                    mask, positions, rep, dl, total,
                                    n_packed=len(offs_np) - 1,
                                    n_bytes=nbytes)
            # merge per-page byte columns: rebase offsets, concat data
            offsets, n_bytes = _rebased_offsets(
                [(o, nb) for o, _, nb in bytes_parts])
            datas = []
            for _, data, nbytes in bytes_parts:
                data = jnp.asarray(data)
                if data.shape[0] != nbytes:
                    data = data[:nbytes]
                    programs += 1
                datas.append(data)
            _count_programs(_cs, programs + 1)
            return DeviceColumn(ptype, type_length, jnp.concatenate(datas),
                                jnp.asarray(offsets), mask, positions,
                                rep, dl, total,
                                n_packed=offsets.shape[0] - 1,
                                n_bytes=n_bytes)

        data, n_packed, n_val = _merge_parts(parts["val"], lanes=vlanes)
        _count_programs(_cs, programs + n_val)
        return DeviceColumn(ptype, type_length, data, None, mask,
                            positions, rep, dl, total,
                            n_packed=n_packed or 0)

    # the value kinds of a fused chunk's groups, for the dispatch span
    finish.kinds = chunk.kinds if chunk is not None else ""
    return finish


def _defer_levels(ops, stager, kind, scan, host_vals, n, width,
                  max_level=None, cast=jnp.int32):
    """Register a deferred hybrid-stream expansion: scan -> device
    expand, or host-decoded values -> staged transfer.  Levels use the
    default int32 ``cast``; value streams (boolean RLE) pass
    ``cast=None`` to keep the expand's u32.  ``max_level`` enables the
    range validation of ``cpu/levels._check`` (rep levels would otherwise
    silently mis-nest on corrupt streams)."""
    if scan is not None:
        from .hybrid import count_eq_scan, plan_stream_args

        if max_level is not None:
            count_eq_scan(scan, width, max_level, validate_max=True)
        args, cnt, nbp, sg = plan_stream_args(scan, n, width)
        hs = stager.add_many(args, pad=False)

        def op(s, p, _hs=hs, _cnt=cnt, _nbp=nbp, _n=n, _w=width, _sg=sg):
            from .decode import expand_tbl

            dev = expand_tbl(
                s[_hs[0]], s[_hs[1]], _cnt, _w, _nbp, single=_sg)
            if cast is not None:
                dev = dev.astype(cast)
            p[kind].append((dev, _n))

        ops.append(op)
    elif host_vals is not None:
        hh = stager.add(np.asarray(host_vals, dtype=np.int32))
        ops.append(lambda s, p, _h=hh, _n=n: p[kind].append((s[_h], _n)))


class _PageOp:
    """One data page's device work, recorded at plan time: the page's
    def-level stream and its value kernel, by stager handle, with the
    kernel's bucketed statics and the page's exact counts.

    ``lev``: ``((bp, table) handles, cnt, nbp, single)`` of the def
    levels (width ``dw``), or None.  ``kind``, with its ``val`` handles
    and ``statics``:

    - ``"dict"``: a fixed-width dictionary gather; the indices' ``(bp,
      table)`` and ``(icnt, iw, inbp, isingle)``;
    - ``"dict_bytes"``: a BYTE_ARRAY dictionary gather; the same, plus
      the byte cap and the dictionary's one entry length (0 where the
      lengths differ);
    - ``"plain"``: PLAIN fixed-width words staged raw, ``(words,)``;
    - ``"plain_bytes"``: PLAIN BYTE_ARRAY bytes staged raw,
      ``(bytes,)``;
    - ``"planes"``: PLAIN fixed-width under the byte-plane transport;
      its six staged arrays and ``(spec, stride)``
      (``decode.planes_to_words``);
    - ``"delta"``: PLAIN INT32/INT64 under the delta-lane transport;
      its words and min_delta lanes, ``(n_vals, w, wide)``, and the
      first value's (lo, hi) words in ``aux``;
    - ``"tokens"``: PLAIN fixed-width under the snappy-token transport,
      which only the per-page path decodes.

    ``shared``: the dictionary's handles; ``offsets`` and ``nbytes``: a
    byte-array page's host output offsets and byte count; ``words``:
    the transport's own per-page expansion.

    Called as ``op(staged, parts)`` it enqueues the page's own kernels
    (``programs`` of them); ``finish()`` instead hands a chunk whose
    pages are all such ops to one chunk program (``_chunk_plan``,
    ``_chunk_column``)."""

    __slots__ = ("lev", "dw", "kind", "val", "statics", "shared",
                 "lanes", "n", "nn", "offsets", "nbytes", "aux", "words")

    def __init__(self, lev, dw, kind, val, statics, shared, lanes, n, nn,
                 offsets=None, nbytes=0, aux=(0, 0), words=None):
        self.lev = lev
        self.dw = dw
        self.kind = kind
        self.val = val
        self.statics = statics
        self.shared = shared
        self.lanes = lanes
        self.n = n
        self.nn = nn
        self.offsets = offsets
        self.nbytes = nbytes
        self.aux = aux
        self.words = words

    @property
    def programs(self) -> int:
        if self.kind == "dict_bytes" and self.lev:
            return 3
        return 2 if self.kind == "plain_bytes" and self.lev else 1

    def __call__(self, s, p):
        from . import decode as dk

        lev, nn = self.lev, self.nn
        v = [s[h] for h in self.val]
        if self.offsets is not None:
            if lev is not None:
                dl = dk.expand_tbl(s[lev[0][0]], s[lev[0][1]], lev[1],
                                   self.dw, lev[2], single=lev[3])
                p["def"].append((dl.astype(jnp.int32), self.n))
            if self.kind == "plain_bytes":
                data = v[0]
            else:
                icnt, iw, inbp, isingle, cap, fixed = self.statics
                data = dk.page_dict_bytes_tbl(
                    s[self.shared[0]], s[self.shared[1]], v[0], v[1],
                    np.int32(nn), icnt, iw, inbp, cap, isingle=isingle,
                    width=fixed)
            p["bytes"].append((self.offsets, data, self.nbytes))
            return
        if self.kind == "dict":
            icnt, iw, inbp, isingle = self.statics
            if lev is None:
                vals = dk.page_dict_fixed_tbl(
                    s[self.shared[0]], v[0], v[1], icnt, iw, inbp,
                    lanes=self.lanes, isingle=isingle)
            else:
                vals, dl = dk.page_dict_fixed_levels_tbl(
                    s[self.shared[0]], s[lev[0][0]], s[lev[0][1]],
                    v[0], v[1], lev[1], self.dw, lev[2], icnt, iw, inbp,
                    lanes=self.lanes, dsingle=lev[3], isingle=isingle)
        else:
            words = v[0] if self.kind == "plain" else self.words(s)
            if lev is None:
                vals = plain_fixed_to_lanes(words, nn, self.lanes)
            else:
                vals, dl = dk.page_plain_fixed_levels_tbl(
                    words, s[lev[0][0]], s[lev[0][1]], nn, self.lanes,
                    lev[1], self.dw, lev[2], dsingle=lev[3])
        if lev is not None:
            p["def"].append((dl, self.n))
        p["val"].append((vals, nn))


# A chunk program's size and compile time grow with its groups; a
# chunk whose pages fall into more groups than this share few shapes,
# and keeps the per-page path.
_MAX_CHUNK_GROUPS = 16


def _page_slots(n: int) -> int:
    """Slots for a group of ``n`` pages: a power of two up to 8, then a
    multiple of 8 (a padding slot costs a page's device work)."""
    return 1 << max(n - 1, 0).bit_length() if n <= 8 else -(-n // 8) * 8


class _ChunkPlan(NamedTuple):
    """A chunk's pages grouped for one ``decode.chunk_program``: each
    group's stacked buffers' handles, one per role (``_Stager.stack``),
    the program's ``sig`` and ``meta`` rows, the value ``kinds`` of its
    groups, and a byte-array chunk's host offsets and byte count."""

    shared: tuple
    lev: tuple
    val: tuple
    sig: tuple
    meta: np.ndarray
    kinds: str
    pages: int
    n_packed: int
    offsets: np.ndarray | None
    n_bytes: int | None


def _chunk_plan(ops, stager, max_def, arena):
    """Group a chunk whose pages are all :class:`_PageOp` for ONE
    program, from the shapes the stager will give their arrays; None
    for a single page, a snappy-token page, or where the pages fall
    into more than ``_MAX_CHUNK_GROUPS`` groups of equal statics and
    shapes.

    Each group's page count pads to a bucket (``_page_slots``), and the
    running offsets and counts go in as runtime data, so the program's
    key holds no exact count.  Each group's pages are stacked, role by
    role, into buffers from ``arena`` (``_Stager.stack``), which ship
    in place of the page arrays.  Byte-array offsets are built here, on
    the host."""
    from .decode import bucket

    if len(ops) < 2 or not all(type(op) is _PageOp for op in ops):
        return None
    if any((op.lev is not None) != bool(max_def) or op.kind == "tokens"
           for op in ops):
        return None
    lanes = ops[0].lanes or 1
    levs, vals = {}, {}
    lev_off = val_off = 0
    for op in ops:
        if op.lev is not None:
            hs, cnt, nbp, single = op.lev
            statics = (cnt, op.dw, nbp, single)
            shapes = tuple(_staged_shape(stager, h) for h in hs)
            levs.setdefault(statics + shapes, (statics, []))[1].append(
                (hs, (lev_off, op.n, 0, 0, 0)))
        lev_off += op.n
        statics = (op.kind, *op.statics)
        shapes = tuple(_staged_shape(stager, h) for h in op.val)
        width = op.nbytes if op.offsets is not None else op.nn * lanes
        vals.setdefault(statics + shapes, (statics, []))[1].append(
            (op.val, (val_off, width, op.nn, *op.aux)))
        val_off += width
    if len(levs) + len(vals) > _MAX_CHUNK_GROUPS:
        return None

    rows = []

    def padded(groups):
        # sorted, so the key is the same whatever the pages' order
        sig, handles = [], []
        for key in sorted(groups):
            statics, pages = groups[key]
            slots = _page_slots(len(pages))
            handles.append(stager.stack([h for h, _ in pages], slots,
                                        arena))
            rows.extend([r for _, r in pages]
                        + [(0,) * 5] * (slots - len(pages)))
            sig.append(statics)
        return tuple(sig), tuple(handles)

    lev_sig, lev_handles = padded(levs)
    val_sig, val_handles = padded(vals)
    offsets = n_bytes = None
    if ops[0].offsets is not None:
        offsets, n_bytes = _rebased_offsets(
            [(op.offsets, op.nbytes) for op in ops])
    return _ChunkPlan(
        shared=next((op.shared for op in ops if op.shared), ()),
        lev=lev_handles, val=val_handles,
        sig=(lev_sig, val_sig, bucket(lev_off) if levs else 0,
             bucket(val_off), lanes, max_def),
        # aux words are unsigned: int32 by their bits
        meta=np.asarray(rows, np.int64).astype(np.uint32).view(np.int32),
        kinds=",".join(sorted({kind for kind, *_ in val_sig})),
        pages=len(ops), n_packed=sum(op.nn for op in ops),
        offsets=offsets, n_bytes=n_bytes)


def _chunk_column(plan, staged, ptype, type_length, total):
    """Decode a planned chunk (:func:`_chunk_plan`) in ONE program
    (``decode.chunk_program``)."""
    from .decode import chunk_program

    def arrays(groups):
        return tuple(tuple(staged[h] for h in group) for group in groups)

    dl, data, mask, positions = chunk_program(
        tuple(staged[h] for h in plan.shared), arrays(plan.lev),
        arrays(plan.val), plan.meta, sig=plan.sig)
    if plan.offsets is not None:
        return DeviceColumn(ptype, type_length, data,
                            jnp.asarray(plan.offsets), mask, positions,
                            None, dl, total, n_packed=plan.n_packed,
                            n_bytes=plan.n_bytes)
    return DeviceColumn(ptype, type_length, data, None, mask, positions,
                        None, dl, total, n_packed=plan.n_packed)


def _merge_parts(parts, lanes: int = 1):
    """Merge [(padded device array, logical n)] -> (array, total n,
    programs enqueued).

    Single-part chunks keep their padding (consumers slice lazily);
    multi-part chunks slice then concatenate.  ``lanes`` scales the
    slice for flat value buffers (n u32 words per value)."""
    if not parts:
        return None, 0, 0
    if len(parts) == 1:
        return (*parts[0], 0)
    k = lanes or 1
    arrs = [a if a.shape[0] == m * k else a[: m * k] for a, m in parts]
    slices = sum(a.shape[0] != m * k for a, m in parts)
    return (jnp.concatenate(arrs), sum(m for _, m in parts),
            slices + 1)


def _count_programs(st, n: int) -> None:
    if st is not None:
        st.programs_dispatched += n


def _rebased_offsets(pages) -> tuple[np.ndarray, int]:
    """Per-page ``(offsets, nbytes)`` of a byte-array chunk -> (the
    chunk's int64 offsets, its byte total)."""
    out = [np.zeros(1, dtype=np.int64)]
    base = 0
    for offs, nbytes in pages:
        out.append(np.asarray(offs[1:], dtype=np.int64) + base)
        base += nbytes
    return np.concatenate(out), base


def stage_chunkdata(cd, node) -> DeviceColumn:
    """Stage one host-decoded :class:`~tpuparquet.io.chunk.ChunkData`
    as a :class:`DeviceColumn` — the transfer step of the
    late-materialization path: the predicate already ran on host, so
    only the SURVIVING rows' bytes cross the link.  Buffer layout
    matches the fused-kernel path exactly (flat u32 lanes / byte-array
    offsets+data), so downstream consumers (``gather_column`` et al.)
    cannot tell the difference."""
    ptype = Type(node.element.type)
    dl = np.asarray(cd.def_levels, dtype=np.int32)
    rep = np.asarray(cd.rep_levels, dtype=np.int32)
    num = dl.shape[0]
    max_def = node.max_def_level
    mask_h = pos_h = None
    if max_def:
        valid = dl == max_def
        if not valid.all():
            pidx = np.cumsum(valid, dtype=np.int64) - 1
            mask_h = valid
            pos_h = np.maximum(pidx, 0).astype(np.int32)
    vals = cd.values
    offsets = None
    n_bytes = None
    if isinstance(vals, ByteArrayColumn):
        offs = np.asarray(vals.offsets)
        n_bytes = int(offs[-1]) if offs.size else 0
        odt = np.int32 if n_bytes <= np.iinfo(np.int32).max else np.int64
        offsets = jnp.asarray(offs.astype(odt))
        data = jnp.asarray(np.asarray(vals.data, dtype=np.uint8))
        n_packed = max(offs.size - 1, 0)
    else:
        arr = np.asarray(vals)
        n_packed = arr.shape[0]
        if ptype == Type.BOOLEAN:
            flat = arr.astype(np.uint32)
        elif ptype == Type.FIXED_LEN_BYTE_ARRAY:
            flat = _stage_byte_rows_np(arr)
        elif ptype == Type.INT96:
            flat = np.ascontiguousarray(arr, dtype="<u4").reshape(-1)
        else:
            flat = np.ascontiguousarray(arr).view("<u4").reshape(-1)
        data = jnp.asarray(flat)
    return DeviceColumn(
        ptype, node.element.type_length, data, offsets,
        None if mask_h is None else jnp.asarray(mask_h),
        None if pos_h is None else jnp.asarray(pos_h),
        jnp.asarray(rep) if node.max_rep_level else None,
        jnp.asarray(dl) if max_def else None,
        num, n_packed=n_packed, n_bytes=n_bytes)


def read_row_group_device(reader, rg_index: int, filter=None,
                          verdict=None) -> dict[str, DeviceColumn]:
    """Decode the selected columns of one row group onto the device.

    The device-path sibling of ``FileReader.read_row_group_arrays``: same
    selection semantics, device-resident results.  The one-unit driver
    of the unit pipeline: :func:`_submit_unit` plans each column chunk
    as a task on a pool of ``_plan_threads()`` workers — so a SINGLE
    large row group (the common TPU-input shape) fans its columns across
    the pool — and :func:`_finish_unit` ships all columns' plan tables
    and page words in one batched wave transfer (``_put_all``) and
    dispatches and drains each column's chunk program (or page kernels)
    before returning.  It opens no ``unit`` span: its callers own the
    unit (``resilient_unit_scan`` opens one around it).  For
    multi-row-group reads prefer :func:`read_row_groups_device`, whose
    window (:func:`pipelined_reads`) additionally overlaps row group
    N+1's host planning with N's transfer.

    ``filter`` (a :mod:`tpuparquet.filter` expression, optionally with
    a precomputed ``verdict``) switches to the late-materialized
    pushdown path: filter columns decode on host first, pruned pages
    are never decompressed, and only surviving rows transfer —
    bit-exact vs decode-everything-then-post-filter."""
    from concurrent.futures import ThreadPoolExecutor

    from ..stats import current_stats

    _cs = current_stats()
    if _cs is not None:
        _cs.row_groups += 1
    if filter is None:
        # remote sources: batch-prefetch the row group's chunk ranges
        # (coalesced, parallel) so the column planners hit the disk
        # tier instead of issuing one round trip each.  No-op for
        # local/in-memory sources.
        pf = getattr(reader, "prefetch_chunks", None)
        if pf is not None:
            pf(reader.meta.row_groups[rg_index])
    n_workers = _plan_threads()
    with ThreadPoolExecutor(max_workers=n_workers) as ex:
        futs, arenas = _submit_unit(ex, n_workers, reader, rg_index, _cs,
                                    filter=filter, verdict=verdict,
                                    tctx=_trace.current_ctx())
        return _finish_unit(reader, rg_index, futs, arenas, _cs,
                            filtered=filter is not None)


def read_row_group_device_resilient(reader, rg_index: int,
                                    retries: int | None = None,
                                    sleep=time.sleep,
                                    dispatch_deadline: float | None = None,
                                    filter=None, verdict=None):
    """:func:`read_row_group_device` with the device-failure policy:
    retry device dispatch with bounded exponential backoff, then
    degrade to the bit-exact CPU decode (:func:`cpu_fallback_values`)
    for this unit.  Corruption errors propagate unchanged — they are
    permanent and belong to the quarantine layer, not retry.

    ``dispatch_deadline`` (None = env ``TPQ_DISPATCH_DEADLINE_S``,
    off) bounds EACH attempt's wall: an attempt that runs past it —
    a wedged accelerator that neither fails nor finishes — is
    abandoned and counted as a
    :class:`~tpuparquet.errors.DispatchDeadlineError`, which takes
    exactly the retry → CPU-fallback ladder a failing dispatch does.

    Counts ``DecodeStats.dispatch_retries`` per retry and
    ``units_degraded`` when the CPU fallback engages; the fallback is
    also recorded as an obs fault event.  The retry schedule shares
    the transient-I/O knobs (``TPQ_IO_RETRIES`` etc.).

    Counter exactness: each attempt runs under a scratch collector
    that merges into the caller's only on SUCCESS — a unit that
    retried N times still counts its pages/values/bytes exactly once
    and leaves no phantom page events from aborted attempts.  Failed
    attempts contribute only their fault-layer observability
    (``faults_injected``/``crc_mismatches``/``io_retries`` and fault
    events)."""
    from ..deadline import call_with_deadline, dispatch_deadline_default
    from ..errors import DispatchDeadlineError
    from ..stats import current_stats, merge_worker_stats, worker_stats

    if dispatch_deadline is None:
        dispatch_deadline = dispatch_deadline_default()
    # the deadline wrapper executes on a disposable worker thread, but
    # both the degraded-decode flag and jax's default device are
    # THREAD-LOCAL — the work callable re-enters them itself
    _dev = getattr(jax.config, "jax_default_device", None)

    def work(degraded: bool):
        dev_ctx = (jax.default_device(_dev) if _dev is not None
                   else contextlib.nullcontext())
        deg_ctx = cpu_fallback_values() if degraded \
            else contextlib.nullcontext()
        with dev_ctx, deg_ctx:
            return read_row_group_device(reader, rg_index,
                                         filter=filter, verdict=verdict)

    def attempt_bare(degraded):
        st = current_stats()
        if st is None:
            return work(degraded)
        with worker_stats(like=st) as ws:
            try:
                out = work(degraded)
            except BaseException:
                merge_worker_stats(st, ws, failed=True)
                raise
        merge_worker_stats(st, ws, failed=False)
        return out

    def attempt_once(degraded=False):
        # the deadline wrapper already runs the attempt under a worker
        # collector with the same merge policy; only the bare attempt
        # needs its own.  The DEGRADED attempt is never bounded: the
        # dispatch budget is sized for device-dispatch latency, and
        # the CPU fallback is the last-resort path that must be
        # allowed to finish (the unit-level deadline still bounds it
        # in a quarantining scan).
        if degraded or not dispatch_deadline:
            return attempt_bare(degraded)
        return call_with_deadline(
            lambda: work(degraded),
            dispatch_deadline, site="kernels.device.unit_dispatch",
            error=DispatchDeadlineError,
            file=getattr(reader, "name", None), row_group=rg_index)

    last = None
    delays = backoff_delays(retries)
    for attempt in range(len(delays) + 1):
        try:
            return attempt_once()
        except DeviceDispatchError as e:
            last = e
        except RuntimeError as e:
            # a real accelerator failure surfaces as a JAX/XLA
            # RuntimeError; treat it exactly like a dispatch fault
            if isinstance(e, (NotImplementedError, RecursionError)):
                raise
            last = e
        if attempt < len(delays):
            if _flightrec._active is not None:
                _flightrec.flight(
                    "dispatch_retry",
                    site="kernels.device.unit_dispatch",
                    row_group=rg_index, error=type(last).__name__)
            if _trace._active is not None:
                _trace.emit_span(
                    "dispatch_retry", time.perf_counter(), 0.0,
                    status="error", row_group=rg_index,
                    error=type(last).__name__)
            st = current_stats()
            if st is not None:
                st.dispatch_retries += 1
            sleep(delays[attempt])
    # retries exhausted: degrade this unit to the CPU oracle decode
    # (cold site — the bare emit_span, like the bare flight above)
    flight("degraded-to-host", site="kernels.device.unit_dispatch",
           row_group=rg_index, error=type(last).__name__,
           message=str(last))
    emit_span("degraded_to_host", time.perf_counter(), 0.0,
              status="error", row_group=rg_index,
              error=type(last).__name__)
    st = current_stats()
    if st is not None:
        st.units_degraded += 1
        if st.events is not None:
            st.events.fault(
                site="kernels.device.unit_dispatch",
                kind="degraded-to-host", row_group=rg_index,
                error=type(last).__name__, message=str(last))
    return attempt_once(degraded=True)


def _drop_range_caches(reader) -> None:
    """Corruption hook for remote sources: the bad bytes may have been
    SERVED from the range cache, so evict both tiers for this source —
    the resilient retry then refetches from the store, not the poison.
    No-op for local readers."""
    src = getattr(reader, "_source", None)
    if src is None:
        return
    from ..io.rangecache import invalidate_source_caches

    invalidate_source_caches(src.uri)


def _plan_one_column(reader, rg_index: int, path, node, cm,
                     arena: HostArena, degraded: bool = False):
    """Plan ONE column chunk into its own stager — the unit of work the
    column-parallel planner schedules.  Returns ``(path, finish,
    stager)``; plan wall and the plan span (with its plan-cache verdict)
    are recorded on the calling thread's collector.

    ``degraded`` re-enters :func:`cpu_fallback_values` — the flag is
    thread-local, so a pool worker must restore the submitting thread's
    degradation state itself."""
    from .plancache import plan_cache

    deg_ctx = (cpu_fallback_values() if degraded
               else contextlib.nullcontext())
    # the plan span is the ambient context while it runs, so the chunk
    # read it triggers nests under it as a child span
    with _trace.stage("plan", "plan_s", cpu="plan_cpu_s",
                      column=path) as sp:
        stager = _Stager()
        # fingerprint only when the cache is on: computing it lazily
        # costs a footer re-read on file-backed sources, which
        # cache-off scans must never pay
        fingerprint = (getattr(reader, "plan_fingerprint", None)
                       if plan_cache() is not None else None)
        cache_key = (None if fingerprint is None
                     else (fingerprint, rg_index, path))
        cache_state = []
        try:
            with deg_ctx:
                blob, start = reader.chunk_blob(cm, path)
                finish = plan_chunk_device(
                    memoryview(blob), cm, node, start, stager, arena,
                    verify_crc=getattr(reader, "_verify_crc", None),
                    cache_key=cache_key, cache_state=cache_state)
        except ScanError as e:
            if isinstance(e, (CorruptPageError, CorruptChunkError)):
                # the bytes no longer match the footer: cached plans
                # for this file identity are stale
                from .plancache import invalidate_fingerprint

                invalidate_fingerprint(fingerprint)
                _drop_range_caches(reader)
            raise e.annotate(column=path,
                             file=getattr(reader, "name", None))
        except ValueError as e:
            # codec-layer domain errors become taxonomy errors with
            # coordinates; raw crash types propagate as the bugs they
            # are (the crash-corpus clean-failure contract)
            from .plancache import invalidate_fingerprint

            invalidate_fingerprint(fingerprint)
            _drop_range_caches(reader)
            raise CorruptChunkError(
                str(e), column=path,
                file=getattr(reader, "name", None)) from e
        sp.note(cache=cache_state[0] if cache_state else "off")
    return path, finish, stager


def _plan_column_task(reader, rg_index: int, path, node, cm,
                      arena: HostArena, like, degraded: bool,
                      tctx=None, usp=None):
    """Pool-worker wrapper around :func:`_plan_one_column`: fresh
    per-thread collector (``worker_stats(like=)`` — the coordinator
    merges after joining, the exactness discipline ``stats.py``
    documents) and the submitting thread's degradation state.
    ``tctx`` re-enters the submitting site's trace context (the
    unit's span) so this column's plan/read spans parent causally
    under their unit regardless of which pool thread ran them;
    ``usp`` is that unit's OPEN span handle — the first task to run
    stamps its execution start (``setdefault`` is GIL-atomic), so the
    unit span measures work, not submission-queue wait."""
    from ..stats import worker_stats

    if usp is not None:
        usp.setdefault("t0_exec", time.perf_counter())
    with _trace.adopt(tctx), worker_stats(like=like) as ws:
        entry = _plan_one_column(reader, rg_index, path, node, cm,
                                 arena, degraded=degraded)
    return entry, ws


def _plan_filtered_task(reader, rg_index: int, filt, verdict, like,
                        degraded: bool, tctx=None, usp=None):
    """The filtered unit's one plan task: the late-materialized host
    decode (:func:`~tpuparquet.filter.read_row_group_filtered` — filter
    columns first, pruned pages skipped, survivors gathered) under the
    same per-thread collector, degradation state and trace context
    discipline as :func:`_plan_column_task`.  Returns ``(chunks, ws)``:
    ``chunks`` maps each selected column to its surviving rows'
    :class:`~tpuparquet.io.chunk.ChunkData`."""
    from ..filter import read_row_group_filtered
    from ..stats import worker_stats

    deg_ctx = (cpu_fallback_values() if degraded
               else contextlib.nullcontext())
    if usp is not None:
        usp.setdefault("t0_exec", time.perf_counter())
    with _trace.adopt(tctx), worker_stats(like=like) as ws, deg_ctx, \
            _trace.stage("plan", "plan_s", cpu="plan_cpu_s",
                         filtered=True):
        chunks, _rows = read_row_group_filtered(reader, rg_index, filt,
                                                verdict)
    return chunks, ws


def _submit_unit(ex, n_workers: int, reader, rg_index: int, like, *,
                 filter=None, verdict=None, tctx=None, usp=None):
    """Submit one unit's plan work to the pool ``ex`` (of ``n_workers``
    workers); returns ``(futures, leased arenas)`` for
    :func:`_finish_unit`.  Unfiltered, one :func:`_plan_column_task`
    per selected column; with ``filter``, one
    :func:`_plan_filtered_task` for the whole unit.  ``like`` is the
    collector the tasks' counts merge into, ``tctx`` the trace context
    they re-enter and ``usp`` the unit's open span, if any."""
    degraded = _host_values_only()  # thread-local: workers re-enter it
    if filter is not None:
        return [ex.submit(_plan_filtered_task, reader, rg_index, filter,
                          verdict, like, degraded, tctx, usp)], []
    cols = reader.selected_chunks(reader.meta.row_groups[rg_index])
    futs, ars = [], []
    # single-worker pools run a unit's column tasks sequentially,
    # so one shared arena per unit keeps the cross-column slab
    # reuse; real parallelism needs a lease per racing task
    shared = lease_arena() if n_workers == 1 and cols else None
    if shared is not None:
        ars.append(shared)
    for path, node, cm in cols:
        a = shared
        if a is None:
            a = lease_arena()
            ars.append(a)
        futs.append(ex.submit(_plan_column_task, reader, rg_index, path,
                              node, cm, a, like, degraded, tctx, usp))
    return futs, ars


def _join_plans(futs, st, parent=None):
    """The consumer's wait on one unit's plan tasks, in column order
    (the ``plan_wait`` stage; ``parent`` is the unit's span ctx): each
    worker's collector merges into ``st``.  Returns ``(planned, the
    first error or None)``; a failed task's counts are dropped."""
    planned, err = [], None
    with _trace.stage("plan_wait", "plan_wait_s", parent=parent):
        for f in futs:
            try:
                entry, ws = f.result()
            except BaseException as e:
                err = err if err is not None else e
                continue
            if st is not None:
                st.merge_from(ws)
            planned.append(entry)
    return planned, err


def _finish_unit(reader, rg_index: int, futs, arenas, like, *,
                 filtered: bool = False, usp=None):
    """Join one unit's plan tasks (from :func:`_submit_unit`, in column
    order) and put the unit on the device.

    Unfiltered, ``planned`` is ``[(path, finish, stager)]`` from
    :func:`_plan_one_column`: all columns' arrays ship in ONE shared
    wave sequence (``_put_all``, in column order — wave composition is
    independent of plan-thread count), then each column's device work
    is enqueued (one ``dispatch`` stage per column: one chunk program,
    or the per-page programs of a chunk that takes the per-page path)
    and the unit's buffers drained (``drain``).  Filtered, the surviving
    rows are staged by :func:`stage_chunkdata` and drained inside the
    ``transfer`` stage.  The unit's arenas recycle once its buffers have
    drained.  Any ``ScanError`` leaves annotated with ``row_group=``.
    ``usp`` is the unit's open span: its children parent under it, and
    it starts when its first plan task ran.

    The chunk programs trace under this frame on a unit's first decode.
    It calls ``finish()`` itself rather than through a helper: a helper
    frame between the window and the trace made set-up 8-9 s longer in
    both benchmark cells on a TPU v5e."""
    parent = _trace.ctx_of(usp)
    try:
        planned, err = _join_plans(futs, like, parent=parent)
        if usp is not None and "t0_exec" in usp:
            # the unit span starts when its first plan task RAN
            # (stamped by the worker; all futures joined above), not
            # when the window submitted it — queue wait belongs to the
            # scan's driver time, not the unit
            usp["t0"] = usp["t0_exec"]
        if err is not None:
            raise err
        with _trace.adopt(parent):
            if filtered:
                chunks = planned[0]
                with _trace.stage("transfer", "transfer_s",
                                  columns=len(chunks)):
                    out = {path: stage_chunkdata(
                               cd, reader.schema.leaf(path))
                           for path, cd in chunks.items()}
                    jax.block_until_ready(
                        [x for c in out.values() for x in c._buffers()])
            else:
                if not _host_values_only():
                    # unit-level simulated device failures (harness
                    # sites); skipped on the degraded re-plan, whose
                    # remaining device work is bare buffer staging.
                    # The hang site simulates a wedged accelerator:
                    # under a dispatch deadline it becomes a
                    # DispatchDeadlineError instead of a stalled scan.
                    fault_point("kernels.device.unit_dispatch")
                    fault_point("kernels.device.hang")
                with _trace.stage("transfer", "transfer_s",
                                  columns=len(planned)):
                    staged_lists = _put_all(
                        [stager for _, _, stager in planned])
                out = {}
                for (path, finish, _), staged in zip(planned,
                                                     staged_lists):
                    with _trace.stage("dispatch", "dispatch_s",
                                      column=path,
                                      kinds=getattr(finish, "kinds", "")):
                        out[path] = finish(staged)
                # Drain the unit before returning: one batched
                # block_until_ready on its buffers, which also fences
                # the finish()-time transfers sourced from arena slabs
                # before the slabs recycle.  With one program per chunk
                # the enqueue is short, so the drain carries the device
                # time of the unit's chunk programs, which start only
                # once the unit's transfer is done.
                with _trace.stage("drain", "drain_s", columns=len(out)):
                    jax.block_until_ready(
                        [x for c in out.values() for x in c._buffers()])
    except ScanError as e:
        # arenas are dropped, not recycled: in-flight transfers (or
        # abandoned plan tasks) may still read their slabs
        raise e.annotate(row_group=rg_index)
    for a in arenas:
        return_arena(a)
    return out


def _plan_threads() -> int:
    """Plan-phase worker count (column-parallel planner).

    On a good link the pipeline is PLAN-bound (50M taxi: plan 1.1-2.4 s
    vs ~9 ms of transfer at PCIe rates), and the plan phase is
    GIL-releasing C/numpy whose file reads are already lock-protected
    (``FileReader._io_lock``), so planning many columns concurrently is
    the direct lever on the e2e wall.  Default: one worker per USABLE
    core (affinity/cpuset-aware — a 1-core container gets exactly one
    planner and the exact serial-plan behavior; this is also the
    oversubscription clamp).  ``TPQ_PLAN_THREADS`` is authoritative
    when set.  The writer's encode pool (``TPQ_WRITE_THREADS``)
    defaults to the same core count: a process that scans and writes
    CONCURRENTLY should split the budget explicitly (e.g.
    ``TPQ_PLAN_THREADS=N/2 TPQ_WRITE_THREADS=N/2``) — the library
    never runs both pools for the same operation, so sequential
    read-then-write workloads need no tuning.  Stats stay exact at any
    worker count: each column plan runs under a per-thread collector
    (``stats.worker_stats``) merged on the coordinating thread when its
    future is consumed.

    Under an active serve arbiter (``tpuparquet.serve``) a thread
    bound to a tenant sizes from that tenant's share of the GLOBAL
    worker budget instead — consulted per call, so adaptive
    rebalances take effect at the next unit boundary; unbound threads
    and arbiter-less processes keep the legacy behavior exactly."""
    from ..serve import arbiter as _arbiter

    share = _arbiter.plan_budget()
    if share is not None:
        return share
    _arbiter.warn_if_oversubscribed()
    v = os.environ.get("TPQ_PLAN_THREADS")
    if v is not None:
        try:
            return max(int(v), 1)
        except ValueError:
            pass  # malformed override falls back to the default
    return _usable_cpus()


def _usable_cpus() -> int:
    """CPUs this process may actually run on: honors cpuset/affinity
    restrictions that ``os.cpu_count()`` ignores (a 16-core box pinned
    to one CPU must not spin up 4 contending planners)."""
    try:
        return len(os.sched_getaffinity(0)) or 1
    except (AttributeError, OSError):
        return os.cpu_count() or 1


def pipelined_reads(readers, units, device_for=None, start: int = 0, *,
                    filter=None, verdicts=None):
    """Yield ``(unit_index, {path: DeviceColumn})`` for
    ``units[start:]`` (each a ``(reader_index, rg_index)`` pair),
    overlapping host planning with device transfer.  The one unit
    window: under ``read_row_groups_device`` and the fail-fast scan
    drivers in ``shard/``, filtered or not.

    One shared pool of ``_plan_threads()`` workers runs each unit's plan
    tasks (:func:`_submit_unit`: file reads, block decompression,
    run-table scans — all GIL-releasing C/numpy work) while the main
    thread transfers and dispatches unit N on its assigned device
    (:func:`_finish_unit`; ``device_for(unit_index)``, default device
    when None; plans are device-independent, so the target only matters
    at transfer time).  Unfiltered units plan one task per column, so
    workers steal across units: a single wide row group fans out, and a
    fast unit's idle workers pull the next unit's columns.  The
    submission window is derived from in-flight TASKS (at least
    ``n_workers + 1`` tasks and one whole unit ahead), and every column
    task leases its own arena from the shared pool
    (``kernels/arena.py``) so racing planners never share a slab; leases
    recycle only after the unit's transfers drain.

    ``filter`` (with ``verdicts`` optionally mapping ``(reader_index,
    rg_index)`` to a precomputed
    :class:`~tpuparquet.filter.PruneVerdict`) decodes each unit
    late-materialized as ONE task — filter columns first, pruned pages
    skipped — and stages only the surviving rows; one task per unit
    keeps ``n_workers + 1`` units in flight.  Results are identical to
    a serial :func:`read_row_group_device` loop at any thread count."""
    from concurrent.futures import ThreadPoolExecutor

    from ..stats import current_stats

    order = list(range(start, len(units)))
    if not order:
        return
    _cs = current_stats()
    n_workers = _plan_threads()

    ex = ThreadPoolExecutor(max_workers=n_workers)
    inflight = {}    # unit k -> (plan futures, leased arenas)
    unit_spans = {}  # unit k -> open trace span handle (or None)
    state = {"next_j": 0, "tasks": 0}

    def submit_unit():
        k = order[state["next_j"]]
        state["next_j"] += 1
        ri, rgi = units[k]
        # unit span: opened WITHOUT pushing the ambient context (its
        # open/close straddles generator yields) — the plan tasks and
        # the finish step re-enter it explicitly, so a unit's spans
        # connect under it even though planning overlaps other units
        usp = None
        if _trace._active is not None:
            usp = _trace.open_span("unit", push=False, unit=k,
                                   file=ri, row_group=rgi)
        unit_spans[k] = usp
        inflight[k] = _submit_unit(
            ex, n_workers, readers[ri], rgi, _cs, filter=filter,
            verdict=None if verdicts is None else verdicts.get((ri, rgi)),
            tctx=_trace.ctx_of(usp), usp=usp)
        state["tasks"] += len(inflight[k][0])

    def fill_window(min_units: int):
        while state["next_j"] < len(order) and (
                len(inflight) < min_units
                or state["tasks"] < n_workers + 1):
            submit_unit()

    try:
        fill_window(2)  # current unit + at least one planned ahead
        for k in order:
            futs, arenas = inflight.pop(k)
            state["tasks"] -= len(futs)
            usp = unit_spans.pop(k, None)
            ri, rgi = units[k]
            dev_ctx = (jax.default_device(device_for(k))
                       if device_for is not None
                       else contextlib.nullcontext())
            try:
                with dev_ctx:  # drains; arenas free
                    out = _finish_unit(readers[ri], rgi, futs, arenas,
                                       _cs, filtered=filter is not None,
                                       usp=usp)
            except BaseException as e:
                _trace.close_span(usp, status="error",
                                  error=type(e).__name__)
                raise
            _trace.close_span(usp)
            fill_window(1)
            if _cs is not None:
                _cs.row_groups += 1
            yield k, out
    finally:
        # On error/early close just drop the leased arenas (never
        # recycle slabs that in-flight transfers might still read); the
        # workers are joined so no new borrows can race interpreter
        # shutdown.  Trimming releases the scan's slab high-water mark
        # back to the allocator (keep=2: the resilient per-unit path
        # still reuses a couple of warm arenas between scans).
        ex.shutdown(wait=True)
        # pre-submitted units the consumer never drained: their plan
        # spans were already emitted (the workers ran), so emit the
        # unit spans as cancelled rather than orphaning the children
        for usp in unit_spans.values():
            _trace.close_span(usp, status="cancelled")
        unit_spans.clear()
        trim_arena_pool(keep=2)


def read_row_groups_device(reader, rg_indices=None, filter=None,
                           out_sharding=None, gather_to=None):
    """Yield ``(rg_index, {path: DeviceColumn})`` for several row groups
    through the one unit window, :func:`pipelined_reads`, which
    overlaps host planning with device transfer.  Results are identical
    to calling :func:`read_row_group_device` per index.  With
    ``filter``, row groups the static verdict proves empty are skipped
    entirely (not yielded) and the rest decode late-materialized in the
    same window.

    ``out_sharding`` (a ``NamedSharding`` over the consumer's mesh) /
    ``gather_to`` (a device or local-device index) place the decode
    itself: row groups round-robin the TARGET's devices, so every
    decoded buffer is born on a shard that will consume it — the
    device-read face of the scan layer's consumer-aligned output
    placement (:func:`tpuparquet.shard.scan.gather_column`).  Explicit
    only — the ``TPQ_GATHER_TO`` env default is a scan-level knob and
    does not reach this surface."""
    from ..stats import current_stats

    device_for = None
    if out_sharding is not None or gather_to is not None:
        from ..shard.mesh import placement_devices, resolve_out_sharding

        target = resolve_out_sharding(None, out_sharding, gather_to,
                                      env_default=False)
        # "replicated" resolves to None: the default decode placement
        if target is not None:
            devs = placement_devices(target)
            device_for = lambda k: devs[k % len(devs)]  # noqa: E731
    if rg_indices is None:
        rg_indices = range(reader.row_group_count())
    indices = list(rg_indices)
    verdicts = None
    if filter is not None:
        from ..filter import bind_filter

        bind_filter(filter, reader.schema)
        kept, verdicts = [], {}
        st = current_stats()
        for i in indices:
            v = reader.prune_row_group(filter, i)
            if v.skip:
                if st is not None:
                    st.row_groups_pruned += 1
                    st.rows_pruned += \
                        reader.meta.row_groups[i].num_rows
                    st.bloom_hits += v.bloom_hits
                continue
            if st is not None:
                st.bloom_hits += v.bloom_hits
            verdicts[(0, i)] = v
            kept.append(i)
        indices = kept
    for k, out in pipelined_reads([reader], [(0, i) for i in indices],
                                  device_for, filter=filter,
                                  verdicts=verdicts):
        yield indices[k], out


def decode_values_cpu(ptype, enc, data, count, type_length):
    from ..io.pages import decode_values

    return decode_values(ptype, enc, data, count, type_length)


def _stage_numpy_fixed(col, ptype: Type) -> jax.Array:
    """Host-decoded values -> flat u32 lane buffer."""
    arr = np.asarray(col)
    if arr.dtype == np.bool_:
        return jnp.asarray(arr.astype(np.uint32).reshape(-1))
    if arr.dtype.itemsize in (4, 8):
        return jnp.asarray(np.ascontiguousarray(arr).view("<u4")
                           .reshape(-1))
    if arr.ndim == 2:  # FLBA / int96 byte matrices
        return _stage_byte_rows(arr)
    raise TypeError(f"cannot stage {arr.dtype} for {ptype}")


def _scan_levels_v1(raw, n, max_level, pos, encoding=Encoding.RLE):
    """Scan a V1 def-level stream without expanding it.

    Returns (scan | None, host levels | None, end pos); expansion happens
    inside the fused page kernel (or standalone for non-fused paths)."""
    if max_level == 0:
        return None, None, pos
    width = max_level.bit_length()
    if encoding == Encoding.BIT_PACKED:
        from ..cpu import decode_levels_bitpacked

        nbytes = (n * width + 7) // 8
        vals = decode_levels_bitpacked(raw[pos : pos + nbytes], n, max_level)
        return None, vals, pos + nbytes
    import struct

    from ..cpu.hybrid import scan_hybrid

    (size,) = struct.unpack_from("<I", raw, pos)
    sc = scan_hybrid(raw[pos + 4 : pos + 4 + size], n, width)
    return sc, None, pos + 4 + size


