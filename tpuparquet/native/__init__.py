"""Native host-runtime components (C, loaded via ctypes).

The compute plane is JAX/XLA; the host runtime around it (block codecs,
byte-stream scanning) is native C where a Python loop would dominate —
the TPU-build counterpart of the reference keeping its codecs in compiled
Go.  The shared library is built from the checked-in sources with the
system compiler on first import and cached next to them; every consumer
must degrade gracefully to its pure-Python fallback when no compiler is
available (``snappy_native() is None``).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

__all__ = ["snappy_native", "NativeSnappy", "hybrid_native", "NativeHybrid",
           "plane_native", "NativePlane", "delta_native", "NativeDelta",
           "pack_native", "NativePack", "page_native", "NativePage",
           "lz4_native", "NativeLz4"]

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRCS = [os.path.join(_DIR, "snappy.c"), os.path.join(_DIR, "hybrid.c"),
         os.path.join(_DIR, "plane.c"), os.path.join(_DIR, "delta.c"),
         os.path.join(_DIR, "pack.c"), os.path.join(_DIR, "intern.c"),
         os.path.join(_DIR, "page.c"), os.path.join(_DIR, "lz4raw.c")]
_SO = os.path.join(_DIR, "_tpq_native.so")
# content hash of the sources the .so was built from, kept beside it
_STAMP = _SO + ".sha256"
_CFLAGS = ["-O3", "-shared", "-fPIC"]

_lock = threading.Lock()
_cached: "ctypes.CDLL | None | bool" = False  # False = not tried yet


def _as_u8(block) -> np.ndarray:
    """Zero-copy u8 view of bytes / memoryview / ndarray input."""
    if isinstance(block, np.ndarray):
        return np.ascontiguousarray(block.reshape(-1).view(np.uint8))
    return np.frombuffer(block, dtype=np.uint8)


def hybrid_encode_cap(count: int, width: int) -> int:
    """Output-capacity bound for one hybrid RLE/BP encode of ``count``
    ``width``-bit values: packed groups + per-group headers + slack.
    The ONE copy of this formula — the encoder bindings size their
    buffers with it and the write-side page assembler
    (``io/pages.py``) budgets its body buffer from it; a silent
    desync would turn every native page into a cap-shortfall
    fallback."""
    groups = (count + 7) // 8
    return groups * width + 5 * (groups + 2) + 32


def _source_hash() -> str:
    h = hashlib.sha256(" ".join(_CFLAGS).encode())
    for src in _SRCS:
        with open(src, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _build() -> bool:
    """(Re)build the shared library unless the stamp beside it names
    the current sources' hash; returns success.  A hash, not mtimes: a
    .so copied in from another machine or checkout is rebuilt unless it
    was built from exactly these sources."""
    try:
        want = _source_hash()
        try:
            with open(_STAMP) as f:
                if os.path.exists(_SO) and f.read().strip() == want:
                    return True
        except OSError:
            pass
        # per-process temp names: concurrent builders must not
        # interleave writes into one file and then promote the garbage
        # via replace
        tmp = f"{_SO}.{os.getpid()}.tmp"
        for cc in ("cc", "gcc", "clang"):
            try:
                subprocess.run(
                    [cc, *_CFLAGS, "-o", tmp, *_SRCS],
                    check=True, capture_output=True, timeout=120,
                )
                os.replace(tmp, _SO)
                with open(f"{_STAMP}.{os.getpid()}.tmp", "w") as f:
                    f.write(want)
                os.replace(f"{_STAMP}.{os.getpid()}.tmp", _STAMP)
                return True
            except (FileNotFoundError, subprocess.CalledProcessError,
                    subprocess.TimeoutExpired):
                continue
        return False
    except OSError:
        return False


def _lib() -> "ctypes.CDLL | None":
    global _cached
    with _lock:
        if _cached is False:
            _cached = None
            # TPQ_NATIVE_SO: load a prebuilt shared library instead of
            # building from the checked-in sources — the sanitizer leg
            # (tools/analyze/native.sh) points this at its ASan+UBSan
            # instrumented build so the whole test suite exercises the
            # instrumented codecs without touching the cached .so
            override = os.environ.get("TPQ_NATIVE_SO")
            if override:
                try:
                    _cached = ctypes.CDLL(override)
                except OSError:
                    _cached = None
            elif _build():
                try:
                    _cached = ctypes.CDLL(_SO)
                except OSError:
                    _cached = None
        return _cached


class NativeSnappy:
    """ctypes bindings over the C snappy block codec."""

    def __init__(self, lib: ctypes.CDLL):
        self._lib = lib
        lib.tpq_snappy_decompress.restype = ctypes.c_int
        lib.tpq_snappy_decompress.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.tpq_snappy_compress.restype = ctypes.c_int
        lib.tpq_snappy_compress.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        self._compress_opt_fn = getattr(lib, "tpq_snappy_compress_opt", None)
        if self._compress_opt_fn is not None:
            self._compress_opt_fn.restype = ctypes.c_int
            self._compress_opt_fn.argtypes = [
                ctypes.c_char_p, ctypes.c_size_t,
                ctypes.c_char_p, ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_size_t),
                ctypes.c_int,
            ]
        lib.tpq_snappy_uncompressed_length.restype = ctypes.c_int
        lib.tpq_snappy_uncompressed_length.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.tpq_snappy_max_compressed_length.restype = ctypes.c_uint64
        lib.tpq_snappy_max_compressed_length.argtypes = [ctypes.c_uint64]
        # optional symbol (absent in a stale .so): bind once here rather
        # than per call — ctypes function objects are shared across threads
        self._scan_tokens_fn = getattr(lib, "tpq_snappy_scan_tokens", None)
        if self._scan_tokens_fn is not None:
            self._scan_tokens_fn.restype = ctypes.c_int
            self._scan_tokens_fn.argtypes = [
                ctypes.c_void_p, ctypes.c_size_t,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_size_t),
                ctypes.POINTER(ctypes.c_uint64),
            ]

    def uncompressed_length(self, block) -> int:
        buf = _as_u8(block)
        out = ctypes.c_uint64()
        rc = self._lib.tpq_snappy_uncompressed_length(
            buf.ctypes.data, buf.size, ctypes.byref(out)
        )
        if rc != 0:
            raise ValueError("snappy: bad size header")
        return out.value

    def scan_tokens(self, block: bytes):
        """Parse the tag stream into (tok_out_end, tok_src, literals,
        out_len) for the device copy-resolution kernel — host cost is
        O(#tokens + literal bytes), no output materialization."""
        fn = self._scan_tokens_fn
        if fn is None:
            raise RuntimeError("native library too old; rebuild")
        buf = _as_u8(block)  # zero-copy for bytes/memoryview/ndarray
        cap_tokens = max(buf.size, 1)  # every token needs >= 1 input byte
        tok_end = np.empty(cap_tokens, dtype=np.int64)
        tok_src = np.empty(cap_tokens, dtype=np.int64)
        lits = np.empty(cap_tokens, dtype=np.uint8)
        n_tok = ctypes.c_int64()
        lit_len = ctypes.c_size_t()
        out_len = ctypes.c_uint64()
        rc = fn(buf.ctypes.data, buf.size,
                tok_end.ctypes.data, tok_src.ctypes.data, cap_tokens,
                lits.ctypes.data, lits.size,
                ctypes.byref(n_tok), ctypes.byref(lit_len),
                ctypes.byref(out_len))
        if rc != 0:
            raise ValueError(f"snappy: corrupt block (rc={rc})")
        t = int(n_tok.value)
        return (tok_end[:t], tok_src[:t], lits[: lit_len.value],
                int(out_len.value))

    def decompress_np(self, block, expected_size: int | None = None,
                      out: np.ndarray | None = None) -> np.ndarray:
        """Decompress into a numpy buffer (no intermediate copies).

        ``out``, when given, must be a u8 array of >= total + 16 bytes
        (the slack opts into the codec's fixed-width speculative copies);
        the caller owns its lifetime (arena recycling)."""
        buf = _as_u8(block)
        total = self.uncompressed_length(buf)
        if expected_size is not None and total != expected_size:
            raise ValueError(
                f"snappy: header size {total} != expected {expected_size}"
            )
        if out is None:
            out = np.empty(max(total, 1) + 16, dtype=np.uint8)
        elif out.size < total + 16:
            raise ValueError("snappy: output buffer too small")
        produced = ctypes.c_size_t()
        rc = self._lib.tpq_snappy_decompress(
            buf.ctypes.data, buf.size, out.ctypes.data,
            out.size, ctypes.byref(produced),
        )
        if rc != 0:
            raise ValueError(f"snappy: corrupt block (rc={rc})")
        return out[: produced.value]

    def decompress(self, block: bytes, expected_size: int | None = None):
        return self.decompress_np(block, expected_size).tobytes()

    def compress_into(self, src, out: np.ndarray,
                      min_match: int = 8) -> int:
        """Compress ``src`` into the caller's u8 buffer (arena-backed on
        the write path); returns the produced length.  No intermediate
        zeroed ctypes buffer and no copy-out — the two hidden whole-
        body passes ``compress`` pays per page."""
        buf = _as_u8(src)
        cap = self._lib.tpq_snappy_max_compressed_length(buf.size)
        if out.size < cap:
            raise ValueError("snappy: output buffer too small")
        produced = ctypes.c_size_t()
        opt = self._compress_opt_fn
        src_p = buf.ctypes.data_as(ctypes.c_char_p)
        out_p = out.ctypes.data_as(ctypes.c_char_p)
        if opt is not None:
            rc = opt(src_p, buf.size, out_p, out.size,
                     ctypes.byref(produced), min_match)
        else:  # stale .so without the tunable: fixed min_match = 8
            rc = self._lib.tpq_snappy_compress(
                src_p, buf.size, out_p, out.size, ctypes.byref(produced))
        if rc != 0:
            raise ValueError(f"snappy: compress failed (rc={rc})")
        return int(produced.value)

    def compress(self, data: bytes, min_match: int = 8) -> bytes:
        cap = self._lib.tpq_snappy_max_compressed_length(len(data))
        buf = ctypes.create_string_buffer(cap)
        produced = ctypes.c_size_t()
        opt = self._compress_opt_fn
        if opt is not None:
            rc = opt(data, len(data), buf, cap, ctypes.byref(produced),
                     min_match)
        else:  # stale .so without the tunable: fixed min_match = 8
            rc = self._lib.tpq_snappy_compress(
                data, len(data), buf, cap, ctypes.byref(produced)
            )
        if rc != 0:
            raise ValueError(f"snappy: compress failed (rc={rc})")
        return ctypes.string_at(buf, produced.value)


class NativeHybrid:
    """ctypes bindings over the C hybrid RLE/BP run scanner."""

    def __init__(self, lib: ctypes.CDLL):
        self._lib = lib
        self._scan = lib.tpq_hybrid_scan
        self._scan.restype = ctypes.c_int
        self._scan.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_size_t,
            ctypes.c_int64, ctypes.c_int,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_size_t), ctypes.POINTER(ctypes.c_size_t),
        ]
        # optional symbol (absent in a stale .so): bind once here rather
        # than per call — ctypes function objects are shared across threads
        self._bp_stats_fn = getattr(lib, "tpq_bp_stats", None)
        if self._bp_stats_fn is not None:
            self._bp_stats_fn.restype = ctypes.c_int
            self._bp_stats_fn.argtypes = [
                ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_uint32,
                ctypes.POINTER(ctypes.c_uint32),
                ctypes.POINTER(ctypes.c_int64),
            ]

    def bp_stats(self, bp_bytes, width: int, starts, lens,
                 target: int = 0):
        """(max value | None, count of == target) over the consumed lanes
        of bit-packed segments — one C pass, no unpack materialization."""
        fn = self._bp_stats_fn
        if fn is None:
            raise RuntimeError("native library too old; rebuild")
        bp = np.ascontiguousarray(
            np.frombuffer(bp_bytes, dtype=np.uint8)
            if not isinstance(bp_bytes, np.ndarray) else bp_bytes
        )
        s = np.ascontiguousarray(starts, dtype=np.int64)
        ln = np.ascontiguousarray(lens, dtype=np.int64)
        mx = ctypes.c_uint32()
        cnt = ctypes.c_int64()
        rc = fn(bp.ctypes.data_as(ctypes.c_char_p), bp.size, width,
                s.ctypes.data, ln.ctypes.data, s.size, target,
                ctypes.byref(mx), ctypes.byref(cnt))
        if rc == 1:
            return None, 0
        if rc != 0:
            raise ValueError(f"bit-packed segment out of bounds (rc={rc})")
        return int(mx.value), int(cnt.value)

    def scan(self, buf, count: int, width: int, pos: int = 0):
        """Parse run headers; returns (run_ends, run_is_rle, run_value,
        run_bp_start, bp_bytes, n_bp_values, end_pos) — numpy arrays plus
        the concatenated bit-packed segment bytes."""
        if isinstance(buf, np.ndarray):
            data = np.ascontiguousarray(buf.view(np.uint8))
        else:
            data = np.frombuffer(buf, dtype=np.uint8)  # zero-copy
        # every run consumes >= 1 header byte, so runs are bounded by the
        # stream's byte length as well as by the value count
        cap_runs = max(min(count, max(data.size - pos, 0)) + 1, 1)
        bp_cap = max(data.size - pos, 1)
        ends = np.empty(cap_runs, dtype=np.int32)
        is_rle = np.empty(cap_runs, dtype=np.uint8)
        value = np.empty(cap_runs, dtype=np.uint32)
        bp_start = np.empty(cap_runs, dtype=np.int32)
        bp_out = np.empty(bp_cap, dtype=np.uint8)
        n_runs = ctypes.c_int64()
        n_bp = ctypes.c_int64()
        bp_len = ctypes.c_size_t()
        end_pos = ctypes.c_size_t()
        rc = self._scan(
            data.ctypes.data_as(ctypes.c_char_p), data.size, pos, count,
            width,
            ends.ctypes.data, is_rle.ctypes.data, value.ctypes.data,
            bp_start.ctypes.data, cap_runs,
            bp_out.ctypes.data, bp_cap,
            ctypes.byref(n_runs), ctypes.byref(n_bp),
            ctypes.byref(bp_len), ctypes.byref(end_pos),
        )
        if rc == -1:
            raise ValueError("truncated hybrid run")
        if rc == -2:
            raise ValueError("zero-length RLE run")
        if rc == -6:
            raise ValueError("RLE run value exceeds bit width")
        if rc != 0:
            raise ValueError(f"hybrid scan failed (rc={rc})")
        r = int(n_runs.value)
        return (ends[:r], is_rle[:r].astype(bool), value[:r], bp_start[:r],
                bp_out[: bp_len.value], int(n_bp.value), int(end_pos.value))


class NativePlane:
    """ctypes bindings over the strided lane/byte-plane primitives used
    by the device wire planner (one C pass per run-scan / gather)."""

    def __init__(self, lib: ctypes.CDLL):
        self._scan32 = getattr(lib, "tpq_run_scan32", None)
        self._scan8 = getattr(lib, "tpq_run_scan8", None)
        self._gather32 = getattr(lib, "tpq_lane_gather32", None)
        self._gather8 = getattr(lib, "tpq_lane_gather8", None)
        if None in (self._scan32, self._scan8,
                    self._gather32, self._gather8):
            raise RuntimeError("native library too old; rebuild")
        for fn, val in ((self._scan32, ctypes.c_longlong),
                        (self._scan8, ctypes.c_longlong)):
            fn.restype = val
            fn.argtypes = [
                ctypes.c_void_p, ctypes.c_longlong, ctypes.c_longlong,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_longlong,
            ]
        for fn in (self._gather32, self._gather8):
            fn.restype = None
            fn.argtypes = [
                ctypes.c_void_p, ctypes.c_longlong, ctypes.c_longlong,
                ctypes.c_void_p,
            ]

    @staticmethod
    def _strided(arr: np.ndarray, esize: int):
        """(base pointer, element stride) for a 1-D strided view."""
        if arr.ndim != 1 or arr.itemsize != esize:
            raise ValueError("expected a 1-D view of the element type")
        return arr.ctypes.data, arr.strides[0]

    def run_scan(self, plane: np.ndarray, max_runs: int):
        """Run-table scan of a strided u32/u8 view.  Returns
        (ends[:n], vals[:n]) or None when the plane has more than
        ``max_runs`` runs (the table cannot beat shipping raw)."""
        cap = max(int(max_runs), 1)
        ends = np.empty(cap, dtype=np.int32)
        if plane.itemsize == 4:
            vals = np.empty(cap, dtype=np.uint32)
            base, stride = self._strided(plane, 4)
            n = self._scan32(base, plane.size, stride,
                             ends.ctypes.data, vals.ctypes.data, cap)
        else:
            vals = np.empty(cap, dtype=np.uint8)
            base, stride = self._strided(plane, 1)
            n = self._scan8(base, plane.size, stride,
                            ends.ctypes.data, vals.ctypes.data, cap)
        if n < 0:
            return None
        return ends[:n], vals[:n]

    def gather(self, plane: np.ndarray) -> np.ndarray:
        """Contiguous copy of a strided u32/u8 view (one pass)."""
        out = np.empty(plane.size, dtype=plane.dtype)
        if plane.itemsize == 4:
            base, stride = self._strided(plane, 4)
            self._gather32(base, plane.size, stride, out.ctypes.data)
        else:
            base, stride = self._strided(plane, 1)
            self._gather8(base, plane.size, stride, out.ctypes.data)
        return out


class NativeDelta:
    """ctypes binding over the DELTA_BINARY_PACKED block scanner."""

    _ERRORS = {
        -1: "truncated uvarint",
        -5: "truncated miniblock width list",
        -7: "truncated miniblock payload",
        -9: "uvarint too long",
    }

    def __init__(self, lib: ctypes.CDLL):
        self._scan = getattr(lib, "tpq_delta_scan_blocks", None)
        if self._scan is None:
            raise RuntimeError("native library too old; rebuild")
        self._scan.restype = ctypes.c_longlong
        self._scan.argtypes = [
            ctypes.c_void_p, ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_longlong, ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_int,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_longlong, ctypes.c_longlong,
            ctypes.POINTER(ctypes.c_longlong),
            ctypes.POINTER(ctypes.c_longlong),
            ctypes.POINTER(ctypes.c_longlong),
        ]
        self._decode = getattr(lib, "tpq_delta_decode", None)
        if self._decode is not None:
            self._decode.restype = ctypes.c_longlong
            self._decode.argtypes = [
                ctypes.c_void_p, ctypes.c_longlong,
                ctypes.c_void_p, ctypes.c_longlong,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_longlong, ctypes.c_longlong, ctypes.c_longlong,
                ctypes.c_longlong, ctypes.c_uint64,
                ctypes.c_void_p,
            ]
        self._gather = getattr(lib, "tpq_gather_segments", None)
        if self._gather is not None:
            self._gather.restype = ctypes.c_longlong
            self._gather.argtypes = [
                ctypes.c_void_p, ctypes.c_longlong,
                ctypes.c_void_p, ctypes.c_longlong, ctypes.c_longlong,
                ctypes.c_void_p,
            ]
        self._gather_var = getattr(lib, "tpq_gather_var", None)
        if self._gather_var is not None:
            self._gather_var.restype = ctypes.c_longlong
            self._gather_var.argtypes = [
                ctypes.c_void_p, ctypes.c_longlong,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_longlong,
                ctypes.c_void_p, ctypes.c_longlong,
            ]
        self._dba = getattr(lib, "tpq_dba_assemble", None)
        if self._dba is not None:
            self._dba.restype = ctypes.c_longlong
            self._dba.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_longlong,
                ctypes.c_void_p, ctypes.c_longlong,
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong),
            ]
        self._ba_emit = getattr(lib, "tpq_byte_array_emit", None)
        if self._ba_emit is not None:
            self._ba_emit.restype = ctypes.c_longlong
            self._ba_emit.argtypes = [
                ctypes.c_void_p, ctypes.c_longlong,
                ctypes.c_void_p, ctypes.c_longlong,
                ctypes.c_void_p,
            ]
        self._ba_scan = getattr(lib, "tpq_byte_array_scan", None)
        if self._ba_scan is not None:
            self._ba_scan.restype = ctypes.c_longlong
            self._ba_scan.argtypes = [
                ctypes.c_void_p, ctypes.c_longlong, ctypes.c_longlong,
                ctypes.c_void_p, ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_longlong),
                ctypes.POINTER(ctypes.c_longlong),
            ]

    def decode_all(self, data, st) -> "np.ndarray | None":
        """Full DELTA_BINARY_PACKED decode from a scanned
        :class:`~tpuparquet.cpu.delta.DeltaStructure` — unpack + per-block
        min_delta + prefix sum in one GIL-releasing C pass.  Returns the
        (total,) uint64 value array (two's-complement wrap, byte-exact
        with the numpy decode), or None when the symbol is missing
        (stale .so)."""
        if self._decode is None:
            return None
        buf = _as_u8(data)
        md = np.ascontiguousarray(st.md_blocks, dtype=np.int64)
        w = np.ascontiguousarray(st.mb_w, dtype=np.int32)
        p = np.ascontiguousarray(st.mb_pos, dtype=np.int64)
        s = np.ascontiguousarray(st.mb_start, dtype=np.int64)
        out = np.empty(max(st.total, 1), dtype=np.uint64)[: st.total]
        rc = self._decode(
            buf.ctypes.data, buf.size, md.ctypes.data, md.size,
            w.ctypes.data, p.ctypes.data, s.ctypes.data, w.size,
            st.mb_size, st.block_size, st.total,
            ctypes.c_uint64(st.first & 0xFFFFFFFFFFFFFFFF),
            out.ctypes.data)
        if rc != 0:
            raise ValueError(f"delta decode failed (rc={rc})")
        return out

    def dba_assemble(self, prefix_lens, suffix_offs, suffix_data,
                     out_offsets, total: int):
        """Front-coded DELTA_BYTE_ARRAY fill in one C pass; None when
        the symbol is missing.  Raises ValueError with the CPU
        assembler's messages on malformed streams."""
        if self._dba is None:
            return None
        pl = np.ascontiguousarray(prefix_lens, dtype=np.int64)
        so = np.ascontiguousarray(suffix_offs, dtype=np.int64)
        sd = _as_u8(suffix_data)
        oo = np.ascontiguousarray(out_offsets, dtype=np.int64)
        out = np.empty(max(total, 1), dtype=np.uint8)[:total]
        err = ctypes.c_longlong()
        rc = self._dba(pl.ctypes.data, so.ctypes.data,
                       sd.ctypes.data, sd.size,
                       oo.ctypes.data, pl.size, out.ctypes.data,
                       ctypes.byref(err))
        if rc == -1:
            raise ValueError("DELTA_BYTE_ARRAY: first prefix must be 0")
        if rc == -2:
            raise ValueError(
                f"DELTA_BYTE_ARRAY: prefix {int(pl[err.value])} longer "
                "than previous value")
        if rc != 0:
            raise ValueError(f"DELTA_BYTE_ARRAY assembly failed "
                             f"(rc={rc})")
        return out

    def byte_array_emit(self, data, offsets):
        """PLAIN-encode a ByteArrayColumn's records (u32-LE prefix +
        bytes) in one C pass; None when the symbol is missing."""
        if self._ba_emit is None:
            return None
        d = _as_u8(data)
        offs = np.ascontiguousarray(offsets, dtype=np.int64)
        count = offs.size - 1
        total = 4 * count + int(offs[-1]) - int(offs[0])
        out = np.empty(max(total, 1), dtype=np.uint8)[:total]
        rc = self._ba_emit(d.ctypes.data, d.size, offs.ctypes.data,
                           count, out.ctypes.data)
        if rc != 0:
            raise ValueError(
                "byte-array offsets out of bounds or value too long "
                "for a u32 prefix")
        return out

    def byte_array_scan(self, buf, count: int):
        """Scan PLAIN BYTE_ARRAY length prefixes in one C pass:
        (positions, offsets) or None when the symbol is missing.
        Raises ValueError with the CPU scanner's messages."""
        if self._ba_scan is None or count < 0:
            return None  # negative counts keep the legacy Python path
        b = _as_u8(buf)
        positions = np.empty(max(count, 1), dtype=np.int64)[:count]
        offsets = np.zeros(count + 1, dtype=np.int64)
        err = ctypes.c_longlong()
        err_len = ctypes.c_longlong()
        rc = self._ba_scan(b.ctypes.data, b.size, count,
                           positions.ctypes.data, offsets.ctypes.data,
                           ctypes.byref(err), ctypes.byref(err_len))
        if rc == -1:
            raise ValueError(
                f"PLAIN BYTE_ARRAY: truncated length prefix at value "
                f"{err.value}")
        if rc == -2:
            raise ValueError(
                f"PLAIN BYTE_ARRAY: length {err_len.value} out of "
                f"bounds at value {err.value}")
        if rc != 0:
            raise ValueError(f"byte-array scan failed (rc={rc})")
        return positions, offsets

    def gather_var(self, src, starts, lens, total: int):
        """Concatenate variable-length segments of ``src`` in one C
        pass; None when the symbol is missing (stale .so)."""
        if self._gather_var is None:
            return None
        buf = _as_u8(src)
        s = np.ascontiguousarray(starts, dtype=np.int64)
        ln = np.ascontiguousarray(lens, dtype=np.int64)
        out = np.empty(max(total, 1), dtype=np.uint8)[:total]
        rc = self._gather_var(buf.ctypes.data, buf.size,
                              s.ctypes.data, ln.ctypes.data, s.size,
                              out.ctypes.data, total)
        if rc != 0:
            raise ValueError("segment out of bounds")
        return out

    def gather_segments(self, src, positions, nbytes: int):
        """Concatenate fixed-size segments of ``src`` at ``positions``
        in one C pass; None when the symbol is missing (stale .so)."""
        if self._gather is None:
            return None
        buf = _as_u8(src)
        pos = np.ascontiguousarray(positions, dtype=np.int64)
        out = np.empty(pos.size * nbytes, dtype=np.uint8)
        rc = self._gather(buf.ctypes.data, buf.size, pos.ctypes.data,
                          pos.size, nbytes, out.ctypes.data)
        if rc != 0:
            raise ValueError("miniblock payload out of bounds")
        return out

    def scan_blocks(self, data, pos: int, n_deltas: int, mb_size: int,
                    n_miniblocks: int, max_width: int):
        """Scan the block loop of a DELTA stream whose 4 header varints
        the caller already consumed.  Returns (md_blocks, mb_w, mb_pos,
        mb_start, end_pos) as numpy arrays / int; raises ValueError with
        the CPU scanner's messages on malformed input."""
        buf = _as_u8(data)
        block_size = mb_size * n_miniblocks
        # clamp by remaining bytes: each block consumes >= 1 byte of
        # min_delta varint + n_miniblocks width bytes, so a corrupt
        # total claiming 2^62 values must not size the allocation (the
        # scan will hit its truncation error long before these caps)
        max_blocks = max(buf.size - pos, 0) // (1 + n_miniblocks) + 2
        cap_blocks = min(n_deltas // block_size + 2, max_blocks)
        # likewise for recorded miniblocks: each non-zero-width one
        # consumes >= mb_size/8 payload bytes, so a corrupt header with
        # a huge n_miniblocks cannot size a multi-GB table either
        max_mb = max(buf.size - pos, 0) // max(mb_size // 8, 1) + 2
        cap_mb = min(cap_blocks * n_miniblocks + 2, max_mb)
        md = np.empty(cap_blocks, dtype=np.int64)
        w = np.empty(cap_mb, dtype=np.int32)
        p = np.empty(cap_mb, dtype=np.int64)
        s = np.empty(cap_mb, dtype=np.int64)
        nb = ctypes.c_longlong()
        nm = ctypes.c_longlong()
        end = ctypes.c_longlong()
        rc = self._scan(
            buf.ctypes.data, buf.size, pos,
            n_deltas, mb_size, n_miniblocks, max_width,
            md.ctypes.data, w.ctypes.data, p.ctypes.data, s.ctypes.data,
            cap_blocks, cap_mb,
            ctypes.byref(nb), ctypes.byref(nm), ctypes.byref(end),
        )
        if rc == -6:
            raise ValueError(
                f"delta miniblock width > {max_width} for this column's "
                "physical type")
        if rc != 0:
            raise ValueError(self._ERRORS.get(
                rc, f"delta scan failed (rc={rc})"))
        b, m = int(nb.value), int(nm.value)
        return md[:b], w[:m], p[:m], s[:m], int(end.value)


class NativePack:
    """ctypes bindings over the bit-packing primitives."""

    def __init__(self, lib: ctypes.CDLL):
        self._pack64 = getattr(lib, "tpq_pack64", None)
        self._repack = getattr(lib, "tpq_hybrid_repack", None)
        self._expand = getattr(lib, "tpq_hybrid_expand32", None)
        if None in (self._pack64, self._repack, self._expand):
            raise RuntimeError("native library too old; rebuild")
        self._delta_emit = getattr(lib, "tpq_delta_emit", None)
        if self._delta_emit is not None:
            self._delta_emit.restype = ctypes.c_longlong
            self._delta_emit.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_longlong, ctypes.c_longlong,
                ctypes.c_void_p, ctypes.c_longlong, ctypes.c_longlong,
                ctypes.c_void_p, ctypes.c_longlong,
                ctypes.POINTER(ctypes.c_longlong),
            ]
        self._hybrid_encode = getattr(lib, "tpq_hybrid_encode", None)
        if self._hybrid_encode is not None:
            self._hybrid_encode.restype = ctypes.c_longlong
            self._hybrid_encode.argtypes = [
                ctypes.c_void_p, ctypes.c_longlong, ctypes.c_int,
                ctypes.c_void_p, ctypes.c_longlong,
                ctypes.POINTER(ctypes.c_longlong),
            ]
        self._hybrid_encode32 = getattr(lib, "tpq_hybrid_encode32", None)
        if self._hybrid_encode32 is not None:
            self._hybrid_encode32.restype = ctypes.c_longlong
            self._hybrid_encode32.argtypes = [
                ctypes.c_void_p, ctypes.c_longlong, ctypes.c_int,
                ctypes.c_void_p, ctypes.c_longlong,
                ctypes.POINTER(ctypes.c_longlong),
            ]
        self._expand.restype = ctypes.c_longlong
        self._expand.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_longlong,
            ctypes.c_void_p, ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_longlong, ctypes.c_int, ctypes.c_void_p,
        ]
        self._pack64.restype = ctypes.c_longlong
        self._pack64.argtypes = [
            ctypes.c_void_p, ctypes.c_longlong, ctypes.c_int,
            ctypes.c_void_p,
        ]
        self._repack.restype = ctypes.c_longlong
        self._repack.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_longlong,
            ctypes.c_void_p, ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_longlong, ctypes.c_int, ctypes.c_void_p,
        ]

    def pack(self, values: np.ndarray, width: int) -> np.ndarray:
        """LSB-first pack of a contiguous uint64 array; raises on a
        value that does not fit ``width`` bits."""
        v = np.ascontiguousarray(values, dtype=np.uint64)
        n = (v.size * width + 7) // 8
        out = np.empty(n + 8, dtype=np.uint8)  # word-writer slack
        rc = self._pack64(v.ctypes.data, v.size, width, out.ctypes.data)
        if rc == -1:
            raise ValueError(
                f"value {int(v.max())} does not fit in {width} bits")
        if rc != 0:
            raise ValueError(f"bit width {width} out of range 0..64")
        return out[:n]

    def hybrid_encode(self, values: np.ndarray, width: int):
        """Hybrid RLE/BP encode in one C pass, byte-identical to the
        Python encoder.  None when the symbol is missing (stale .so) or
        the capacity estimate fell short (the fallback then encodes);
        raises on a value that does not fit the width — writing it
        would corrupt the stream at read time."""
        if self._hybrid_encode is None:
            return None
        v = np.ascontiguousarray(values, dtype=np.uint64)
        cap = hybrid_encode_cap(v.size, width)
        out = np.empty(cap, dtype=np.uint8)
        out_len = ctypes.c_longlong()
        rc = self._hybrid_encode(v.ctypes.data, v.size, width,
                                 out.ctypes.data, cap,
                                 ctypes.byref(out_len))
        if rc == -1:
            raise ValueError(
                f"value {int(v.max())} does not fit in {width} bits")
        if rc != 0:
            return None  # cap shortfall / bad width: fallback decides
        return out[: out_len.value]

    def hybrid_encode32(self, values: np.ndarray, width: int):
        """Hybrid RLE/BP encode straight from a u32 array — the same
        bytes as :meth:`hybrid_encode` without the u64-widening copy
        the write path paid per dict-index/level stream.  None when
        the symbol is missing (stale .so) or the capacity estimate
        fell short; raises on a value that does not fit the width."""
        if self._hybrid_encode32 is None or width > 32:
            return None
        v = np.ascontiguousarray(values, dtype=np.uint32)
        cap = hybrid_encode_cap(v.size, width)
        out = np.empty(cap, dtype=np.uint8)
        out_len = ctypes.c_longlong()
        rc = self._hybrid_encode32(v.ctypes.data, v.size, width,
                                   out.ctypes.data, cap,
                                   ctypes.byref(out_len))
        if rc == -1:
            raise ValueError(
                f"value {int(v.max())} does not fit in {width} bits")
        if rc != 0:
            return None  # cap shortfall / bad width: fallback decides
        return out[: out_len.value]

    def delta_emit(self, adj, widths, mb_size: int, min_deltas,
                   n_miniblocks: int):
        """Emit the per-block body of a DELTA_BINARY_PACKED stream in
        one C pass (zigzag min_delta varints + width bytes + packed
        miniblocks); None when the symbol is missing (stale .so)."""
        if self._delta_emit is None:
            return None
        a = np.ascontiguousarray(adj, dtype=np.uint64).reshape(-1)
        w = np.ascontiguousarray(widths, dtype=np.uint8)
        md = np.ascontiguousarray(min_deltas, dtype=np.int64)
        n_mb = w.size
        packed_bytes = int((w.astype(np.int64) * mb_size).sum()) // 8
        cap = packed_bytes + md.size * (10 + n_miniblocks) + 16
        out = np.empty(cap, dtype=np.uint8)
        out_len = ctypes.c_longlong()
        rc = self._delta_emit(
            a.ctypes.data, w.ctypes.data, n_mb, mb_size,
            md.ctypes.data, md.size, n_miniblocks,
            out.ctypes.data, cap, ctypes.byref(out_len))
        if rc != 0:
            raise ValueError(f"delta emit failed (rc={rc})")
        return out[: out_len.value]

    @staticmethod
    def _run_table(run_ends, run_is_rle, run_value, run_bp_start,
                   bp_bytes, count: int, width: int):
        """Validated, C-ready run table for expand/repack, or None
        when the fallback must handle it: widths > 32, or a table that
        does not cover count — that shape cannot come from a valid
        scan, and the numpy paths disagree with each other on it, so
        don't pin semantics here."""
        if not 0 < width <= 32 or not len(run_ends):
            return None
        if int(run_ends[-1]) < count:
            return None
        return (np.ascontiguousarray(run_ends, dtype=np.int32),
                np.ascontiguousarray(run_is_rle, dtype=np.uint8),
                np.ascontiguousarray(run_value, dtype=np.uint32),
                np.ascontiguousarray(run_bp_start, dtype=np.int32),
                _as_u8(bp_bytes))

    def hybrid_expand(self, run_ends, run_is_rle, run_value,
                      run_bp_start, bp_bytes, n_bp: int, count: int,
                      width: int) -> np.ndarray | None:
        """Run table -> (count,) u32 values in one C pass (pass 2 of
        the two-pass hybrid decode).  None for widths > 32 or tables
        that do not cover count (caller falls back to numpy)."""
        t = self._run_table(run_ends, run_is_rle, run_value,
                            run_bp_start, bp_bytes, count, width)
        if t is None:
            return None
        ends, rle, val, bps, bp = t
        out = np.empty(count, dtype=np.uint32)
        rc = self._expand(
            ends.ctypes.data, rle.ctypes.data, val.ctypes.data,
            bps.ctypes.data, ends.size, bp.ctypes.data, bp.size,
            int(n_bp), count, width, out.ctypes.data)
        if rc != 0:
            raise ValueError(f"hybrid expand failed (rc={rc})")
        return out

    def hybrid_repack(self, run_ends, run_is_rle, run_value,
                      run_bp_start, bp_bytes, n_bp: int, count: int,
                      width: int) -> np.ndarray | None:
        """Run table -> ONE bit-packed run, no expanded intermediate.
        Returns the packed bytes, or None for widths > 32 (caller
        falls back to expand + pack)."""
        t = self._run_table(run_ends, run_is_rle, run_value,
                            run_bp_start, bp_bytes, count, width)
        if t is None:
            return None
        ends, rle, val, bps, bp = t
        n = (count * width + 7) // 8
        out = np.empty(n + 8, dtype=np.uint8)  # word-writer slack
        rc = self._repack(
            ends.ctypes.data, rle.ctypes.data, val.ctypes.data,
            bps.ctypes.data, ends.size, bp.ctypes.data, bp.size,
            int(n_bp), count, width, out.ctypes.data)
        if rc == -1:  # same contract as pack(): refuse, don't truncate
            raise ValueError(
                f"value {int(val.max())} does not fit in {width} bits")
        if rc != 0:
            raise ValueError(f"hybrid repack failed (rc={rc})")
        return out[:n]


class NativePage:
    """ctypes bindings over the write-side page assembly (page.c):
    one-pass body encode into a caller buffer + the zlib-polynomial
    CRC32 the PageHeader carries."""

    def __init__(self, lib: ctypes.CDLL):
        self._encode = getattr(lib, "tpq_page_encode", None)
        self._crc = getattr(lib, "tpq_crc32", None)
        if None in (self._encode, self._crc):
            raise RuntimeError("native library too old; rebuild")
        self._encode.restype = ctypes.c_longlong
        self._encode.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_longlong,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_void_p, ctypes.c_longlong, ctypes.c_int,
            ctypes.c_void_p, ctypes.c_longlong,
            ctypes.c_void_p, ctypes.c_longlong,
            ctypes.POINTER(ctypes.c_longlong),
            ctypes.POINTER(ctypes.c_longlong),
            ctypes.POINTER(ctypes.c_longlong),
        ]
        self._crc.restype = ctypes.c_uint32
        self._crc.argtypes = [ctypes.c_void_p, ctypes.c_longlong,
                              ctypes.c_uint32]

    def crc32(self, buf, crc: int = 0) -> int:
        """zlib-compatible CRC32 (slice-by-8, GIL released)."""
        b = _as_u8(buf)
        return int(self._crc(b.ctypes.data, b.size, crc & 0xFFFFFFFF))

    def encode(self, rep, dl, n: int, rep_width: int, def_width: int,
               v2: bool, idx, idx_width: int, values,
               out: np.ndarray):
        """Lay one data page's uncompressed body into ``out``:
        ``[rep stream][def stream][values]``, V1 length-prefixed or V2
        raw level framing.  ``rep``/``dl`` are u32 level arrays or
        None; the values segment is either ``idx`` (u32 dictionary
        indices, hybrid-encoded behind the width byte) or ``values``
        (pre-encoded u8 bytes, copied verbatim).  Returns
        ``(rep_len, dl_len, val_len)`` — framing included — or None
        when the buffer capacity fell short (caller falls back);
        raises on a level/index exceeding its width."""
        def _c(a):
            # contiguity is load-bearing: C walks n consecutive words
            # from the base pointer (no-op for the write path's own
            # arrays; a caller-provided strided view copies here)
            return None if a is None else np.ascontiguousarray(a)

        def _p(a):
            return None if a is None else a.ctypes.data

        rep, dl, idx, values = _c(rep), _c(dl), _c(idx), _c(values)
        rep_len = ctypes.c_longlong()
        dl_len = ctypes.c_longlong()
        val_len = ctypes.c_longlong()
        rc = self._encode(
            _p(rep), _p(dl), n, rep_width, def_width, 1 if v2 else 0,
            _p(idx), 0 if idx is None else idx.size, idx_width,
            _p(values), 0 if values is None else values.size,
            out.ctypes.data, out.size,
            ctypes.byref(rep_len), ctypes.byref(dl_len),
            ctypes.byref(val_len))
        if rc == -1:
            raise ValueError("level/index value does not fit its width")
        if rc != 0:
            return None  # cap shortfall / bad width: fallback decides
        return int(rep_len.value), int(dl_len.value), int(val_len.value)


class NativeLz4:
    """ctypes bindings over the C LZ4 raw-block codec (lz4raw.c) —
    Parquet's LZ4_RAW.  Same buffer discipline as :class:`NativeSnappy`:
    ``compress_into``/``decompress_np`` take caller (arena) buffers so
    the write/read hot paths pay no scratch copies."""

    def __init__(self, lib: ctypes.CDLL):
        self._lib = lib
        comp = getattr(lib, "tpq_lz4_compress", None)
        dec = getattr(lib, "tpq_lz4_decompress", None)
        bound = getattr(lib, "tpq_lz4_max_compressed_length", None)
        if None in (comp, dec, bound):
            raise RuntimeError("native library too old; rebuild")
        comp.restype = ctypes.c_int
        comp.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        dec.restype = ctypes.c_int
        dec.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        bound.restype = ctypes.c_uint64
        bound.argtypes = [ctypes.c_uint64]
        self._comp = comp
        self._dec = dec
        self._bound = bound

    def max_compressed_length(self, n: int) -> int:
        return int(self._bound(n))

    def compress_into(self, src, out: np.ndarray) -> int:
        """Compress ``src`` into the caller's u8 buffer; returns the
        produced length.  ``out`` must hold max_compressed_length."""
        buf = _as_u8(src)
        if out.size < self.max_compressed_length(buf.size):
            raise ValueError("lz4: output buffer too small")
        produced = ctypes.c_size_t()
        rc = self._comp(buf.ctypes.data, buf.size, out.ctypes.data,
                        out.size, ctypes.byref(produced))
        if rc != 0:
            raise ValueError(f"lz4: compress failed (rc={rc})")
        return int(produced.value)

    def compress(self, data) -> bytes:
        buf = _as_u8(data)
        out = np.empty(self.max_compressed_length(buf.size),
                       dtype=np.uint8)
        return out[: self.compress_into(buf, out)].tobytes()

    def decompress_np(self, block, expected_size: int,
                      out: np.ndarray | None = None) -> np.ndarray:
        """Decompress into a numpy buffer sized by the caller's
        ``expected_size`` (LZ4 raw blocks carry no length header; the
        Parquet page header supplies it)."""
        buf = _as_u8(block)
        if expected_size < 0:
            raise ValueError("lz4: missing decompressed size")
        if out is None:
            out = np.empty(max(expected_size, 1), dtype=np.uint8)
        elif out.size < expected_size:
            raise ValueError("lz4: output buffer too small")
        produced = ctypes.c_size_t()
        rc = self._dec(buf.ctypes.data, buf.size, out.ctypes.data,
                       ctypes.c_size_t(expected_size),
                       ctypes.byref(produced))
        if rc != 0:
            raise ValueError(f"lz4: corrupt block (rc={rc})")
        if int(produced.value) != expected_size:
            raise ValueError(
                f"lz4: stream produced {int(produced.value)} bytes, "
                f"expected {expected_size}")
        return out[:expected_size]

    def decompress(self, block, expected_size: int) -> bytes:
        return self.decompress_np(block, expected_size).tobytes()


# sentinel: the interner hit its distinct-value cap (callers compare
# with ``is``; a string literal here invited silent typo mismatches)
TOO_MANY_DISTINCT = object()


class NativeIntern:
    """ctypes binding over the one-pass byte-value interner."""

    def __init__(self, lib: ctypes.CDLL):
        self._intern = getattr(lib, "tpq_intern_var", None)
        if self._intern is None:
            raise RuntimeError("native library too old; rebuild")
        self._intern.restype = ctypes.c_longlong
        self._intern.argtypes = [
            ctypes.c_void_p, ctypes.c_longlong,
            ctypes.c_void_p, ctypes.c_longlong,
            ctypes.c_void_p, ctypes.c_longlong, ctypes.c_int,
            ctypes.c_void_p, ctypes.c_longlong,
            ctypes.c_void_p,
        ]
        # optional symbols (absent in a stale .so): bound once here
        self._range32 = getattr(lib, "tpq_intern_range32", None)
        self._range64 = getattr(lib, "tpq_intern_range64", None)
        for fn, lo_t in ((self._range32, ctypes.c_uint32),
                         (self._range64, ctypes.c_uint64)):
            if fn is not None:
                fn.restype = ctypes.c_longlong
                fn.argtypes = [
                    ctypes.c_void_p, ctypes.c_longlong, lo_t,
                    ctypes.c_longlong,
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ]

    def intern_range(self, arr: np.ndarray, lo: int, rng: int):
        """First-occurrence intern of a small-range integer column in
        one C pass: ``(uniq_positions int64[D], indices int32[n])``, or
        None when the symbol is missing (stale .so).  ``lo``/``rng``
        come from the column's true min/max (offsets are computed with
        wraparound subtraction, exact for signed and unsigned alike);
        raises on a value outside ``[lo, lo + rng)``."""
        fn = self._range64 if arr.itemsize == 8 else self._range32
        if fn is None or arr.itemsize not in (4, 8):
            return None
        u = np.ascontiguousarray(arr).view(
            np.uint64 if arr.itemsize == 8 else np.uint32)
        mask = (1 << (8 * arr.itemsize)) - 1
        rank = np.full(rng, -1, dtype=np.int32)
        uniq_pos = np.empty(rng, dtype=np.int64)
        indices = np.empty(max(u.size, 1), dtype=np.int32)[: u.size]
        d = fn(u.ctypes.data, u.size, lo & mask, rng,
               rank.ctypes.data, uniq_pos.ctypes.data,
               indices.ctypes.data)
        if d < 0:
            raise ValueError(f"value outside interning range (rc={d})")
        return uniq_pos[:d].copy(), indices

    def intern_var(self, data, offsets, max_d: int):
        """First-occurrence intern of n variable byte values.

        Returns ``(first_indices int64[D], indices int32[n])``, or
        ``TOO_MANY_DISTINCT`` when more than ``max_d`` distinct values
        exist (the early exit the caller's dictionary gate wants), or
        raises on corrupt offsets."""
        buf = _as_u8(data)
        offs = np.ascontiguousarray(offsets, dtype=np.int64)
        n = offs.size - 1
        # ~4x max occupancy at the distinct cap keeps probe chains
        # short; the cap (not n) sizes the table, so high-cardinality
        # columns abort cheaply instead of growing the table
        tbits = max(16, (4 * max_d - 1).bit_length())
        # rc=-1 is the C pass reporting table saturation ("caller
        # resizes" in intern.c): unreachable under the 4x sizing above
        # (at most max_d entries ever occupy T >= 4*max_d slots), but
        # honored anyway — retry with a doubled table rather than
        # failing a write on a contract bug.  Bounded at +3 doublings
        # (32x occupancy headroom): a .so that STILL claims saturation
        # is lying, and an unbounded ladder would allocate multi-GiB
        # tables on its way to the error below.
        max_tbits = min(tbits + 3, 31)
        firsts = np.empty(max_d, dtype=np.int64)
        indices = np.empty(max(n, 1), dtype=np.int32)[:n]
        while True:
            T = 1 << tbits
            slots = np.full(T, -1, dtype=np.int32)
            d = self._intern(buf.ctypes.data, buf.size,
                             offs.ctypes.data, n,
                             slots.ctypes.data, T - 1, tbits,
                             firsts.ctypes.data, max_d,
                             indices.ctypes.data)
            if d != -1 or tbits >= max_tbits:
                break
            tbits += 1
        if d == -2:
            return TOO_MANY_DISTINCT
        if d == -3:
            raise ValueError("byte column offsets out of bounds")
        if d < 0:
            raise ValueError(f"intern failed (rc={d})")
        return firsts[:d].copy(), indices


_snappy_inst: "NativeSnappy | None" = None
_hybrid_inst: "NativeHybrid | None" = None
_PLANE_UNAVAILABLE = object()  # cached stale-.so miss (see plane_native)
_plane_inst = None
_DELTA_UNAVAILABLE = object()
_delta_inst = None
_PACK_UNAVAILABLE = object()
_pack_inst = None
_INTERN_UNAVAILABLE = object()
_intern_inst = None
_PAGE_UNAVAILABLE = object()
_page_inst = None
_LZ4_UNAVAILABLE = object()
_lz4_inst = None


def snappy_native() -> NativeSnappy | None:
    """The process-wide native snappy codec, or None if unbuildable."""
    global _snappy_inst
    lib = _lib()
    if lib is None:
        return None
    if _snappy_inst is None:
        _snappy_inst = NativeSnappy(lib)
    return _snappy_inst


def hybrid_native() -> NativeHybrid | None:
    """The process-wide native hybrid scanner, or None if unbuildable."""
    global _hybrid_inst
    lib = _lib()
    if lib is None:
        return None
    if _hybrid_inst is None:
        _hybrid_inst = NativeHybrid(lib)
    return _hybrid_inst


def delta_native() -> NativeDelta | None:
    """The process-wide delta block scanner, or None if unbuildable."""
    global _delta_inst
    if _delta_inst is not None:
        return None if _delta_inst is _DELTA_UNAVAILABLE else _delta_inst
    lib = _lib()
    if lib is None:
        return None
    try:
        _delta_inst = NativeDelta(lib)
    except RuntimeError:  # stale .so predating delta.c: cache the miss
        _delta_inst = _DELTA_UNAVAILABLE
        from ..stats import current_stats

        st = current_stats()
        if st is not None:
            st.native_fallbacks += 1
        return None
    return _delta_inst


def pack_native() -> NativePack | None:
    """The process-wide packing primitives, or None if unbuildable."""
    global _pack_inst
    if _pack_inst is not None:
        return None if _pack_inst is _PACK_UNAVAILABLE else _pack_inst
    lib = _lib()
    if lib is None:
        return None
    try:
        _pack_inst = NativePack(lib)
    except RuntimeError:  # stale .so predating pack.c: cache the miss
        _pack_inst = _PACK_UNAVAILABLE
        from ..stats import current_stats

        st = current_stats()
        if st is not None:
            st.native_fallbacks += 1
        return None
    return _pack_inst


def intern_native() -> NativeIntern | None:
    """The process-wide byte interner, or None if unbuildable."""
    global _intern_inst
    if _intern_inst is not None:
        return None if _intern_inst is _INTERN_UNAVAILABLE \
            else _intern_inst
    lib = _lib()
    if lib is None:
        return None
    try:
        _intern_inst = NativeIntern(lib)
    except RuntimeError:  # stale .so predating intern.c: cache the miss
        _intern_inst = _INTERN_UNAVAILABLE
        from ..stats import current_stats

        st = current_stats()
        if st is not None:
            st.native_fallbacks += 1
        return None
    return _intern_inst


def page_native() -> NativePage | None:
    """The process-wide page assembler, or None if unbuildable."""
    global _page_inst
    if _page_inst is not None:
        return None if _page_inst is _PAGE_UNAVAILABLE else _page_inst
    lib = _lib()
    if lib is None:
        return None
    try:
        _page_inst = NativePage(lib)
    except RuntimeError:  # stale .so predating page.c: cache the miss
        _page_inst = _PAGE_UNAVAILABLE
        from ..stats import current_stats

        st = current_stats()
        if st is not None:
            st.native_fallbacks += 1
        return None
    return _page_inst


def lz4_native() -> NativeLz4 | None:
    """The process-wide native LZ4 raw-block codec, or None if
    unbuildable."""
    global _lz4_inst
    if _lz4_inst is not None:
        return None if _lz4_inst is _LZ4_UNAVAILABLE else _lz4_inst
    lib = _lib()
    if lib is None:
        return None
    try:
        _lz4_inst = NativeLz4(lib)
    except RuntimeError:  # stale .so predating lz4raw.c: cache the miss
        _lz4_inst = _LZ4_UNAVAILABLE
        from ..stats import current_stats

        st = current_stats()
        if st is not None:
            st.native_fallbacks += 1
        return None
    return _lz4_inst


def plane_native() -> NativePlane | None:
    """The process-wide plane primitives, or None if unbuildable."""
    global _plane_inst
    if _plane_inst is not None:
        return None if _plane_inst is _PLANE_UNAVAILABLE else _plane_inst
    lib = _lib()
    if lib is None:
        return None
    try:
        _plane_inst = NativePlane(lib)
    except RuntimeError:  # stale .so predating plane.c: cache the miss
        _plane_inst = _PLANE_UNAVAILABLE
        from ..stats import current_stats

        st = current_stats()
        if st is not None:
            st.native_fallbacks += 1
        return None
    return _plane_inst
