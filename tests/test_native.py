"""Native C snappy codec: parity with the Python fallback and pyarrow.

pyarrow links the reference C++ snappy, so round-trips through it prove
wire-format conformance of both our implementations.
"""

import io

import numpy as np
import pytest

from tpuparquet.compress import snappy_compress, snappy_decompress
from tpuparquet.native import snappy_native

nat = snappy_native()
pytestmark = pytest.mark.skipif(
    nat is None, reason="no C compiler available for the native codec"
)


def _corpus():
    rng = np.random.default_rng(3)
    return [
        b"",
        b"a",
        b"abc",
        b"aaaa",
        b"abcabcabcabcabcabcabc",  # overlapping copies
        bytes(rng.integers(0, 256, 100_000, dtype=np.uint8).tobytes()),
        bytes(1000) + b"hello" * 2000 + bytes(1000),
        np.arange(30_000, dtype=np.int64).tobytes(),  # typical column data
        (b"0123456789abcdef" * 5000),  # long-range matches
        bytes(rng.integers(0, 4, 200_000, dtype=np.uint8).tobytes()),
    ]


class TestNativeSnappy:
    def test_roundtrip_native(self):
        for data in _corpus():
            out = nat.decompress(nat.compress(data))
            assert out == data

    def test_cross_python_native(self):
        for data in _corpus():
            # native-compressed decodes with the python decoder and back
            assert snappy_decompress(nat.compress(data)) == data
            assert nat.decompress(snappy_compress(data)) == data

    def test_pyarrow_interop(self):
        import pyarrow as pa

        codec = pa.Codec("snappy")
        for data in _corpus():
            assert bytes(codec.decompress(
                nat.compress(data), len(data)
            )) == data
            assert nat.decompress(
                bytes(codec.compress(data))
            ) == data

    def test_corrupt_rejected(self):
        with pytest.raises(ValueError):
            nat.decompress(b"\xff\xff\xff\xff\xff\xff\xff\xff\xff\xff")
        good = nat.compress(b"hello world, hello world, hello world")
        with pytest.raises(ValueError):
            nat.decompress(good[:-3])
        with pytest.raises(ValueError):
            nat.decompress(good, expected_size=5)

    def test_file_roundtrip_native(self):
        from tpuparquet import CompressionCodec, FileReader, FileWriter

        buf = io.BytesIO()
        w = FileWriter(buf, "message m { required int64 a; }",
                       codec=CompressionCodec.SNAPPY)
        for i in range(20_000):
            w.add_data({"a": i * 11})
        w.close()
        buf.seek(0)
        r = FileReader(buf)
        vals = np.asarray(r.read_row_group_arrays(0)["a"].values)
        np.testing.assert_array_equal(vals, np.arange(20_000) * 11)


class TestNativeHybridScan:
    """Native C run scanner vs the pure-Python scanner (oracle)."""

    def _nat(self):
        from tpuparquet.native import hybrid_native

        nat = hybrid_native()
        if nat is None:
            pytest.skip("no C compiler available")
        return nat

    @pytest.mark.parametrize("width", [1, 2, 3, 7, 8, 13, 20, 32])
    def test_scan_parity_random(self, width):
        from tpuparquet.cpu.hybrid import _scan_hybrid_py, encode_hybrid

        nat = self._nat()
        rng = np.random.default_rng(width)
        n = 5000
        # mix of constant stretches (RLE) and noise (bit-packed)
        vals = rng.integers(0, 1 << width, size=n, dtype=np.uint64)
        run_starts = rng.choice(n, size=40, replace=False)
        for s in run_starts:
            vals[s : s + int(rng.integers(5, 60))] = vals[s]
        enc = encode_hybrid(vals, width)
        got = nat.scan(enc, n, width, 0)
        exp = _scan_hybrid_py(enc, n, width, 0)
        for g, e in zip(got, exp):
            if isinstance(g, np.ndarray):
                np.testing.assert_array_equal(g, np.asarray(e))
            else:
                assert g == e

    def test_scan_errors(self):
        nat = self._nat()
        with pytest.raises(ValueError):
            nat.scan(b"\x03", 8, 4, 0)        # truncated BP run
        with pytest.raises(ValueError):
            nat.scan(b"\x00\x01", 4, 4, 0)    # zero-length RLE
        with pytest.raises(ValueError):
            nat.scan(b"\x04", 2, 4, 0)        # truncated RLE value
        with pytest.raises(ValueError):
            nat.scan(b"\x04\xff", 2, 4, 0)    # RLE value exceeds width

    def test_decode_uses_native_and_matches(self):
        from tpuparquet.cpu.hybrid import decode_hybrid, encode_hybrid

        self._nat()
        rng = np.random.default_rng(0)
        vals = np.repeat(rng.integers(0, 32, size=300, dtype=np.uint64),
                         rng.integers(1, 30, size=300))
        enc = encode_hybrid(vals, 5)
        got = decode_hybrid(enc, len(vals), 5)
        np.testing.assert_array_equal(got.astype(np.uint64), vals)


class TestDeviceSnappy:
    """Device (token-table + pointer-doubling) snappy vs host C oracle."""

    def _nat(self):
        from tpuparquet.native import snappy_native

        nat = snappy_native()
        if nat is None:
            pytest.skip("no C compiler available")
        return nat

    def cases(self):
        rng = np.random.default_rng(0)
        text = b"the quick brown fox jumps over the lazy dog. " * 500
        return {
            "random": bytes(rng.integers(0, 256, 10_000, dtype=np.uint8)),
            "text": text,
            "rle": b"\xab" * 50_000,           # offset-1 overlap chains
            "mixed": text + b"\x00" * 10_000 + text[:1000],
            "tiny": b"xy",
            "empty": b"",
        }

    def test_parity_all_cases(self):
        from tpuparquet.kernels.snappy import decompress_device

        nat = self._nat()
        for name, data in self.cases().items():
            block = nat.compress(data)
            got = np.asarray(decompress_device(block, len(data)))
            assert got.tobytes() == data, name

    def test_parity_pyarrow_block(self):
        pa = pytest.importorskip("pyarrow")
        from tpuparquet.kernels.snappy import decompress_device

        self._nat()
        data = (b"abcabcabc" * 3000) + bytes(range(256)) * 40
        block = pa.compress(data, codec="snappy", asbytes=True)
        got = np.asarray(decompress_device(block))
        assert got.tobytes() == data

    def test_scan_tokens_shape(self):
        nat = self._nat()
        data = b"hello world, hello world, hello world!"
        tok_end, tok_src, lits, out_len = nat.scan_tokens(nat.compress(data))
        assert out_len == len(data)
        assert tok_end[-1] == len(data)
        assert (np.diff(tok_end) > 0).all()
        # at least one literal and (for this input) one copy token
        assert (tok_src < 0).any() and (tok_src >= 0).any()

    def test_corrupt_rejected(self):
        from tpuparquet.kernels.snappy import decompress_device

        nat = self._nat()
        good = nat.compress(b"hello world, hello world")
        with pytest.raises(ValueError):
            decompress_device(good[:-2])
        with pytest.raises(ValueError):
            decompress_device(good, expected_size=5)


class TestNativePlane:
    """Strided lane/byte-plane primitives behind the wire planner."""

    def _nat(self):
        from tpuparquet.native import plane_native

        p = plane_native()
        if p is None:
            pytest.skip("native plane primitives unavailable")
        return p

    def test_gather_parity_all_strides(self):
        nat = self._nat()
        rng = np.random.default_rng(11)
        buf = rng.integers(0, 256, 8192, dtype=np.uint8)
        words = buf.view("<u4")
        views = [
            words[0::2], words[1::2],          # int64 u32 lanes
            words[0::3], words[2::3],          # FLBA 12-byte lanes
            buf[0::4], buf[3::4],              # int32 byte planes
            buf[1::8], buf[7::8],              # int64 byte planes
            buf[5::12],                        # FLBA byte plane
        ]
        for v in views:
            assert np.array_equal(nat.gather(v), np.ascontiguousarray(v))

    def test_gather_no_overread_at_page_boundary(self):
        """The widened-load fast paths must not read past the buffer:
        lane bases are offset into the segment, so the last element's
        natural 8-byte load would cross the end (SIGSEGV when the
        segment is a zero-copy view ending at an mmap page edge)."""
        import mmap

        nat = self._nat()
        m = mmap.mmap(-1, 4096 * 2)
        seg = np.frombuffer(m, dtype=np.uint8)[4096:]  # ends at map end
        seg[:] = np.arange(4096, dtype=np.uint64).view(np.uint8)[:4096]
        words = seg.view("<u4")
        for v in (words[1::2], seg[3::4], seg[7::8]):
            assert np.array_equal(nat.gather(v), np.ascontiguousarray(v))

    def test_run_scan_matches_numpy(self):
        nat = self._nat()
        rng = np.random.default_rng(12)
        for plane in (
            rng.integers(0, 3, 10_000, dtype=np.uint8)[1::4],
            np.repeat(rng.integers(0, 9, 40), 25).astype(np.uint8),
            rng.integers(0, 2, 5_000, dtype=np.uint32)[0::2].copy().reshape(-1),
            np.zeros(1, dtype=np.uint32),
        ):
            count = plane.size
            ends, vals = nat.run_scan(plane, count + 1)
            change = np.flatnonzero(plane[1:] != plane[:-1]) + 1
            assert np.array_equal(ends[:-1], change.astype(np.int32))
            assert ends[-1] == count
            assert np.array_equal(
                vals, plane[np.concatenate(([0], change)).astype(np.int64)]
            )

    def test_run_scan_cap_aborts(self):
        nat = self._nat()
        plane = np.arange(1000, dtype=np.uint32)  # 1000 runs
        assert nat.run_scan(plane, 10) is None

    def test_rle_table_native_numpy_identical(self):
        import tpuparquet.kernels.device as D
        from tpuparquet.kernels.decode import bucket

        self._nat()
        rng = np.random.default_rng(13)
        plane = np.repeat(rng.integers(0, 50, 200), 17).astype(np.uint32)
        n = plane.size
        t1 = D._rle_table(plane, n, np.uint32, bucket, max_runs=n)
        orig = D.plane_native
        D.plane_native = lambda: None
        try:
            t2 = D._rle_table(plane, n, np.uint32, bucket, max_runs=n)
        finally:
            D.plane_native = orig
        for a, b in zip(t1[:2], t2[:2]):
            assert np.array_equal(a, b)
        assert t1[2] == t2[2]


class TestNativeDeltaScan:
    """C block scanner vs the pure-Python structure pass."""

    def _force_fallback(self, monkeypatch):
        import tpuparquet.native as N

        monkeypatch.setattr(N, "_delta_inst", N._DELTA_UNAVAILABLE)

    def _scan_both(self, monkeypatch, data):
        from tpuparquet.cpu.delta import scan_delta_structure

        try:
            a = scan_delta_structure(data)
        except ValueError:
            a = ("error", )
        self._force_fallback(monkeypatch)
        try:
            b = scan_delta_structure(data)
        except ValueError:
            b = ("error", )
        monkeypatch.undo()
        return a, b

    def test_parity_roundtrip_streams(self, monkeypatch):
        from tpuparquet.cpu.delta import encode_delta_binary_packed
        from tpuparquet.native import delta_native

        if delta_native() is None:
            pytest.skip("native delta scanner unavailable")
        rng = np.random.default_rng(21)
        streams = [
            encode_delta_binary_packed(rng.integers(-50, 50, 1000)),
            encode_delta_binary_packed(
                np.int64(1 << 40) + rng.integers(0, 9, 4099).cumsum()),
            encode_delta_binary_packed(np.array([7], dtype=np.int64)),
            encode_delta_binary_packed(np.zeros(0, dtype=np.int64)),
            encode_delta_binary_packed(
                rng.integers(-(1 << 62), 1 << 62, 513)),
        ]
        for enc in streams:
            a, b = self._scan_both(monkeypatch, np.frombuffer(enc, np.uint8))
            assert a != ("error",) and b != ("error",)
            assert np.array_equal(np.asarray(a.md_blocks, dtype=np.int64),
                                  np.asarray(b.md_blocks, dtype=np.int64))
            for f in ("mb_w", "mb_pos", "mb_start"):
                assert np.array_equal(
                    np.asarray(getattr(a, f), dtype=np.int64),
                    np.asarray(getattr(b, f), dtype=np.int64)), f
            assert (a.end_pos, a.total, a.first, a.block_size) == \
                   (b.end_pos, b.total, b.first, b.block_size)

    def test_parity_malformed(self, monkeypatch):
        from tpuparquet.cpu.delta import encode_delta_binary_packed
        from tpuparquet.native import delta_native

        if delta_native() is None:
            pytest.skip("native delta scanner unavailable")
        rng = np.random.default_rng(22)
        enc = bytearray(encode_delta_binary_packed(
            rng.integers(-1000, 1000, 700)))
        cases = [bytes(enc[:i]) for i in (0, 1, 3, 5, 9, len(enc) - 7)]
        for i in range(4, len(enc), 37):
            bad = bytearray(enc)
            bad[i] ^= 0xFF
            cases.append(bytes(bad))
        for data in cases:
            a, b = self._scan_both(monkeypatch, np.frombuffer(
                data, dtype=np.uint8))
            ea, eb = a == ("error",), b == ("error",)
            assert ea == eb, f"native={'err' if ea else 'ok'} " \
                             f"fallback={'err' if eb else 'ok'}"

    def test_overlong_varint_rejected(self, monkeypatch):
        """A >64-bit total/min_delta must raise ValueError on both
        paths, not surface as OverflowError from np.asarray."""
        from tpuparquet.cpu.delta import scan_delta_structure

        # header: block_size=128, n_miniblocks=4, then an 11-byte
        # uvarint total (> 2^70 continuation limit passes; value huge)
        stream = bytes([128, 1, 4]) + b"\xff" * 10 + b"\x01"
        for force in (False, True):
            if force:
                self._force_fallback(monkeypatch)
            with pytest.raises(ValueError):
                scan_delta_structure(np.frombuffer(stream, np.uint8))
            if force:
                monkeypatch.undo()


class TestNativePack:
    """C bit packer + fused hybrid run-table repack."""

    def _nat(self):
        from tpuparquet.native import pack_native

        p = pack_native()
        if p is None:
            pytest.skip("native pack primitives unavailable")
        return p

    def test_pack_roundtrip_all_widths(self):
        from tpuparquet.cpu.bitpack import pack, unpack

        self._nat()
        rng = np.random.default_rng(31)
        for w in (1, 2, 3, 5, 7, 8, 12, 17, 22, 31, 32, 33, 40, 48,
                  63, 64):
            hi = (1 << w) - 1 if w < 64 else (1 << 64) - 1
            v = rng.integers(0, hi, 1003, dtype=np.uint64) if hi \
                else np.zeros(1003, np.uint64)
            v[0] = hi  # boundary value
            out = unpack(pack(v, w), len(v), w)
            assert np.array_equal(out.astype(np.uint64), v), w

    def test_pack_rejects_oversized_value(self):
        from tpuparquet.cpu.bitpack import pack

        self._nat()
        with pytest.raises(ValueError, match="does not fit"):
            pack(np.array([4], dtype=np.uint64), 2)

    def test_hybrid_repack_matches_expand_pack(self):
        from tpuparquet.cpu.bitpack import pack
        from tpuparquet.cpu.hybrid import (
            encode_hybrid,
            expand_scan,
            scan_hybrid,
        )

        nat = self._nat()
        rng = np.random.default_rng(32)
        for trial in range(60):
            w = int(rng.integers(1, 33))
            n = int(rng.integers(1, 6000))
            vals = rng.integers(0, 1 << w, n, dtype=np.uint64)
            mode = trial % 4
            if mode == 0:  # long RLE runs
                vals = np.repeat(vals[: max(n // 8, 1)], 8)
            elif mode == 1:  # mixed runs + noise
                vals = np.where(rng.random(n) < 0.7, vals[0], vals)
            n = len(vals)
            enc = encode_hybrid(vals.astype(np.uint32), w)
            scan = scan_hybrid(np.frombuffer(enc, np.uint8), n, w)
            want = pack(expand_scan(*scan[:6], n, w)[:n], w)
            got = nat.hybrid_repack(scan[0], scan[1], scan[2], scan[3],
                                    scan[4], scan[5], n, w)
            assert got is not None and got.tobytes() == want, (trial, w)

    def test_hybrid_repack_declines_uncovered_table(self):
        nat = self._nat()
        # a table that stops short of count is not a valid scan output;
        # the wrapper leaves it to the fallback
        assert nat.hybrid_repack(
            np.array([5], dtype=np.int32), np.array([1], np.uint8),
            np.array([3], np.uint32), np.array([0], np.int32),
            np.zeros(0, np.uint8), 0, 10, 3) is None

    def test_hybrid_repack_rejects_oversized_rle_value(self):
        nat = self._nat()
        with pytest.raises(ValueError, match="does not fit"):
            nat.hybrid_repack(
                np.array([16], dtype=np.int32), np.array([1], np.uint8),
                np.array([5], np.uint32), np.array([0], np.int32),
                np.zeros(0, np.uint8), 0, 16, 2)

    def test_hybrid_expand_matches_numpy(self):
        import tpuparquet.native as N
        from tpuparquet.cpu.hybrid import (
            encode_hybrid,
            expand_scan,
            scan_hybrid,
        )

        self._nat()
        rng = np.random.default_rng(33)
        for trial in range(50):
            w = int(rng.integers(1, 33))
            n = int(rng.integers(1, 6000))
            vals = rng.integers(0, 1 << w, n, dtype=np.uint64)
            if trial % 3 == 0:
                vals = np.where(rng.random(n) < 0.6, vals[0], vals)
            enc = encode_hybrid(vals.astype(np.uint32), w)
            scan = scan_hybrid(np.frombuffer(enc, np.uint8), n, w)
            got = expand_scan(*scan[:6], n, w)
            # numpy fallback as the oracle for the oracle
            from unittest import mock
            with mock.patch.object(N, "_pack_inst",
                                   N._PACK_UNAVAILABLE):
                want = expand_scan(*scan[:6], n, w)
            assert np.array_equal(got, want), (trial, w, n)
            assert np.array_equal(got, vals.astype(got.dtype))


class TestNativeDeltaEmit:
    def test_byte_identical_to_numpy(self):
        from unittest import mock

        import tpuparquet.native as N
        from tpuparquet.cpu.delta import (
            decode_delta_binary_packed,
            encode_delta_binary_packed,
        )

        nat = N.pack_native()
        if nat is None or nat._delta_emit is None:
            pytest.skip("native delta emit unavailable")
        rng = np.random.default_rng(90)
        cases = [
            np.int64(1 << 41) + rng.integers(0, 9, 40_000).cumsum(),
            rng.integers(-(2**62), 2**62, 4099),
            rng.integers(-5, 5, 1),
            np.zeros(0, dtype=np.int64),
            np.full(777, -3, dtype=np.int64),
            rng.integers(-(2**30), 2**30, 513).astype(np.int32),
        ]
        for i, v in enumerate(cases):
            is32 = v.dtype == np.int32
            a = encode_delta_binary_packed(v, is32=is32)
            with mock.patch.object(N, "_pack_inst",
                                   N._PACK_UNAVAILABLE):
                b = encode_delta_binary_packed(v, is32=is32)
            assert a == b, i
            dec, _ = decode_delta_binary_packed(
                np.frombuffer(a, np.uint8),
                np.int32 if is32 else np.int64)
            np.testing.assert_array_equal(dec, v)


def test_native_library_builds_when_compiler_available():
    """A compile error in any native/*.c silently downgrades every
    consumer to its Python fallback (the skip-based tests then skip
    rather than fail).  On a machine WITH a compiler, failure to build
    is a bug, not an environment limitation."""
    import shutil

    from tpuparquet.native import _lib

    if not any(shutil.which(cc) for cc in ("cc", "gcc", "clang")):
        pytest.skip("no C compiler on this machine")
    assert _lib() is not None, \
        "native library failed to build with a compiler present " \
        "(check cc errors on tpuparquet/native/*.c)"


@pytest.mark.parametrize("carried", ["no-stamp", "stale-stamp"])
def test_native_rebuilds_a_library_not_built_from_these_sources(
        tmp_path, monkeypatch, carried):
    """A .so carried in from elsewhere (no stamp, or a stamp for other
    sources) is rebuilt, whatever its mtime; a matching stamp is
    trusted."""
    import os

    import tpuparquet.native as native

    so = tmp_path / "_tpq_native.so"
    monkeypatch.setattr(native, "_SO", str(so))
    monkeypatch.setattr(native, "_STAMP", str(so) + ".sha256")
    so.write_bytes(b"not a library")
    os.utime(so, (2**31, 2**31))  # newer than any source
    if carried == "stale-stamp":
        (tmp_path / "_tpq_native.so.sha256").write_text("0" * 64)
    assert native._build()
    assert so.read_bytes()[:4] == b"\x7fELF"
    assert ((tmp_path / "_tpq_native.so.sha256").read_text()
            == native._source_hash())
    built = so.stat().st_mtime_ns
    assert native._build()
    assert so.stat().st_mtime_ns == built  # matching stamp: no rebuild


class TestNativeHybridEncode:
    def test_byte_identical_to_python(self):
        from unittest import mock

        import tpuparquet.native as N
        from tpuparquet.cpu.hybrid import decode_hybrid, encode_hybrid

        nat = N.pack_native()
        if nat is None or nat._hybrid_encode is None:
            pytest.skip("native hybrid encode unavailable")
        rng = np.random.default_rng(91)
        for trial in range(120):
            w = int(rng.integers(1, 33)) if trial % 4 \
                else int(rng.integers(33, 65))
            n = int(rng.integers(0, 3000))
            vals = rng.integers(0, 1 << min(w, 62), n, dtype=np.uint64)
            mode = trial % 5
            if mode == 0:  # exact 8-runs
                vals = np.repeat(vals[: max(n // 8, 1)], 8)[:n]
            elif mode == 1:  # long constant stretches + noise
                vals = np.where(rng.random(n) < 0.8,
                                vals[0] if n else 0, vals)
            elif mode == 2 and n:  # one constant run
                vals = np.full(n, vals[0])
            a = encode_hybrid(vals, w)
            with mock.patch.object(N, "_pack_inst",
                                   N._PACK_UNAVAILABLE):
                b = encode_hybrid(vals, w)
            assert a == b, (trial, w, len(vals))
            if len(vals):
                dec = decode_hybrid(np.frombuffer(a, np.uint8),
                                    len(vals), w)
                assert np.array_equal(dec.astype(np.uint64), vals)

    def test_oversized_rle_value_refused_both_paths(self):
        from unittest import mock

        import tpuparquet.native as N
        from tpuparquet.cpu.hybrid import encode_hybrid

        for force in (False, True):
            ctx = (mock.patch.object(N, "_pack_inst",
                                     N._PACK_UNAVAILABLE)
                   if force else mock.patch.object(
                       N, "_pack_inst", N._pack_inst))
            with ctx:
                with pytest.raises(ValueError, match="does not fit"):
                    encode_hybrid(np.full(16, 12, dtype=np.uint64), 3)


class TestNativeDbaAssemble:
    def test_parity_and_malformed(self):
        from unittest import mock

        import tpuparquet.native as N
        from tpuparquet.cpu.delta import (
            decode_delta_byte_array,
            encode_delta_byte_array,
        )

        nat = N.delta_native()
        if nat is None or nat._dba is None:
            pytest.skip("native DBA assembler unavailable")
        rng = np.random.default_rng(95)
        for trial in range(20):
            n = int(rng.integers(1, 2000))
            vals = [f"pre_{trial}_{rng.integers(0, 40)}_{i}".encode()
                    for i in range(n)]
            enc = encode_delta_byte_array(vals)
            a, _ = decode_delta_byte_array(
                np.frombuffer(enc, np.uint8), n)
            with mock.patch.object(N, "_delta_inst",
                                   N._DELTA_UNAVAILABLE):
                b, _ = decode_delta_byte_array(
                    np.frombuffer(enc, np.uint8), n)
            assert np.array_equal(a.offsets, b.offsets)
            assert np.array_equal(a.data, b.data)
            assert a.to_list() == vals
        # malformed: both paths raise the same ValueError message
        from tpuparquet.cpu.delta import assemble_delta_byte_array

        cases = [
            (np.array([0, 5], dtype=np.int64),   # prefix > prev len
             np.array([0, 2, 4], dtype=np.int64),
             np.frombuffer(b"abcd", np.uint8)),
            (np.array([0, -1], dtype=np.int64),  # negative prefix
             np.array([0, 2, 4], dtype=np.int64),
             np.frombuffer(b"abcd", np.uint8)),
        ]
        for args in cases:
            self._both_raise_same(args)

    def _both_raise_same(self, args):
        from unittest import mock

        import tpuparquet.native as N
        from tpuparquet.cpu.delta import assemble_delta_byte_array

        msgs = []
        for force in (False, True):
            ctx = (mock.patch.object(N, "_delta_inst",
                                     N._DELTA_UNAVAILABLE)
                   if force else mock.patch.object(
                       N, "_delta_inst", N._delta_inst))
            with ctx:
                with pytest.raises(ValueError) as ei:
                    assemble_delta_byte_array(*args)
                msgs.append(str(ei.value))
        assert msgs[0] == msgs[1], msgs


class TestNativeIntern:
    """One-pass C byte interner vs the numpy interner: identical
    (dictionary, indices) on every shape, plus the early exits the
    numpy path cannot express."""

    def test_parity_with_numpy_interner(self):
        import tpuparquet.cpu.dictionary as D
        from tpuparquet.cpu.plain import ByteArrayColumn
        from tpuparquet.native import intern_native

        if intern_native() is None:
            pytest.skip("native interner unavailable")
        rng = np.random.default_rng(60)
        cases = [
            [f"v{i % 37}".encode() for i in range(5_000)],
            [b"", b"a\x00", b"a", b"", b"a\x00"],           # NULs, dups
            [rng.bytes(int(rng.integers(0, 50)))
             for _ in range(3_000)],                         # random blobs
            [b"x"] * 2_000,                                  # constant
            [f"{i}".encode() for i in range(3_000)],         # all distinct
        ]
        for vals in cases:
            col = ByteArrayColumn.from_list(vals)
            want = D.build_dictionary(col)
            got = D.intern_byte_column(col, 1 << 15)
            from tpuparquet.native import TOO_MANY_DISTINCT
            if got is TOO_MANY_DISTINCT:
                assert len(set(vals)) > (1 << 15)
                continue
            assert got is not None
            assert got[0] == want[0]
            np.testing.assert_array_equal(got[1], want[1])

    def test_too_many_early_exit(self):
        import tpuparquet.cpu.dictionary as D
        from tpuparquet.cpu.plain import ByteArrayColumn
        from tpuparquet.native import intern_native

        if intern_native() is None:
            pytest.skip("native interner unavailable")
        from tpuparquet.native import TOO_MANY_DISTINCT

        col = ByteArrayColumn.from_list(
            [f"u{i}".encode() for i in range(40_000)])
        assert D.intern_byte_column(col, 1 << 15) is TOO_MANY_DISTINCT
        # cap + 1 distinct is the boundary; cap distinct is accepted
        col2 = ByteArrayColumn.from_list(
            [f"u{i}".encode() for i in range(100)])
        out = D.intern_byte_column(col2, 100)
        assert out is not None and out is not TOO_MANY_DISTINCT
        assert len(out[0]) == 100
        assert D.intern_byte_column(col2, 99) is TOO_MANY_DISTINCT

    def test_custom_row_hash_bypasses_native(self):
        """A pluggable hash must not be silently ignored by the C
        pass (which has its own FNV)."""
        import tpuparquet.cpu.dictionary as D
        from tpuparquet.cpu.plain import ByteArrayColumn

        col = ByteArrayColumn.from_list([b"a", b"b", b"a"])
        try:
            D.row_hash_func = lambda rows: np.zeros(
                rows.shape[0], dtype=np.uint64)
            assert D.intern_byte_column(col, 100) is None
        finally:
            D.row_hash_func = None

    def test_writer_output_byte_identical(self):
        """Files written through the native interner equal the numpy
        path byte for byte (first-occurrence order preserved)."""
        import io as _io

        import tpuparquet.cpu.dictionary as D
        from tpuparquet import CompressionCodec, FileWriter
        from tpuparquet.native import intern_native

        if intern_native() is None:
            pytest.skip("native interner unavailable")
        rng = np.random.default_rng(61)
        vals = [f"s{int(i) % 211}".encode()
                for i in rng.integers(0, 10_000, 50_000)]

        def build():
            buf = _io.BytesIO()
            w = FileWriter(buf,
                           "message m { required binary s (STRING); }",
                           codec=CompressionCodec.SNAPPY)
            w.write_columns(
                {"s": __import__("tpuparquet.cpu.plain",
                                 fromlist=["ByteArrayColumn"])
                 .ByteArrayColumn.from_list(vals)})
            w.close()
            return buf.getvalue()

        native_bytes = build()
        orig = D.intern_byte_column
        D.intern_byte_column = lambda *a, **k: None  # force numpy path
        try:
            numpy_bytes = build()
        finally:
            D.intern_byte_column = orig
        assert native_bytes == numpy_bytes


class TestNativeHybridEncode32:
    """The u32-input hybrid encoder (hybrid.c tpq_hybrid_encode32) —
    the write pipeline's dict-index/level stream source — must be
    byte-identical to the u64 encoder and the Python encoder, and runs
    under the ASan/UBSan leg on every shape here."""

    def _shapes(self, width, rng):
        top = 1 << min(width, 16)
        return [
            np.zeros(0, dtype=np.uint64),
            rng.integers(0, top, size=1009).astype(np.uint64),
            np.repeat(rng.integers(0, top, size=37).astype(np.uint64),
                      rng.integers(1, 41, size=37)),
            np.full(801, top - 1, dtype=np.uint64),
            np.arange(13, dtype=np.uint64) % top,
            np.r_[np.zeros(64), rng.integers(0, top, size=7),
                  np.zeros(9)].astype(np.uint64),
        ]

    @pytest.mark.parametrize("width", [1, 2, 3, 5, 7, 8, 12, 16, 31, 32])
    def test_byte_identical_to_u64_and_python(self, width):
        from tpuparquet.cpu import hybrid as H
        from tpuparquet.native import pack_native

        nat = pack_native()
        if nat is None or nat._hybrid_encode32 is None:
            pytest.skip("native encoder unavailable")
        rng = np.random.default_rng(width)
        for v in self._shapes(width, rng):
            ref64 = nat.hybrid_encode(v, width)
            got = nat.hybrid_encode32(v.astype(np.uint32), width)
            assert got is not None
            assert bytes(got) == bytes(ref64)
            # and the pure-Python encoder agrees (decode side re-pins)
            py = H.encode_hybrid.__wrapped__(v, width) if hasattr(
                H.encode_hybrid, "__wrapped__") else None
            dec = H.decode_hybrid(bytes(got), v.size, width)
            assert np.array_equal(dec.astype(np.uint64), v)
            assert py is None or py == bytes(got)

    def test_oversized_value_refused(self):
        from tpuparquet.native import pack_native

        nat = pack_native()
        if nat is None or nat._hybrid_encode32 is None:
            pytest.skip("native encoder unavailable")
        v = np.array([7, 9], dtype=np.uint32)
        with pytest.raises(ValueError, match="does not fit"):
            nat.hybrid_encode32(v, 3)

    def test_int32_view_path_in_encode_hybrid(self):
        """encode_hybrid takes the no-widening view for (u)int32 input
        and the bytes match the u64 widening path."""
        from tpuparquet.cpu.hybrid import encode_hybrid

        rng = np.random.default_rng(5)
        idx = rng.integers(0, 1 << 10, size=4096).astype(np.int32)
        assert encode_hybrid(idx, 11) == encode_hybrid(
            idx.astype(np.uint64), 11)


class TestNativePageAssembly:
    """page.c: CRC32 parity with zlib and one-pass body encode parity
    with the pure level/index composition — native encode must decode
    through the pure decoders (and vice versa for the CRC)."""

    def _pg(self):
        from tpuparquet.native import page_native

        pg = page_native()
        if pg is None:
            pytest.skip("native page assembler unavailable")
        return pg

    def test_crc32_matches_zlib(self):
        import zlib

        pg = self._pg()
        rng = np.random.default_rng(9)
        for size in (0, 1, 3, 7, 8, 9, 63, 64, 65, 4097, 1 << 18):
            b = rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
            assert pg.crc32(b) == zlib.crc32(b)
            assert pg.crc32(b, 0xDEAD) == zlib.crc32(b, 0xDEAD)
        # chained == whole (the V2 multi-segment CRC path)
        a, b = b[: 1000], b[1000:]
        assert pg.crc32(b, pg.crc32(a)) == zlib.crc32(a + b)

    def test_encode_v1_matches_pure_composition(self):
        from tpuparquet.cpu.dictionary import encode_dict_indices
        from tpuparquet.cpu.levels import encode_levels_v1

        pg = self._pg()
        rng = np.random.default_rng(11)
        n = 6000
        rep = rng.integers(0, 2, size=n).astype(np.int32)
        rep[0] = 0
        dl = rng.integers(0, 4, size=n).astype(np.int32)
        nn = int((dl == 3).sum())
        idx = rng.integers(0, 29, size=nn).astype(np.int32)
        pure = (encode_levels_v1(rep, 1) + encode_levels_v1(dl, 3)
                + encode_dict_indices(idx, 29))
        out = np.empty(len(pure) + 8192, dtype=np.uint8)
        r = pg.encode(rep.view(np.uint32), dl.view(np.uint32), n,
                      1, 2, False, idx.view(np.uint32), 5, None, out)
        assert r is not None and bytes(out[: sum(r)]) == pure

    def test_encode_v2_matches_pure_composition(self):
        from tpuparquet.cpu.dictionary import encode_dict_indices
        from tpuparquet.cpu.levels import encode_levels_v2

        pg = self._pg()
        rng = np.random.default_rng(12)
        n = 3000
        dl = rng.integers(0, 2, size=n).astype(np.int32)
        nn = int((dl == 1).sum())
        idx = rng.integers(0, 6, size=nn).astype(np.int32)
        pure = encode_levels_v2(dl, 1) + encode_dict_indices(idx, 6)
        out = np.empty(len(pure) + 8192, dtype=np.uint8)
        r = pg.encode(None, dl.view(np.uint32), n, 0, 1, True,
                      idx.view(np.uint32), 3, None, out)
        assert r is not None and r[0] == 0
        assert bytes(out[: sum(r)]) == pure

    def test_native_encode_pure_decode_roundtrip(self):
        """Native-assembled streams decode through the pure two-pass
        decoders (and the values segment passes through verbatim)."""
        from tpuparquet.cpu.dictionary import decode_dict_indices
        from tpuparquet.cpu.levels import decode_levels_v1

        pg = self._pg()
        rng = np.random.default_rng(13)
        n = 5000
        dl = rng.integers(0, 2, size=n).astype(np.int32)
        nn = int((dl == 1).sum())
        idx = rng.integers(0, 17, size=nn).astype(np.int32)
        out = np.empty(1 << 16, dtype=np.uint8)
        r = pg.encode(None, dl.view(np.uint32), n, 0, 1, False,
                      idx.view(np.uint32), 5, None, out)
        body = bytes(out[: sum(r)])
        dec_dl, pos = decode_levels_v1(body, n, 1)
        assert np.array_equal(dec_dl, dl)
        assert np.array_equal(decode_dict_indices(body[pos:], nn), idx)

    def test_values_passthrough_and_cap_shortfall(self):
        pg = self._pg()
        vals = np.arange(997, dtype=np.uint8)
        out = np.empty(2048, dtype=np.uint8)
        r = pg.encode(None, None, 0, 0, 0, False, None, 0, vals, out)
        assert r == (0, 0, 997)
        assert bytes(out[:997]) == vals.tobytes()
        tiny = np.empty(16, dtype=np.uint8)
        assert pg.encode(None, None, 0, 0, 0, False, None, 0, vals,
                         tiny) is None  # caller falls back, no crash

    def test_compress_into_matches_compress(self):
        from tpuparquet.native import snappy_native

        sn = snappy_native()
        if sn is None:
            pytest.skip("native snappy unavailable")
        rng = np.random.default_rng(14)
        bodies = [
            (np.arange(50_000, dtype=np.int64) // 7).tobytes(),
            rng.integers(0, 256, size=200_000, dtype=np.uint8).tobytes(),
            b"",
            b"x" * (1 << 17),  # crosses the 64 KiB block boundary
        ]
        for mm in (4, 8):
            for body in bodies:
                ref = sn.compress(body, min_match=mm)
                out = np.empty(len(body) + len(body) // 2 + 64,
                               dtype=np.uint8)
                k = sn.compress_into(np.frombuffer(body, np.uint8),
                                     out, min_match=mm)
                assert bytes(out[:k]) == ref
                # slack-store decompress path: out sized exactly
                # total + 16 opts into the speculative fixed-width
                # copies — must still round-trip byte-exact
                buf = np.empty(max(len(body), 1) + 16, dtype=np.uint8)
                got = sn.decompress_np(ref, len(body), out=buf)
                assert got.tobytes() == body


class TestNativeInternRange:
    """intern.c tpq_intern_range32/64 vs the numpy small-range
    dictionary build: identical first-occurrence dictionaries and
    indices for signed/unsigned 32/64-bit columns."""

    def _nat(self):
        from tpuparquet.native import intern_native

        nat = intern_native()
        if nat is None or nat._range64 is None:
            pytest.skip("native range interner unavailable")
        return nat

    @pytest.mark.parametrize("dt", [np.int32, np.int64,
                                    np.uint32, np.uint64])
    def test_matches_numpy_smallrange(self, dt):
        import tpuparquet.cpu.dictionary as D
        from tpuparquet.native import intern_native

        nat = self._nat()
        rng = np.random.default_rng(15)
        arr = rng.integers(3, 400, size=20_000).astype(dt)
        lo = int(arr.min())
        span = int(arr.max()) - lo + 1
        up, ind = nat.intern_range(arr, lo, span)
        uniq = arr[up]
        # numpy reference: force the pure path by hiding the native
        # (the builder resolves it through the module at call time)
        import tpuparquet.native as N

        orig = N.intern_native
        N.intern_native = lambda: None
        try:
            ref_uniq, ref_ind = D._build_int_dictionary_smallrange(arr)
        finally:
            N.intern_native = orig
        assert np.array_equal(uniq, ref_uniq)
        assert np.array_equal(ind, ref_ind)

    def test_signed_negative_span(self):
        import tpuparquet.cpu.dictionary as D

        nat = self._nat()
        rng = np.random.default_rng(16)
        arr = rng.integers(-200, 55, size=9000).astype(np.int64)
        up, ind = nat.intern_range(arr, int(arr.min()),
                                   int(arr.max()) - int(arr.min()) + 1)
        import tpuparquet.native as N

        orig = N.intern_native
        N.intern_native = lambda: None
        try:
            ref_uniq, ref_ind = D._build_int_dictionary_smallrange(arr)
        finally:
            N.intern_native = orig
        assert np.array_equal(arr[up], ref_uniq)
        assert np.array_equal(ind, ref_ind)

    def test_out_of_range_value_raises(self):
        nat = self._nat()
        arr = np.array([5, 6, 99], dtype=np.int64)
        with pytest.raises(ValueError, match="outside"):
            nat.intern_range(arr, 5, 10)
