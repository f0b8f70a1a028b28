"""On-chip parity check of EVERY device decode branch on small files.

One minute of chip time validates what the CPU-backend test suite
can't: that each branch's kernels compile and run bit-exactly on real
hardware (the Mosaic straddle miscompile showed interpret-mode parity
is not sufficient).  Builds one small file per encoding family and
runs the `parquet-tool verify` comparison (CPU oracle vs device path,
bitwise).

Usage: python tools/check_device_paths.py [--events]
(exit 0 = all bit-exact; --events additionally asserts PER-PAGE
transport decisions against the aggregate counters and prints the
exact page a gate regression demoted).  ``chip_smoke.py`` imports
:func:`check_all` and runs it in its own process.
"""

from __future__ import annotations

import io
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _files():
    from tpuparquet import CompressionCodec, Encoding, FileWriter
    from tpuparquet.cpu.plain import ByteArrayColumn

    rng = np.random.default_rng(5)
    n = 4000

    def build(name, schema, cols, masks=None, offsets=None,
              expect=None, **kw):
        buf = io.BytesIO()
        w = FileWriter(buf, schema, **kw)
        w.write_columns(cols, masks=masks, offsets=offsets)
        w.close()
        buf.seek(0)
        return name, buf, expect

    m = rng.random(n) >= 0.2
    yield build(
        "plain+dict+snappy (v1)",
        "message m { required int64 a; optional int32 b; "
        "required binary s (STRING); }",
        {"a": rng.integers(-(2**60), 2**60, size=n),
         "b": rng.integers(0, 9, size=int(m.sum()), dtype=np.int32),
         "s": ByteArrayColumn.from_list(
             [b"cat-%d" % (i % 17) for i in range(n)])},
        masks={"b": m}, codec=CompressionCodec.SNAPPY)
    yield build(
        "plain fixed v2 + device snappy path",
        "message m { required int64 a; required double d; }",
        {"a": np.arange(n, dtype=np.int64) % 13,  # compressible
         "d": rng.random(n)},
        codec=CompressionCodec.SNAPPY, data_page_v2=True)
    yield build(
        "delta int64 + int32",
        "message m { required int64 t; required int32 k; }",
        {"t": 1_700_000_000_000 + rng.integers(0, 9000, n).cumsum(),
         "k": rng.integers(-999, 999, size=n, dtype=np.int32)},
        column_encodings={"t": Encoding.DELTA_BINARY_PACKED,
                          "k": Encoding.DELTA_BINARY_PACKED},
        allow_dict=False)
    yield build(
        "byte_stream_split + boolean RLE",
        "message m { required double x; required float y; "
        "required boolean f; }",
        {"x": rng.random(n) * 1e6, "y": rng.random(n).astype(np.float32),
         "f": rng.random(n) >= 0.5},
        column_encodings={"x": Encoding.BYTE_STREAM_SPLIT,
                          "y": Encoding.BYTE_STREAM_SPLIT,
                          "f": Encoding.RLE},
        allow_dict=False)
    yield build(
        "delta_length + delta_byte_array (front-coded)",
        "message m { required binary u; required binary v; }",
        {"u": ByteArrayColumn.from_list(
            [b"val-%d" % (i % 23) for i in range(n)]),
         "v": ByteArrayColumn.from_list(
            [("warehouse/region-3/shelf-%04d/item-%07d"
              % (i // 40, i)).encode() for i in range(n)])},
        column_encodings={"u": Encoding.DELTA_LENGTH_BYTE_ARRAY,
                          "v": Encoding.DELTA_BYTE_ARRAY},
        allow_dict=False)
    yield build(
        "nested list + levels",
        "message m { optional group l (LIST) { repeated group list { "
        "optional int64 element; } } }",
        {"l": rng.integers(0, 10**9, size=3 * n)},
        offsets={"l": np.arange(0, 3 * n + 1, 3, dtype=np.int64)})
    # -- round-4 wire transports -----------------------------------------
    big = 50_000  # large enough to clear the transports' savings gates
    yield build(
        "lane-RLE transport (timestamp i64 uncompressed)",
        "message m { required int64 t; }",
        {"t": 1_700_000_000_000
         + rng.integers(0, 3_600_000, size=big).cumsum()},
        allow_dict=False)
    yield build(
        "byte-plane descent (small-range i32) + V1 optional levels",
        "message m { optional int32 k; }",
        {"k": rng.integers(0, 1000, size=big - big // 10,
                           dtype=np.int32)},
        masks={"k": np.arange(big) % 10 != 0},
        codec=CompressionCodec.SNAPPY, allow_dict=False)
    yield build(
        "PLAIN byte-array token+gather (compressible strings)",
        "message m { required binary s (STRING); }",
        {"s": ByteArrayColumn.from_list(
            [b"the-quick-brown-fox-%d" % (i % 97) for i in range(big)])},
        codec=CompressionCodec.SNAPPY, allow_dict=False)
    # -- round-5 transports / kernels ------------------------------------
    # (the uncompressed-timestamp case above now rides DELTA lanes; these
    # pin the remaining new paths on real silicon)
    flba_rows = rng.integers(0, 256, (n, 16)).astype(np.uint8)
    flba_rows[:, :12] = 7  # shared prefixes -> expanding front coding
    yield build(
        "FLBA delta_byte_array (device copy-token expansion -> lanes)",
        "message m { required fixed_len_byte_array(16) k; }",
        {"k": flba_rows},
        column_encodings={"k": Encoding.DELTA_BYTE_ARRAY},
        allow_dict=False, codec=CompressionCodec.SNAPPY,
        expect={"pages_host_values": 0})
    yield build(
        "delta-lane w=0 (arithmetic sequence ships in 8 bytes)",
        "message m { required int64 t; }",
        {"t": np.arange(big, dtype=np.int64) * 12345},
        allow_dict=False, expect={"pages_device_delta_lanes": 1})
    yield build(
        "byte planes on doubles (delta-ineligible type)",
        "message m { required double d; }",
        {"d": rng.integers(0, 255, size=big).astype(np.float64)},
        allow_dict=False, codec=CompressionCodec.SNAPPY,
        expect={"pages_device_planes": 1})


def _device_pages(st):
    """Device-path page events (the CPU-oracle half of verify emits
    transport="cpu" events; those are not routing decisions)."""
    return [e for e in st.events.pages if e.transport != "cpu"]


def check_all(events_mode: bool, log=print) -> int:
    """Verify every branch's file on both paths; returns the number of
    failed files.  ``events_mode`` asserts PER-PAGE transport decisions,
    not just aggregate counters — a gate regression is then localized
    to the exact page (column, page ordinal, gate numbers) on real
    silicon.  Every file must also decode with no degraded page or
    unit: a device path that quietly fell back to the CPU oracle is a
    failure here, not a pass."""
    from tpuparquet.cli.parquet_tool import cmd_verify

    from tpuparquet.stats import collect_stats

    failures = 0
    for name, buf, expect in _files():
        class _A:
            file = buf

        out = io.StringIO()
        with collect_stats(events=events_mode) as st:
            rc = cmd_verify(_A, out=out)
        detail = out.getvalue().strip().splitlines()[-1]
        if rc == 0 and (st.pages_degraded or st.units_degraded):
            rc = 1
            detail = (f"degraded to the CPU oracle: {st.pages_degraded} "
                      f"pages, {st.units_degraded} units")
        if rc == 0 and events_mode:
            from tpuparquet.obs import TRANSPORT_COUNTER, counter_counts

            # counter/event agreement for EVERY transport counter: each
            # counted page must have exactly one event claiming that
            # transport (the event log and the counters cannot drift)
            d = st.as_dict()
            ev_counts = counter_counts(_device_pages(st))
            for counter in sorted(set(TRANSPORT_COUNTER.values())):
                if d.get(counter, 0) != ev_counts.get(counter, 0):
                    rc = 1
                    detail = (
                        f"event/counter drift: {counter}="
                        f"{d.get(counter, 0)} but "
                        f"{ev_counts.get(counter, 0)} page events")
                    break
        # transport pinning: bit-exactness alone is vacuous for the
        # cases whose point is WHICH path ran (a gate regression that
        # demotes the transport must fail here, not pass silently)
        if rc == 0 and expect:
            d = st.as_dict()
            for key, want in expect.items():
                if d.get(key, 0) < want:
                    rc = 1
                    detail = (f"transport regression: {key}={d.get(key)}"
                              f" < {want} (decode was bit-exact)")
                    if events_mode:
                        # the per-page log names the page that demoted
                        # and what the gate saw
                        detail += "".join(
                            f"\n    {e.column}[{e.page}] {e.encoding} "
                            f"-> {e.transport}"
                            + (f" ({e.reason})" if e.reason else "")
                            for e in _device_pages(st))
                    break
        status = "OK" if rc == 0 else "FAIL"
        log(f"[{status}] {name}: {detail}")
        failures += rc
    log("ALL DEVICE PATHS BIT-EXACT" if not failures
        else f"{failures} FAILURES")
    return failures


def main() -> int:
    import jax

    events_mode = "--events" in sys.argv[1:]
    print(f"backend={jax.default_backend()}"
          + (" (per-page events mode)" if events_mode else ""))
    return 1 if check_all(events_mode) else 0


if __name__ == "__main__":
    sys.exit(main())
