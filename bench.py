"""Benchmark: decoded values/sec across the BASELINE.md config ladder.

Each config builds its file through the columnar writer
(``write_columns``), decodes ≥50M values, and is parity-gated against
the CPU oracle before its number is reported:

  1. single int64 column, PLAIN, uncompressed, 1 row group
  2. NYC-Taxi-like int32/int64, hybrid + dictionary, Snappy  (headline)
  3. DELTA_BINARY_PACKED int64 timestamps + nullable nested LIST
  4. mixed wide table: STRING dict + float64 PLAIN, DataPage V2, Snappy
  5. multi-file sharded scan (ShardedScan over the device mesh)

The baseline for every config is this framework's own CPU oracle path
(the reference publishes no numbers — SURVEY.md §6) measured in the same
process; the device number is the pipelined device batch-decode path.

Parity gate per row group: full elementwise comparison on the first row
group, and a device-computed checksum (data-lane/level sums, no bulk
device->host readback) against the CPU oracle's checksum on every one.

Prints one JSON line per config, then the headline line (config 2) in
the driver schema — the LAST line is the official record:
    {"metric": ..., "value": N, "unit": "values/sec", "vs_baseline": N,
     "configs": {...all five...}}
"""

from __future__ import annotations

import io
import json
import os
import sys
import time

import numpy as np

# ≥50M decoded values per config (the honest regime — fixed overheads
# amortized; VERDICT round-2 ask #2).  Env override is for smoke tests.
TARGET = int(os.environ.get("TPQ_BENCH_TARGET", 50_000_000))
CPU_REPS = 2
DEV_REPS = 3


# --------------------------------------------------------------------------
# file builders (write time is not measured)
# --------------------------------------------------------------------------

def build_config1() -> io.BytesIO:
    """Single int64 column, PLAIN, uncompressed, one row group."""
    from tpuparquet import CompressionCodec, FileWriter

    rng = np.random.default_rng(1)
    buf = io.BytesIO()
    w = FileWriter(buf, "message m { required int64 v; }",
                   codec=CompressionCodec.UNCOMPRESSED)
    w.write_columns({"v": rng.integers(-(2**62), 2**62, size=TARGET)})
    w.close()
    buf.seek(0)
    return buf


def build_config2(n_values: int = TARGET, n_groups: int = 8,
                  seed: int = 42) -> io.BytesIO:
    """NYC-Taxi-shaped: int32/int64 hybrid+dict columns, Snappy."""
    from tpuparquet import CompressionCodec, FileWriter

    rng = np.random.default_rng(seed)
    buf = io.BytesIO()
    w = FileWriter(
        buf,
        """message taxi {
            required int64 pickup_ts;
            required int32 passenger_count;
            required int32 rate_code;
            required int64 trip_distance_mm;
            optional int32 payment_type;
        }""",
        codec=CompressionCodec.SNAPPY,
    )
    per = n_values // 5 // n_groups
    base_ts = 1_700_000_000_000
    for _ in range(n_groups):
        pay_mask = rng.random(per) >= 0.05
        w.write_columns(
            {
                "pickup_ts": base_ts
                + rng.integers(0, 3_600_000, size=per).cumsum(),
                "passenger_count": rng.integers(1, 7, size=per,
                                                dtype=np.int32),
                "rate_code": rng.integers(1, 6, size=per, dtype=np.int32),
                "trip_distance_mm": rng.integers(100, 50_000, size=per),
                "payment_type": rng.integers(
                    0, 5, size=int(pay_mask.sum()), dtype=np.int32),
            },
            masks={"payment_type": pay_mask},
        )
    w.close()
    buf.seek(0)
    return buf


def build_config3() -> io.BytesIO:
    """DELTA_BINARY_PACKED int64 timestamps in a nullable nested LIST."""
    from tpuparquet import CompressionCodec, Encoding, FileWriter

    rng = np.random.default_rng(3)
    buf = io.BytesIO()
    w = FileWriter(
        buf,
        """message m {
            optional group events (LIST) {
                repeated group list {
                    optional int64 element (TIMESTAMP(MILLIS, true));
                }
            }
        }""",
        codec=CompressionCodec.SNAPPY,
        column_encodings={
            "events.list.element": Encoding.DELTA_BINARY_PACKED},
    )
    n_groups = 8
    # lens ~ U[0,8) has mean 3.5 -> ~3.4 slots/row after null rows, so
    # TARGET//3 rows keeps total element slots (num_values counts level
    # entries: null rows and null elements included) above TARGET
    rows_per = TARGET // 3 // n_groups
    base_ts = 1_600_000_000_000
    for _ in range(n_groups):
        lens = rng.integers(0, 8, size=rows_per)
        row_mask = rng.random(rows_per) >= 0.03     # 3% null rows
        lens[~row_mask] = 0                          # null rows are empty
        offs = np.zeros(rows_per + 1, dtype=np.int64)
        np.cumsum(lens, out=offs[1:])
        n_slots = int(offs[-1])
        elem_mask = rng.random(n_slots) >= 0.02     # 2% null elements
        n_vals = int(elem_mask.sum())
        ts = base_ts + rng.integers(0, 60_000, size=n_vals).cumsum()
        w.write_columns(
            {"events": ts},
            offsets={"events": offs},
            masks={"events": row_mask},
            element_masks={"events": elem_mask},
        )
    w.close()
    buf.seek(0)
    return buf


def build_config4(n_values: int = TARGET, n_groups: int = 8,
                  seed: int = 4) -> io.BytesIO:
    """Mixed wide table: STRING dict + float64 PLAIN, DataPage V2."""
    from tpuparquet import CompressionCodec, FileWriter
    from tpuparquet.cpu.plain import ByteArrayColumn

    rng = np.random.default_rng(seed)
    buf = io.BytesIO()
    w = FileWriter(
        buf,
        """message m {
            required binary vendor (STRING);
            required double fare;
            required double tip;
            optional binary note (STRING);
        }""",
        codec=CompressionCodec.SNAPPY,
        data_page_v2=True,
    )
    per = n_values // 4 // n_groups
    vocab = [f"vendor-{i:03d}".encode() for i in range(200)]
    notes = [f"note text {i}".encode() for i in range(50)]

    def bytes_col(choices, picks):
        """Vectorized gather of vocabulary strings into a ByteArrayColumn
        (a Python join at 1.5M picks/group is slower than the decode
        being measured)."""
        cb = np.frombuffer(b"".join(choices), dtype=np.uint8)
        co = np.zeros(len(choices) + 1, dtype=np.int64)
        np.cumsum([len(c) for c in choices], out=co[1:])
        lens = (co[1:] - co[:-1])[picks]
        offs = np.zeros(len(picks) + 1, dtype=np.int64)
        np.cumsum(lens, out=offs[1:])
        pos = (np.arange(int(offs[-1]), dtype=np.int64)
               - np.repeat(offs[:-1], lens)
               + np.repeat(co[:-1][picks], lens))
        return ByteArrayColumn(offs, cb[pos])

    for _ in range(n_groups):
        note_mask = rng.random(per) >= 0.4
        n_notes = int(note_mask.sum())
        w.write_columns(
            {
                "vendor": bytes_col(vocab, rng.integers(0, len(vocab),
                                                        size=per)),
                "fare": rng.random(per) * 100.0,
                "tip": rng.random(per) * 20.0,
                "note": bytes_col(notes, rng.integers(0, len(notes),
                                                      size=n_notes)),
            },
            masks={"note": note_mask},
        )
    w.close()
    buf.seek(0)
    return buf


# --------------------------------------------------------------------------
# measurement helpers
# --------------------------------------------------------------------------

def total_values(reader) -> int:
    return sum(
        cc.meta_data.num_values
        for rg in reader.meta.row_groups
        for cc in rg.columns
    )


def _cpu_pass(reader) -> None:
    for rg in range(reader.row_group_count()):
        reader.read_row_group_arrays(rg)


def time_cpu(reader) -> float:
    best = float("inf")
    for _ in range(CPU_REPS):
        t0 = time.perf_counter()
        _cpu_pass(reader)
        best = min(best, time.perf_counter() - t0)
    return best


def time_device(reader):
    """(best wall, {plan_s, transfer_s, dispatch_s, bytes_staged} of the
    best rep) — the phase split says which side binds on the chip."""
    from tpuparquet.kernels.device import read_row_groups_device
    from tpuparquet.stats import collect_stats

    best, phases = float("inf"), {}
    for _ in range(DEV_REPS):
        with collect_stats() as st:
            t0 = time.perf_counter()
            outs = [out for _, out in read_row_groups_device(reader)]
            for o in outs:
                for c in o.values():
                    c.block_until_ready()
            dt = time.perf_counter() - t0
        if dt < best:
            best = dt
            phases = {"plan_s": round(st.plan_s, 3),
                      "transfer_s": round(st.transfer_s, 3),
                      "dispatch_s": round(st.dispatch_s, 3),
                      "bytes_staged": st.bytes_staged}
    return best, phases


def _cpu_checksum(cd) -> dict:
    """Order-sensitive u64 sums over the oracle chunk representation."""
    from tpuparquet.cpu.plain import ByteArrayColumn

    v = cd.values
    idx_mod = np.uint64(1_000_003)
    if isinstance(v, ByteArrayColumn):
        data = np.asarray(v.data, dtype=np.uint8)
        offs = np.asarray(v.offsets, dtype=np.uint64)
        pos = np.arange(data.size, dtype=np.uint64) % idx_mod
        val = int((data.astype(np.uint64) * (pos + np.uint64(1))).sum())
        val += int((offs * ((np.arange(offs.size, dtype=np.uint64)
                             % idx_mod) + np.uint64(1))).sum())
    else:
        u = np.ascontiguousarray(v).reshape(-1).view(np.uint8)
        u32 = np.zeros((u.size + 3) // 4 * 4, dtype=np.uint8)
        u32[: u.size] = u
        u32 = u32.view(np.uint32).astype(np.uint64)
        pos = np.arange(u32.size, dtype=np.uint64) % idx_mod
        val = int((u32 * (pos + np.uint64(1))).sum())
    lv = int(np.asarray(cd.rep_levels, dtype=np.uint64).sum()
             + np.asarray(cd.def_levels, dtype=np.uint64).sum())
    return {"v": val & 0xFFFFFFFFFFFFFFFF, "l": lv,
            "n": len(cd.def_levels)}


_CKSUM_JITS: dict = {}


def _device_checksum(col) -> dict:
    """Same sums computed on device; only scalars cross to the host.
    Needs x64 (sums wrap mod 2^64 like the numpy side).  Each variant
    is ONE jitted dispatch returning two scalars: the parity phase runs
    it for every (row group x column), and eager per-op execution
    would dispatch a dozen programs each time."""
    import jax
    import jax.numpy as jnp

    idx_mod = 1_000_003

    with jax.enable_x64(True):
        def wsum(x):
            x = x.reshape(-1).astype(jnp.uint64)
            pos = (jnp.arange(x.shape[0], dtype=jnp.uint64)
                   % jnp.uint64(idx_mod))
            return jnp.sum(x * (pos + jnp.uint64(1)), dtype=jnp.uint64)

        if "bytes" not in _CKSUM_JITS:
            @jax.jit
            def _ck_bytes(data, offs, rep, dl):
                offs = offs.astype(jnp.uint64)
                v = wsum(data) + jnp.sum(
                    offs * ((jnp.arange(offs.shape[0], dtype=jnp.uint64)
                             % jnp.uint64(idx_mod)) + jnp.uint64(1)),
                    dtype=jnp.uint64)
                lv = (jnp.sum(rep.astype(jnp.uint64))
                      + jnp.sum(dl.astype(jnp.uint64)))
                return v, lv

            @jax.jit
            def _ck_fixed(data, rep, dl):
                lv = (jnp.sum(rep.astype(jnp.uint64))
                      + jnp.sum(dl.astype(jnp.uint64)))
                return wsum(data), lv

            _CKSUM_JITS["bytes"] = _ck_bytes
            _CKSUM_JITS["fixed"] = _ck_fixed

        if col.offsets is not None:
            v, lv = _CKSUM_JITS["bytes"](col.data, col.offsets,
                                         col.rep_levels, col.def_levels)
        else:
            v, lv = _CKSUM_JITS["fixed"](col.data, col.rep_levels,
                                         col.def_levels)
        val, lvi = int(v), int(lv)
    return {"v": val & 0xFFFFFFFFFFFFFFFF, "l": lvi, "n": col.num_values}


# Elementwise-comparison budget for row group 0: the weighted checksums
# cover EVERY value of EVERY row group; the elementwise pass exists to
# turn "something differs" into a concrete position, so a bounded
# prefix is enough and keeps the device->host readback small.
_ELEMWISE_VALUES = 2_000_000


def parity(reader) -> None:
    """Elementwise parity on a row-group-0 prefix; checksum parity on
    every value of every row group.

    Decodes through ``read_row_groups_device`` — the SAME pipelined path
    the timing uses — so the validated path is the reported one."""
    from tpuparquet.cpu.plain import ByteArrayColumn
    from tpuparquet.kernels.device import read_row_groups_device

    for rg, dev in read_row_groups_device(reader):
        cpu = reader.read_row_group_arrays(rg)
        for path, cd in cpu.items():
            if rg == 0:
                col = dev[path]
                k = min(col.num_values, _ELEMWISE_VALUES)
                vals, rep, dl = col.to_numpy(limit=k)
                np.testing.assert_array_equal(rep, cd.rep_levels[:k],
                                              err_msg=path)
                np.testing.assert_array_equal(dl, cd.def_levels[:k],
                                              err_msg=path)
                nn = len(vals)
                if isinstance(cd.values, ByteArrayColumn):
                    woffs = np.asarray(cd.values.offsets[: nn + 1])
                    want = ByteArrayColumn(
                        woffs, cd.values.data[: int(woffs[-1])])
                    assert vals == want, path
                else:
                    np.testing.assert_array_equal(
                        np.asarray(vals),
                        np.asarray(cd.values)[:nn], err_msg=path)
            want = _cpu_checksum(cd)
            got = _device_checksum(dev[path])
            if want != got:
                raise AssertionError(
                    f"checksum mismatch rg={rg} col={path}: "
                    f"cpu={want} device={got}")


def _progress(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def time_pyarrow(buf: io.BytesIO) -> float:
    """Decode the same file with pyarrow.parquet — the external anchor
    the ratio can be checked against (the role the Java harness plays
    for correctness in the reference, ``compatibility/compare.go:35``).
    Single-threaded: values/sec/chip is a per-core metric here."""
    import pyarrow.parquet as pq

    best = float("inf")
    for _ in range(CPU_REPS):
        buf.seek(0)
        t0 = time.perf_counter()
        pq.read_table(buf, use_threads=False)
        best = min(best, time.perf_counter() - t0)
    return best


# --------------------------------------------------------------------------
# write-side external anchor (round-4 verdict item 7): our columnar
# writer vs pyarrow writing the SAME logical data with matched settings
# (snappy, dictionary on).  Configs 2 and 4 — the dict-int and string
# shapes whose interning is the writer's wall.
# --------------------------------------------------------------------------

def _write_anchor_config2(n: int) -> dict:
    from tpuparquet import CompressionCodec, FileWriter

    rng = np.random.default_rng(52)
    per = n // 5
    pay_mask = rng.random(per) >= 0.05
    cols = {
        "pickup_ts": 1_700_000_000_000
        + rng.integers(0, 3_600_000, size=per).cumsum(),
        "passenger_count": rng.integers(1, 7, size=per, dtype=np.int32),
        "rate_code": rng.integers(1, 6, size=per, dtype=np.int32),
        "trip_distance_mm": rng.integers(100, 50_000, size=per),
        "payment_type": rng.integers(0, 5, size=int(pay_mask.sum()),
                                     dtype=np.int32),
    }

    def ours():
        buf = io.BytesIO()
        w = FileWriter(
            buf,
            """message taxi {
                required int64 pickup_ts;
                required int32 passenger_count;
                required int32 rate_code;
                required int64 trip_distance_mm;
                optional int32 payment_type;
            }""",
            codec=CompressionCodec.SNAPPY,
        )
        w.write_columns(cols, masks={"payment_type": pay_mask})
        w.close()

    import pyarrow as pa
    import pyarrow.parquet as pq

    # table built OUTSIDE the timed region: ours starts from ready
    # columns, so pyarrow must too — timing its Python->Arrow
    # conversion would inflate our ratio
    pay_full = np.zeros(per, dtype=np.int32)
    pay_full[pay_mask] = cols["payment_type"]
    table = pa.table({
        "pickup_ts": cols["pickup_ts"],
        "passenger_count": cols["passenger_count"],
        "rate_code": cols["rate_code"],
        "trip_distance_mm": cols["trip_distance_mm"],
        "payment_type": pa.array(pay_full, mask=~pay_mask),
    })

    def theirs():
        pq.write_table(table, io.BytesIO(), compression="snappy",
                       use_dictionary=True)

    return _time_write_pair(5 * per, ours, theirs)


def _write_anchor_config4(n: int) -> dict:
    from tpuparquet import CompressionCodec, FileWriter
    from tpuparquet.cpu.plain import ByteArrayColumn

    rng = np.random.default_rng(54)
    per = n // 4
    vocab = [f"vendor-{i:03d}".encode() for i in range(200)]
    picks = rng.integers(0, len(vocab), size=per)
    fare = rng.random(per) * 100.0
    tip = rng.random(per) * 20.0
    vendor_list = [vocab[i] for i in picks]
    vendor_col = ByteArrayColumn.from_list(vendor_list)

    def ours():
        buf = io.BytesIO()
        w = FileWriter(
            buf,
            """message m {
                required binary vendor (STRING);
                required double fare;
                required double tip;
            }""",
            codec=CompressionCodec.SNAPPY, data_page_v2=True,
        )
        w.write_columns({"vendor": vendor_col, "fare": fare, "tip": tip})
        w.close()

    import pyarrow as pa
    import pyarrow.parquet as pq

    # pre-built like ours (see _write_anchor_config2)
    table = pa.table({"vendor": pa.array(vendor_list, type=pa.binary()),
                      "fare": fare, "tip": tip})

    def theirs():
        pq.write_table(table, io.BytesIO(), compression="snappy",
                       use_dictionary=True, data_page_version="2.0")

    return _time_write_pair(3 * per, ours, theirs)


def _time_write_pair(n_values: int, ours, theirs) -> dict:
    best_us = best_pa = float("inf")
    for _ in range(CPU_REPS):
        t0 = time.perf_counter()
        ours()
        best_us = min(best_us, time.perf_counter() - t0)
        t0 = time.perf_counter()
        theirs()
        best_pa = min(best_pa, time.perf_counter() - t0)
    return {
        "write_vps": round(n_values / best_us, 1),
        "pyarrow_write_vps": round(n_values / best_pa, 1),
        "write_vs_pyarrow": round(best_pa / best_us, 3),
    }


_WRITE_ANCHORS = {2: _write_anchor_config2, 4: _write_anchor_config4}


def run_config(name: str, buf: io.BytesIO) -> dict:
    from tpuparquet import FileReader

    reader = FileReader(buf)
    n_values = total_values(reader)
    _progress(f"[{name}] file built ({len(buf.getbuffer())/1e6:.0f} MB, "
              f"{n_values/1e6:.1f}M values); timing cpu oracle")
    _cpu_pass(reader)  # warm page cache / allocator (one pass suffices)
    cpu_s = time_cpu(reader)
    pa_s = time_pyarrow(buf)
    _progress(f"[{name}] cpu {cpu_s:.2f}s pyarrow {pa_s:.2f}s; "
              "timing device path")
    time_device(reader)  # compile warmup
    dev_s, phases = time_device(reader)
    _progress(f"[{name}] device {dev_s:.2f}s ({phases}); parity check")
    # Parity AFTER timing, so the timed reps see no readback; the
    # report is still gated on it — a mismatch raises before printing.
    # The parity pass runs under an event-carrying collector: it decodes
    # every page on the device path anyway, so the per-page transport
    # mix rides along free (timed reps stay event-free — the log
    # allocates per page).  event_summary drops the parity pass's
    # CPU-oracle pages.
    from tpuparquet.obs import event_summary
    from tpuparquet.stats import collect_stats

    with collect_stats(events=True) as pst:
        parity(reader)
    return {
        "config": name,
        "n_values": n_values,
        "cpu_vps": round(n_values / cpu_s, 1),
        "pyarrow_vps": round(n_values / pa_s, 1),
        "device_vps": round(n_values / dev_s, 1),
        "vs_baseline": round(cpu_s / dev_s, 3),
        "vs_pyarrow": round(pa_s / dev_s, 3),
        "device_phases": phases,
        "events": event_summary(pst.events),
    }


def run_config5() -> dict:
    """Multi-file sharded scan across the device mesh + all-gather."""
    from tpuparquet import FileReader
    from tpuparquet.shard.mesh import make_mesh
    from tpuparquet.shard.scan import ShardedScan, gather_column

    n_files = 4
    bufs = [build_config2(n_values=TARGET // n_files, n_groups=4,
                          seed=100 + i) for i in range(n_files)]
    readers = [FileReader(b) for b in bufs]
    n_values = sum(total_values(r) for r in readers)

    cpu_best = float("inf")
    for _ in range(CPU_REPS):
        t0 = time.perf_counter()
        for r in readers:
            for rg in range(r.row_group_count()):
                r.read_row_group_arrays(rg)
        cpu_best = min(cpu_best, time.perf_counter() - t0)
    pa_best = sum(time_pyarrow(b) for b in bufs)

    mesh = make_mesh()
    for b in bufs:
        b.seek(0)

    def one_scan():
        scan = ShardedScan(bufs, mesh=mesh)
        t0 = time.perf_counter()
        results = scan.run()
        vals, _counts = gather_column(mesh, results, "pickup_ts")
        np.asarray(vals)  # gathered result on host: scan is complete
        return time.perf_counter() - t0, results

    # warmup doubles as the event-collection pass: the timed reps stay
    # event-free (the log allocates per page)
    from tpuparquet.obs import event_summary
    from tpuparquet.stats import collect_stats

    with collect_stats(events=True) as pst:
        one_scan()
    dev_best, results = float("inf"), None
    for _ in range(DEV_REPS):
        s, res = one_scan()
        if s < dev_best:
            dev_best, results = s, res

    # parity gate over EVERY column of every unit: full elementwise on
    # unit 0, device-vs-cpu checksums elsewhere (same gate as the other
    # configs, applied to the scan path's own outputs)
    unit = 0
    for r in readers:
        for rg in range(r.row_group_count()):
            cpu = r.read_row_group_arrays(rg)
            for path, cd in cpu.items():
                if unit == 0:
                    got, grep_, gdl = results[unit][path].to_numpy()
                    np.testing.assert_array_equal(
                        got, np.asarray(cd.values), err_msg=path)
                    np.testing.assert_array_equal(gdl, cd.def_levels,
                                                  err_msg=path)
                want = _cpu_checksum(cd)
                have = _device_checksum(results[unit][path])
                if want != have:
                    raise AssertionError(
                        f"checksum mismatch unit={unit} col={path}: "
                        f"cpu={want} device={have}")
            unit += 1
    return {
        "config": "5-multifile-sharded-scan",
        "n_values": n_values,
        "cpu_vps": round(n_values / cpu_best, 1),
        "pyarrow_vps": round(n_values / pa_best, 1),
        "device_vps": round(n_values / dev_best, 1),
        "vs_baseline": round(cpu_best / dev_best, 3),
        "vs_pyarrow": round(pa_best / dev_best, 3),
        "events": event_summary(pst.events),
    }


# --------------------------------------------------------------------------
# orchestration
#
# Each config runs in its own subprocess with a timeout, one at a time.
# The orchestrator never imports JAX, so no parent holds the chip while
# a child wants it, and a config that fails or hangs costs its own line,
# not the others'.  Results persist to BENCH_PARTIAL.json as each config
# completes.  A device run needs a TPU: a child that finds another
# platform fails, and any failed config makes the exit code nonzero.
# TPQ_BENCH_CPU=1 is the explicit CPU smoke mode, labelled "cpu-smoke".
# --------------------------------------------------------------------------

_REPO = os.path.dirname(os.path.abspath(__file__))
PARTIAL_PATH = os.path.join(_REPO, "BENCH_PARTIAL.json")
CONFIG_NAMES = {
    1: "1-plain-int64-uncompressed",
    2: "2-taxi-dict-snappy",
    3: "3-delta-int64-nested-list",
    4: "4-wide-string-dict-float64-v2",
    5: "5-multifile-sharded-scan",
}
_BUILDERS = {1: build_config1, 2: build_config2, 3: build_config3,
             4: build_config4}


def _utcnow() -> str:
    import datetime

    return datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds")


def _persist(path: str, obj: dict) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=1)
    os.replace(tmp, path)


def device_info() -> dict:
    """The device every record names, as JAX reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def child_main(idx: int) -> None:
    """Run ONE config and print its JSON line (invoked as a subprocess
    by the orchestrator; stderr progress passes through)."""
    cpu_smoke = bool(os.environ.get("TPQ_BENCH_CPU"))
    if cpu_smoke:
        # the smoke mode stays on the CPU even on a host with a chip
        import jax

        jax.config.update("jax_platforms", "cpu")
    dev = device_info()
    if not cpu_smoke and dev["platform"] != "tpu":
        raise SystemExit(
            f"bench: no TPU found (platform {dev['platform']!r}); "
            "TPQ_BENCH_CPU=1 runs the labelled CPU smoke mode")
    if idx == 5:
        r = run_config5()
    else:
        name = CONFIG_NAMES[idx]
        _progress(f"[{name}] building file")
        r = run_config(name, _BUILDERS[idx]())
        if idx in _WRITE_ANCHORS:
            _progress(f"[{name}] write-side anchor vs pyarrow")
            r.update(_WRITE_ANCHORS[idx](
                min(TARGET, 10_000_000)))  # write anchor needs no 50M
    r["device"] = dev
    print(json.dumps(r), flush=True)


def _run_config_subprocess(idx: int, timeout_s: int):
    """(result dict | None, error str | None) for one config child."""
    import subprocess

    env = dict(os.environ)
    # persistent compilation cache shared by the children, at a fixed
    # path (the path is part of the cache key)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(_REPO, ".jax_cache"))
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--config", str(idx)],
            timeout=timeout_s, stdout=subprocess.PIPE, text=True, env=env,
        )
    except subprocess.TimeoutExpired:
        return None, f"timeout after {timeout_s}s"
    lines = [ln for ln in (proc.stdout or "").splitlines() if ln.strip()]
    if proc.returncode != 0:
        tail = lines[-1][:500] if lines else ""
        return None, f"rc={proc.returncode} {tail}"
    try:
        return json.loads(lines[-1]), None
    except (ValueError, IndexError):
        return None, "no JSON line in child output"


def _ladder(source: str) -> tuple[dict, dict]:
    """Run all five configs, one subprocess each; persist as they land."""
    per_cfg_timeout = int(os.environ.get("TPQ_BENCH_CONFIG_TIMEOUT", 1500))
    results: dict = {}
    errors: dict = {}
    partial = {"ts": _utcnow(), "backend": source, "target": TARGET,
               "configs": results, "errors": errors}
    for idx in range(1, 6):
        name = CONFIG_NAMES[idx]
        r, err = _run_config_subprocess(idx, per_cfg_timeout)
        if r is not None:
            r["ts"] = _utcnow()
            results[name] = r
            print(json.dumps(r), flush=True)
        else:
            errors[name] = err
            _progress(f"bench: config {idx} failed: {err}")
        _persist(PARTIAL_PATH, partial)
    return results, errors


def _final_record(results: dict, source: str) -> dict:
    """The driver-schema line over a complete ladder."""
    head = results[CONFIG_NAMES[2]]
    return {
        "metric": "decoded values/sec/chip, NYC-Taxi-like (Snappy+dict), "
                  f"{head['n_values']/1e6:.0f}M values",
        "value": head["device_vps"],
        "unit": "values/sec",
        "vs_baseline": head["vs_baseline"],
        "pyarrow_values_per_sec": head["pyarrow_vps"],
        "vs_pyarrow": head["vs_pyarrow"],
        "ok": True,
        "source": source,
        "device": head["device"],
        "configs": {k: {kk: v[kk] for kk in (
                        "n_values", "cpu_vps", "pyarrow_vps",
                        "device_vps", "vs_baseline", "vs_pyarrow",
                        "write_vps", "pyarrow_write_vps",
                        "write_vs_pyarrow", "events", "ts") if kk in v}
                    for k, v in results.items()},
    }


def main() -> int:
    if len(sys.argv) >= 3 and sys.argv[1] == "--config":
        child_main(int(sys.argv[2]))
        return 0
    source = "device"
    if os.environ.get("TPQ_BENCH_CPU"):
        source = "cpu-smoke"
        os.environ.setdefault("TPQ_BENCH_CONFIG_TIMEOUT", "600")
    results, errors = _ladder(source)
    if errors:
        _progress(f"bench: {len(errors)} of {len(CONFIG_NAMES)} configs "
                  "failed; no record")
        return 1
    print(json.dumps(_final_record(results, source)), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    # Hard exit once all output is flushed: the PJRT/arrow C++
    # teardown intermittently aborts the process ("terminate called
    # without an active exception") AFTER the final record is printed,
    # turning a successful bench into rc=134.  Failures still raise
    # and exit nonzero through the normal path above.
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
