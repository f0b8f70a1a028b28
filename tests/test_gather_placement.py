"""Consumer-aligned output placement: byte parity and placement pins.

The gather wall fix (SCAN_SCALE_r05 → r06): ``gather_column`` /
``gather_byte_column`` accept an ``out_sharding=`` spec (a
``NamedSharding`` over the consumer's mesh, or a ``PartitionSpec``
over the scan mesh) or a ``gather_to=`` single device, so decoded
columns are assembled directly onto the shards that will consume them
instead of being all-gathered everywhere.  The contract pinned here:

* BYTE PARITY — a placed gather's values/offsets/data/counts equal
  the replicated gather's, across the hard scan paths (filter pruning,
  fault injection + quarantine, salvage, cursor resume, MultiHostScan);
* PLACEMENT — the result really lands under the requested sharding
  (single device, consumer sub-mesh, spec over the scan mesh);
* COUNTERS — ``gather_bytes_moved`` / ``gather_bytes_replicated`` /
  ``gather_reshard_s`` decompose what the reshard shipped: replicated
  pays ~global x n_devices with the excess visible as replication;
  a 1:1 consumer placement pays ~global with ZERO replication;
* ERRORS — mesh-mismatch and conflicting specs fail loudly with
  actionable messages.
"""

import io

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpuparquet import CompressionCodec, FileReader, FileWriter
from tpuparquet.shard import (
    MultiHostScan,
    ShardedScan,
    gather_byte_column,
    gather_column,
    make_mesh,
    resolve_out_sharding,
)
from tpuparquet.stats import collect_stats


def _write_file(n_rows=240, n_groups=3, seed=0, with_strings=True):
    buf = io.BytesIO()
    schema = ("message m { required int64 v; optional binary s (STRING); }"
              if with_strings else "message m { required int64 v; }")
    w = FileWriter(buf, schema, codec=CompressionCodec.SNAPPY)
    rng = np.random.default_rng(seed)
    per = n_rows // n_groups
    for g in range(n_groups):
        for i in range(per):
            row = {"v": int(rng.integers(-(2**40), 2**40))}
            if with_strings and i % 5:
                row["s"] = f"s{g}-{i}" * (i % 3 + 1)
            w.add_data(row)
        w.flush_row_group()
    w.close()
    buf.seek(0)
    return buf


def _consumer(n):
    """A 1-D consumer mesh over the first n local devices — distinct
    axis name, distinct Mesh object: nothing shared with the scan
    mesh except the devices."""
    return Mesh(np.asarray(jax.local_devices()[:n]), ("data",))


def _assert_parity(mesh, results, placements, byte_col=True):
    """Placed gathers must be byte-identical to the replicated gather
    (padding rows past the true unit count are zero)."""
    ref_v, ref_c = gather_column(mesh, results, "v")
    n = len(ref_c)
    if byte_col:
        ref_o, ref_d, ref_rc, ref_bc = gather_byte_column(
            mesh, results, "s")
    for kw in placements:
        v, c = gather_column(mesh, results, "v", **kw)
        np.testing.assert_array_equal(c, ref_c)
        got = np.asarray(v)
        np.testing.assert_array_equal(got[:n], ref_v, err_msg=str(kw))
        assert not got[n:].any(), f"padding rows not zero under {kw}"
        if byte_col:
            o, d, rc, bc = gather_byte_column(mesh, results, "s", **kw)
            np.testing.assert_array_equal(rc, ref_rc)
            np.testing.assert_array_equal(bc, ref_bc)
            np.testing.assert_array_equal(np.asarray(o)[:n], ref_o,
                                          err_msg=str(kw))
            np.testing.assert_array_equal(np.asarray(d)[:n], ref_d,
                                          err_msg=str(kw))


def _placements():
    devs = jax.local_devices()
    return [
        {"gather_to": devs[0]},
        {"gather_to": 3},
        {"out_sharding": NamedSharding(_consumer(2), P("data"))},
        {"out_sharding": P("rg")},
    ]


class TestPlacementParity:
    def test_plain_scan_all_placements(self):
        mesh = make_mesh(8)
        with ShardedScan([_write_file(seed=1)], mesh=mesh) as scan:
            results = scan.run()
            _assert_parity(mesh, results, _placements())

    def test_gather_to_lands_on_the_device(self):
        mesh = make_mesh(8)
        dev = jax.local_devices()[5]
        with ShardedScan([_write_file(seed=2)], mesh=mesh) as scan:
            results = scan.run()
            v, c = gather_column(mesh, results, "v", gather_to=dev)
            assert set(v.devices()) == {dev}
            o, d, _, _ = gather_byte_column(mesh, results, "s",
                                            gather_to=dev)
            assert set(o.devices()) == set(d.devices()) == {dev}

    def test_out_sharding_lands_under_the_spec(self):
        mesh = make_mesh(8)
        tgt = NamedSharding(_consumer(2), P("data"))
        with ShardedScan([_write_file(seed=3)], mesh=mesh) as scan:
            results = scan.run()
            v, _ = gather_column(mesh, results, "v", out_sharding=tgt)
            assert v.sharding.is_equivalent_to(tgt, v.ndim)
            # unit axis padded to the spec's partition count
            assert v.shape[0] % 2 == 0
            o, d, _, _ = gather_byte_column(mesh, results, "s",
                                            out_sharding=tgt)
            # offsets and data rows land on the SAME shards, so the
            # per-unit offsets need no per-destination rebase
            assert o.sharding.is_equivalent_to(
                NamedSharding(_consumer(2), P("data")), o.ndim)

    def test_foreign_submesh_rank3_spec(self):
        """A consumer sub-mesh spec that shards MORE than the unit
        axis takes the hop-then-place path; the hop must carry only
        the spec's dim-0 partitioning (the full rank-3 spec would
        mis-rank against the flat 2-D intermediate)."""
        devs = jax.local_devices()
        consumer = Mesh(np.asarray(devs[:2]).reshape(2, 1),
                        ("data", "model"))
        tgt = NamedSharding(consumer, P("data", None, "model"))
        mesh = make_mesh(8)
        with ShardedScan([_write_file(seed=4)], mesh=mesh) as scan:
            results = scan.run()
            ref_v, ref_c = gather_column(mesh, results, "v")
            v, c = gather_column(mesh, results, "v", out_sharding=tgt)
            np.testing.assert_array_equal(c, ref_c)
            np.testing.assert_array_equal(
                np.asarray(v)[: len(ref_c)], ref_v)
            assert v.sharding.is_equivalent_to(tgt, v.ndim)

    def test_filter_pruning_scan(self):
        from tpuparquet.filter import col

        buf = io.BytesIO()
        w = FileWriter(buf, "message m { required int64 v; }",
                       codec=CompressionCodec.SNAPPY)
        for g in range(4):
            w.write_columns(
                {"v": np.arange(g * 1000, g * 1000 + 200,
                                dtype=np.int64)})
        w.close()
        buf.seek(0)
        mesh = make_mesh(4, sp=1)
        with ShardedScan([buf], mesh=mesh,
                         filter=col("v") >= 2000) as scan:
            assert len(scan.units) < 4  # pruning really engaged
            results = scan.run()
            _assert_parity(mesh, results, _placements(),
                           byte_col=False)

    def _corrupt_unit(self, data: bytes, rg: int) -> bytes:
        buf = bytearray(data)
        cm = FileReader(io.BytesIO(data)) \
            .meta.row_groups[rg].columns[0].meta_data
        buf[cm.data_page_offset + cm.total_compressed_size // 2] ^= 0xFF
        return bytes(buf)

    def test_quarantine_scan(self):
        data = self._corrupt_unit(_write_file(n_groups=4).getvalue(), 2)
        mesh = make_mesh(8)
        with ShardedScan([io.BytesIO(data)], mesh=mesh,
                         on_error="quarantine") as scan:
            results = scan.run()
            assert scan.quarantine.units() == [2]
            _assert_parity(mesh, results, _placements())

    def test_salvage_scan(self):
        good = _write_file(seed=7).getvalue()
        torn = _write_file(seed=8).getvalue()
        torn = torn[: len(torn) * 2 // 3]  # tear footer + tail units
        mesh = make_mesh(8)
        with ShardedScan([io.BytesIO(good), io.BytesIO(torn)],
                         mesh=mesh, on_error="quarantine",
                         salvage=True) as scan:
            results = scan.run()
            assert results  # at least the healthy file decoded
            _assert_parity(mesh, results, _placements())

    def test_cursor_resume(self):
        data = _write_file(seed=9).getvalue()
        mesh = make_mesh(4, sp=1)
        with ShardedScan([io.BytesIO(data)], mesh=mesh) as scan:
            it = scan.run_iter()
            got = dict([next(it), next(it)])
            it.close()
            cursor = scan.state()
        with ShardedScan([io.BytesIO(data)], mesh=mesh,
                         resume=cursor) as scan2:
            for k, out in scan2.run_iter():
                got[k] = out
            results = [got[k] for k in sorted(got)]
            _assert_parity(mesh, results, _placements())

    def test_multihost_scan(self, tmp_path):
        p = tmp_path / "m.parquet"
        p.write_bytes(_write_file(seed=11).getvalue())
        dev = jax.local_devices()[1]
        scan = MultiHostScan([str(p)], gather_to=dev)
        results = scan.run()
        ref_v, ref_c = gather_column(scan.mesh, results, "v")
        v, c = scan.gather_column(results, "v")
        assert set(v.devices()) == {dev}
        np.testing.assert_array_equal(np.asarray(v)[: len(ref_c)],
                                      ref_v)
        np.testing.assert_array_equal(c, ref_c)


class TestScanLevelDefault:
    def test_scan_default_and_per_call_override(self):
        mesh = make_mesh(8)
        dev = jax.local_devices()[2]
        with ShardedScan([_write_file(seed=13)], mesh=mesh,
                         gather_to=dev) as scan:
            results = scan.run()
            v, c = scan.gather_column(results, "v")
            assert set(v.devices()) == {dev}
            # per-call override beats the scan default
            other = jax.local_devices()[4]
            v2, _ = scan.gather_column(results, "v", gather_to=other)
            assert set(v2.devices()) == {other}
            np.testing.assert_array_equal(np.asarray(v),
                                          np.asarray(v2))

    def test_env_knob_default(self, monkeypatch):
        monkeypatch.setenv("TPQ_GATHER_TO", "0")
        mesh = make_mesh(4, sp=1)
        with ShardedScan([_write_file(seed=14)], mesh=mesh) as scan:
            results = scan.run()
            # the env default is a SCAN-level knob: the scan's gather
            # methods pick it up ...
            v, _ = scan.gather_column(results, "v")
            assert set(v.devices()) == {jax.local_devices()[0]}
            # ... but the free functions do NOT — an env knob must
            # never silently change their return type (ndarray) under
            # existing callers
            v_free, _ = gather_column(mesh, results, "v")
            assert isinstance(v_free, np.ndarray)

    def test_replicated_sentinel_overrides_armed_default(self):
        """out_sharding="replicated" is the explicit spelling of the
        seed gather — the only way back to the replicated ndarray
        contract on a scan whose default placement is armed (None
        means "use the default" there)."""
        mesh = make_mesh(4, sp=1)
        with ShardedScan([_write_file(seed=16)], mesh=mesh,
                         gather_to=2) as scan:
            results = scan.run()
            ref_v, ref_c = gather_column(mesh, results, "v")
            v, c = scan.gather_column(results, "v",
                                      out_sharding="replicated")
            assert isinstance(v, np.ndarray)
            np.testing.assert_array_equal(v, ref_v)
            np.testing.assert_array_equal(c, ref_c)
            with pytest.raises(ValueError, match="not both"):
                scan.gather_column(results, "v",
                                   out_sharding="replicated",
                                   gather_to=1)

    def test_env_knob_rejects_garbage(self, monkeypatch):
        mesh = make_mesh(2, sp=1)
        monkeypatch.setenv("TPQ_GATHER_TO", "notadevice")
        with pytest.raises(ValueError, match="TPQ_GATHER_TO"):
            resolve_out_sharding(mesh)
        monkeypatch.setenv("TPQ_GATHER_TO", "99")
        with pytest.raises(ValueError, match="out of range"):
            resolve_out_sharding(mesh)


class TestCounters:
    def test_replication_vs_consumer_aligned(self):
        mesh = make_mesh(8)
        with ShardedScan([_write_file(seed=15)], mesh=mesh) as scan:
            results = scan.run()
            with collect_stats() as st_rep:
                gather_column(mesh, results, "v")
            with collect_stats() as st_one:
                gather_column(mesh, results, "v", gather_to=0)
        # replicated: every byte lands n_devices times; the excess is
        # visible as replication.  Consumer-aligned single target:
        # zero replication, and strictly fewer bytes moved.
        assert st_rep.gather_bytes_replicated > 0
        assert st_rep.gather_bytes_moved > st_rep.gather_bytes_replicated
        assert st_one.gather_bytes_replicated == 0
        assert 0 < st_one.gather_bytes_moved < st_rep.gather_bytes_moved
        assert st_rep.gather_reshard_s > 0
        assert st_one.gather_reshard_s > 0

    def test_counters_merge_and_allgather(self):
        from tpuparquet.shard.distributed import allgather_stats
        from tpuparquet.stats import DecodeStats

        a = DecodeStats()
        a.gather_bytes_moved = 10
        a.gather_bytes_replicated = 4
        a.gather_reshard_s = 0.5
        b = DecodeStats()
        b.gather_bytes_moved = 7
        b.merge_from(a)
        assert b.gather_bytes_moved == 17
        assert b.gather_bytes_replicated == 4
        assert b.gather_reshard_s == 0.5
        fleet = allgather_stats(b)  # single process: identity fold
        assert fleet.gather_bytes_moved == 17
        assert fleet.gather_bytes_replicated == 4
        d = fleet.as_dict()
        for key in ("gather_bytes_moved", "gather_bytes_replicated",
                    "gather_reshard_s"):
            assert key in d

    def test_summary_mentions_gather(self):
        from tpuparquet.stats import DecodeStats

        st = DecodeStats()
        st.gather_bytes_moved = 1024
        st.gather_bytes_replicated = 512
        assert "GATHER" in st.summary()


class TestErrors:
    def test_partition_spec_mesh_mismatch_message(self):
        mesh = make_mesh(2, sp=1)
        with pytest.raises(ValueError) as ei:
            resolve_out_sharding(mesh, out_sharding=P("model"))
        msg = str(ei.value)
        # the message names the bad axis, the scan mesh's axes, and
        # the fix (a NamedSharding over the consumer's mesh)
        assert "model" in msg and "rg" in msg
        assert "NamedSharding" in msg

    def test_both_specs_rejected(self):
        mesh = make_mesh(2, sp=1)
        with pytest.raises(ValueError, match="not both"):
            resolve_out_sharding(mesh, out_sharding=P("rg"),
                                 gather_to=0)

    def test_bare_spec_needs_a_mesh(self):
        with pytest.raises(ValueError, match="NamedSharding"):
            resolve_out_sharding(None, out_sharding=P("data"))

    def test_gather_to_index_out_of_range(self):
        mesh = make_mesh(2, sp=1)
        with pytest.raises(ValueError, match="out of range"):
            resolve_out_sharding(mesh, gather_to=99)

    def test_junk_spec_rejected(self):
        mesh = make_mesh(2, sp=1)
        with pytest.raises(ValueError, match="out_sharding must be"):
            resolve_out_sharding(mesh, out_sharding="replicate-please")

    def test_unsupported_sharding_flavor_rejected(self):
        """A sharding flavor other than Named/SingleDevice gives the
        unit-axis padding nothing to derive from — it must be rejected
        loudly, not crash with a raw jax divisibility error
        mid-gather."""

        class _OtherSharding(jax.sharding.Sharding):
            """Any Sharding subclass the gather cannot pad for."""

        mesh = make_mesh(2, sp=1)
        with pytest.raises(ValueError, match="NamedSharding"):
            resolve_out_sharding(mesh, out_sharding=_OtherSharding())


class TestDeviceReadSurface:
    def test_read_row_groups_device_gather_to(self):
        from tpuparquet.kernels.device import (
            read_row_group_device,
            read_row_groups_device,
        )

        dev = jax.local_devices()[3]
        r = FileReader(_write_file(seed=20))
        placed = dict(read_row_groups_device(r, gather_to=dev))
        assert sorted(placed) == [0, 1, 2]
        for cols in placed.values():
            for c in cols.values():
                for buf in c._buffers():
                    assert set(buf.devices()) == {dev}
        # bit-exact vs the default-placement read
        r2 = FileReader(_write_file(seed=20))
        for rg, cols in placed.items():
            ref = read_row_group_device(r2, rg)
            for path in ref:
                rv, rr, rd = ref[path].to_numpy()
                pv, pr, pd = cols[path].to_numpy()
                np.testing.assert_array_equal(rr, pr)
                np.testing.assert_array_equal(rd, pd)
                from tpuparquet.cpu.plain import ByteArrayColumn

                if isinstance(rv, ByteArrayColumn):
                    assert rv == pv
                else:
                    np.testing.assert_array_equal(rv, pv)

    def test_read_row_groups_device_replicated_sentinel(self):
        """out_sharding="replicated" on the read surface is the
        default decode placement, not a crash."""
        from tpuparquet.kernels.device import read_row_groups_device

        r = FileReader(_write_file(seed=22))
        out = dict(read_row_groups_device(r,
                                          out_sharding="replicated"))
        assert sorted(out) == [0, 1, 2]

    def test_read_row_groups_device_out_sharding_round_robins(self):
        from tpuparquet.kernels.device import read_row_groups_device

        tgt = NamedSharding(_consumer(2), P("data"))
        r = FileReader(_write_file(seed=21))
        placed = dict(read_row_groups_device(r, out_sharding=tgt))
        devs = jax.local_devices()[:2]
        seen = set()
        for rg, cols in placed.items():
            for c in cols.values():
                for buf in c._buffers():
                    (d,) = buf.devices()
                    assert d == devs[rg % 2]
                    seen.add(d)
        assert seen == set(devs)
