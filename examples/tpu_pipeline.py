"""End-to-end tour of tpu-parquet: every layer in one runnable script.

Runs anywhere JAX runs — on a CPU backend it exercises the identical
code paths the TPU uses (the kernels are backend-agnostic jits):

    JAX_PLATFORMS=cpu python examples/tpu_pipeline.py

Add ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to see the
sharded scan spread over a virtual 8-device mesh; on a machine with a
TPU attached, drop JAX_PLATFORMS to run on the chip.
"""

import io
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tpuparquet as tpq
from tpuparquet import CompressionCodec, FileReader, FileWriter
from tpuparquet.kernels.device import read_row_group_device
from tpuparquet.kernels.encode import DeviceValues
from tpuparquet.shard.mesh import make_mesh
from tpuparquet.shard.scan import ShardedScan, gather_column

rng = np.random.default_rng(0)

# 1. Columnar write: whole arrays + validity masks, no per-row shredding.
n = 200_000
mask = rng.random(n) >= 0.1
buf = io.BytesIO()
w = FileWriter(buf, """message trips {
    required int64 pickup_ts;
    required double fare;
    optional int32 payment_type;
    required binary vendor (STRING);
}""", codec=CompressionCodec.SNAPPY)
from tpuparquet.cpu.plain import ByteArrayColumn

vendor_col = ByteArrayColumn.from_list(
    [f"vendor-{i % 7}".encode() for i in range(n)])
for _ in range(4):  # four row groups
    w.write_columns({
        "pickup_ts": 1_700_000_000_000
        + rng.integers(0, 60_000, n).cumsum(),
        "fare": rng.random(n) * 80,
        "payment_type": rng.integers(0, 5, size=int(mask.sum()),
                                     dtype=np.int32),
        "vendor": vendor_col,
    }, masks={"payment_type": mask})
w.close()
buf.seek(0)
print(f"wrote {4 * n:,} rows, {len(buf.getvalue()) / 1e6:.1f} MB")

# 2. Device batch decode: pages staged to HBM, fused kernels, results
#    device-resident (Arrow layout: packed values + validity + levels).
with FileReader(buf) as r, tpq.collect_stats() as st:
    cols = read_row_group_device(r, 0)
print("device decode:", st.summary())
fare = cols["fare"]  # DeviceColumn: flat u32 lanes + mask + levels

# 3. Compute directly on the decoded device buffers (no host round trip),
#    then write the result back through the device encoder: only encoded
#    bytes cross the host link, and the file is byte-identical to what
#    the host encoder would produce.
import jax.numpy as jnp

lanes = fare.data.reshape(-1, 2)  # f64 as (lo, hi) u32 pairs

import jax

with jax.enable_x64(True):
    f64 = jax.lax.bitcast_convert_type(lanes, jnp.float64)
    tipped = f64 * 1.15
    out_lanes = jax.lax.bitcast_convert_type(tipped, jnp.uint32)
out2 = io.BytesIO()
w2 = FileWriter(out2, "message m { required double fare_tipped; }",
                column_encodings={
                    "fare_tipped": tpq.Encoding.BYTE_STREAM_SPLIT},
                allow_dict=False)
w2.write_columns({
    "fare_tipped": DeviceValues(out_lanes.reshape(-1), np.float64)})
w2.close()
out2.seek(0)
with FileReader(out2) as rcheck:
    check = rcheck.read_row_group_arrays(0)["fare_tipped"]
print(f"device-encoded round trip: {len(check.values):,} values, "
      f"max {np.asarray(check.values).max():.2f}")

# 4. Sharded scan over a device mesh: (file x row-group) units decode
#    data-parallel, one XLA all-gather collects a column, resumable
#    cursors checkpoint progress.
buf.seek(0)
mesh = make_mesh()
with ShardedScan([buf], mesh=mesh) as scan:
    results = scan.run()
    vals, counts = gather_column(mesh, results, "pickup_ts")
    cursor = scan.state()  # JSON-serializable resume point
print(f"sharded scan: {len(scan.units)} units over "
      f"{len(list(mesh.devices.flat))} device(s); gathered "
      f"{int(counts.sum()):,} values; cursor={cursor['next_unit']}")

# 5. The row-oriented reference-style API and the floor object mapper
#    sit on the same files; floor's bulk columnar paths skip per-row
#    shredding/assembly for flat dataclasses.
buf.seek(0)
with FileReader(buf, "fare", "vendor") as r2:  # column projection
    row = next(r2.rows())
print("first row (projected):", row)

import dataclasses

from tpuparquet import floor


@dataclasses.dataclass
class Reading:
    sensor: int
    value: float


out3 = io.BytesIO()
with floor.new_file_writer(out3, cls=Reading) as fw:
    fw.write_columns([Reading(sensor=i % 4, value=i / 9)
                      for i in range(10_000)])  # bulk columnar objects
out3.seek(0)
with floor.new_file_reader(out3, Reading) as fr:
    objs = fr.read_columns(0)  # bulk materialization, no row assembly
print(f"floor columnar round trip: {len(objs):,} objects, "
      f"last={objs[-1]}")
