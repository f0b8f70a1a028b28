#!/bin/bash
# One chip call, everything measured: official bench ladder first
# (the number that matters), then the scale sweep, then the Pallas A/B.
# Run it on a host with one TPU; nothing else may hold the chip.
# Usage: bash tools/run_tpu_suite.sh [outdir]
set -u
cd "$(dirname "$0")/.."
OUT=$(realpath -m "${1:-/tmp/tpu_suite}")
mkdir -p "$OUT"

echo "=== bench.py (official ladder) ==="
timeout 2400 python bench.py > "$OUT/bench.out" 2> "$OUT/bench.err"
echo "rc=$?" | tee -a "$OUT/bench.err"
tail -1 "$OUT/bench.out"

echo "=== unpack hardware parity sweep (catches Mosaic regressions) ==="
timeout 900 python tools/check_unpack_hw.py 200000 \
  > "$OUT/unpack_hw.out" 2>&1
echo "rc=$?"
tail -1 "$OUT/unpack_hw.out"

echo "=== every device decode branch, bit-exact on chip ==="
timeout 900 python tools/check_device_paths.py \
  > "$OUT/device_paths.out" 2>&1
echo "rc=$?"
tail -1 "$OUT/device_paths.out"

echo "=== profile_decode scale sweep ==="
for rows in 2000000 4000000 10000000; do
  timeout 900 python tools/profile_decode.py $rows 8 \
    > "$OUT/profile_${rows}.out" 2>&1
  echo "rows=$rows rc=$?"
  grep -E "e2e|device:" "$OUT/profile_${rows}.out" | head -4
done

echo "=== wire transport A/B (planes/tokens on vs off) ==="
timeout 1800 python tools/bench_wire.py > "$OUT/wire.out" 2>&1
echo "rc=$?"
cat "$OUT/wire.out"

echo "=== pallas vs xla unpack A/B ==="
timeout 1200 python tools/bench_pallas.py 50000000 \
  > "$OUT/pallas.out" 2>&1
echo "rc=$?"
tail -10 "$OUT/pallas.out"
