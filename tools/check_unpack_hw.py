"""On-chip bit-unpack parity sweep: XLA and Pallas vs the CPU oracle.

Codifies the hardware check that caught the Mosaic straddle-shift
miscompile (see ``kernels/bitunpack.py:_unpack_block_unrolled``): on
TPU v5e, the ``(lo >> sh) | (hi << (32-sh))`` formulation corrupted
every width >= 17 while interpret mode was clean.  The shipped kernel
uses the multiply workaround; this sweep re-verifies both device
formulations at every width against the NumPy oracle so a Mosaic or
XLA regression (or a workaround regression) is caught in one minute of
chip time.

Usage: python tools/check_unpack_hw.py [n_values]   (default 1M)
Exit code 0 = all clean.
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax

    from tpuparquet.cpu.bitpack import pack, unpack
    from tpuparquet.kernels.bitunpack import (pad_to_words, unpack_u32,
                                              unpack_u32_pallas)

    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    print(f"backend={jax.default_backend()}  n={n}")
    rng = np.random.default_rng(1)
    failures = 0
    for width in range(1, 33):
        vals = rng.integers(0, 1 << width, size=n, dtype=np.uint64)
        packed = pack(vals, width)
        oracle = unpack(packed, n, width).astype(np.uint32)
        words = jax.device_put(pad_to_words(packed, width, n).reshape(-1))
        for name, fn in (("xla", unpack_u32), ("pallas", unpack_u32_pallas)):
            got = np.asarray(fn(words, width, n))
            bad = np.nonzero(got != oracle)[0]
            if bad.size:
                failures += 1
                lanes = sorted(set((bad % 32).tolist()))
                print(f"FAIL width {width:2d} {name}: {bad.size} bad, "
                      f"lanes {lanes[:8]}")
    print("ALL CLEAN (widths 1..32, xla + pallas)" if not failures
          else f"{failures} (width, path) failures")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
