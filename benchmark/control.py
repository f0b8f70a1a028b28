"""A run of a cell with its timed path broken (``benchmark/faults.py``):
the readings that set the upper end of each limit.  Not part of the
benchmark's own runs.

    python3 benchmark/control.py --fault control --workload <cell>
        --seed <n> --seconds <s>

It drives the whole of ``run.py`` at the cell's own size, on the chip,
and prints the result line; ``correct`` has to read false.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import run  # noqa: E402
from benchmark.faults import FAULTS  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fault", choices=sorted(FAULTS), required=True)
    args, rest = ap.parse_known_args(argv)
    from tpuparquet.kernels.device import read_row_groups_device

    try:
        result = run.run(rest,
                         read=FAULTS[args.fault](read_row_groups_device))
    except run.NoResult as e:
        run.say(f"FAIL: {e}")
        return 2
    print(json.dumps(dict(result, fault=args.fault)), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
