"""Record the compact trace of a short traced run of one cell.

    python chipbench/checks/record_trace.py --workload <name> --seconds 0.3 --out <file.json.gz>

The record is what `tracereduce.load` keeps of the profiler's trace; the
self-check (`test_tracereduce.py`) reduces one that was recorded on the
chip and is committed beside it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 11)
    ap.add_argument("--seconds", type=float, default=0.3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from chipbench import harness

    workload, config, traffic = harness.find_cell(args.workload)
    out = harness.run_cell(workload, config, traffic, args.seed, args.seconds, True,
                           keep_trace=args.out)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
