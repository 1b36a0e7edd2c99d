"""Run one cell of the on-chip benchmark once.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell asks
for.  The last line of standard output is the result's JSON object; the
numbers compared with the reference, each beside its limit, are the last
lines of standard error.  Exits nonzero, printing no result, where JAX
finds no accelerator or too few chips.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    workload, config, traffic = harness.find_cell(args.workload)
    out = harness.run_cell(workload, config, traffic, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
