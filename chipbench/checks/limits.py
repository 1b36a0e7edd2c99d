"""Readings that a cell's limits are set from.

    python chipbench/checks/limits.py --workload <name> --seeds 1,2,3 --control-seeds 4,5,6

For each seed of `--seeds` it builds the cell as a run does, runs as many
calls as a run compares (`check_calls` of the traffic) and compares each
with the plain reference: the largest reading per number is the program's
reading for that seed.  For each of `--control-seeds` it puts the
reference, computed in bfloat16, in the program's place on the same calls:
the control, which has to read far above the program.  Everything runs in
one process, so the cell's programs compile once.  Prints one line per
seed, and a JSON summary as the last line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def readings(cell, calls, control: bool) -> dict:
    import ml_dtypes

    out = {}
    for i in calls:
        if control:
            answer, _ = cell.reference(i, ml_dtypes.bfloat16)
        else:
            cell.prepare(i)
            answer = cell.call(i)
        for key, value in cell.compare(i, answer).items():
            out[key] = max(out.get(key, 0.0), value)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--cpu", action="store_true", help="allow a run without a chip")
    args = ap.parse_args(argv)

    from chipbench import harness

    workload, config, traffic = harness.find_cell(args.workload)
    harness.setup_jax(not args.cpu, workload["chips"])
    entry = harness.load_entry(traffic["entry"])
    calls = list(range(1, traffic["check_calls"] + 1))
    summary = {"workload": args.workload, "program": {}, "control": {}}
    for kind, seeds in (("program", args.seeds), ("control", args.control_seeds)):
        for seed in [int(s) for s in seeds.split(",") if s]:
            t0 = time.perf_counter()
            cell = entry.build(config, traffic, seed)
            r = readings(cell, calls, kind == "control")
            summary[kind][seed] = r
            print(f"{kind} seed={seed} " + " ".join(f"{k}={v:.6e}" for k, v in r.items())
                  + f" ({time.perf_counter() - t0:.1f} s)", flush=True)
    for kind in ("program", "control"):
        keys = sorted({k for r in summary[kind].values() for k in r})
        for k in keys:
            vals = [r[k] for r in summary[kind].values()]
            print(f"{kind} {k}: max {max(vals):.6e} min {min(vals):.6e} over {len(vals)} seeds")
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
