# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
#
#   PYTHONPATH=src python -m benchmarks.run            # everything
#   PYTHONPATH=src python -m benchmarks.run --only trace table1
#
# Artifacts (full curves/tables) land in benchmarks/results/*.json.  Runs
# that include the fleet or kernels benches additionally write a repo-root
# BENCH_fleet.json perf trajectory (timings, speedups, gate outcomes, git
# sha) so future PRs can diff hot-path regressions against this commit.
import argparse
import json
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

from . import (
    bench_dag,
    bench_fig3_fig5,
    bench_fig4_fig6,
    bench_fleet,
    bench_kernels,
    bench_roofline,
    bench_runtime,
    bench_scaling,
    bench_table1,
    bench_trace,
)
from .common import GATES, REPO_ROOT, emit, git_sha

BENCHES = {
    "fig3_fig5": bench_fig3_fig5,  # sim vs analytic latency (Figs. 3, 5)
    "fig4_fig6": bench_fig4_fig6,  # E[T]/E[C]/trade-off sweeps (Figs. 4, 6)
    "trace": bench_trace,  # bootstrap trade-offs on traces (Figs. 7-10)
    "table1": bench_table1,  # policy optimization (Table 1)
    "scaling": bench_scaling,  # Corollary 1 growth exponents
    "kernels": bench_kernels,  # Pallas kernels + Algorithm 1 throughput
    "runtime": bench_runtime,  # trainer/serving economics
    "fleet": bench_fleet,  # multi-job finite-capacity frontier
    "dag": bench_dag,  # multi-stage DAG jobs: fused stage rollout + joint search
    "roofline": bench_roofline,  # dry-run roofline summary
}

#: benches whose rows/gates feed the repo-root perf trajectory
TRAJECTORY_BENCHES = ("fleet", "kernels", "dag")


def _write_trajectory(results: dict) -> None:
    """BENCH_fleet.json at the repo root: the hot-path perf record this
    commit leaves behind (written even when a gate failed, so regressions
    are diagnosable from the artifact alone).  `ok` covers only the
    trajectory benches — an unrelated bench failing elsewhere in the run
    must not read as a hot-path regression.

    Partial runs merge: `--only dag` refreshes the dag entry (and the
    gates that run recorded) while keeping the other trajectory benches'
    rows and gate outcomes from the existing file, so iterating on one
    bench never erases the baselines future PRs diff against.  `ok` /
    `all_gates_passed` are recomputed over the merged content."""
    path = REPO_ROOT / "BENCH_fleet.json"
    benches = {}
    gates = list(GATES)
    if path.exists():
        try:
            prev = json.loads(path.read_text())
            benches = {
                k: v for k, v in prev.get("benches", {}).items()
                if k in TRAJECTORY_BENCHES
            }
            fresh = {g["name"] for g in gates}
            gates = [
                g for g in prev.get("gates", []) if g["name"] not in fresh
            ] + gates
        except (json.JSONDecodeError, OSError):
            pass  # corrupt/unreadable: rebuild from this run alone
    benches.update(
        {
            name: dict(
                rows=[dict(name=r[0], us_per_call=r[1], derived=r[2]) for r in rows],
                error=err,
            )
            for name, (rows, err) in results.items()
        }
    )
    payload = dict(
        git_sha=git_sha(),
        generated_unix=time.time(),
        benches=benches,
        gates=gates,
        all_gates_passed=all(g["passed"] for g in gates),
        ok=all(b.get("error") is None for b in benches.values()),
    )
    path.write_text(json.dumps(payload, indent=1, default=float))
    print(f"# perf trajectory -> {path}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None, choices=list(BENCHES))
    args = ap.parse_args()
    enable_compile_cache()
    names = args.only or list(BENCHES)
    print("name,us_per_call,derived")
    failed = 0
    results: dict[str, tuple[list, str | None]] = {}
    for name in names:
        t0 = time.time()
        rows: list = []
        err = None
        try:
            rows = BENCHES[name].run()
            emit(rows)
        except Exception as e:
            failed += 1
            traceback.print_exc()
            err = f"{type(e).__name__}: {e}"
            rows = list(getattr(e, "rows", []))  # GateFailure keeps measurements
            emit(rows)
            print(f"{name},0.0,ERROR:{type(e).__name__}:{e}")
        if name in TRAJECTORY_BENCHES:
            results[name] = (rows, err)
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    if results:
        _write_trajectory(results)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
