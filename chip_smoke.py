"""One-chip smoke run of the fused replication planner on a TPU.

    python chip_smoke.py

Drives the planner's main path once, in one process, through the public
entry points (`repro.fleet`, `repro.dag`), at the width of the paper's own
jobs: job1's n = 1026 tasks per job, c = 4 gang blocks (4104 task slots) in
two machine classes, and a grid of 8 policies x 4 arrival rates.  Task
times bootstrap the synthesized Fig. 7 traces, made from a fixed seed.

  (a) `frontier` with the Kiefer-Wolfowitz queue as an XLA scan and as the
      Pallas `kw_queue` kernel, on one key: the rows must agree;
  (b) `policy_search` at one arrival rate, twice: the second call must not
      compile again;
  (c) `trace_kill_rollout`, whose straggler residuals run through the
      Pallas `residual_sample` kernel, against `residual_sample_ref` on the
      same uniforms;
  (d) `dag_frontier` on a map (n = 1026) -> reduce (n = 488) DAG with both
      queue realizations, which must agree;
  (e) at n = 16, c = 3, 200 jobs, `frontier` against the event engine
      (`FleetSim`, aligned placement), within 5 sigma.

For each phase it prints compile seconds, steady wall seconds (after
`block_until_ready`), the compiled program's `memory_analysis()` bytes and
the device's `peak_bytes_in_use`; kernel phases check that the compiled HLO
holds the Mosaic kernel (`tpu_custom_call`).  Any failed check raises.  The
last line of standard output is one JSON object naming the device.  Where
JAX finds no TPU the script exits nonzero before any phase, printing no
result.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    n: int = 1026  # job1's task count (repro.data.traces)
    n_reduce: int = 488  # job2's task count: the DAG's reduce stage
    # trials x jobs per grid cell: the 32-cell frontier program then holds
    # about 3.8 GB of the chip's 16 GB (compiled for a described v5e)
    m_trials: int = 16
    n_jobs: int = 512
    small_n: int = 16  # phase (e): small enough for the event engine
    small_c: int = 3
    small_jobs: int = 200
    small_seeds: int = 12


#: replicas per straggler are at most 2, so every program draws r + 1 <= 3
R_CAP = 3
#: arrival rates: the baseline policy's gang-block occupancy on the
#: two-class fleet (block speeds 1, 1, 0.7, 0.7) runs from about 0.2 to 0.8
LAMS = (0.05, 0.1, 0.15, 0.2)
#: relative agreement of the two queue realizations on the same draws
RTOL = 1e-4


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: check failed: {what}")


def has_kernel(compiled) -> bool:
    """The compiled program runs a Pallas kernel through Mosaic."""
    return "tpu_custom_call" in compiled.as_text()


def peak_bytes(dev) -> int:
    """The device's peak allocation so far in this process."""
    return dev.memory_stats()["peak_bytes_in_use"]


def rows_agree(a: list[dict], b: list[dict]) -> float:
    """Largest relative difference over the numeric keys of two row lists
    (a difference below RTOL of the row's mean sojourn counts as none, so
    near-zero waits do not dominate); raises if they disagree."""
    import numpy as np

    worst = 0.0
    check(len(a) == len(b), "row counts differ")
    for ra, rb in zip(a, b):
        scale = abs(ra["mean_sojourn"])
        for k, va in ra.items():
            if not isinstance(va, float):
                continue
            vb = rb[k]
            check(np.isfinite(va) and np.isfinite(vb), f"{k} not finite")
            err = abs(va - vb) / max(abs(va), abs(vb), scale)
            worst = max(worst, err)
    check(worst <= RTOL, f"rows differ by {worst:.3e} > {RTOL}")
    return worst


def main() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}")
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(f"device {dev.device_kind} x{len(jax.devices())}, compile cache {cache}")
    run(Sizes())
    print(json.dumps(
        {"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                "count": len(jax.devices())}}
    ))


def run(sz: Sizes) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import SingleForkPolicy, ShiftedExp
    from repro.core.distributions import Empirical, quantile_draws
    from repro.core.policy import num_stragglers
    from repro.dag import JobDAG, StageSpec, dag_frontier, lower_dag_frontier
    from repro.data.traces import load_stage_trace
    from repro.fleet import (
        FleetConfig, FleetSim, MachineClass, frontier, lower_frontier,
        poisson_workload, policy_search, trace_kill_rollout, vector,
    )
    from repro.kernels.ref import residual_sample_ref
    from repro.kernels.residual_sampler import residual_sample
    from repro.obs.profile import jit_cache_size

    dev = jax.devices()[0]
    n, c = sz.n, 4
    classes = (MachineClass("fast", 2 * n, 1.0), MachineClass("slow", 2 * n, 0.7))
    policies = [
        SingleForkPolicy(0.0, 0, True),
        SingleForkPolicy(0.02, 1, True),
        SingleForkPolicy(0.05, 1, True),
        SingleForkPolicy(0.05, 2, True),
        SingleForkPolicy(0.1, 2, True),
        SingleForkPolicy(0.02, 1, False),
        SingleForkPolicy(0.05, 1, False),
        SingleForkPolicy(0.1, 2, False),
    ]
    samples = load_stage_trace("map")
    key = jax.random.PRNGKey(0)
    grid = dict(m_trials=sz.m_trials, n_jobs=sz.n_jobs, key=key, r_cap=R_CAP)
    print(
        f"sizes: n={n} tasks/job, c={c} gang blocks ({c * n} task slots, classes "
        f"{[(k.name, k.slots, k.speed) for k in classes]}), {len(policies)} policies x "
        f"{len(LAMS)} rates, m_trials={sz.m_trials} x n_jobs={sz.n_jobs} "
        f"({sz.m_trials * sz.n_jobs} jobs per cell), r_cap={R_CAP}, "
        f"DAG map n={n} -> reduce n={sz.n_reduce}"
    )

    def timed(fn):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        return out, time.perf_counter() - t0

    def report(phase, compiled, compile_s, first_s, wall_s, kernel, extra=""):
        ma = compiled.memory_analysis()
        prog = ma.temp_size_in_bytes + ma.argument_size_in_bytes + ma.output_size_in_bytes
        if kernel:
            check(has_kernel(compiled), f"{phase}: no tpu_custom_call in the compiled HLO")
        print(
            f"[{phase}] compile_s={compile_s:.3f} first_call_s={first_s:.3f} "
            f"wall_s={wall_s:.4f} memory_analysis_bytes={prog} "
            f"(temp={ma.temp_size_in_bytes} args={ma.argument_size_in_bytes} "
            f"out={ma.output_size_in_bytes}) peak_bytes_in_use={peak_bytes(dev)} "
            f"tpu_custom_call={has_kernel(compiled)}"
            + (f" {extra}" if extra else ""),
            flush=True,
        )

    def compile_(lower):
        """Trace, lower and compile one program: the public call that follows
        with the same shapes reuses the executable."""
        t0 = time.perf_counter()
        compiled = lower().compile()
        return compiled, time.perf_counter() - t0

    # (a) frontier: the KW queue as an XLA scan and as the Pallas kernel
    rows = {}
    for kernel in (False, True):
        compiled, compile_s = compile_(lambda: lower_frontier(
            samples, policies, LAMS, n, classes=classes, kernel=kernel, **grid))
        call = lambda: frontier(samples, policies, LAMS, n, classes=classes,  # noqa: E731
                                kernel=kernel, **grid)
        _, first_s = timed(call)
        rows[kernel], wall_s = timed(call)
        report(f"a.frontier kernel={kernel}", compiled, compile_s, first_s, wall_s, kernel)
    worst = rows_agree(rows[False], rows[True])
    best = min(rows[True], key=lambda r: r["mean_sojourn"])
    print(f"[a] kernel vs scan: max relative difference {worst:.3e} over "
          f"{len(rows[True])} rows; lowest mean sojourn {best['mean_sojourn']:.4f} "
          f"({best['policy']}, lam={best['lam']})")

    # (b) the controller's re-plan: one λ, the same shapes twice
    lam = LAMS[2]
    compiled, compile_s = compile_(lambda: lower_frontier(
        samples, policies, [lam], n, classes=classes, kernel=True, **grid))
    search = lambda k: policy_search(  # noqa: E731
        samples, policies, lam, n, classes=classes, kernel=True,
        **dict(grid, key=jax.random.PRNGKey(k)))
    _, first_s = timed(lambda: search(1))
    before = jit_cache_size(vector._frontier_jit)
    scored, wall_s = timed(lambda: search(2))
    after = jit_cache_size(vector._frontier_jit)
    check(before is not None and after == before, f"re-plan compiled again ({before} -> {after})")
    check(all(np.isfinite(r["mean_sojourn"]) for r in scored), "non-finite search row")
    pick = min(scored, key=lambda r: r["mean_sojourn"])
    report("b.policy_search", compiled, compile_s, first_s, wall_s, True,
           f"recompiles=0 pick={pick['label']}")

    # (c) π_kill on the trace: residuals through the Pallas residual sampler
    pol = SingleForkPolicy(0.05, 2, False)
    s, r1 = num_stragglers(n, pol.p), pol.r + 1
    M = sz.m_trials * sz.n_jobs
    xs = Empirical(samples).sorted
    u_shape = jax.ShapeDtypeStruct((M, s, r1), xs.dtype)
    compiled, compile_s = compile_(lambda: residual_sample.lower(u_shape, xs))
    call = lambda: trace_kill_rollout(  # noqa: E731
        samples, pol, LAMS[1], n, sz.n_jobs, sz.m_trials, key=key, classes=classes,
        kernel=True)
    _, first_s = timed(call)
    res, wall_s = timed(call)
    # the reference redraws what the rollout consumed from its key, on the
    # host: the originals and the uniforms, then residual_sample_ref
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        k0, k1, _ = jax.random.split(jax.device_put(key, cpu), 3)
        emp = Empirical(samples)
        x_sorted = jnp.sort(quantile_draws(k0, emp.quantile, (M, n)), axis=1)
        u = jax.random.uniform(k1, (M, s, r1), dtype=emp.sorted.dtype)
        mx_ref, sm_ref = residual_sample_ref(u, emp.sorted)
        t_ref = np.asarray(x_sorted[:, n - s - 1] + mx_ref)
    mx, sm = residual_sample(jax.device_put(u, dev), jax.device_put(emp.sorted, dev))
    kern_err = max(
        float(np.max(np.abs(np.asarray(mx) - np.asarray(mx_ref)))),
        float(np.max(np.abs(np.asarray(sm) - np.asarray(sm_ref)) / np.abs(np.asarray(sm_ref)))),
    )
    check(kern_err <= 1e-5, f"residual_sample vs ref: {kern_err:.3e}")
    speeds = np.array([1.0, 1.0, 0.7, 0.7], np.float32)  # slots fastest first
    t_roll = (np.asarray(res.service) * speeds[np.asarray(res.slot)]).ravel()
    t_err = float(np.max(np.abs(t_roll - t_ref) / t_ref))
    check(t_err <= 1e-5, f"trace_kill_rollout T vs reference: {t_err:.3e}")
    report("c.trace_kill_rollout", compiled, compile_s, first_s, wall_s, True,
           f"residual_sample_vs_ref={kern_err:.3e} rollout_T_vs_ref={t_err:.3e} "
           f"s={s} r+1={r1} mean_sojourn={res.mean_sojourn:.4f}")
    del res, x_sorted, u

    # (d) a map -> reduce DAG with both queue realizations
    dag = JobDAG([
        StageSpec("map", n, samples, c=c),
        StageSpec("reduce", sz.n_reduce, load_stage_trace("reduce"), c=2, deps=("map",)),
    ])
    vectors = [
        (pm, pr)
        for pm in (policies[0], policies[1], policies[3], policies[6])
        for pr in (SingleForkPolicy(0.0, 0, True), SingleForkPolicy(0.05, 1, True))
    ]
    dgrid = dict(m_trials=sz.m_trials, key=key, r_caps=(R_CAP, R_CAP))
    dag_rows = {}
    for kernel in (False, True):
        compiled, compile_s = compile_(lambda: lower_dag_frontier(
            dag, vectors, LAMS, sz.n_jobs, kernel=kernel, **dgrid))
        call = lambda: dag_frontier(dag, vectors, LAMS, sz.n_jobs, kernel=kernel, **dgrid)  # noqa: E731
        _, first_s = timed(call)
        dag_rows[kernel], wall_s = timed(call)
        report(f"d.dag_frontier kernel={kernel}", compiled, compile_s, first_s, wall_s, kernel)
    worst = rows_agree(dag_rows[False], dag_rows[True])
    print(f"[d] kernel vs scan: max relative difference {worst:.3e} over "
          f"{len(dag_rows[True])} rows")

    # (e) small enough for the event engine: the chip's numbers are right,
    # not merely self-consistent
    dist, small_pol, small_lam = ShiftedExp(1.0, 1.0), SingleForkPolicy(0.2, 1, True), 0.45
    sn, sc, sj = sz.small_n, sz.small_c, sz.small_jobs
    sgrid = dict(m_trials=32, c=sc, kernel=True)
    compiled, compile_s = compile_(lambda: lower_frontier(dist, [small_pol], [small_lam], sn, sj, **sgrid))
    call = lambda: frontier(dist, [small_pol], [small_lam], sn, sj, **sgrid)  # noqa: E731
    _, first_s = timed(call)
    (row,), wall_s = timed(call)
    soj = []
    for seed in range(sz.small_seeds):
        jobs = poisson_workload(sj, rate=small_lam, n_tasks=sn, dist=dist, seed=seed)
        cfg = FleetConfig(capacity=sc * sn, policy=small_pol, seed=seed, placement="aligned")
        soj.append(FleetSim(cfg).run(jobs).stats.mean_sojourn)
    se = float(np.hypot(np.std(soj, ddof=1) / np.sqrt(len(soj)), row["sojourn_std_err"]))
    z = abs(np.mean(soj) - row["mean_sojourn"]) / se
    check(z < 5.0, f"frontier vs event engine: {z:.2f} sigma")
    report("e.frontier_vs_event_engine", compiled, compile_s, first_s, wall_s, True,
           f"frontier={row['mean_sojourn']:.4f} event_engine={np.mean(soj):.4f} "
           f"sigma={se:.4f} z={z:.2f}")


if __name__ == "__main__":
    main()
