"""One-chip smoke run of the fused replication planner on a TPU.

    python chip_smoke.py

Drives the planner's main path once, in one process, through the public
entry points (`repro.fleet`, `repro.dag`), at the width of the paper's own
jobs: job1's n = 1026 tasks per job, c = 4 gang blocks (4104 task slots) in
two machine classes, and a grid of 8 policies x 4 arrival rates.  Task
times bootstrap the synthesized Fig. 7 traces, made from a fixed seed.

  (a) `frontier` with `kernel=False` and `kernel=True`: on a TPU both run
      the Kiefer-Wolfowitz queue (c = 4) as the Pallas `kw_queue` kernel,
      so both programs must hold it; then the kernel (`batched_queue`) on
      one cell's queue inputs against `kw_queue_ref`, the scan, on the
      host;
  (b) `policy_search` at one arrival rate, twice: the second call must not
      compile again;
  (c) `trace_kill_rollout`, whose straggler residuals run through the
      Pallas `residual_sample` kernel, against `residual_sample_ref` on the
      same uniforms;
  (d) `dag_frontier` on a map (n = 1026) -> reduce (n = 488) DAG with
      `kernel=False` and `kernel=True`, both holding the kernel; then the
      kernel against `kw_queue_ref` on the queue inputs of one reduce-stage
      cell (c = 2 blocks of one speed);
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
#: relative agreement of the queue kernel and its reference on the same inputs
RTOL = 1e-4


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: check failed: {what}")


def has_kernel(compiled) -> bool:
    """The compiled program runs a Pallas kernel through Mosaic."""
    return "tpu_custom_call" in compiled.as_text()


def has_queue_kernel(compiled) -> bool:
    """The compiled program runs the Pallas `kw_queue` kernel."""
    import re

    return re.search(r'%kw_queue(\.\d+)? = [^\n]*"tpu_custom_call"', compiled.as_text()) is not None


def peak_bytes(dev) -> int:
    """The device's peak allocation so far in this process."""
    return dev.memory_stats()["peak_bytes_in_use"]


def queue_vs_ref(samples, n: int, speeds, pol, lam: float, sz: Sizes, key) -> str:
    """The queue kernel on one grid cell's queue inputs against
    `kw_queue_ref` (the per-queue scan) run on the host's CPU.

    The inputs are drawn as the fused frontier draws a cell's: arrivals at
    rate `lam`, and each job's service under `pol` from the originals and
    fresh copies of `samples` (n tasks, R_CAP draws a copy).  The program
    `batched_queue` lowers for the chip must hold the kernel; starts,
    finishes and stretched services must agree within RTOL of the cell's
    mean sojourn.  Returns a summary for the phase's line."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.distributions import Empirical
    from repro.core.policy import num_stragglers
    from repro.fleet import vector
    from repro.kernels.ref import kw_queue_ref

    @jax.jit
    def inputs(key, xs):
        ka, kf = jax.random.split(key)
        quantile = functools.partial(vector.emp_quantile, xs)
        x_sorted, fresh = vector.fork_draws(kf, quantile, (sz.m_trials, sz.n_jobs), n, R_CAP)
        arrivals = jnp.cumsum(jax.random.exponential(ka, (sz.m_trials, sz.n_jobs)), axis=1) / lam
        T, _ = vector.masked_single_fork(
            x_sorted, fresh, n - num_stragglers(n, pol.p), pol.r, pol.keep)
        return arrivals, T

    arrivals, T = inputs(key, Empirical(samples).sorted)
    speeds = jnp.asarray(speeds, jnp.float32)
    compiled = jax.jit(vector.batched_queue).lower(arrivals, T, speeds).compile()
    check(has_queue_kernel(compiled), "batched_queue: no kw_queue kernel in the compiled HLO")
    got = [np.asarray(z) for z in compiled(arrivals, T, speeds)]
    cpu = jax.devices("cpu")[0]
    host = [jax.device_put(np.asarray(z), cpu) for z in (arrivals, T, speeds)]
    with jax.default_device(cpu):
        want = [np.asarray(z) for z in kw_queue_ref(*host)]
    a = np.asarray(arrivals)
    scale = float(np.mean(want[1] - a))
    worst = max(float(np.max(np.abs(g - w))) for g, w in zip(got[:3], want[:3])) / scale
    for z in got[:3]:
        check(bool(np.all(np.isfinite(z))), "queue kernel: non-finite output")
    check(worst <= RTOL, f"queue kernel vs kw_queue_ref: {worst:.3e} > {RTOL}")
    slots_differ = int(np.sum(got[3] != want[3]))
    return (f"kernel vs kw_queue_ref over {a.size} jobs on {speeds.shape[0]} slots: "
            f"max difference {worst:.3e} of the mean sojourn, slots differing {slots_differ}")


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

    # (a) frontier: on a TPU both settings of kernel= run the KW queue kernel
    rows = {}
    for kernel in (False, True):
        compiled, compile_s = compile_(lambda: lower_frontier(
            samples, policies, LAMS, n, classes=classes, kernel=kernel, **grid))
        call = lambda: frontier(samples, policies, LAMS, n, classes=classes,  # noqa: E731
                                kernel=kernel, **grid)
        _, first_s = timed(call)
        rows[kernel], wall_s = timed(call)
        report(f"a.frontier kernel={kernel}", compiled, compile_s, first_s, wall_s, True)
        check(has_queue_kernel(compiled), f"a.frontier kernel={kernel}: no kw_queue kernel")
    best = min(rows[False], key=lambda r: r["mean_sojourn"])
    print(f"[a] {queue_vs_ref(samples, n, [1.0, 1.0, 0.7, 0.7], policies[3], LAMS[2], sz, key)}; "
          f"lowest mean sojourn {best['mean_sojourn']:.4f} ({best['policy']}, lam={best['lam']})")

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

    # (d) a map -> reduce DAG: both settings of kernel= run the kernel per stage
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
    for kernel in (False, True):
        compiled, compile_s = compile_(lambda: lower_dag_frontier(
            dag, vectors, LAMS, sz.n_jobs, kernel=kernel, **dgrid))
        call = lambda: dag_frontier(dag, vectors, LAMS, sz.n_jobs, kernel=kernel, **dgrid)  # noqa: E731
        _, first_s = timed(call)
        _, wall_s = timed(call)
        report(f"d.dag_frontier kernel={kernel}", compiled, compile_s, first_s, wall_s, True)
        check(has_queue_kernel(compiled), f"d.dag_frontier kernel={kernel}: no kw_queue kernel")
    reduce = load_stage_trace("reduce")
    print(f"[d] {queue_vs_ref(reduce, sz.n_reduce, [1.0, 1.0], vectors[1][1], LAMS[3], sz, key)}")

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
