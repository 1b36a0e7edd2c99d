"""Chaos-engine invariants (`repro.faults` + the fault paths threaded through
the event scheduler, fused JAX engines, metrics, controller, and server):
capacity conservation under crash/recovery, no-job-lost accounting, backoff
monotonicity, the bitwise q=0 contract, and 5σ agreement of the fused
geometric-retry transform with the event-engine oracle.  Property tests use
hypothesis when present; fixed adversarial cases keep the file biting
without it."""

import dataclasses
import math

import numpy as np
import pytest

from hypothesis_stubs import HAVE_HYPOTHESIS, given, settings, st  # noqa: F401

from repro.core import Empirical, ShiftedExp, SingleForkPolicy
from repro.faults import (
    ChaosSchedule,
    CrashProcess,
    FaultSpec,
    Outage,
    effective_fail_prob,
    schedule_for_kill_fraction,
)
from repro.fleet import (
    EventHeap,
    FleetConfig,
    FleetScheduler,
    FleetSim,
    MachineClass,
    poisson_workload,
    vector,
)

DIST = ShiftedExp(1.0, 1.0)
POL = SingleForkPolicy(0.2, 1, True)


def _jobs(n_jobs, lam=0.4, n_tasks=8, seed=3, priority_levels=1):
    return poisson_workload(
        n_jobs, rate=lam, n_tasks=n_tasks, dist=DIST, seed=seed,
        priority_levels=priority_levels,
    )


# ------------------------------------------------------------ fault model


def test_fault_spec_validation():
    with pytest.raises(ValueError):
        FaultSpec(q=1.5)
    with pytest.raises(ValueError):
        FaultSpec(q=-0.1)
    with pytest.raises(ValueError):
        FaultSpec(max_attempts=0)
    with pytest.raises(ValueError):
        FaultSpec(backoff_base=-1.0)
    with pytest.raises(ValueError):
        CrashProcess(mtbf=0.0, mttr=1.0)
    with pytest.raises(ValueError):
        Outage(time=10.0, duration=-1.0, n_slots=2)
    assert not FaultSpec().enabled
    assert FaultSpec(q=0.1).enabled and FaultSpec(q=0.1).task_faults
    assert FaultSpec(crashes=(CrashProcess(100.0, 10.0),)).machine_faults
    assert FaultSpec(schedule=ChaosSchedule((Outage(1.0, 2.0, 3),))).machine_faults


def test_backoff_delays_monotone_and_capped():
    spec = FaultSpec(q=0.5, backoff_base=0.5, backoff_factor=2.0, backoff_cap=3.0,
                     max_attempts=16)
    ds = spec.delays(16)
    assert len(ds) == 15  # one delay per retry, not per attempt
    assert all(b >= a for a, b in zip(ds, ds[1:]))  # non-decreasing
    assert max(ds) <= 3.0  # capped
    assert ds[0] == 0.5 and ds[1] == 1.0 and ds[2] == 2.0 and ds[3] == 3.0


if HAVE_HYPOTHESIS:

    @given(
        base=st.floats(min_value=0.0, max_value=10.0),
        factor=st.floats(min_value=1.0, max_value=4.0),
        cap=st.floats(min_value=0.1, max_value=100.0),
        failures=st.integers(min_value=1, max_value=30),
    )
    @settings(max_examples=60, deadline=None)
    def test_backoff_monotonicity_property(base, factor, cap, failures):
        spec = FaultSpec(q=0.5, backoff_base=base, backoff_factor=factor,
                         backoff_cap=cap)
        a = spec.attempt_delay(failures)
        b = spec.attempt_delay(failures + 1)
        assert 0.0 <= a <= b <= max(cap, base)


def test_effective_fail_prob_folds_crash_hazard():
    assert effective_fail_prob(0.1) == pytest.approx(0.1)
    assert effective_fail_prob(0.0, crash_rate=0.0) == 0.0
    q_eff = effective_fail_prob(0.1, crash_rate=0.05, mean_service=2.0)
    assert q_eff == pytest.approx(1.0 - 0.9 * math.exp(-0.1))
    assert 0.1 < q_eff < 1.0


def test_schedule_for_kill_fraction_windows():
    sched = schedule_for_kill_fraction(64, 0.3, start=100.0, duration=50.0)
    (out,) = sched.outages
    assert out.n_slots == 19  # floor(0.3 * 64), at least 1
    assert out.time == 100.0 and out.duration == 50.0
    assert schedule_for_kill_fraction(4, 0.01, start=1.0, duration=1.0).outages[0].n_slots == 1


# ----------------------------------------------------------- event heap


def test_event_heap_cancel_clears_payload():
    heap = EventHeap()
    payload = {"big": list(range(10))}
    ev = heap.push(1.0, "copy_done", payload)
    heap.cancel(ev)
    assert ev.data is None  # payload released at cancel, not at pop
    assert heap.pop() is None


def test_event_heap_compacts_when_mostly_dead():
    heap = EventHeap()
    events = [heap.push(float(i), "e") for i in range(200)]
    for ev in events[:150]:
        heap.cancel(ev)
    # compaction fired at least once: without it the backing list would
    # still hold all 200 entries
    assert len(heap._heap) <= 100
    seen = [heap.pop() for _ in range(50)]
    assert [ev.time for ev in seen] == [float(i) for i in range(150, 200)]
    assert heap.pop() is None


# --------------------------------------------- event engine: q=0 contract


def test_q0_spec_is_bitwise_identical_to_no_fault():
    jobs = _jobs(120)
    base = FleetSim(FleetConfig(capacity=24, policy=POL, seed=5)).run(jobs)
    gated = FleetSim(FleetConfig(
        capacity=24, policy=POL, seed=5, fault=FaultSpec(q=0.0),
    )).run(jobs)
    assert len(base.records) == len(gated.records)
    for a, b in zip(base.records, gated.records):
        assert dataclasses.asdict(a) == dataclasses.asdict(b)
    assert gated.n_task_failures == 0 and gated.n_retries == 0
    assert gated.stats.availability == 1.0
    assert gated.stats.failed_job_share == 0.0


# ------------------------------------- event engine: conservation ledgers


def _chaos_run(capacity=16, n_jobs=80, q=0.15, seed=2, max_attempts=3,
               outage=(20.0, 30.0, 5), classes=None, placement="pooled",
               backoff_base=0.0, crashes=()):
    sched = FleetScheduler(
        capacity=capacity if classes is None else None,
        default_policy=POL,
        seed=seed,
        classes=classes,
        placement=placement,
        fault=FaultSpec(
            q=q,
            max_attempts=max_attempts,
            backoff_base=backoff_base,
            crashes=crashes,
            schedule=ChaosSchedule((Outage(*outage),)) if outage else None,
        ),
    )
    records = sched.run(_jobs(n_jobs, seed=seed))
    return sched, records


def _assert_conserved(sched, records, n_jobs):
    # post-run ledgers: every slot back, no downed slots, peak within cap
    assert sched.free == sched.capacity
    assert sum(sched.down_by_class) == 0
    assert 0 < sched.max_busy <= sched.capacity
    assert all(f >= 0 for f in sched.free_by_class)
    # no job lost: exactly one record per job, each either completed or a
    # terminal failure with a reason
    assert sorted(r.job_id for r in records) == list(range(n_jobs))
    for r in records:
        if r.failed:
            assert r.failure in ("max_attempts", "timeout", "shed")
        else:
            assert r.failure == ""
            assert r.finish >= r.start >= r.arrival


def test_capacity_conserved_under_outage_and_task_failures():
    sched, records = _chaos_run()
    _assert_conserved(sched, records, 80)
    assert sched.n_task_failures > 0 and sched.n_retries > 0
    assert sched.down_time == pytest.approx(5 * 30.0)


def test_capacity_conserved_with_crash_process_and_classes():
    classes = (MachineClass("fast", 8, 1.5), MachineClass("slow", 8, 1.0))
    sched, records = _chaos_run(
        classes=classes, outage=None,
        crashes=(CrashProcess(mtbf=40.0, mttr=8.0, n_slots=2),),
    )
    _assert_conserved(sched, records, 80)
    assert sched.n_crash_kills >= 0  # crashes may or may not hit live copies
    assert sum(len(r) for r in sched.repairs_by_class) > 0


def test_max_attempts_one_fails_jobs_but_loses_none():
    sched, records = _chaos_run(q=0.4, max_attempts=1, outage=None)
    _assert_conserved(sched, records, 80)
    failed = [r for r in records if r.failed]
    assert failed and all(r.failure == "max_attempts" for r in failed)
    assert sched.n_retries == 0  # no budget for retries


if HAVE_HYPOTHESIS:

    @given(
        seed=st.integers(min_value=0, max_value=2**16),
        q=st.floats(min_value=0.0, max_value=0.5),
        max_attempts=st.integers(min_value=1, max_value=4),
        start=st.floats(min_value=0.0, max_value=60.0),
        duration=st.floats(min_value=0.1, max_value=60.0),
        down=st.integers(min_value=1, max_value=12),
    )
    @settings(max_examples=15, deadline=None)
    def test_conservation_property(seed, q, max_attempts, start, duration, down):
        sched, records = _chaos_run(
            n_jobs=40, q=q, seed=seed, max_attempts=max_attempts,
            outage=(start, duration, down),
        )
        _assert_conserved(sched, records, 40)


# ----------------------------------------- event engine: backoff timing


def test_backoff_delays_push_terminal_failure_later():
    """Constant service 2.0 racing a constant fail time 1.0: every attempt
    fails deterministically, so the terminal-failure time of a backoff run
    exceeds the zero-backoff run by exactly the sum of the retry delays."""
    const = Empirical([2.0])
    jobs = [
        # a single one-task job so the timeline is fully deterministic
        j for j in poisson_workload(1, rate=1.0, n_tasks=1, dist=const, seed=0)
    ]

    def finish(backoff_base):
        sched = FleetScheduler(
            capacity=1, default_policy=SingleForkPolicy(0.0, 0, True), seed=0,
            fault=FaultSpec(fail_dist=Empirical([1.0]), max_attempts=3,
                            backoff_base=backoff_base,
                            backoff_factor=2.0, backoff_cap=64.0),
        )
        (rec,) = sched.run(jobs)
        assert rec.failed and rec.failure == "max_attempts"
        assert rec.n_attempts == 3
        return rec.finish

    # delays after attempt 1 and 2: base, 2*base
    assert finish(0.5) - finish(0.0) == pytest.approx(0.5 + 1.0)


# ----------------------------------------------------- metrics satellite


def test_chaos_metrics_availability_mttr_and_shares():
    classes = (MachineClass("fast", 8, 1.5), MachineClass("slow", 8, 1.0))
    report = FleetSim(FleetConfig(
        classes=classes, policy=POL, seed=4,
        fault=FaultSpec(q=0.3, max_attempts=2,
                        schedule=ChaosSchedule((Outage(10.0, 40.0, 4),))),
    )).run(_jobs(80, seed=4))
    s = report.stats
    assert 0.0 < s.availability < 1.0
    assert s.mean_attempts > 1.0
    assert 0.0 <= s.failed_job_share <= 1.0
    assert report.n_failed == sum(r.failed for r in report.records)
    # class shares (incl. "mixed"/"unplaced" buckets) still partition jobs
    assert sum(s.class_job_share.values()) == pytest.approx(1.0)
    assert s.class_mttr is not None
    assert any(v == pytest.approx(40.0) for v in s.class_mttr.values() if v == v)


# --------------------------------------------- fused engines: q=0 bitwise


def _strip_q(rows):
    out = []
    for r in rows:
        r = dict(r)
        assert r.pop("q") == 0.0
        out.append(r)
    return out


def test_fused_frontier_q0_bitwise():
    import jax

    key = jax.random.PRNGKey(7)
    pols = [POL, SingleForkPolicy(0.3, 2, False)]
    lams = (0.05, 0.2)
    plain = vector.frontier(DIST, pols, lams, n=8, n_jobs=150, m_trials=8, key=key)
    gated = vector.frontier(DIST, pols, lams, n=8, n_jobs=150, m_trials=8, key=key,
                            fault=FaultSpec(q=0.0))
    assert _strip_q(gated) == plain  # bitwise: identical floats, field by field


def test_fused_dag_frontier_q0_bitwise():
    import jax

    from repro.dag import JobDAG, StageSpec, dag_frontier

    dag = JobDAG([
        StageSpec("map", 6, DIST),
        StageSpec("red", 3, ShiftedExp(1.0, 0.5), deps=("map",)),
    ])
    key = jax.random.PRNGKey(3)
    vecs = [dag.policies(), (POL, SingleForkPolicy(0.0, 0, True))]
    plain = dag_frontier(dag, vecs, (0.1,), 120, m_trials=8, key=key)
    gated = dag_frontier(dag, vecs, (0.1,), 120, m_trials=8, key=key,
                         fault=FaultSpec(q=0.0))
    assert _strip_q(gated) == plain


def test_fused_rejects_event_only_fault_features():
    with pytest.raises(ValueError, match="backoff"):
        vector.frontier(DIST, [POL], (0.1,), n=4, n_jobs=20, m_trials=4,
                        fault=FaultSpec(q=0.1, backoff_base=1.0))
    with pytest.raises(ValueError, match="machine|crash|effective_fail_prob"):
        vector.frontier(DIST, [POL], (0.1,), n=4, n_jobs=20, m_trials=4,
                        fault=FaultSpec(q=0.1, crashes=(CrashProcess(10.0, 1.0),)))
    with pytest.raises(ValueError):
        # mixed retry budgets cannot share one static draw width
        vector.frontier(DIST, [POL], (0.1,), n=4, n_jobs=20, m_trials=4,
                        fault=[FaultSpec(q=0.1, max_attempts=4),
                               FaultSpec(q=0.2, max_attempts=8)])


def test_retry_transform_limits_and_monotonicity():
    import jax
    import jax.numpy as jnp

    x, v = vector.retry_draws(jax.random.PRNGKey(0), DIST.quantile,
                              (64, 16), attempts=6)
    # attempts and the draw's last axis lead; the transform gives the shape
    assert x.shape == (6, 16, 64) and v.shape == (5, 16, 64)
    base = vector.retry_transform(x, v, 0.0)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(x[0].T))
    full = vector.retry_transform(x, v, 1.0)
    np.testing.assert_allclose(np.asarray(full), np.asarray(jnp.sum(x, axis=0).T),
                               rtol=1e-6)
    means = [float(jnp.mean(vector.retry_transform(x, v, q)))
             for q in (0.0, 0.2, 0.5, 0.8)]
    assert all(b > a for a, b in zip(means, means[1:]))  # E[total] grows with q


# ------------------------------------------ fused engines: the q axis


@pytest.mark.parametrize(
    "n_pols,lams,qs",
    [
        (3, (0.05, 0.2), (0.2,)),  # one q: 6 cells padded to 8
        (2, (0.05, 0.2), (0.1, 0.3)),  # two q interleaved, 8 cells, no padding
        (3, (0.1,), (0.05, 0.25)),  # 6 cells padded to 8 (4 a q)
        (2, (0.1,), (0.0, 0.1, 0.3)),  # 6 cells padded to 9 (3 a q)
    ],
    ids=["one_q", "two_q", "pads", "three_q"],
)
def test_q_axis_rows_match_per_cell_evaluation(n_pols, lams, qs):
    """The q axis forms each q's effective draws once and evaluates that
    q's cells against them; a one-cell grid draws the same numbers (the key
    layout does not depend on the grid), so every row of the grid equals
    the row of its cell evaluated alone, bit for bit: the cells of a q
    share its arrays, and no reduction runs across cells."""
    import jax

    pols = [POL, SingleForkPolicy(0.3, 2, False), SingleForkPolicy(0.0, 0, True)][:n_pols]
    key = jax.random.PRNGKey(13)
    rows = vector.frontier(DIST, pols, lams, n=8, n_jobs=64, m_trials=4, key=key, c=2,
                           r_cap=3, fault=[FaultSpec(q=q) for q in qs])
    assert len(rows) == len(pols) * len(lams) * len(qs)
    it = iter(rows)
    for pol in pols:
        for lam in lams:
            for q in qs:
                # a one-cell q axis, q = 0 included (frontier would take a
                # lone disabled spec to the fault-free program)
                (alone,) = vector._eval_cells(DIST, [pol], [lam], 8, 64, 4, key, 2, None,
                                              False, 3, True, qs=[q], attempts=8)
                assert next(it) == alone


def test_lower_frontier_lowers_the_failure_program():
    """lower_frontier(fault=...) hands out the program frontier(fault=...)
    dispatches: once it is compiled, the call compiles nothing more."""
    import jax

    compiles = []

    def listener(name, duration, **kwargs):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    pols, lams = [POL, SingleForkPolicy(0.3, 2, False)], (0.05, 0.2)
    kw = dict(m_trials=4, c=2, r_cap=3, fault=[FaultSpec(q=0.1), FaultSpec(q=0.2)])
    compiled = vector.lower_frontier(DIST, pols, lams, 8, 48, **kw).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        rows = vector.frontier(DIST, pols, lams, 8, 48, **kw)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert compiles == [] and [r["q"] for r in rows[:2]] == [0.1, 0.2]


# ------------------------------------ fused vs event oracle (5σ agreement)


def _event_cell(policy, lam, q, n=8, c=2, n_jobs=150, n_seeds=6):
    """Aligned placement with c gang blocks realizes exactly the KW G/G/c
    model the fused path runs — the oracle the fault cells must match."""
    soj, cost = [], []
    for seed in range(n_seeds):
        jobs = poisson_workload(n_jobs, rate=lam, n_tasks=n, dist=DIST, seed=seed)
        rep = FleetSim(FleetConfig(
            capacity=c * n, policy=policy, seed=seed, placement="aligned",
            fault=FaultSpec(q=q, max_attempts=8) if q > 0 else None,
        )).run(jobs)
        soj.append(rep.stats.mean_sojourn)
        cost.append(rep.stats.mean_cost)
    return np.asarray(soj), np.asarray(cost)


def _assert_cell_agreement(row, policy, lam, q):
    soj, cost = _event_cell(policy, lam, q)
    se = float(np.hypot(np.std(soj) / np.sqrt(len(soj)), row["sojourn_std_err"]))
    assert abs(row["mean_sojourn"] - float(np.mean(soj))) < 5 * se + 0.05, (
        f"fused/event sojourn disagree at λ={lam} q={q}: "
        f"{row['mean_sojourn']:.4f} vs {np.mean(soj):.4f} (5σ={5 * se:.4f})"
    )
    assert abs(row["mean_cost"] - float(np.mean(cost))) < 0.15


def test_fused_matches_event_oracle_single_fault_cell():
    import jax

    (row,) = vector.frontier(
        DIST, [POL], (0.1,), n=8, n_jobs=150, m_trials=24,
        key=jax.random.PRNGKey(11), c=2, fault=FaultSpec(q=0.2, max_attempts=8),
    )
    assert row["q"] == 0.2
    _assert_cell_agreement(row, POL, 0.1, 0.2)


@pytest.mark.slow
def test_fused_matches_event_oracle_grid():
    import jax

    pols = [POL, SingleForkPolicy(0.0, 0, True)]
    lams = (0.05, 0.15)
    qs = [FaultSpec(q=0.0, max_attempts=8), FaultSpec(q=0.25, max_attempts=8)]
    rows = vector.frontier(
        DIST, pols, lams, n=8, n_jobs=150, m_trials=24,
        key=jax.random.PRNGKey(11), c=2, fault=qs,
    )
    assert len(rows) == len(pols) * len(lams) * len(qs)
    # cells expand policy-major, λ next, q fastest
    it = iter(rows)
    for pol in pols:
        for lam in lams:
            for spec in qs:
                row = next(it)
                assert row["q"] == spec.q
                _assert_cell_agreement(row, pol, lam, spec.q)
    # failure-aware ordering: more task failures => strictly more cost
    for i in range(0, len(rows), 2):
        assert rows[i + 1]["mean_cost"] > rows[i]["mean_cost"]


# --------------------------------------------- controller: failure drift


def test_controller_failure_rate_estimate_and_drift():
    from repro.fleet.adaptive import FleetPolicyController

    ctl = FleetPolicyController(min_samples=8, fail_window=32, drift_cooldown=0)
    assert ctl.fail_rate_estimate() is None
    for _ in range(16):
        ctl.record_task_time(1.0)
    for _ in range(16):
        ctl.record_task_failure()
    assert ctl.fail_rate_estimate() == pytest.approx(0.5)
    # half-split over the full window sees 0 -> 1: a drift
    assert ctl._fail_drift_detected()
    assert ctl.last_fail_drift == pytest.approx(1.0)


def test_controller_drift_requires_full_window():
    from repro.fleet.adaptive import FleetPolicyController

    ctl = FleetPolicyController(min_samples=4, fail_window=64, drift_cooldown=0)
    for _ in range(10):
        ctl.record_task_failure()
    assert not ctl._fail_drift_detected()  # partial window: no verdict


# ----------------------------------------------- serving degradation


def test_server_deadlines_shed_and_failed_outcomes():
    from repro.runtime import FleetHedgedServer

    srv = FleetHedgedServer(
        capacity=4,
        latency_dist=ShiftedExp(1.0, 2.0),
        serve_fn=lambda r: r + 1,
        adapt=False,
        seed=3,
        deadlines={1: 0.75},  # best-effort class gets a tight deadline
        fault=FaultSpec(q=0.1),
        shed_rho=0.5,
    )
    batches = [[i, i + 1] for i in range(60)]
    priorities = [i % 2 for i in range(60)]
    outcomes, stats = srv.serve_stream(batches, rate=4.0, seed=3,
                                       priorities=priorities)
    assert len(outcomes) == 60
    degraded = [o for o in outcomes if o.failed]
    assert degraded, "tight deadline + shed guard should degrade some batches"
    for o, batch in zip(outcomes, batches):
        if o.failed:
            assert o.values == []
            assert o.failure in ("timeout", "shed", "max_attempts")
        else:
            assert o.values == [b + 1 for b in batch]
    assert 0.0 <= stats.failed_job_share <= 1.0


def test_server_degradation_metrics_reach_registry():
    from repro.runtime import FleetHedgedServer

    srv = FleetHedgedServer(
        capacity=4, latency_dist=ShiftedExp(1.0, 2.0), serve_fn=lambda r: r,
        adapt=False, seed=5, deadlines={0: 0.5},
    )
    srv.serve_stream([[1]] * 40, rate=6.0, seed=5)
    assert srv.metrics.gauge("fleet.availability").value == pytest.approx(1.0)
    assert srv.metrics.counter("serve.timeout").value > 0
