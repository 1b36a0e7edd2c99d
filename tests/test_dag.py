"""repro.dag: multi-stage DAG jobs, fused stage-composed rollouts, joint
per-stage search, and the stage-aware event engine.

Anchors:
  * the degenerate one-stage DAG reproduces the single-stage fleet engines
    on the SAME key — bit-level vs `fleet.vector.frontier` (shared draw
    structure) and to float tolerance vs `fleet_rollout` (baseline);
  * the two-stage fused rollout agrees with the stage-aware event engine
    (`DagFleetSim`, aligned per-stage pools) within Monte-Carlo error;
  * barrier monotonicity: adding a stage can never reduce a job's sojourn,
    checked pathwise inside one rollout;
  * critical-path shares sum to 1 exactly on both engines;
  * the Pallas kw_queue kernel path ≡ the scan path at 1e-5.
"""

import jax
import numpy as np
import pytest

from repro.core import ShiftedExp, SingleForkPolicy
from repro.dag import (
    DagFleetConfig,
    DagFleetSim,
    JobDAG,
    StageSpec,
    coordinate_search,
    dag_frontier,
    dag_rollout,
    exhaustive_search,
    lower_dag_frontier,
    poisson_arrivals,
    uniform_vectors,
)
from repro.fleet import vector

BASE = SingleForkPolicy(0.0, 0, True)
KEEP = SingleForkPolicy(0.2, 1, True)
KILL = SingleForkPolicy(0.25, 1, False)
MAP_DIST = ShiftedExp(1.0, 1.0)
RED_DIST = ShiftedExp(0.5, 2.0)


def two_stage(map_policy=KEEP, reduce_policy=BASE, c_map=2, c_reduce=2):
    return JobDAG.map_reduce(
        8, 4, MAP_DIST, RED_DIST, map_policy=map_policy,
        reduce_policy=reduce_policy, c_map=c_map, c_reduce=c_reduce,
    )


# ----------------------------------------------------------------- graph


def test_graph_validation():
    with pytest.raises(ValueError, match="topological"):
        JobDAG([
            StageSpec("a", 4, MAP_DIST, deps=("b",)),
            StageSpec("b", 4, MAP_DIST),
        ])
    with pytest.raises(ValueError, match="unknown stage"):
        JobDAG([StageSpec("a", 4, MAP_DIST, deps=("ghost",))])
    with pytest.raises(ValueError, match="duplicate"):
        JobDAG([StageSpec("a", 4, MAP_DIST), StageSpec("a", 4, MAP_DIST)])
    with pytest.raises(ValueError, match="n_tasks"):
        StageSpec("a", 0, MAP_DIST)
    with pytest.raises(ValueError, match="at least one stage"):
        JobDAG([])
    # a stage cannot name itself as a dependency (no earlier occurrence)
    with pytest.raises(ValueError, match="topological"):
        JobDAG([StageSpec("a", 4, MAP_DIST, deps=("a",))])


def test_graph_views_and_builders():
    dag = JobDAG([
        StageSpec("m1", 4, MAP_DIST),
        StageSpec("m2", 4, MAP_DIST),
        StageSpec("r", 2, RED_DIST, deps=("m1", "m2")),
    ])
    assert dag.sources == ("m1", "m2")
    assert dag.sinks == ("r",)
    assert dag.succs["m1"] == ("r",)
    pipe = JobDAG.pipeline([
        StageSpec("a", 4, MAP_DIST),
        StageSpec("b", 4, MAP_DIST),
        StageSpec("c", 4, MAP_DIST),
    ])
    assert pipe.preds == {"a": (), "b": ("a",), "c": ("b",)}
    # raw trace slices wrap into Empirical
    s = StageSpec("t", 4, np.array([1.0, 2.0, 3.0]))
    from repro.core import Empirical

    assert isinstance(s.dist, Empirical)
    with pytest.raises(ValueError, match="policy vector"):
        pipe.validate_policy_vector((BASE,))


# ------------------------------------------- degenerate one-stage anchors


def test_one_stage_equals_frontier_exact_crn():
    """Same key, same draw structure: a one-stage DAG cell is the fused
    single-stage frontier cell, draw for draw."""
    one = JobDAG([StageSpec("s", 8, MAP_DIST, KEEP)])
    key = jax.random.PRNGKey(7)
    a = dag_frontier(one, [one.policies()], (0.25,), 150, m_trials=8, key=key)[0]
    b = vector.frontier(MAP_DIST, [KEEP], (0.25,), 8, 150, m_trials=8, key=key)[0]
    for k in ("mean_sojourn", "mean_cost", "p50", "p99", "sojourn_std_err"):
        assert a[k] == pytest.approx(b[k], rel=1e-6), k
    assert a["s/share"] == pytest.approx(1.0)


def test_one_stage_baseline_equals_fleet_rollout_exact_crn():
    """Baseline policy: the one-stage DAG consumes the key exactly like
    `fleet_rollout` (split -> arrivals | draws), so the sample paths match
    to float tolerance (the only difference is cumsum(x)/λ vs cumsum(x/λ))."""
    one = JobDAG([StageSpec("s", 8, MAP_DIST, BASE)])
    key = jax.random.PRNGKey(3)
    res = dag_rollout(one, lam=0.3, n_jobs=120, m_trials=6, key=key)
    ref = vector.fleet_rollout(MAP_DIST, BASE, 0.3, 8, 120, m_trials=6, key=key)
    # float32 rounding of the arrival cumsum differs between the two sides
    np.testing.assert_allclose(res.sojourn, ref.sojourn, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(res.service[0], ref.service, rtol=1e-6)
    np.testing.assert_allclose(res.cost[0], ref.cost, rtol=1e-6)
    np.testing.assert_allclose(res.wait[0], ref.wait, rtol=1e-4, atol=1e-4)


def test_one_stage_replicated_matches_fleet_rollout_within_mc():
    one = JobDAG([StageSpec("s", 8, MAP_DIST, KEEP)])
    res = dag_rollout(one, lam=0.25, n_jobs=300, m_trials=24,
                      key=jax.random.PRNGKey(0))
    ref = vector.fleet_rollout(MAP_DIST, KEEP, 0.25, 8, 300, m_trials=24,
                               key=jax.random.PRNGKey(1))
    sigma = max(np.hypot(res.sojourn_std_err, ref.sojourn_std_err), 1e-12)
    assert abs(res.mean_sojourn - ref.mean_sojourn) / sigma < 5.0
    assert res.mean_cost == pytest.approx(ref.mean_cost, abs=0.1)


# ------------------------------------------- fused rollout vs event engine


def test_two_stage_vector_vs_event_within_mc():
    """The tentpole agreement: fused stage-composed rollout ≡ stage-aware
    event engine (aligned per-stage pools) within combined MC error, on
    both E[T] and E[C]."""
    dag = two_stage()
    lam = 0.3
    ev_soj, ev_cost = [], []
    for seed in range(4):
        rep = DagFleetSim(DagFleetConfig(dag, seed=seed)).run(
            poisson_arrivals(400, lam, seed=seed)
        )
        ev_soj.append(rep.stats.mean_sojourn)
        ev_cost.append(rep.stats.mean_cost)
    res = dag_rollout(dag, lam=lam, n_jobs=400, m_trials=32,
                      key=jax.random.PRNGKey(5))
    sigma = max(
        float(np.hypot(np.std(ev_soj) / np.sqrt(len(ev_soj)), res.sojourn_std_err)),
        1e-12,
    )
    assert abs(float(np.mean(ev_soj)) - res.mean_sojourn) / sigma < 5.0
    assert float(np.mean(ev_cost)) == pytest.approx(res.mean_cost, abs=0.1)


def test_event_engine_barrier_semantics():
    """A linear DAG job re-enters the queue per stage: the reduce record's
    release time IS the map record's finish, per job."""
    dag = two_stage()
    rep = DagFleetSim(DagFleetConfig(dag)).run(poisson_arrivals(60, 0.2, seed=2))
    for rec in rep.jobs:
        m, r = rec.stages["map"], rec.stages["reduce"]
        assert r.arrival == pytest.approx(m.finish)
        assert rec.finish == pytest.approx(r.finish)
        assert rec.cost == pytest.approx(m.cost + r.cost)
        assert rec.sojourn >= m.sojourn
    # per-stage pools never over-commit
    assert rep.stats.stage["map"].n_jobs == 60
    assert rep.stats.stage["reduce"].n_jobs == 60


def test_event_fan_in_barrier():
    """Fan-in: the reduce stage releases only after BOTH map stages."""
    dag = JobDAG([
        StageSpec("m1", 4, MAP_DIST, KEEP, c=2),
        StageSpec("m2", 4, RED_DIST, c=2),
        StageSpec("r", 2, RED_DIST, deps=("m1", "m2")),
    ])
    rep = DagFleetSim(DagFleetConfig(dag)).run(poisson_arrivals(50, 0.15, seed=3))
    for rec in rep.jobs:
        release = rec.stages["r"].arrival
        assert release == pytest.approx(
            max(rec.stages["m1"].finish, rec.stages["m2"].finish)
        )
    assert sum(rep.stats.critical_path_shares.values()) == pytest.approx(1.0)


# ----------------------------------------------- pathwise DAG properties


def test_barrier_monotonicity_pathwise():
    """Adding a stage never reduces E[T]: within one rollout, the job's
    completion is bounded below by every stage's barrier — so the 2-stage
    sojourn dominates the 1-stage sojourn job by job, not just on average."""
    dag = two_stage()
    res = dag_rollout(dag, lam=0.3, n_jobs=200, m_trials=8,
                      key=jax.random.PRNGKey(11))
    one_stage_sojourn = res.finish[0] - res.arrivals  # map barrier alone
    assert np.all(np.asarray(res.finish[1] - res.finish[0]) >= -1e-9)
    assert np.all(np.asarray(res.sojourn - one_stage_sojourn) >= -1e-9)
    # and the barrier feeds the next queue: reduce never starts early
    assert np.all(np.asarray(res.ready[1] - res.finish[0]) >= -1e-9)
    assert np.all(np.asarray(res.start - res.ready) >= -1e-9)


def test_critical_path_shares_sum_to_one():
    dag = JobDAG([
        StageSpec("m1", 4, MAP_DIST, KEEP, c=2),
        StageSpec("m2", 4, RED_DIST, c=2),
        StageSpec("r", 2, RED_DIST, deps=("m1", "m2")),
    ])
    res = dag_rollout(dag, lam=0.2, n_jobs=150, m_trials=8,
                      key=jax.random.PRNGKey(13))
    shares = res.stage_shares()
    assert sum(shares.values()) == pytest.approx(1.0, abs=1e-5)
    assert all(v >= 0.0 for v in shares.values())
    # pathwise: attributions telescope to the sojourn exactly
    np.testing.assert_allclose(
        np.asarray(res.attr).sum(axis=0), np.asarray(res.sojourn), rtol=1e-5
    )
    # frontier rows carry the same shares
    row = dag_frontier(dag, [dag.policies()], (0.2,), 150, m_trials=8,
                       key=jax.random.PRNGKey(13))[0]
    total = row["m1/share"] + row["m2/share"] + row["r/share"]
    assert total == pytest.approx(1.0, abs=1e-5)


# ------------------------------------------------------- engine knobs


def test_kernel_matches_scan():
    """kernel=True routes every stage queue through the Pallas kw_queue
    kernel on identical draws: results match the scan path at 1e-5."""
    dag = two_stage()
    key = jax.random.PRNGKey(6)
    scan = dag_frontier(dag, [dag.policies(), (KILL, BASE)], (0.35,), 120,
                        m_trials=8, key=key)
    kern = dag_frontier(dag, [dag.policies(), (KILL, BASE)], (0.35,), 120,
                        m_trials=8, key=key, kernel=True)
    for a, b in zip(scan, kern):
        assert a["mean_sojourn"] == pytest.approx(b["mean_sojourn"], rel=1e-5)
        assert a["mean_cost"] == pytest.approx(b["mean_cost"], rel=1e-5)
        assert a["map/share"] == pytest.approx(b["map/share"], rel=1e-4)


def test_lower_dag_frontier_is_the_program_dag_frontier_runs():
    """Once lower_dag_frontier's program is compiled, dag_frontier with the
    same shapes compiles nothing more."""
    dag = two_stage()
    vecs, kw = [dag.policies(), (KILL, BASE)], dict(m_trials=3, kernel=True)
    compiled = lower_dag_frontier(dag, vecs, (0.3,), 61, **kw).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
    seen = []

    def listener(name, duration, **kwargs):
        if name == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        rows = dag_frontier(dag, vecs, (0.3,), 61, **kw)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert seen == [] and len(rows) == 2


def test_padding_and_rcap_invariance():
    dag = two_stage()
    key = jax.random.PRNGKey(8)
    vecs = [dag.policies(), (BASE, BASE), (KILL, KEEP)]
    base = dag_frontier(dag, vecs, (0.3,), 100, m_trials=8, key=key,
                        pad_cells=False)
    padded = dag_frontier(dag, vecs, (0.3,), 100, m_trials=8, key=key,
                          pad_cells=True)
    for a, b in zip(base, padded):
        assert a["mean_sojourn"] == pytest.approx(b["mean_sojourn"], rel=1e-6)
    # widening r_caps only reshapes the masked fresh draws: estimates move
    # within MC error, never in expectation
    wide = dag_frontier(dag, vecs, (0.3,), 100, m_trials=8, key=key,
                        r_caps=(4, 4))
    for a, b in zip(base, wide):
        sigma = max(np.hypot(a["sojourn_std_err"], b["sojourn_std_err"]), 1e-12)
        assert abs(a["mean_sojourn"] - b["mean_sojourn"]) / sigma < 5.0
    with pytest.raises(ValueError, match="r_cap"):
        dag_frontier(dag, vecs, (0.3,), 100, m_trials=8, r_caps=(1, 1))
    with pytest.raises(ValueError, match="lam"):
        dag_frontier(dag, vecs, (0.0,), 100, m_trials=8)
    with pytest.raises(ValueError, match="policy vector"):
        dag_frontier(dag, [(BASE,)], (0.3,), 100, m_trials=8)


def test_empirical_stage_dists():
    """Per-stage trace slices drive the traced empirical path."""
    rng = np.random.default_rng(0)
    map_trace = rng.exponential(1.0, 400) + 1.0
    red_trace = rng.uniform(0.5, 2.0, 300)
    dag = JobDAG.map_reduce(8, 4, map_trace, red_trace, map_policy=KEEP,
                            c_map=2, c_reduce=2)
    res = dag_rollout(dag, lam=0.25, n_jobs=150, m_trials=8,
                      key=jax.random.PRNGKey(2))
    assert res.mean_sojourn > 0
    rep = DagFleetSim(DagFleetConfig(dag)).run(poisson_arrivals(150, 0.25))
    sigma = max(
        float(np.hypot(rep.stats.sojourn_std_err, res.sojourn_std_err)), 1e-12
    )
    assert abs(rep.stats.mean_sojourn - res.mean_sojourn) / sigma < 5.0


# ------------------------------------------------------------- search


SEARCH_CANDS = [BASE, SingleForkPolicy(0.1, 1, True), KILL]


def test_coordinate_search_improves_and_converges():
    dag = two_stage(map_policy=BASE, reduce_policy=BASE)
    out = coordinate_search(dag, SEARCH_CANDS, lam=0.3, n_jobs=128,
                            m_trials=8, key=jax.random.PRNGKey(4))
    assert out["converged"]
    assert out["n_evals"] > 0
    # CRN-consistent: the reported best is reproducible from dag_frontier
    row = dag_frontier(dag, [out["best"]["policies"]], (0.3,), 128,
                       m_trials=8, key=jax.random.PRNGKey(4),
                       r_caps=(2, 2))[0]
    assert row["mean_sojourn"] == pytest.approx(
        out["best"]["mean_sojourn"], rel=1e-6
    )


def test_coordinate_search_escapes_unstable_incumbent():
    """The ρ-guard outranks the objective: starting from an incumbent the
    fleet cannot absorb (ρ ≥ ρ_max), coordinate ascent must move to a
    stable vector when one exists — even at a worse objective — matching
    exhaustive_search's veto on the same grid."""
    hot = ShiftedExp(0.2, 3.0)
    dag = JobDAG.map_reduce(8, 4, hot, hot, c_map=1, c_reduce=1)
    cands = [BASE, SingleForkPolicy(0.3, 2, True)]
    kw = dict(lam=0.88, n_jobs=192, m_trials=12, key=jax.random.PRNGKey(1),
              objective="cost")
    co = coordinate_search(dag, cands, init=(BASE, BASE), **kw)
    assert co["best"]["rho"] < 0.95, "must escape the unstable baseline"
    ex = exhaustive_search(dag, cands, **kw)
    assert ex["best"]["rho"] < 0.95


def test_stage_scheduler_cannot_run_standalone():
    """A DAG stage scheduler shares its heap: popping through its OwnedHeap
    view (what a direct FleetScheduler.run() would do) must refuse rather
    than hand it another stage's events."""
    from repro.dag.engine import DagFleetScheduler

    sched = DagFleetScheduler(two_stage())
    sched._done = [set()]
    sched._release(0, 0, 0.0)  # a pending event makes the shared heap truthy
    stage0 = sched.stage_scheds[0]
    assert stage0.heap  # truthiness reflects the SHARED heap
    with pytest.raises(RuntimeError, match="shares its event heap"):
        stage0.run([])


@pytest.mark.slow
def test_exhaustive_search_dominates_uniform():
    """The joint per-stage optimum can only improve on the uniform slice of
    its own grid (shared CRN makes this exact, not statistical)."""
    dag = two_stage(map_policy=BASE, reduce_policy=BASE)
    key = jax.random.PRNGKey(9)
    out = exhaustive_search(dag, SEARCH_CANDS, lam=0.3, n_jobs=192,
                            m_trials=12, key=key)
    assert out["n_cells"] == len(SEARCH_CANDS) ** 2
    uni_rows = dag_frontier(dag, uniform_vectors(dag, SEARCH_CANDS), (0.3,),
                            192, m_trials=12, key=key, r_caps=(2, 2))
    best_uniform = min(uni_rows, key=lambda r: r["mean_sojourn"])
    assert out["best"]["mean_sojourn"] <= best_uniform["mean_sojourn"] + 1e-9


@pytest.mark.slow
def test_exhaustive_and_coordinate_agree_on_small_grid():
    dag = two_stage(map_policy=BASE, reduce_policy=BASE)
    key = jax.random.PRNGKey(10)
    ex = exhaustive_search(dag, SEARCH_CANDS, lam=0.25, n_jobs=160,
                           m_trials=12, key=key)
    co = coordinate_search(dag, SEARCH_CANDS, lam=0.25, n_jobs=160,
                           m_trials=12, key=key)
    # coordinate ascent can stop at a coordinate-wise local optimum, but it
    # must never end somewhere worse than the incumbent column minimum
    assert co["best"]["mean_sojourn"] <= ex["rows"][-1]["mean_sojourn"]
    ex_labels = {r["label"] for r in ex["rows"]}
    assert co["best"]["label"] in ex_labels


# ------------------------------------------------- stage traces + serving


def test_stage_trace_synthesis():
    from repro.data.traces import STAGE_TRACES, load_stage_trace, load_trace

    m = load_stage_trace("map")
    assert np.mean(m) == pytest.approx(1.0, rel=1e-6)
    raw = load_stage_trace("reduce", normalize=False)
    np.testing.assert_allclose(raw, load_trace(STAGE_TRACES["reduce"]))
    with pytest.raises(KeyError, match="shuffle|unknown"):
        load_stage_trace("not-a-stage")
    # map (job1) is heavier-tailed than reduce (job3) once normalized —
    # the asymmetry the per-stage policy split exploits
    r = load_stage_trace("reduce")
    assert np.max(m) / np.mean(m) > np.max(r) / np.mean(r)


def test_fleet_hedged_server_dag_mode():
    from repro.runtime import FleetHedgedServer

    dag = two_stage()
    srv = FleetHedgedServer(dag=dag, serve_fn=lambda r: r * 2)
    batches = [[1, 2, 3]] * 20
    outcomes, stats = srv.serve_stream(batches, rate=0.3, seed=0)
    assert [o.values for o in outcomes] == [[2, 4, 6]] * 20
    assert all(o.finish >= o.start >= o.arrival for o in outcomes)
    assert sum(stats.critical_path_shares.values()) == pytest.approx(1.0)
    with pytest.raises(ValueError, match="stage specs"):
        FleetHedgedServer(dag=dag, capacity=8, serve_fn=lambda r: r)
    # single-pool knobs are rejected, not silently dropped
    with pytest.raises(ValueError, match="stage specs"):
        FleetHedgedServer(dag=dag, serve_fn=lambda r: r, policy=KEEP)
    with pytest.raises(ValueError, match="stage specs"):
        FleetHedgedServer(dag=dag, serve_fn=lambda r: r, adapt=False)
    with pytest.raises(ValueError, match="stage specs"):
        FleetHedgedServer(dag=dag, serve_fn=lambda r: r, placement="aligned")


def test_public_exports():
    import repro.dag as dag_mod
    import repro.fleet as fleet_mod

    for name in ("frontier", "policy_search", "sweep", "fleet_rollout"):
        assert name in fleet_mod.__all__ and hasattr(fleet_mod, name)
    for name in ("JobDAG", "StageSpec", "dag_frontier", "dag_rollout",
                 "DagFleetSim", "coordinate_search", "exhaustive_search"):
        assert name in dag_mod.__all__ and hasattr(dag_mod, name)
