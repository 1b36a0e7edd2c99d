"""The observability stack: sketch accuracy, trace export, instrumentation.

Four layers under test:

  * `obs.sketch` — the DDSketch-style streaming quantile sketch: relative-
    accuracy guarantee on heavy-tailed inputs (property test), exact and
    associative merges, exact min/max/count riding along;
  * `obs.trace` / `obs.export` — the recorder protocol and its Chrome
    trace-event JSON round trip (what Perfetto loads);
  * the instrumented engines — FleetSim / DagFleetSim job spans telescope
    exactly (queue + service = sojourn), the controller's decision log
    records drift flushes across a regime change, serving reports live
    per-priority tails, and the fused frontier's `tail="hist"` device
    histograms agree with the exact percentiles;
  * zero-cost disabled paths — NullRecorder records nothing and the
    default config emits nothing.
"""

import json

import numpy as np
import pytest

from hypothesis_stubs import HAVE_HYPOTHESIS, given, settings, st

from repro.obs import (
    DEFAULT_HIST,
    DecisionEvent,
    DecisionLog,
    HistSpec,
    KIND_DRIFT,
    KIND_REPLAN,
    MetricsRegistry,
    NULL_RECORDER,
    NullRecorder,
    QuantileSketch,
    Recorder,
    device_histogram,
    kernel_profile,
    load_chrome_trace,
    sketch_from_device,
    write_chrome_trace,
)
from repro.obs import trace as obs_trace


# --------------------------------------------------------------------------
# sketch
# --------------------------------------------------------------------------


def _rank_of(sorted_x, v):
    return np.searchsorted(sorted_x, v, side="right") / len(sorted_x)


def test_sketch_relative_accuracy_heavy_tail():
    rng = np.random.default_rng(0)
    x = rng.pareto(1.5, size=50_000) + 1.0
    sk = QuantileSketch(rel_acc=0.01)
    sk.add_many(x)
    for q in (0.01, 0.25, 0.5, 0.9, 0.99, 0.999):
        exact = np.quantile(x, q)
        est = sk.quantile(q)
        assert abs(est - exact) <= 0.011 * exact + 1e-12, (q, est, exact)


def test_sketch_exact_extremes_count_sum():
    x = np.array([3.0, 0.1, 7.5, 2.2, 9.9])
    sk = QuantileSketch()
    sk.add_many(x)
    assert sk.count == 5
    assert sk.min == 0.1 and sk.max == 9.9  # exact extremes ride along
    # quantile endpoints stay within the clamp and the rel_acc contract
    assert sk.quantile(0.0) >= 0.1 * (1 - 0.0101)
    assert 9.9 * (1 - 0.0101) <= sk.quantile(1.0) <= 9.9
    assert sk.total == pytest.approx(x.sum())
    assert sk.mean == pytest.approx(x.mean())


def test_sketch_merge_associative_and_exact():
    rng = np.random.default_rng(1)
    parts = [rng.exponential(1.0, 500) + 0.01 for _ in range(3)]
    a, b, c = (QuantileSketch() for _ in range(3))
    for sk, xs in zip((a, b, c), parts):
        sk.add_many(xs)
    ab_c = a.copy().merge(b).merge(c)
    a_bc = b.copy().merge(c)
    a_bc = a.copy().merge(a_bc)
    one = QuantileSketch()
    one.add_many(np.concatenate(parts))
    for q in (0.1, 0.5, 0.99):
        assert ab_c.quantile(q) == a_bc.quantile(q) == one.quantile(q)
    assert ab_c.count == len(np.concatenate(parts))


def test_sketch_merge_requires_same_accuracy():
    with pytest.raises(ValueError):
        QuantileSketch(rel_acc=0.01).merge(QuantileSketch(rel_acc=0.02))


if HAVE_HYPOTHESIS:

    @settings(max_examples=60, deadline=None)
    @given(
        st.lists(
            st.floats(min_value=1e-6, max_value=1e9, allow_nan=False),
            min_size=1,
            max_size=300,
        ),
        st.sampled_from([0.5, 0.9, 0.99]),
    )
    def test_sketch_rank_accuracy_property(xs, q):
        """A returned quantile's *rank* error is bounded: the sketch value
        sits within rel_acc of some sample whose rank brackets q."""
        sk = QuantileSketch(rel_acc=0.01)
        sk.add_many(xs)
        est = sk.quantile(q)
        xs_sorted = np.sort(xs)
        # est must be within rel_acc of a value between the floor/ceil rank
        lo_i = int(np.floor(q * (len(xs) - 1)))
        hi_i = int(np.ceil(q * (len(xs) - 1)))
        lo, hi = xs_sorted[lo_i], xs_sorted[hi_i]
        assert est >= lo * (1 - 0.0101) - 1e-12
        assert est <= hi * (1 + 0.0101) + 1e-12


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------


def test_registry_instruments_and_type_clash():
    reg = MetricsRegistry()
    reg.counter("jobs").inc()
    reg.counter("jobs").inc(2)
    reg.gauge("rho").set(0.7)
    reg.histogram("lat", labels={"class": "gpu"}).observe_many([1.0, 2.0, 4.0])
    snap = reg.collect()
    assert snap["jobs"]["value"] == 3
    assert snap["rho"]["value"] == 0.7
    assert snap['lat{class="gpu"}']["count"] == 3
    with pytest.raises(TypeError):
        reg.gauge("jobs")


def test_registry_merge():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("n").inc(2)
    b.counter("n").inc(3)
    a.histogram("h").observe_many([1.0, 2.0])
    b.histogram("h").observe_many([3.0, 4.0])
    a.merge(b)
    assert a.counter("n").value == 5
    assert a.histogram("h").count == 4


# --------------------------------------------------------------------------
# trace recorder + Chrome export
# --------------------------------------------------------------------------


def test_chrome_trace_round_trip(tmp_path):
    rec = Recorder()
    rec.name_process(7, "myproc")
    rec.name_thread(7, 3, "lane")
    rec.span("job", "fleet", 1.5, 2.25, pid=7, tid=3, args={"n": 4})
    rec.instant("fork", "fleet", 2.0, pid=7, tid=3)
    rec.counter_sample("depth", 1.0, 5.0, pid=7)
    rec.count("events", 2)
    path = tmp_path / "trace.json"
    write_chrome_trace(path, rec)
    doc = json.loads(path.read_text())
    kinds = {e["ph"] for e in doc["traceEvents"]}
    assert {"X", "i", "C", "M"} <= kinds
    x = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    assert x["ts"] == pytest.approx(1.5e6)  # sim seconds -> µs
    assert x["dur"] == pytest.approx(2.25e6)
    back = load_chrome_trace(path)
    assert len(back.spans) == 1 and len(back.instants) == 1
    s = back.spans[0]
    assert (s.name, s.pid, s.tid) == ("job", 7, 3)
    assert s.ts == pytest.approx(1.5) and s.dur == pytest.approx(2.25)
    assert back.process_names[7] == "myproc"


def test_null_recorder_is_inert():
    n = NullRecorder()
    n.span("a", "b", 0, 1)
    n.instant("a", "b", 0)
    n.count("x")
    assert len(n) == 0 and not n.enabled and n.spans_named("a") == []
    assert len(NULL_RECORDER) == 0


def test_global_enable_disable():
    assert not obs_trace.get_recorder().enabled
    rec = obs_trace.enable()
    try:
        assert obs_trace.get_recorder() is rec
        rec.count("x")
    finally:
        obs_trace.disable()
    assert not obs_trace.get_recorder().enabled
    assert rec.counters["x"] == 1


# --------------------------------------------------------------------------
# instrumented engines
# --------------------------------------------------------------------------


def _fleet_trace(n_jobs=120):
    from repro.core import ShiftedExp
    from repro.fleet import FleetConfig, FleetSim, poisson_workload

    jobs = poisson_workload(n_jobs, rate=0.3, n_tasks=8,
                            dist=ShiftedExp(1.0, 1.0), seed=0)
    rep = FleetSim(FleetConfig(capacity=8, obs=True, seed=0)).run(jobs)
    return rep


def test_fleet_spans_telescope():
    rep = _fleet_trace()
    trace = rep.trace
    jobs = {s.tid: s for s in trace.spans_named("job")}
    queue = {s.tid: s for s in trace.spans_named("queue")}
    service = {s.tid: s for s in trace.spans_named("service")}
    assert len(jobs) == rep.stats.n_jobs
    for tid, job in jobs.items():
        svc = service[tid]
        wait = queue[tid].dur if tid in queue else 0.0
        # queue + service telescope exactly to the job's sojourn
        assert wait + svc.dur == pytest.approx(job.dur, abs=1e-9)
        assert svc.ts + svc.dur == pytest.approx(job.ts + job.dur, abs=1e-9)
    assert trace.counters["jobs_completed"] == rep.stats.n_jobs
    assert trace.counters["events.pushed"] >= trace.counters["events.popped"]


def test_fleet_disabled_records_nothing():
    from repro.core import ShiftedExp
    from repro.fleet import FleetConfig, FleetSim, poisson_workload

    jobs = poisson_workload(40, rate=0.3, n_tasks=8,
                            dist=ShiftedExp(1.0, 1.0), seed=0)
    rep = FleetSim(FleetConfig(capacity=8, seed=0)).run(jobs)
    assert not rep.trace.enabled and len(rep.trace) == 0


def test_fleet_private_recorder_does_not_touch_global():
    rep = _fleet_trace(40)
    assert len(rep.trace) > 0
    assert not obs_trace.get_recorder().enabled
    assert len(obs_trace.get_recorder()) == 0


def test_dag_spans_and_barriers():
    from repro.core import ShiftedExp
    from repro.dag import DagFleetConfig, DagFleetSim, JobDAG, poisson_arrivals

    dag = JobDAG.map_reduce(4, 2, ShiftedExp(1.0, 1.0), ShiftedExp(1.0, 0.5))
    n = 60
    rep = DagFleetSim(DagFleetConfig(dag, obs=True)).run(
        poisson_arrivals(n, 0.3, seed=1)
    )
    trace = rep.trace
    assert len(trace.spans_named("dag_job")) == n
    rels = [i for i in trace.instants if i.name == "barrier_release"]
    assert len(rels) == n  # one map -> reduce release per job
    names = set(trace.process_names.values())
    assert {"stage:map", "stage:reduce", "dag.jobs"} <= names
    # per-stage job spans telescope within each stage pid
    for pid in (obs_trace.PID_DAG_BASE, obs_trace.PID_DAG_BASE + 1):
        jobs = [s for s in trace.spans_named("job") if s.pid == pid]
        assert len(jobs) == n


def test_decision_log_drift_on_regime_shift():
    from repro.fleet import REGIME_SHIFT, FleetConfig, FleetSim

    jobs = REGIME_SHIFT.workload(240)
    rep = FleetSim(
        FleetConfig(capacity=REGIME_SHIFT.capacity, adapt=True,
                    seed=REGIME_SHIFT.seed, obs=True)
    ).run(jobs)
    ctrl = rep.controller
    log = ctrl.decisions
    assert log.n_replans == len(ctrl.history)
    assert log.n_drifts == ctrl.n_drifts >= 1
    kinds = {e.kind for e in log}
    assert KIND_REPLAN in kinds and KIND_DRIFT in kinds
    # every decision also landed as a marker on the controller pid
    markers = [i for i in rep.trace.instants
               if i.pid == obs_trace.PID_CONTROLLER]
    assert len(markers) == len(log.events)
    # timeline rows are JSON-ready
    json.dumps(log.timeline())
    assert all(e.t == e.t for e in log)  # sim-stamped, not NaN


def test_decision_log_standalone():
    log = DecisionLog(recorder=NULL_RECORDER)
    log.log(DecisionEvent(t=1.0, kind=KIND_REPLAN, label="baseline",
                          trigger="periodic", lam_hat=0.3, rho=0.2))
    log.log(DecisionEvent(t=2.0, kind=KIND_DRIFT, label="flush",
                          trigger="ks", ks_stat=0.4))
    assert log.n_replans == 1 and log.n_drifts == 1
    assert "ks=0.400" in log.render()


def test_serving_per_class_tails():
    from repro.core import ShiftedExp
    from repro.runtime.serving import FleetHedgedServer

    fs = FleetHedgedServer(capacity=32, latency_dist=ShiftedExp(1.0, 0.5),
                           serve_fn=lambda r: r, seed=0)
    batches = [list(range(4))] * 120
    pris = [i % 3 for i in range(120)]
    fs.serve_stream(batches, rate=1.5, priorities=pris)
    tails = fs.tail_latencies()
    assert set(tails) == {0, 1, 2}
    assert sum(t["count"] for t in tails.values()) == 120
    for t in tails.values():
        assert t["p50"] <= t["p99"] <= t["p999"]


def test_serve_batch_p999():
    from repro.core import ShiftedExp
    from repro.runtime.cluster import SimCluster
    from repro.runtime.serving import HedgedServer

    srv = HedgedServer(SimCluster(48, ShiftedExp(1.0, 0.5), seed=1),
                       serve_fn=lambda r: r)
    for _ in range(4):
        _, stats = srv.serve_batch(list(range(16)))
    assert np.isfinite(stats.p999)
    assert stats.p50 <= stats.p99 <= stats.p999
    assert srv.latency_sketch.count == 4 * 16


# --------------------------------------------------------------------------
# device-side histograms + fused engines' hist tails
# --------------------------------------------------------------------------


def test_device_histogram_matches_sketch():
    rng = np.random.default_rng(3)
    x = rng.pareto(1.5, 4096).astype(np.float32) + 1.0
    counts, vmin, vmax, total = device_histogram(x, DEFAULT_HIST)
    sk = sketch_from_device(np.asarray(counts), float(vmin), float(vmax),
                            float(total), spec=DEFAULT_HIST)
    assert sk.count == len(x)
    for q in (0.5, 0.99, 0.999):
        exact = np.quantile(x, q)
        assert abs(sk.quantile(q) - exact) <= 0.05 * exact + 1e-6


def test_frontier_hist_tail_matches_exact():
    from repro.core import ShiftedExp, SingleForkPolicy
    from repro.fleet import vector

    pols = (SingleForkPolicy(0.0, 0, True), SingleForkPolicy(0.1, 1, True))
    lams = (0.08, 0.16)
    kw = dict(n=8, n_jobs=200, m_trials=16)
    import jax

    key = jax.random.PRNGKey(5)
    exact = vector.frontier(ShiftedExp(1.0, 1.0), pols, lams, key=key, **kw)
    hist = vector.frontier(ShiftedExp(1.0, 1.0), pols, lams, key=key,
                           tail="hist", **kw)
    for e, h in zip(exact, hist):
        # identical program path for the means; sketch-accuracy tails
        assert h["mean_sojourn"] == pytest.approx(e["mean_sojourn"], rel=1e-6)
        assert h["p50"] == pytest.approx(e["p50"], rel=0.08)
        assert h["p99"] == pytest.approx(e["p99"], rel=0.12)
        assert {"cost_p50", "cost_p99", "cost_p999"} <= set(h)
        assert "cost_p50" not in e


def test_dag_frontier_hist_tail():
    from repro.core import ShiftedExp, SingleForkPolicy
    from repro.dag import JobDAG, dag_frontier

    dag = JobDAG.map_reduce(4, 2, ShiftedExp(1.0, 1.0), ShiftedExp(1.0, 0.5))
    base = SingleForkPolicy(0.0, 0, True)
    import jax

    key = jax.random.PRNGKey(6)
    kw = dict(n_jobs=128, m_trials=8, key=key)
    exact = dag_frontier(dag, [(base, base)], (0.3,), **kw)
    hist = dag_frontier(dag, [(base, base)], (0.3,), tail="hist", **kw)
    assert hist[0]["mean_sojourn"] == pytest.approx(
        exact[0]["mean_sojourn"], rel=1e-6
    )
    assert hist[0]["p50"] == pytest.approx(exact[0]["p50"], rel=0.08)
    assert "cost_p99" in hist[0]


def test_frontier_emits_dispatch_span_when_enabled():
    from repro.core import ShiftedExp, SingleForkPolicy
    from repro.fleet import vector

    pols = (SingleForkPolicy(0.0, 0, True),)
    rec = obs_trace.enable()
    try:
        vector.frontier(ShiftedExp(1.0, 1.0), pols, (0.1,), 8, 64, m_trials=4)
    finally:
        obs_trace.disable()
    spans = rec.spans_named("grid.dispatch")
    assert len(spans) == 1 and spans[0].pid == obs_trace.PID_PROFILER
    assert spans[0].args == {"cells": 1, "padded": 8}


@pytest.mark.parametrize(
    "search,span_args",
    [
        (False, {"cells": 8, "padded": 8, "qs": 2, "attempts": 4}),
        (True, {"cells": 3, "padded": 8, "qs": 1, "attempts": 4}),
    ],
    ids=["frontier", "policy_search"],
)
def test_fault_grid_dispatch_args_and_retry_draw_counter(search, span_args):
    """A grid on the q axis names its q count and attempt cap on
    `grid.dispatch`, and `fault.retry_draws` counts the uniforms its
    failure law draws a call: every attempt of the n originals and r_cap
    fresh copies, and the fates of all attempts but the last."""
    from repro.core import ShiftedExp, SingleForkPolicy
    from repro.faults import FaultSpec
    from repro.fleet import vector

    pols = (SingleForkPolicy(0.0, 0, True), SingleForkPolicy(0.25, 1, False),
            SingleForkPolicy(0.25, 2, True))
    law = ShiftedExp(1.0, 1.0)
    kw = dict(n_jobs=32, m_trials=4, r_cap=3)
    rec = obs_trace.enable()
    try:
        vector.frontier(law, pols[:2], (0.1,), 8, **kw)  # fault-free: counts nothing
        for _ in range(2):
            if search:
                vector.policy_search(law, pols, 0.1, 8, fault=FaultSpec(q=0.1, max_attempts=4),
                                     **kw)
            else:
                vector.frontier(law, pols[:2], (0.1, 0.2), 8,
                                fault=[FaultSpec(q=q, max_attempts=4) for q in (0.1, 0.2)],
                                **kw)
    finally:
        obs_trace.disable()
    spans = rec.spans_named("grid.dispatch")
    assert spans[0].args == {"cells": 2, "padded": 8}
    assert [s.args for s in spans[1:]] == [span_args] * 2
    assert rec.counters["fault.retry_draws"] == 2 * 4 * 32 * 8 * (1 + 3) * (2 * 4 - 1)


GRID_SPANS = ("grid.lower", "grid.dispatch", "grid.fetch", "grid.tail")


def _grid_call(engine, tail="exact"):
    """One small grid evaluation of `engine` ("fleet" or "dag")."""
    import jax

    from repro.core import ShiftedExp, SingleForkPolicy

    base, kill = SingleForkPolicy(0.0, 0, True), SingleForkPolicy(0.2, 1, False)
    kw = dict(n_jobs=64, m_trials=4, key=jax.random.PRNGKey(5), tail=tail)
    if engine == "fleet":
        from repro.fleet import frontier

        return frontier(ShiftedExp(1.0, 1.0), (base, kill), (0.1, 0.2), 8, **kw)
    from repro.dag import JobDAG, dag_frontier

    dag = JobDAG.map_reduce(4, 2, ShiftedExp(1.0, 1.0), ShiftedExp(1.0, 0.5))
    return dag_frontier(dag, [(base, base), (kill, base)], (0.1, 0.2), **kw)


def _in_order_without_overlap(spans):
    """(name, start, end) triples: the four grid spans once each, in order,
    each ending before the next starts."""
    assert [name for name, _, _ in spans] == list(GRID_SPANS)
    for (_, s, e), (_, s_next, _) in zip(spans, spans[1:]):
        assert s <= e <= s_next


@pytest.mark.parametrize("engine", ["fleet", "dag"])
def test_grid_spans_on_the_profiler_clock(engine, tmp_path):
    import jax

    _grid_call(engine)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        _grid_call(engine)
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    spans = sorted(
        ((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
         for plane in data.planes for line in plane.lines for ev in line.events
         if ev.name in GRID_SPANS),
        key=lambda sp: sp[1],
    )
    _in_order_without_overlap(spans)


@pytest.mark.parametrize("engine", ["fleet", "dag"])
def test_grid_spans_reach_the_recorder(engine):
    rec = obs_trace.enable()
    try:
        _grid_call(engine)
    finally:
        obs_trace.disable()
    spans = sorted(((s.name, s.ts, s.ts + s.dur) for s in rec.spans
                    if s.name in GRID_SPANS), key=lambda sp: sp[1])
    _in_order_without_overlap(spans)
    assert all(s.pid == obs_trace.PID_PROFILER for s in rec.spans)
    assert rec.spans_named("grid.dispatch")[0].args == {"cells": 4, "padded": 8}


@pytest.mark.parametrize("tail", ["exact", "hist"])
@pytest.mark.parametrize("engine", ["fleet", "dag"])
def test_rows_bitwise_equal_with_recorder_on_and_off(engine, tail):
    off = _grid_call(engine, tail)
    obs_trace.enable()
    try:
        on = _grid_call(engine, tail)
    finally:
        obs_trace.disable()
    assert [list(r) for r in on] == [list(r) for r in off]
    for r_on, r_off in zip(on, off):
        for k, v in r_off.items():
            same_nan = isinstance(v, float) and v != v and r_on[k] != r_on[k]
            assert r_on[k] == v or same_nan, k


def test_grid_path_waits_on_nothing_while_disabled(monkeypatch):
    """With the recorder off the grid path is its statements plus bare
    profiler annotations: no jit-cache reads, no forced device waits."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.fleet import vector

    def refuse(*a, **k):
        raise AssertionError("called with the recorder disabled")

    assert not obs_trace.get_recorder().enabled
    assert type(obs_trace.host_span("grid.tail", cells=3)) is TraceAnnotation
    monkeypatch.setattr(vector, "jit_cache_size", refuse)
    monkeypatch.setattr(jax, "block_until_ready", refuse)
    for engine in ("fleet", "dag"):
        assert _grid_call(engine)


def test_histspec_alignment():
    # device bucket keys line up with the host sketch's keys: same γ
    spec = HistSpec(lo=1e-3, n_bins=64, rel_acc=0.02)
    sk = QuantileSketch(rel_acc=0.02)
    assert spec.gamma == pytest.approx(sk.gamma)
    assert spec.hi > spec.lo


def test_kernel_profile_smoke():
    import jax.numpy as jnp

    reg = MetricsRegistry()
    rec = Recorder()
    prof = kernel_profile(
        lambda x: jnp.cumsum(x * 2.0),
        np.arange(64, dtype=np.float32),
        name="toy",
        repeats=2,
        recorder=rec,
        registry=reg,
    )
    assert prof["wall_s"] > 0 and prof["compile_s"] > 0
    assert prof["repeats"] == 2
    assert len(rec.spans_named("toy:exec")) == 2
    assert rec.spans_named("toy:compile")
    assert reg.collect("kernel_wall_s")
