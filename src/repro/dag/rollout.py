"""Fused stage-composed DAG rollouts: the vectorized fast path for
multi-stage jobs.

A DAG job traverses its stages through barriers: stage s cannot start
until every predecessor's *last* task (straggler included) has finished.
Each stage owns a dedicated pool of `c` gang blocks (the map-slot /
reduce-slot split), so per stage the fleet is a FIFO G/G/c queue whose
per-job service time is that stage's single-gang makespan T(π_s) under the
stage's replication policy — exactly the `repro.fleet.vector` model, once
per stage, chained by feeding each stage's completion times to its
successors as their arrival (barrier-release) times.

The engine composes the fused frontier machinery stage by stage:

  * per stage, ONE shared common-random-number draw pair (`fork_draws`
    through the stage's quantile transform — analytic or empirical) feeds
    `masked_single_fork` for EVERY (λ × per-stage-policy-vector) grid cell,
    so a whole joint-policy search is a single device program and
    same-grid comparisons are variance-reduced;
  * stage queues run through the shared `fleet.vector.batched_queue` cell
    engine — closed-form Lindley at c = 1, the Kiefer–Wolfowitz scan at
    c > 1, or (`kernel=True`) the Pallas `kernels.kw_queue` kernel with
    (cells × trials) rows tiled across its grid, one call per stage;
  * barrier-release times of a downstream stage need not be monotone (a
    c > 1 upstream queue can complete jobs out of order), so each stage
    sorts jobs by release time, runs the FIFO recursion, and inverts the
    permutation — for a source stage the sort is the identity, which keeps
    the degenerate one-stage DAG draw-for-draw identical to
    `fleet.vector.frontier` (tests pin this);
  * critical-path attribution: walking backwards from the sink that
    finished last, each stage on the critical path credits the predecessor
    whose barrier released it, so per job the per-stage attributions
    telescope EXACTLY to the sojourn — shares sum to 1 by construction,
    and E[share_s] answers "which stage's stragglers dominate E[T]".

Per-stage costs follow Definition 2 within each stage (copy-seconds / n_s)
and a job's cost is the sum over stages; latency E[T] is arrival → last
sink barrier.  The event-engine ground truth with identical semantics is
`repro.dag.engine.DagFleetSim` (per-stage aligned gang blocks);
tests/test_dag.py pins the two within Monte-Carlo error and
benchmarks/bench_dag.py gates the speedup.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import SingleForkPolicy, lower_policies, max_replicas
from repro.core.simulate import lowered_policy_eval, policy_draws
from repro.fleet.vector import (
    _fault_qs,
    _fetch_grid,
    _grid_tails,
    as_quantile_source,
    batched_queue,
    cell_bucket,
    emp_quantile,
    fork_draws,
    masked_single_fork,
    retry_draws,
    retry_transform,
)
from repro.obs.trace import host_span

from .graph import JobDAG

__all__ = [
    "DagRolloutResult", "dag_frontier", "dag_rollout", "lower_dag_frontier",
    "vector_label",
]


def vector_label(policies: Sequence[SingleForkPolicy], dag: Optional[JobDAG] = None) -> str:
    """Human-readable per-stage policy vector, e.g. 'map:pi_keep(p=0.1, r=1) | reduce:baseline'."""
    names = dag.names if dag is not None else tuple(f"s{i}" for i in range(len(policies)))
    return " | ".join(f"{n}:{p.label()}" for n, p in zip(names, policies))


def _plan(dag: JobDAG):
    """The hashable static skeleton `_dag_jit` specializes on, plus the
    traced per-stage empirical sample arrays (dummy for analytic stages)."""
    plan, xss = [], []
    for s in dag.stages:
        dist, xs = as_quantile_source(s.dist)
        plan.append(
            (s.n_tasks, s.c, tuple(dag.index[d] for d in s.deps), dist)
        )
        xss.append(xs)
    sinks = tuple(dag.index[n] for n in dag.sinks)
    return tuple(plan), sinks, tuple(xss)


def _compose(key, xss, kss, rss, keepss, lams, plan, sinks, n_jobs, m_trials,
             r_caps, kernel, modess=None, tss=None, dss=None, n_stagess=None,
             qs=None, attempts=None):
    """The stage-composed core: full (cells, m, J) tensors per stage.

    One CRN draw pair per stage shared by every cell; stages advance in the
    DAG's validated topological order, each one masked-single-fork sampling
    + a FIFO queue on barrier-release order.  Returns per-stage readys /
    starts / finishes / T / C plus arrivals.

    Two per-stage sampling programs, selected host-side (the same contract
    as the fleet `_frontier_jit`): `modess=None` traces the historical
    fork_draws + masked_single_fork program verbatim — the bit-identity
    anchor for all-single-fork vectors, where kss/rss/keepss are (cells, S)
    arrays — while algebra vectors pass per-stage lowered param tuples
    (modess/kss/tss/rss/keepss as (cells, S_s) rows, dss as (cells,) group
    widths, n_stagess static inner stage counts) through the general
    `lowered_policy_eval` on the same CRN layout.

    `qs` (a (cells,) traced vector, with the static draw width `attempts`)
    switches every stage's sampling to the geometric-retry transform: raw
    draws widen by an attempts axis and each cell folds them with ITS q
    before the policy evaluator (fleet.vector.retry_transform semantics).
    qs=None traces the historical programs verbatim — the bit-identity
    anchor, selected host-side exactly as in the fleet frontier.
    """
    S = len(plan)
    ka, kf = jax.random.split(key)
    # S == 1 keeps the exact draw structure of the single-stage frontier
    # engine (kf consumed directly), so a degenerate DAG is bit-identical
    # to fleet.vector.frontier on the same key — a test anchor, not a perf
    # hack.  Multi-stage DAGs give each stage an independent subkey.
    stage_keys = [kf] if S == 1 else list(jax.random.split(kf, S))
    expo_cum = jnp.cumsum(jax.random.exponential(ka, (m_trials, n_jobs)), axis=1)
    arrivals = expo_cum[None, :, :] / lams[:, None, None]  # (cells, m, J)

    readys, starts, finishes, Ts, Cs = [], [], [], [], []
    gather = lambda z, o: jnp.take_along_axis(z, o, axis=-1)  # noqa: E731
    for s in range(S):
        n_s, c_s, preds, dist_s = plan[s]
        quantile = dist_s.quantile if dist_s is not None else partial(emp_quantile, xss[s])
        if modess is None and qs is None:
            x_sorted, fresh = fork_draws(
                stage_keys[s], quantile, (m_trials, n_jobs), n_s, r_caps[s]
            )
            T_s, C_s = jax.vmap(
                lambda k, r, kp: masked_single_fork(x_sorted, fresh, k, r, kp)
            )(kss[:, s], rss[:, s], keepss[:, s])  # each (cells, m, J)
        elif modess is None:
            kx, ky = jax.random.split(stage_keys[s])
            xr, xv = retry_draws(kx, quantile, (m_trials, n_jobs, n_s), attempts)
            fr, fv = retry_draws(
                ky, quantile, (m_trials, n_jobs, n_s, r_caps[s]), attempts
            )
            T_s, C_s = jax.vmap(
                lambda k, r, kp, q: masked_single_fork(
                    jnp.sort(retry_transform(xr, xv, q), axis=-1),
                    retry_transform(fr, fv, q), k, r, kp,
                )
            )(kss[:, s], rss[:, s], keepss[:, s], qs)
        elif qs is None:
            x, fresh = policy_draws(
                stage_keys[s], quantile, (m_trials, n_jobs), n_s, r_caps[s],
                n_stagess[s],
            )
            T_s, C_s = jax.vmap(
                lambda mode, k, t, r, kp, d: lowered_policy_eval(
                    x, fresh, mode, k, t, r, kp, d
                )
            )(modess[s], kss[s], tss[s], rss[s], keepss[s], dss[s])
        else:
            kx, ky = jax.random.split(stage_keys[s])
            xr, xv = retry_draws(kx, quantile, (m_trials, n_jobs, n_s), attempts)
            fr, fv = retry_draws(
                ky, quantile,
                (m_trials, n_jobs, n_stagess[s], n_s, r_caps[s]), attempts,
            )
            T_s, C_s = jax.vmap(
                lambda mode, k, t, r, kp, d, q: lowered_policy_eval(
                    retry_transform(xr, xv, q), retry_transform(fr, fv, q),
                    mode, k, t, r, kp, d,
                )
            )(modess[s], kss[s], tss[s], rss[s], keepss[s], dss[s], qs)
        if preds:
            ready = finishes[preds[0]]
            for p in preds[1:]:
                ready = jnp.maximum(ready, finishes[p])
        else:
            ready = arrivals
        # FIFO on barrier-release order: upstream c > 1 queues may complete
        # out of job order, so sort (stable: ties keep job order), run the
        # recursion, invert.  Source stages sort an already-sorted stream —
        # the permutation is the identity and costs only the argsort.
        order = jnp.argsort(ready, axis=-1)
        inv = jnp.argsort(order, axis=-1)
        speeds = jnp.ones((c_s,), arrivals.dtype)
        st, fi, _, _ = batched_queue(
            gather(ready, order), gather(T_s, order), speeds, kernel=kernel
        )
        readys.append(ready)
        starts.append(gather(st, inv))
        finishes.append(gather(fi, inv))
        Ts.append(T_s)
        Cs.append(C_s)

    return arrivals, readys, starts, finishes, Ts, Cs


def _critical_attribution(arrivals, readys, finishes, plan, sinks):
    """Per-job critical-path decomposition: attr[s] = time the job spent in
    stage s *on the path that determined its completion*, else 0.

    Walk backwards from the sink with the max finish; every critical stage
    credits the predecessor whose barrier released it (argmax over pred
    finishes, first-wins on ties).  The chain telescopes: Σ_s attr_s =
    sojourn exactly, so shares sum to 1 by construction.
    """
    S = len(plan)
    if len(sinks) == 1:
        F = finishes[sinks[0]]
        crit = [jnp.zeros(F.shape, bool) for _ in range(S)]
        crit[sinks[0]] = jnp.ones(F.shape, bool)
    else:
        sink_f = jnp.stack([finishes[s] for s in sinks])
        F = jnp.max(sink_f, axis=0)
        winner = jnp.argmax(sink_f, axis=0)
        crit = [jnp.zeros(F.shape, bool) for _ in range(S)]
        for j, s in enumerate(sinks):
            crit[s] = winner == j
    attrs = [None] * S
    for s in reversed(range(S)):
        _, _, preds, _ = plan[s]
        attrs[s] = jnp.where(crit[s], finishes[s] - readys[s], 0.0)
        if not preds:
            continue
        if len(preds) == 1:
            crit[preds[0]] = crit[preds[0]] | crit[s]
        else:
            pred_f = jnp.stack([finishes[p] for p in preds])
            win = jnp.argmax(pred_f, axis=0)
            for j, p in enumerate(preds):
                crit[p] = crit[p] | (crit[s] & (win == j))
    sojourn = F - arrivals
    return sojourn, attrs


@partial(
    jax.jit,
    static_argnames=("plan", "sinks", "n_jobs", "m_trials", "r_caps", "kernel",
                     "hist", "n_stagess", "attempts"),
)
def _dag_stats_jit(key, xss, kss, rss, keepss, lams, plan, sinks, n_jobs,
                   m_trials, r_caps, kernel, hist=None, modess=None, tss=None,
                   dss=None, n_stagess=None, qs=None, attempts=None):
    """Grid evaluation: one stacked stats row per cell + job sojourns for
    host-side percentiles (XLA CPU sort is ~10x slower than np.partition,
    same split as the fleet frontier).  With `hist` (a static
    `repro.obs.HistSpec`) the raw sojourns stay on device and fixed-size
    γ-bucket sojourn + cost bincounts ship instead — the device-side
    observability path, same layout as the fleet `_frontier_jit`."""
    arrivals, readys, starts, finishes, Ts, Cs = _compose(
        key, xss, kss, rss, keepss, lams, plan, sinks, n_jobs, m_trials,
        r_caps, kernel, modess=modess, tss=tss, dss=dss, n_stagess=n_stagess,
        qs=qs, attempts=attempts,
    )
    sojourn, attrs = _critical_attribution(arrivals, readys, finishes, plan, sinks)
    S = len(plan)
    mean = lambda z: jnp.mean(z, axis=(1, 2))  # noqa: E731  per cell
    cost = sum(Cs)
    wait_total = sum(starts[s] - readys[s] for s in range(S))
    service_total = sum(Ts)
    per_trial = jnp.mean(sojourn, axis=2)  # (cells, m)
    m = per_trial.shape[1]
    se = jnp.std(per_trial, axis=1) / jnp.sqrt(max(m - 1, 1))
    mean_soj = mean(sojourn)
    # per-stage blocks: share, sojourn (ready->finish), wait, service, cost,
    # rho_block (λ·E[T_s] / c_s — the gang-block occupancy bound per pool)
    blocks = []
    for s in range(S):
        _, c_s, _, _ = plan[s]
        blocks.append(
            jnp.stack(
                [
                    mean(attrs[s]) / jnp.maximum(mean_soj, 1e-12),
                    mean(finishes[s] - readys[s]),
                    mean(starts[s] - readys[s]),
                    mean(Ts[s]),
                    mean(Cs[s]),
                    lams * mean(Ts[s]) / c_s,
                ],
                axis=1,
            )
        )
    rho = jnp.max(jnp.stack([b[:, 5] for b in blocks], axis=1), axis=1)
    base = jnp.stack([mean_soj, mean(wait_total), mean(service_total),
                      mean(cost), se, rho], axis=1)
    stats = jnp.concatenate([base] + blocks, axis=1)
    if hist is None:
        return stats, sojourn.reshape(sojourn.shape[0], -1)
    from repro.obs.device import device_histogram

    def cell_hists(soj_cell, cost_cell):
        s_counts, s_min, s_max, s_sum = device_histogram(soj_cell, hist)
        c_counts, c_min, c_max, c_sum = device_histogram(cost_cell, hist)
        return (s_counts, jnp.stack([s_min, s_max, s_sum]),
                c_counts, jnp.stack([c_min, c_max, c_sum]))

    return stats, jax.vmap(cell_hists)(sojourn, cost)


@partial(
    jax.jit,
    static_argnames=("plan", "sinks", "n_jobs", "m_trials", "r_caps", "kernel",
                     "n_stagess"),
)
def _dag_rollout_jit(key, xss, kss, rss, keepss, lams, plan, sinks, n_jobs,
                     m_trials, r_caps, kernel, modess=None, tss=None, dss=None,
                     n_stagess=None):
    """Full-tensor variant for `dag_rollout`: every per-stage path back to
    the host (stacked on a leading stage axis), cells squeezed by caller."""
    arrivals, readys, starts, finishes, Ts, Cs = _compose(
        key, xss, kss, rss, keepss, lams, plan, sinks, n_jobs, m_trials,
        r_caps, kernel, modess=modess, tss=tss, dss=dss, n_stagess=n_stagess,
    )
    sojourn, attrs = _critical_attribution(arrivals, readys, finishes, plan, sinks)
    stack = lambda zs: jnp.stack(zs, axis=0)  # noqa: E731  (S, cells, m, J)
    return (
        arrivals,
        sojourn,
        stack(readys),
        stack(starts),
        stack(finishes),
        stack(Ts),
        stack(Cs),
        stack(attrs),
    )


#: job-level stats emitted by `_dag_stats_jit`, in stack order; the
#: percentile keys are appended host-side from the returned sojourns
_DAG_JIT_KEYS = ("mean_sojourn", "mean_wait", "mean_service", "mean_cost",
                 "sojourn_std_err", "rho")
#: per-stage stats, keyed as "<stage>/<key>" in the row dicts
_DAG_STAGE_KEYS = ("share", "sojourn", "wait", "service", "cost", "rho")


def _stage_lowerings(dag, vecs):
    """One canonical lowering per DAG stage: row i of stage s's tensor is
    cell i's policy for that stage (`core.policy.lower_policies`)."""
    return [
        lower_policies([vec[s] for vec in vecs], spec.n_tasks)
        for s, spec in enumerate(dag.stages)
    ]


def _stage_pol_args(lps):
    """(ks, rs, keeps, general_kwargs) for the fused jits from per-stage
    lowerings.  All-single-fork grids keep the historical (cells, S) array
    layout — the bit-identity anchor — while algebra grids ship the full
    per-stage lowered tensors for the general evaluator."""
    general = any(lp.multi_stage or lp.has_time or lp.has_group for lp in lps)
    if general:
        ks = tuple(jnp.asarray(lp.k) for lp in lps)
        rs = tuple(jnp.asarray(lp.r) for lp in lps)
        keeps = tuple(jnp.asarray(lp.keep) for lp in lps)
        kwargs = dict(
            modess=tuple(jnp.asarray(lp.mode) for lp in lps),
            tss=tuple(jnp.asarray(lp.t) for lp in lps),
            dss=tuple(jnp.asarray(lp.d) for lp in lps),
            n_stagess=tuple(lp.n_stages for lp in lps),
        )
        return ks, rs, keeps, kwargs
    ks = jnp.asarray(np.stack([lp.k[:, 0] for lp in lps], axis=1))
    rs = jnp.asarray(np.stack([lp.r[:, 0] for lp in lps], axis=1))
    keeps = jnp.asarray(np.stack([lp.keep[:, 0] for lp in lps], axis=1))
    return ks, rs, keeps, {}


def _resolve_r_caps(dag, cell_vectors, r_caps):
    r_max = [
        max(max_replicas(vec[s]) for vec in cell_vectors)
        for s in range(len(dag.stages))
    ]
    if r_caps is None:
        return tuple(r + 1 for r in r_max)
    r_caps = tuple(int(r) for r in r_caps)
    if len(r_caps) != len(dag.stages):
        raise ValueError(f"need one r_cap per stage, got {len(r_caps)}")
    for s, (cap, rm) in enumerate(zip(r_caps, r_max)):
        if cap < rm + 1:
            raise ValueError(
                f"stage {dag.stages[s].name!r}: r_cap={cap} < r_max+1={rm + 1}"
            )
    return r_caps


def _dag_cells_call(dag, cell_vectors, cell_lams, n_jobs, m_trials, key, kernel,
                    r_caps, pad_cells, tail, cell_qs, attempts):
    """Check one grid of already validated policy vectors and lower it onto the
    fused program: returns `_dag_stats_jit`'s positional and keyword
    arguments and the hist spec its rows are read back with."""
    if not cell_vectors:
        raise ValueError("need at least one candidate policy vector")
    if any(lam <= 0 for lam in cell_lams):
        raise ValueError("arrival rate lam must be > 0")
    if key is None:
        key = jax.random.PRNGKey(0)
    plan, sinks, xss = _plan(dag)
    r_caps = _resolve_r_caps(dag, cell_vectors, r_caps)

    n_cells = len(cell_vectors)
    n_padded = cell_bucket(n_cells) if pad_cells else n_cells
    vecs = list(cell_vectors) + [cell_vectors[0]] * (n_padded - n_cells)
    lams = [float(lam) for lam in cell_lams]
    lams += [lams[0]] * (n_padded - n_cells)
    qs_arg = None
    if cell_qs is not None:
        if len(cell_qs) != n_cells:
            raise ValueError("need one q per cell")
        if attempts is None or attempts < 1:
            raise ValueError("cell_qs needs a static attempts >= 1")
        qs = [float(q) for q in cell_qs]
        qs += [qs[0]] * (n_padded - n_cells)
        qs_arg = jnp.asarray(qs)
    # canonical per-stage lowering: all-single-fork grids reduce to the
    # historical (cells, S) k/r/keep arrays (k = n - num_stragglers via the
    # one rounding contract), algebra grids carry the general param tensors
    ks, rs, keeps, gen_kwargs = _stage_pol_args(_stage_lowerings(dag, vecs))

    from repro.obs.device import HistSpec, DEFAULT_HIST

    if tail == "exact":
        hist = None
    elif tail == "hist":
        hist = DEFAULT_HIST
    elif isinstance(tail, HistSpec):
        hist = tail
    else:
        raise ValueError(f'tail must be "exact", "hist", or a HistSpec, got {tail!r}')

    args = (key, xss, ks, rs, keeps, jnp.asarray(lams), plan, sinks, n_jobs,
            m_trials, r_caps, kernel)
    return args, dict(hist=hist, qs=qs_arg, attempts=attempts, **gen_kwargs), hist


def _eval_dag_cells(
    dag: JobDAG,
    cell_vectors,
    cell_lams,
    n_jobs: int,
    m_trials: int,
    key,
    kernel: bool,
    r_caps,
    pad_cells: bool,
    tail="exact",
    cell_qs=None,
    attempts=None,
):
    """Shared engine behind `dag_frontier` (and the joint searches): one
    stats dict per (policy-vector, λ) cell from a single fused dispatch.
    `tail` follows the fleet `_eval_cells` convention: "exact" ships the
    sojourn matrices, "hist" / a `repro.obs.HistSpec` ships in-program
    bincounts and adds cost_p50/cost_p99/cost_p999 to every row.
    `cell_qs` (one per cell, static draw width `attempts`) runs every stage
    under the geometric-retry transform; None keeps the historical
    bit-identical programs."""
    with host_span("grid.lower"):
        cell_vectors = [dag.validate_policy_vector(v) for v in cell_vectors]
        args, kwargs, hist = _dag_cells_call(
            dag, cell_vectors, cell_lams, n_jobs, m_trials, key, kernel, r_caps,
            pad_cells, tail, cell_qs, attempts,
        )
    n_cells = len(cell_vectors)
    with host_span("grid.dispatch", cells=n_cells,
                   padded=cell_bucket(n_cells) if pad_cells else n_cells):
        stats, payload = _dag_stats_jit(*args, **kwargs)
    with host_span("grid.fetch"):
        stats, payload = _fetch_grid(stats, payload, hist, n_cells)
    with host_span("grid.tail"):
        pcts, cost_pcts, cell_evt = _grid_tails(payload, hist, n_cells)
        rows = []
        nk = len(_DAG_JIT_KEYS)
        nsk = len(_DAG_STAGE_KEYS)
        for i, (vec, lam) in enumerate(zip(cell_vectors, cell_lams)):
            row = dict(
                lam=float(lam),
                policies=tuple(vec),
                label=vector_label(vec, dag),
                **dict(zip(_DAG_JIT_KEYS, map(float, stats[i, :nk]))),
            )
            if cell_qs is not None:
                row["q"] = float(cell_qs[i])
            row["p50"], row["p99"], row["p999"] = (float(pcts[j, i]) for j in range(3))
            if cost_pcts is not None:
                row["cost_p50"], row["cost_p99"], row["cost_p999"] = (
                    float(cost_pcts[j, i]) for j in range(3)
                )
                row.update(cell_evt[i])
            for s, spec in enumerate(dag.stages):
                off = nk + s * nsk
                for j, k in enumerate(_DAG_STAGE_KEYS):
                    row[f"{spec.name}/{k}"] = float(stats[i, off + j])
            rows.append(row)
    return rows


def dag_frontier(
    dag: JobDAG,
    policy_vectors,
    lams,
    n_jobs: int,
    m_trials: int = 32,
    key=None,
    kernel: bool = False,
    r_caps=None,
    pad_cells: bool = True,
    tail="exact",
    fault=None,
) -> list[dict]:
    """The whole (per-stage-policy-vector × λ) cross-product as ONE fused
    device program over shared CRN draws.

    `policy_vectors` is a sequence of per-stage tuples (one
    `SingleForkPolicy` per stage, in DAG stage order; pass `None` entries
    nowhere — use `dag.policies()` for the specs' defaults).  Rows come
    back vector-major with job-level keys (`mean_sojourn` = arrival → last
    sink barrier, `mean_cost` = Σ stages' Definition-2 costs, `rho` = max
    per-stage gang-block occupancy, percentiles) plus per-stage
    `"<stage>/<key>"` entries — including `"<stage>/share"`, the
    critical-path attribution (shares sum to 1 per cell).

    One compilation covers any same-shaped grid: (k, r, keep) per stage and
    λ are traced per-cell vectors, cells pad to power-of-two buckets, and
    `r_caps` pins per-stage fresh-draw widths for re-plan stability.
    `kernel=True` routes every stage's queue through the Pallas
    `kernels.kw_queue` kernel (one call per stage).

    `fault` (a `repro.faults.FaultSpec` or sequence — q law, immediate
    relaunch only) adds a failure axis exactly as in the fleet `frontier`:
    cells = vectors × λs × faults with q fastest, every stage samples
    through the geometric-retry transform, rows gain "q", and a single
    disabled spec reproduces the fault-free rows bitwise.
    """
    policy_vectors = [tuple(v) for v in policy_vectors]
    lams = [float(lam) for lam in lams]
    if not lams:
        raise ValueError("need at least one arrival rate")
    cell_vectors = [vec for vec in policy_vectors for _ in lams]
    cell_lams = lams * len(policy_vectors)
    cell_qs = attempts = None
    if fault is not None:
        qs, attempts = _fault_qs(fault)
        if len(qs) == 1 and qs[0] == 0.0:
            rows = _eval_dag_cells(
                dag, cell_vectors, cell_lams, n_jobs, m_trials, key, kernel,
                r_caps, pad_cells, tail=tail,
            )
            for row in rows:
                row["q"] = 0.0
            return rows
        cell_vectors = [vec for vec in cell_vectors for _ in qs]
        cell_lams = [lam for lam in cell_lams for _ in qs]
        cell_qs = qs * (len(policy_vectors) * len(lams))
    return _eval_dag_cells(
        dag, cell_vectors, cell_lams, n_jobs, m_trials, key, kernel, r_caps,
        pad_cells, tail=tail, cell_qs=cell_qs, attempts=attempts,
    )


def lower_dag_frontier(
    dag: JobDAG,
    policy_vectors,
    lams,
    n_jobs: int,
    m_trials: int = 32,
    key=None,
    kernel: bool = False,
    r_caps=None,
    pad_cells: bool = True,
    tail="exact",
):
    """The fault-free device program `dag_frontier` runs for these
    arguments, lowered but not run (a `jax.stages.Lowered`).  `.compile()`
    gives its memory analysis and optimized HLO, and a later `dag_frontier`
    call of the same shapes reuses that executable."""
    lams = [float(lam) for lam in lams]
    cell_vectors = [dag.validate_policy_vector(tuple(v)) for v in policy_vectors for _ in lams]
    args, kwargs, _ = _dag_cells_call(
        dag, cell_vectors, lams * len(policy_vectors), n_jobs, m_trials, key,
        kernel, r_caps, pad_cells, tail, None, None,
    )
    return _dag_stats_jit.lower(*args, **kwargs)


@dataclasses.dataclass
class DagRolloutResult:
    """Full per-stage sample paths of one (policy-vector, λ) DAG rollout."""

    stage_names: tuple
    arrivals: jnp.ndarray  # (m_trials, n_jobs)
    sojourn: jnp.ndarray  # (m_trials, n_jobs) arrival -> last sink barrier
    ready: jnp.ndarray  # (S, m, J) barrier-release per stage
    start: jnp.ndarray  # (S, m, J) stage queue admission
    finish: jnp.ndarray  # (S, m, J) stage barrier (last task done)
    service: jnp.ndarray  # (S, m, J) per-stage gang makespan T(π_s)
    cost: jnp.ndarray  # (S, m, J) per-stage Definition-2 cost
    attr: jnp.ndarray  # (S, m, J) critical-path attribution (sums to sojourn)

    @property
    def total_cost(self) -> jnp.ndarray:
        return jnp.sum(self.cost, axis=0)

    @property
    def wait(self) -> jnp.ndarray:
        """(S, m, J) per-stage queueing delay (release -> admission)."""
        return self.start - self.ready

    @property
    def mean_sojourn(self) -> float:
        return float(jnp.mean(self.sojourn))

    @property
    def mean_cost(self) -> float:
        return float(jnp.mean(self.total_cost))

    @property
    def sojourn_std_err(self) -> float:
        per_trial = jnp.mean(self.sojourn, axis=1)
        m = per_trial.shape[0]
        return float(jnp.std(per_trial) / jnp.sqrt(max(m - 1, 1)))

    def stage_shares(self) -> dict:
        """E[critical-path time in stage] / E[sojourn]; sums to 1."""
        denom = max(float(jnp.mean(self.sojourn)), 1e-12)
        return {
            name: float(jnp.mean(self.attr[s]) / denom)
            for s, name in enumerate(self.stage_names)
        }

    def summary(self) -> dict:
        out = dict(
            mean_sojourn=self.mean_sojourn,
            mean_cost=self.mean_cost,
            sojourn_std_err=self.sojourn_std_err,
        )
        soj = np.asarray(self.sojourn).ravel()
        out["p50"], out["p99"], out["p999"] = (
            float(v) for v in np.percentile(soj, (50.0, 99.0, 99.9))
        )
        for s, name in enumerate(self.stage_names):
            out[f"{name}/sojourn"] = float(jnp.mean(self.finish[s] - self.ready[s]))
            out[f"{name}/wait"] = float(jnp.mean(self.start[s] - self.ready[s]))
            out[f"{name}/service"] = float(jnp.mean(self.service[s]))
            out[f"{name}/cost"] = float(jnp.mean(self.cost[s]))
        for name, share in self.stage_shares().items():
            out[f"{name}/share"] = share
        return out


def dag_rollout(
    dag: JobDAG,
    lam: float,
    n_jobs: int,
    m_trials: int = 32,
    policies: Optional[Sequence] = None,
    key=None,
    kernel: bool = False,
    r_caps=None,
) -> DagRolloutResult:
    """m_trials independent fleets of n_jobs Poisson(λ) DAG jobs under one
    per-stage policy vector (default: the stage specs' own policies).

    Returns the full per-stage sample paths — barrier releases, queue
    admissions, stage barriers, per-stage (T, C), and the critical-path
    attribution.  A one-stage DAG reproduces `fleet.vector.fleet_rollout` /
    `frontier` semantics on the same key (tests pin the degenerate case);
    `kernel=True` runs every stage queue through the Pallas kw_queue
    kernel.
    """
    if lam <= 0:
        raise ValueError("arrival rate lam must be > 0")
    if key is None:
        key = jax.random.PRNGKey(0)
    vec = dag.validate_policy_vector(policies)
    plan, sinks, xss = _plan(dag)
    r_caps = _resolve_r_caps(dag, [vec], r_caps)
    ks, rs, keeps, gen_kwargs = _stage_pol_args(_stage_lowerings(dag, [vec]))
    arrivals, sojourn, ready, start, finish, T, C, attr = _dag_rollout_jit(
        key, xss, ks, rs, keeps, jnp.array([float(lam)]), plan, sinks,
        n_jobs, m_trials, r_caps, kernel, **gen_kwargs,
    )
    squeeze = lambda z: z[:, 0] if z.ndim == 4 else z[0]  # noqa: E731  drop the cell axis
    return DagRolloutResult(
        stage_names=dag.names,
        arrivals=arrivals[0],
        sojourn=sojourn[0],
        ready=squeeze(ready),
        start=squeeze(start),
        finish=squeeze(finish),
        service=squeeze(T),
        cost=squeeze(C),
        attr=squeeze(attr),
    )
