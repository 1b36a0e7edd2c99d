"""Vectorized Monte-Carlo simulation of single-/multi-fork job execution.

This is the *exact finite-n* ground truth (the points in the paper's
Figs. 3 and 5): for each trial, draw the n original execution times, apply
the fork semantics of Definition 1, and read off (T, C) per Definitions
1–2.  Everything is jnp; trials are vmapped, so m=10^4 trials of n=10^3
tasks is a single fused device program.

Semantics per trial (policy π(p, r), s = pn stragglers):

  T1    = s-th largest original time  (= (1-p)n-th order statistic)
  C1/n  = Σ_{i<=k} X_(i) + s·T1              (k = n - s finished + stragglers so far)
  Y_j   = min(X_(k+j) - T1, fresh_1..r)       π_keep  (original keeps running)
        = min(fresh_1..r+1)                   π_kill
  T     = T1 + max_j Y_j
  C·n   = C1 + (r+1)·Σ_j Y_j     (each straggler has r+1 copies running
                                  until its first finisher, per Fig. 2)
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from .distributions import Distribution, quantile_draws
from .policy import (
    MODE_QUANTILE,
    MultiForkPolicy,
    SingleForkPolicy,
    lower_policies,
    num_stragglers,
)

__all__ = [
    "SimResult",
    "lowered_policy_eval",
    "policy_draws",
    "simulate",
    "simulate_multifork",
    "single_fork_batch",
    "single_fork_trial",
]


@dataclasses.dataclass
class SimResult:
    latency: jnp.ndarray  # (m,) per-trial T
    cost: jnp.ndarray  # (m,) per-trial C

    @property
    def mean_latency(self) -> float:
        return float(jnp.mean(self.latency))

    @property
    def mean_cost(self) -> float:
        return float(jnp.mean(self.cost))

    @property
    def latency_std_err(self) -> float:
        m = self.latency.shape[0]
        return float(jnp.std(self.latency) / jnp.sqrt(m))

    @property
    def cost_std_err(self) -> float:
        m = self.cost.shape[0]
        return float(jnp.std(self.cost) / jnp.sqrt(m))


def single_fork_batch(key, dist: Distribution, n: int, s: int, r: int, keep: bool, shape=()):
    """(T, C) for a `shape`-batch of independent jobs under π(p, r, keep)
    with s = pn stragglers.

    All randomness is drawn in two bulk calls, so batching costs no extra
    threefry invocations — this is the shared implementation behind both
    `simulate` here and the fleet fast path (`repro.fleet.vector`).
    (n, s, r, keep, shape) must be static under jit.
    """
    kx, ky = jax.random.split(key)
    x_sorted = jnp.sort(dist.sample(kx, shape + (n,)), axis=-1)
    k = n - s
    if s == 0:
        return x_sorted[..., -1], jnp.sum(x_sorted, axis=-1) / n

    t1 = x_sorted[..., k - 1]
    finished_cost = jnp.sum(jnp.where(jnp.arange(n) < k, x_sorted, 0.0), axis=-1)
    c1 = finished_cost + s * t1

    stragglers = x_sorted[..., k:]  # the s largest original times (> t1)
    fresh = dist.sample(ky, shape + (s, r + 1))
    if keep:
        remaining = stragglers - t1[..., None]
        if r > 0:
            y = jnp.minimum(remaining, jnp.min(fresh[..., :r], axis=-1))
        else:
            y = remaining
    else:
        y = jnp.min(fresh, axis=-1)

    latency = t1 + jnp.max(y, axis=-1)
    cost = (c1 + (r + 1) * jnp.sum(y, axis=-1)) / n
    return latency, cost


def single_fork_trial(key, dist: Distribution, n: int, s: int, r: int, keep: bool):
    """One job's (T, C) — `single_fork_batch` with an empty batch shape
    (identical draws per key, so the two are interchangeable)."""
    return single_fork_batch(key, dist, n, s, r, keep, shape=())


# --------------------------------------------------------------------------
# the generalized evaluator: one program for the whole policy algebra
# --------------------------------------------------------------------------


def policy_draws(key, quantile, shape, n: int, r_cap: int, n_stages: int = 1):
    """Shared-CRN draws for the lowered-policy evaluator.

    Returns (x, fresh): x = `shape`-batch of n raw (UNsorted) original
    execution times, fresh = per-stage fresh-replica block of width r_cap
    aligned by completion rank.  Exactly two bulk threefry calls; for
    n_stages=1 the bit stream is identical to the historical
    `fleet.vector.fork_draws` (the sort there moved into the evaluator),
    which is what keeps algebra-lowered single-fork cells bit-identical to
    the pre-algebra fused path.
    """
    kx, ky = jax.random.split(key)
    x = quantile_draws(kx, quantile, shape + (n,))
    fresh = quantile_draws(ky, quantile, shape + (n_stages, n, r_cap))
    return x, fresh


def lowered_policy_eval(x, fresh, mode, k, t, r, keep, d):
    """(T, C) for one lowered policy cell on shared draws.

    Evaluates the full algebra — quantile- and time-triggered stages,
    keep|kill, group selection, multi-stage schedules — as one traced
    program; every argument after `fresh` is a (traced) lowered param from
    `core.policy.lower_policies`, so a grid of mixed families is just a
    vmap of this function over the param rows.

      x      (..., n)             raw original execution times
      fresh  (..., S, n, r_cap)   fresh-replica draws, cummin'd here
      mode, k, t, r, keep  (S,)   per-stage lowered params
      d      ()                   group width (= n → unrestricted)

    Semantics per stage: tasks are ranked within their group of d by
    current earliest-finish time; a quantile stage declares positions
    >= k (per group) stragglers at the group's k-th finish, a time stage
    declares everything unfinished at t a straggler.  Stragglers get r
    fresh copies (keep) or are killed and restarted with r+1 (kill);
    first finisher wins.  Cost is exact cohort accounting (Definition 2),
    and single-stage quantile cells at full width reproduce the
    historical `fleet.vector.masked_single_fork` op sequence bit for bit.
    """
    n = x.shape[-1]
    n_stages = fresh.shape[-3]
    iota = jnp.arange(n)
    gid = iota // d  # group of each ORIGINAL task index
    pos = iota % d  # within-group rank after the group-blocked sort
    base = gid * d
    cm = jax.lax.cummin(fresh, axis=fresh.ndim - 1)

    finish = x
    cohorts = [(jnp.zeros_like(x), jnp.ones_like(x))]  # (start, n_copies)
    cost = jnp.zeros(x.shape[:-1], x.dtype)
    t_leg = c_leg = None
    for s in range(n_stages):
        # group-blocked sort of current finish times: two-level stable
        # argsort (values, then group ids) — for d = n the group ids are
        # all zero and this is bitwise jnp.sort(finish)
        o1 = jnp.argsort(finish, axis=-1)
        o2 = jnp.argsort(jnp.take(gid, o1), axis=-1, stable=True)
        perm = jnp.take_along_axis(o1, o2, axis=-1)
        f_p = jnp.take_along_axis(finish, perm, axis=-1)

        is_q = mode[s] == MODE_QUANTILE
        k_s, t_s, r_s, keep_s = k[s], t[s], r[s], keep[s]
        # each position's group fork instant: the group's k-th finish
        tau_q = jnp.take_along_axis(
            f_p, jnp.broadcast_to(jnp.maximum(base + k_s - 1, 0), f_p.shape), axis=-1
        )
        tau = jnp.where(is_q, tau_q, t_s)
        # inactive padding stages lower to mode=TIME with t=inf → no stragglers
        strag = jnp.where(is_q, pos >= k_s, f_p > t_s)

        cms = cm[..., s, :, :]
        fresh_keep = jnp.where(
            r_s > 0, jnp.take(cms, jnp.maximum(r_s - 1, 0), axis=-1), jnp.inf
        )
        fresh_kill = jnp.take(cms, r_s, axis=-1)
        remaining = f_p - tau
        y = jnp.where(keep_s, jnp.minimum(remaining, fresh_keep), fresh_kill)
        y = jnp.where(strag, y, 0.0)

        if n_stages == 1:
            # the historical single-fork op sequence, bit for bit
            # (selected below for quantile cells at full width)
            t1 = jnp.take(f_p, jnp.maximum(k_s - 1, 0), axis=-1)
            c1 = jnp.sum(jnp.where(strag, 0.0, f_p), axis=-1) + (n - k_s) * t1
            t_leg = t1 + jnp.max(y, axis=-1)
            c_leg = (c1 + (r_s + 1.0) * jnp.sum(y, axis=-1)) / n

        # scatter back to original task order and do cohort accounting
        inv = jnp.argsort(perm, axis=-1)
        strag_o = jnp.take_along_axis(strag & (mode[s] >= 0), inv, axis=-1)
        tau_o = jnp.take_along_axis(jnp.broadcast_to(tau, f_p.shape), inv, axis=-1)
        newf = jnp.take_along_axis(jnp.where(strag, tau + y, f_p), inv, axis=-1)
        settle = strag_o & jnp.logical_not(keep_s)
        new_cohorts = []
        for start, count in cohorts:
            cost = cost + jnp.sum(
                jnp.where(settle, count * jnp.maximum(tau_o - start, 0.0), 0.0),
                axis=-1,
            )
            new_cohorts.append((start, jnp.where(settle, 0.0, count)))
        extra = jnp.where(strag_o, jnp.where(keep_s, r_s * 1.0, r_s + 1.0), 0.0)
        new_cohorts.append((jnp.where(strag_o, tau_o, 0.0), extra))
        cohorts = new_cohorts
        finish = newf
    for start, count in cohorts:
        cost = cost + jnp.sum(count * jnp.maximum(finish - start, 0.0), axis=-1)
    t_gen = jnp.max(finish, axis=-1)
    c_gen = cost / n
    if n_stages == 1:
        use_leg = (mode[0] == MODE_QUANTILE) & (d == n)
        return jnp.where(use_leg, t_leg, t_gen), jnp.where(use_leg, c_leg, c_gen)
    return t_gen, c_gen


@partial(jax.jit, static_argnames=("dist", "n", "m", "n_stages", "r_cap"))
def _simulate_lowered_jit(key, dist, mode, k, t, r, keep, d, n, m, n_stages, r_cap):
    x, fresh = policy_draws(key, dist.quantile, (m,), n, r_cap, n_stages)
    return lowered_policy_eval(x, fresh, mode, k, t, r, keep, d)


@partial(jax.jit, static_argnames=("dist", "policy", "n", "m"))
def _simulate_jit(key, dist, policy, n, m):
    s = num_stragglers(n, policy.p)
    keys = jax.random.split(key, m)
    lat, cost = jax.vmap(lambda k: single_fork_trial(k, dist, n, s, policy.r, policy.keep))(keys)
    return lat, cost


def simulate(
    dist: Distribution,
    policy,
    n: int,
    m: int = 1000,
    key=None,
) -> SimResult:
    """m Monte-Carlo trials of an n-task job under `policy`.

    Accepts any algebra policy (`SingleForkPolicy`, `MultiForkPolicy`,
    `ForkPolicy`, thin constructors like `delayed_relaunch` /
    `group_replication`).  `SingleForkPolicy` keeps its historical program
    (bit-identical draws and floats); everything else lowers to the fused
    tensor evaluator on the same CRN layout.  `OnClass` placement is queue
    geometry, not single-job sampling — rejected here.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    if isinstance(policy, SingleForkPolicy):
        lat, cost = _simulate_jit(key, dist, policy, n, m)
        return SimResult(latency=lat, cost=cost)
    lp = lower_policies([policy], n)
    if lp.class_names[0] is not None:
        raise ValueError(
            "OnClass policies restrict placement in a fleet; a single job "
            "has no machine classes to restrict — use FleetScheduler"
        )
    lat, cost = _simulate_lowered_jit(
        key,
        dist,
        jnp.asarray(lp.mode[0]),
        jnp.asarray(lp.k[0]),
        jnp.asarray(lp.t[0]),
        jnp.asarray(lp.r[0]),
        jnp.asarray(lp.keep[0]),
        int(lp.d[0]),
        n,
        m,
        lp.n_stages,
        max(lp.r_max + 1, 1),
    )
    return SimResult(latency=lat, cost=cost)


# --------------------------------------------------------------------------
# multi-fork generalization ([24, §6.4]) — simulation only
# --------------------------------------------------------------------------


def simulate_multifork(
    dist: Distribution,
    policy: MultiForkPolicy,
    n: int,
    m: int = 1000,
    key=None,
) -> SimResult:
    """Event-accurate multi-fork simulation.

    Tracked per task: earliest possible finish time given copies launched so
    far.  At each stage i (triggered when (1-p_i)n tasks are done), every
    unfinished task gets r_i fresh copies (kill_i additionally discards the
    old copies' remaining work).  Cost accounting mirrors Definition 2.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    stages = policy.stages

    def trial(key):
        keys = jax.random.split(key, len(stages) + 1)
        x = dist.sample(keys[0], (n,))
        finish = x  # current earliest finish time per task
        launch_cost_terms = []  # (start_time, count) pending per task
        # originals: started at 0, will run until min(finish, kill_time)
        run_start = jnp.zeros((n,))
        cost = jnp.zeros(())
        # Active copy bookkeeping: we fold each cohort's cost in when we know
        # the task's final finish time; with first-copy-wins all active
        # copies of task i stop at T_i.
        cohorts = [(jnp.zeros((n,)), jnp.ones((n,)))]  # (start_time, n_copies)

        for i, (p_i, r_i, keep_i) in enumerate(stages):
            s_i = num_stragglers(n, p_i)
            k_i = n - s_i
            t_fork = jnp.sort(finish)[k_i - 1]
            unfinished = finish > t_fork
            n_fresh = r_i if keep_i else r_i + 1  # kill relaunches r+1 copies
            fresh = dist.sample(keys[i + 1], (n, max(n_fresh, 1)))
            fresh_finish = t_fork + jnp.min(fresh[:, : max(n_fresh, 1)], axis=1)
            if not keep_i:
                # discard old copies for unfinished tasks: their cohorts stop
                # accruing at t_fork
                new_cohorts = []
                for start, count in cohorts:
                    stop = jnp.where(unfinished, t_fork, jnp.inf)  # inf = runs to finish
                    cost = cost + jnp.sum(
                        jnp.where(unfinished, count * jnp.maximum(t_fork - start, 0.0), 0.0)
                    )
                    # finished tasks keep their cohort (settled at the end)
                    new_cohorts.append((start, jnp.where(unfinished, 0.0, count)))
                cohorts = new_cohorts
                finish = jnp.where(unfinished, fresh_finish, finish)
                extra = jnp.where(unfinished, float(r_i + 1), 0.0)
                cohorts.append((jnp.full((n,), t_fork), extra))
            else:
                if r_i > 0:
                    finish = jnp.where(unfinished, jnp.minimum(finish, fresh_finish), finish)
                    cohorts.append(
                        (jnp.full((n,), t_fork), jnp.where(unfinished, float(r_i), 0.0))
                    )
        # settle all remaining cohorts at each task's final finish time
        for start, count in cohorts:
            cost = cost + jnp.sum(count * jnp.maximum(finish - start, 0.0))
        return jnp.max(finish), cost / n

    keys = jax.random.split(key, m)
    lat, cost = jax.vmap(trial)(keys)
    return SimResult(latency=lat, cost=cost)
