"""Plain reference of a gang-block fleet under single-fork replication.

The semantics are those of Wang, Joshi and Wornell (arXiv:1503.03128),
Definitions 1 and 2, pushed through a FIFO queue of gang blocks:

* a job of n tasks draws n task times X_i from the empirical trace (the
  type-1 inverse of its sorted samples);
* at the fork point, when k = n - s tasks have finished (t1 = X_(k)), each
  of the s stragglers gets r fresh copies: under keep the original runs on
  and the task ends after min(X_(i) - t1, Y_1..Y_r); under kill it ends
  after min(Y_1..Y_(r+1));
* T = t1 + the largest residual, and the cost C (machine time per task) is
  (sum of the first k times + s * t1 + (r + 1) * sum of the residuals) / n;
* jobs arrive as a Poisson stream and queue FIFO for c gang blocks; a job
  takes the fastest block idle at its arrival, else the one that frees
  first, and a block of speed v stretches T and C by 1 / v.

Nothing here imports the program under test.  The random numbers come from
`jax.random` itself on the key layout the program documents for a call, so
program and reference read the same draws.  The inputs of a call are data
in the precision the configuration states, float32: the index of a draw is
the type-1 inverse ceil(u * m) - 1 in float32, and the arrival times are a
float32 running sum of float32 exponential gaps, divided by the rate in
float32.  All arithmetic after that is numpy in `dt` (float64 for the
reference, a lower precision for the control).  Arrival times near 10^4
carry a float32 rounding of about 10^-3, which is what a block's free time
is compared with: formed in float64 instead, they send a few jobs in a
thousand to another block than the float32 inputs do.
"""

from __future__ import annotations

import math

import numpy as np


def num_stragglers(n: int, p: float) -> int:
    """p * n rounded half up, at least 1 for any p > 0 and at most n - 1."""
    if p <= 0.0:
        return 0
    return max(1, min(n - 1, int(math.floor(p * n + 0.5))))


def is_baseline(p: float, r: int, keep: bool) -> bool:
    return p == 0.0 or (keep and r == 0)


# ---------------------------------------------------------------- draws


def split(key, num: int = 2):
    import jax

    return list(np.asarray(jax.random.split(np.asarray(key, np.uint32), num)))


def uniforms(key, shape) -> np.ndarray:
    """float32 uniforms in [0, 1), numbered in row-major order over `shape`."""
    import jax

    u = jax.random.uniform(np.asarray(key, np.uint32), (math.prod(shape),))
    return np.asarray(u).reshape(shape)


def exponentials(key, shape) -> np.ndarray:
    import jax

    return np.asarray(jax.random.exponential(np.asarray(key, np.uint32), shape))


def arrival_times(key, shape, lams, dt) -> np.ndarray:
    """Arrival times of every cell, (len(lams),) + shape: a float32 running
    sum over the last axis of float32 exponential gaps, over the rate."""
    gaps = exponentials(key, shape).astype(np.float32)
    run = np.cumsum(gaps, axis=-1, dtype=np.float32)
    return np.stack([(run / np.float32(lam)).astype(dt) for lam in lams])


def empirical_index(u: np.ndarray, m: int) -> np.ndarray:
    """Type-1 inverse of an m-sample empirical law, in float32."""
    idx = np.ceil(u.astype(np.float32) * np.float32(m)).astype(np.int64) - 1
    return np.clip(idx, 0, m - 1)


def sorted_table(samples) -> np.ndarray:
    """The trace as the configuration states it: float32 samples, sorted."""
    return np.sort(np.asarray(samples, np.float32).ravel())


# ---------------------------------------------------------- one job's law


def fork_rows(x_sorted, fresh_tail, p, r, keep, dt):
    """(T, C) of every job under one single-fork policy.

    `x_sorted`: (..., n) sorted original task times; `fresh_tail`: the
    fresh copies of the last positions, (..., t, r_cap) with t >= s, whose
    last s rows belong to the s stragglers.
    """
    n = x_sorted.shape[-1]
    s = 0 if is_baseline(p, r, keep) else num_stragglers(n, p)
    if s == 0:
        return x_sorted[..., -1], np.sum(x_sorted, axis=-1, dtype=dt) / dt(n)
    k = n - s
    t1 = x_sorted[..., k - 1]
    stragglers = x_sorted[..., k:]
    fresh = fresh_tail[..., fresh_tail.shape[-2] - s:, :]
    if keep:
        y = np.minimum(stragglers - t1[..., None], np.min(fresh[..., :r], axis=-1))
    else:
        y = np.min(fresh[..., : r + 1], axis=-1)
    T = t1 + np.max(y, axis=-1)
    head = np.sum(x_sorted[..., :k], axis=-1, dtype=dt)
    C = (head + dt(s) * t1 + dt(r + 1) * np.sum(y, axis=-1, dtype=dt)) / dt(n)
    return T, C


def stage_draws(key, table, shape, n, r_cap, s_max, dt):
    """A stage's draws for one call: sorted originals (shape + (n,)) and the
    fresh copies of the last `s_max` sorted positions (shape + (s_max, r_cap)).
    Key layout: (kx, ky) = split(key); originals from kx, copies from ky,
    each numbered row-major over its full shape."""
    kx, ky = split(key)
    m = table.shape[0]
    x = table[empirical_index(uniforms(kx, shape + (n,)), m)].astype(dt)
    x.sort(axis=-1)
    fresh = None
    if s_max > 0:
        u = uniforms(ky, shape + (n, r_cap))[..., n - s_max:, :]
        fresh = table[empirical_index(u, m)].astype(dt)
    return x, fresh


def max_stragglers(n, policies) -> int:
    return max(
        (0 if is_baseline(p, r, keep) else num_stragglers(n, p)) for p, r, keep in policies
    )


# ---------------------------------------------------------------- queue


def kw_queue(arrivals, services, speeds, dt):
    """FIFO gang-block queue over a batch of independent rows.

    `arrivals`, `services`: (B, J), rows sorted by arrival; `speeds`: the
    blocks, fastest first.  Returns (starts, finishes, scaled services,
    blocks), each (B, J)."""
    B, J = arrivals.shape
    speeds = np.asarray(speeds, dt)
    c = speeds.shape[0]
    free = np.zeros((B, c), dt)
    rows = np.arange(B)
    starts = np.empty((B, J), dt)
    fins = np.empty((B, J), dt)
    svcs = np.empty((B, J), dt)
    slots = np.empty((B, J), np.int64)
    for j in range(J):
        a = arrivals[:, j]
        idle = free <= a[:, None]
        slot = np.where(idle.any(axis=1), np.argmax(idle, axis=1), np.argmin(free, axis=1))
        start = np.maximum(a, free[rows, slot])
        svc = services[:, j] / speeds[slot]
        fin = start + svc
        free[rows, slot] = fin
        starts[:, j], fins[:, j], svcs[:, j], slots[:, j] = start, fin, svc, slot
    return starts, fins, svcs, slots


def percentiles(soj) -> tuple:
    """p50, p99, p999 of a cell's sojourns, linear interpolation."""
    return tuple(float(v) for v in np.percentile(np.asarray(soj, np.float64).ravel(), (50.0, 99.0, 99.9)))


# ----------------------------------------------------- a grid of cells


def fleet_cells(key, table, cells, n, n_jobs, m_trials, r_cap, blocks, dt=np.float64):
    """Rows of a single-stage grid evaluated on one call's draws.

    `cells`: list of ((p, r, keep), lam); `blocks`: list of (speed, class
    name), fastest first.  Each row holds the statistics a fleet operator
    reads: mean sojourn, wait, service and cost, utilisation overall and
    per class, the standard error of the mean sojourn over trials, the two
    saturation estimates and their maximum, and the p50 / p99 / p999
    sojourn.  Key layout of the call: (ka, kf) = split(key); the arrivals
    come from ka (`arrival_times`), the draws from kf (`stage_draws`)."""
    ka, kf = split(key)
    shape = (m_trials, n_jobs)
    policies = sorted({pol for pol, _ in cells})
    x, fresh = stage_draws(kf, table, shape, n, r_cap, max_stragglers(n, policies), dt)
    law = {pol: fork_rows(x, fresh, *pol, dt) for pol in policies}
    del x, fresh
    speeds = np.asarray([b[0] for b in blocks], dt)
    names = sorted({b[1] for b in blocks}, key=[b[1] for b in blocks].index)
    block_class = np.asarray([names.index(b[1]) for b in blocks])
    class_blocks = np.bincount(block_class, minlength=len(names))
    c = len(blocks)
    arrivals = arrival_times(ka, shape, [lam for _, lam in cells], dt)  # (cells, m, J)
    T = np.stack([law[pol][0] for pol, _ in cells])
    C = np.stack([law[pol][1] for pol, _ in cells])
    st, fi, sv, sl = (
        z.reshape(arrivals.shape)
        for z in kw_queue(arrivals.reshape(-1, n_jobs), T.reshape(-1, n_jobs), speeds, dt)
    )
    rows = []
    for i, (pol, lam) in enumerate(cells):
        a, soj, wait = arrivals[i], fi[i] - arrivals[i], st[i] - arrivals[i]
        cost = C[i] / speeds[sl[i]]
        makespan = np.max(fi[i], axis=1) - a[:, 0]
        busy = np.sum(cost, axis=1, dtype=dt)  # machine time per trial, in units of n
        per_trial = np.mean(soj, axis=1, dtype=dt)
        rho_work = dt(lam) * np.mean(C[i], dtype=dt) / np.sum(speeds, dtype=dt)
        rho_block = dt(lam) * np.mean(T[i], dtype=dt) / np.sum(speeds, dtype=dt)
        row = dict(
            mean_sojourn=np.mean(soj, dtype=dt),
            mean_wait=np.mean(wait, dtype=dt),
            mean_service=np.mean(sv[i], dtype=dt),
            mean_cost=np.mean(cost, dtype=dt),
            utilization=np.mean(busy / (dt(c) * makespan), dtype=dt),
            sojourn_std_err=np.std(per_trial, dtype=dt) / dt(math.sqrt(max(m_trials - 1, 1))),
            rho=max(rho_work, rho_block),
            rho_work=rho_work,
            rho_block=rho_block,
        )
        cls = block_class[sl[i]]
        for j, name in enumerate(names):
            class_busy = np.sum(np.where(cls == j, cost, dt(0)), axis=1, dtype=dt)
            row[f"util_{name}"] = np.mean(class_busy / (dt(class_blocks[j]) * makespan), dtype=dt)
        row = {k: float(v) for k, v in row.items()}
        row["p50"], row["p99"], row["p999"] = percentiles(soj)
        rows.append(row)
    return rows
