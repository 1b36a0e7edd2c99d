"""Plain reference of a gang-block fleet under single-fork replication
whose task attempts fail and are relaunched at once.

The semantics are those of `reference/fleet.py` (Wang, Joshi and Wornell,
arXiv:1503.03128, Definitions 1 and 2, through a FIFO queue of gang
blocks), with every task copy's time replaced by its busy time under the q
failure law (MapReduce re-executes a failed task from scratch, Dean and
Ghemawat, OSDI 2004, sec. 3.3):

* a copy runs attempts 1, 2, ... of independent task times; attempt k + 1
  runs iff attempts 1..k all failed, each attempt but the last failing
  with probability q, independently of its time; the last of `attempts`
  attempts is taken to succeed;
* the copy holds its slot for the sum of the attempts it ran, and
  Definition 2 bills that whole time;
* the originals are ordered by that busy time, the fork point and the
  stragglers are taken on it, and a straggler's fresh copies follow the
  same law.

Nothing here imports the program under test.  The random numbers come from
`jax.random` on the key layout the program documents for a call: (ka, kf) =
split(key), the arrivals from ka; (kx, ky) = split(kf), the originals
(m, J, n) from kx and the fresh copies (m, J, n, r_cap) from ky; for each,
(ku, kv) = split, the attempts' times from ku numbered row-major over the
shape + (attempts,) and their fates from kv over the shape + (attempts -
1,), attempt k failing iff its fate is below q.  The inputs are data in the
configuration's float32: the type-1 index of each attempt's time, the fate
against q in float32, and the arrival times.  Everything after is numpy in
`dt` (float64 for the reference, a lower precision for the control).
"""

from __future__ import annotations

import math

import numpy as np

from chipbench.reference.fleet import (
    arrival_times,
    empirical_index,
    fork_rows,
    kw_queue,
    max_stragglers,
    percentiles,
    split,
)


def attempt_uniforms(key, shape, attempts, lo=0):
    """The uniforms of a draw of copies of `shape` (trials, jobs, tasks[,
    copies]): the attempts' times, shape + (attempts,), and their fates,
    shape + (attempts - 1,), keeping tasks lo: only.  On the device they
    stay one row of numbers a job, so the cut is a range of columns and
    the copy to the host holds only what is kept."""
    import jax

    m, jobs, n, *copies = shape
    ku, kv = split(key)

    def block(k, width):
        row = n * math.prod(copies) * width  # a job's numbers, row-major
        u = jax.random.uniform(np.asarray(k, np.uint32), (m * jobs * row,))
        kept = np.asarray(u.reshape(m * jobs, row)[:, lo * row // n:])
        return kept.reshape(m, jobs, n - lo, *copies, width)

    return block(ku, attempts), block(kv, attempts - 1)


def attempt_sums(table, u_times, dt):
    """Each copy's attempt times from the trace and their running sums over
    the attempts: the busy time of a copy that runs the first k attempts is
    entry k - 1 of the last axis."""
    x = table[empirical_index(u_times, table.shape[0])].astype(dt)
    return np.cumsum(x, axis=-1, dtype=dt)


def busy_times(sums, fates, q):
    """Busy time of each copy under the relaunch law at failure
    probability q: the sum of the attempts it runs."""
    failed = fates < np.float32(q)
    runs = 1 + np.sum(np.logical_and.accumulate(failed, axis=-1), axis=-1)
    return np.take_along_axis(sums, runs[..., None] - 1, axis=-1)[..., 0]


def relaunch_cells(key, table, cells, n, n_jobs, m_trials, r_cap, attempts, blocks,
                   dt=np.float64):
    """Rows of a single-stage grid under the relaunch law, evaluated on one
    call's draws, in the order of `cells`: ((p, r, keep), lam, q) each.
    `blocks`: list of (speed, class name), fastest first.  Each row holds
    the statistics of `reference.fleet.fleet_cells` and the cell's q."""
    ka, kf = split(key)
    kx, ky = split(kf)
    shape = (m_trials, n_jobs)
    policies = sorted({pol for pol, _, _ in cells})
    s_max = max_stragglers(n, policies)
    u_x, fates_x = attempt_uniforms(kx, shape + (n,), attempts)
    sums_x = attempt_sums(table, u_x, dt)
    del u_x
    if s_max > 0:
        u_f, fates_f = attempt_uniforms(ky, shape + (n, r_cap), attempts, lo=n - s_max)
        sums_f = attempt_sums(table, u_f, dt)
        del u_f
    law = {}
    for q in sorted({q for _, _, q in cells}):
        x = busy_times(sums_x, fates_x, q)
        x.sort(axis=-1)
        fresh = busy_times(sums_f, fates_f, q) if s_max > 0 else None
        for pol in policies:
            law[pol, q] = fork_rows(x, fresh, *pol, dt)
        del x, fresh
    T = np.stack([law[pol, q][0] for pol, _, q in cells])
    C = np.stack([law[pol, q][1] for pol, _, q in cells])
    rows = queue_rows(ka, T, C, [lam for _, lam, _ in cells], blocks, dt)
    for row, (_, _, q) in zip(rows, cells):
        row["q"] = float(q)
    return rows


def queue_rows(ka, T, C, lams, blocks, dt):
    """The rows of cells whose jobs have times T and costs C, (cells, m, J),
    arriving at the rates `lams` from ka and queued FIFO for `blocks`: the
    statistics `reference.fleet.fleet_cells` forms from its own T and C."""
    n_cells, m_trials, n_jobs = T.shape
    speeds = np.asarray([b[0] for b in blocks], dt)
    names = sorted({b[1] for b in blocks}, key=[b[1] for b in blocks].index)
    block_class = np.asarray([names.index(b[1]) for b in blocks])
    class_blocks = np.bincount(block_class, minlength=len(names))
    c = len(blocks)
    arrivals = arrival_times(ka, (m_trials, n_jobs), lams, dt)  # (cells, m, J)
    st, fi, sv, sl = (
        z.reshape(arrivals.shape)
        for z in kw_queue(arrivals.reshape(-1, n_jobs), T.reshape(-1, n_jobs), speeds, dt)
    )
    rows = []
    for i, lam in enumerate(lams):
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
