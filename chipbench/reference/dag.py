"""Plain reference of a stage-composed job DAG on per-stage gang-block pools.

Each stage owns c_s unit-speed gang blocks; a job enters a stage when every
predecessor stage has finished all its tasks (the barrier), and waits FIFO
in order of those release times (ties in job order).  Within a stage the
job's (T, C) follow the single-fork law of `fleet.fork_rows`.  The job's
sojourn runs from its arrival to the finish of the last sink stage, and
each stage on the path that set it is credited with its own time there.

Key layout of a call: (ka, kf) = split(key); arrivals from ka as in
`fleet.fleet_cells`; with more than one stage, the stages' keys are
split(kf, S) in stage order, and each stage draws as `fleet.stage_draws`.
"""

from __future__ import annotations

import math

import numpy as np

from . import fleet as F


def dag_cells(key, stages, cells, n_jobs, m_trials, dt=np.float64):
    """Rows of a DAG grid evaluated on one call's draws.

    `stages`: list of dicts with name, n, c, r_cap, table, deps (names of
    earlier stages), in topological order; `cells`: list of (vector, lam)
    where vector holds one (p, r, keep) per stage."""
    ka, kf = F.split(key)
    S = len(stages)
    keys = [kf] if S == 1 else F.split(kf, S)
    shape = (m_trials, n_jobs)
    arrivals = F.arrival_times(ka, shape, [lam for _, lam in cells], dt)  # (cells, m, J)
    index = {st["name"]: i for i, st in enumerate(stages)}
    ready, start, finish, T, C = [], [], [], [], []
    for s, st in enumerate(stages):
        n = st["n"]
        pols = sorted({vec[s] for vec, _ in cells})
        x, fresh = F.stage_draws(
            keys[s], st["table"], shape, n, st["r_cap"], F.max_stragglers(n, pols), dt
        )
        law = {pol: F.fork_rows(x, fresh, *pol, dt) for pol in pols}
        del x, fresh
        T.append(np.stack([law[vec[s]][0] for vec, _ in cells]))
        C.append(np.stack([law[vec[s]][1] for vec, _ in cells]))
        if st["deps"]:
            rel = np.max(np.stack([finish[index[d]] for d in st["deps"]]), axis=0)
        else:
            rel = arrivals
        order = np.argsort(rel, axis=-1, kind="stable")
        take = lambda z: np.take_along_axis(z, order, axis=-1)  # noqa: E731
        st_, fi_, _, _ = F.kw_queue(
            take(rel).reshape(-1, n_jobs), take(T[s]).reshape(-1, n_jobs),
            np.ones(st["c"]), dt,
        )
        inv = np.argsort(order, axis=-1, kind="stable")
        back = lambda z: np.take_along_axis(z.reshape(rel.shape), inv, axis=-1)  # noqa: E731
        ready.append(rel)
        start.append(back(st_))
        finish.append(back(fi_))
    # the path that set each job's sojourn, walked back from the last sink
    sinks = [i for i in range(S) if not any(stages[i]["name"] in t["deps"] for t in stages)]
    sink_f = np.stack([finish[i] for i in sinks])
    end = np.max(sink_f, axis=0)
    winner = np.argmax(sink_f, axis=0)
    on_path = [np.zeros(end.shape, bool) for _ in range(S)]
    for j, i in enumerate(sinks):
        on_path[i] = winner == j
    attr = [None] * S
    for s in reversed(range(S)):
        attr[s] = np.where(on_path[s], finish[s] - ready[s], dt(0))
        deps = [index[d] for d in stages[s]["deps"]]
        if deps:
            pred_f = np.stack([finish[p] for p in deps])
            win = np.argmax(pred_f, axis=0)
            for j, p in enumerate(deps):
                on_path[p] = on_path[p] | (on_path[s] & (win == j))
    sojourn = end - arrivals
    rows = []
    for i, (vec, lam) in enumerate(cells):
        mean_soj = np.mean(sojourn[i], dtype=dt)
        per_trial = np.mean(sojourn[i], axis=1, dtype=dt)
        stage_rho = [dt(lam) * np.mean(T[s][i], dtype=dt) / dt(st["c"]) for s, st in enumerate(stages)]
        row = dict(
            mean_sojourn=mean_soj,
            mean_wait=np.mean(sum(start[s][i] - ready[s][i] for s in range(S)), dtype=dt),
            mean_service=np.mean(sum(T[s][i] for s in range(S)), dtype=dt),
            mean_cost=np.mean(sum(C[s][i] for s in range(S)), dtype=dt),
            sojourn_std_err=np.std(per_trial, dtype=dt) / dt(math.sqrt(max(m_trials - 1, 1))),
            rho=max(stage_rho),
        )
        for s, st in enumerate(stages):
            name = st["name"]
            row[f"{name}/share"] = np.mean(attr[s][i], dtype=dt) / mean_soj
            row[f"{name}/sojourn"] = np.mean(finish[s][i] - ready[s][i], dtype=dt)
            row[f"{name}/wait"] = np.mean(start[s][i] - ready[s][i], dtype=dt)
            row[f"{name}/service"] = np.mean(T[s][i], dtype=dt)
            row[f"{name}/cost"] = np.mean(C[s][i], dtype=dt)
            row[f"{name}/rho"] = stage_rho[s]
        row = {k: float(v) for k, v in row.items()}
        row["p50"], row["p99"], row["p999"] = F.percentiles(sojourn[i])
        rows.append(row)
    return rows
