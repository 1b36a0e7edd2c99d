"""Pieces the entries share: per-call keys and the comparison of rows."""

from __future__ import annotations

import math

import numpy as np

#: sojourn percentiles, compared as their own number
TAIL_KEYS = ("p50", "p99", "p999")
#: keys in units of time, compared relative to the larger of their value
#: and the cell's mean sojourn (a wait near 0 is a difference of large
#: times, and its rounding is on the scale of the sojourn)
TIME_KEYS = ("mean_sojourn", "mean_wait", "mean_service", "sojourn_std_err")
TIME_SUFFIXES = ("/sojourn", "/wait", "/service")
#: floor of the denominator for dimensionless keys (load, share)
RATIO_FLOOR = 1e-3


def mean_job_time(table: np.ndarray, n: int) -> float:
    """Mean time of a job of n tasks with no replication: the exact mean of
    the largest of n draws from the type-1 empirical law of the sorted
    `table`, sum_k x_(k) ((k/m)^n - ((k-1)/m)^n)."""
    x = np.asarray(table, np.float64)
    k = np.arange(1, x.shape[0] + 1) / x.shape[0]
    return float(np.sum(x * (k**n - (k - 1.0 / x.shape[0]) ** n)))


def rates(occupancy, stages) -> list[float]:
    """Poisson rates at which the jobs, with no replication, hold the
    bottleneck stage's gang blocks for the stated shares of the time.

    A stage of blocks with speeds v_b holds a block for T / v_b per job, so
    its block occupancy is lam * E[T] / sum(v_b); `stages` gives each stage's
    sorted trace table (`table`), task count (`n`) and `speeds`."""
    capacity = min(
        sum(st["speeds"]) / mean_job_time(st["table"], st["n"]) for st in stages
    )
    return [float(rho) * capacity for rho in occupancy]


def call_key(seed: int, i: int) -> np.ndarray:
    """The raw PRNG key of call i of a run, made from the run's seed."""
    rng = np.random.default_rng([abs(int(seed)), int(i)])
    return rng.integers(0, 2**32, size=2, dtype=np.uint32)


def rel_err(key, a, r, row):
    """Relative gap of one key of a row from the reference row `row`."""
    if key in TIME_KEYS or key in TAIL_KEYS or key.endswith(TIME_SUFFIXES):
        den = max(abs(r), abs(row["mean_sojourn"]))
    elif key.startswith("util_"):
        # a class's utilisation is its part of the fleet's: one job placed
        # on another class moves it by that job's share of the fleet's busy
        # time, which is large only against a class that is nearly idle
        den = max(abs(r), abs(row["utilization"]))
    elif key.startswith("mean_cost") or key.endswith("/cost"):
        den = abs(r)
    else:
        den = max(abs(r), RATIO_FLOOR)
    if not (math.isfinite(a) and den > 0):
        return math.inf
    return abs(a - r) / den


def compare_rows(rows, ref_rows) -> dict:
    """Largest relative gap between the program's rows and the reference's:
    `rows_mean` over the means, loads, utilisations and shares, `rows_tail`
    over the sojourn percentiles.  A missing row or key reads infinite."""
    if len(rows) != len(ref_rows):
        return {"rows_mean": math.inf, "rows_tail": math.inf}
    mean = tail = 0.0
    for row, rrow in zip(rows, ref_rows):
        for key, r in rrow.items():
            a = row.get(key)
            e = math.inf if not isinstance(a, (int, float)) else rel_err(key, float(a), r, rrow)
            if key in TAIL_KEYS:
                tail = max(tail, e)
            else:
                mean = max(mean, e)
    return {"rows_mean": mean, "rows_tail": tail}
