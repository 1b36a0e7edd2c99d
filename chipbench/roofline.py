"""Least work of a grid call, counted from the cell's sizes.

A lower bound that holds whatever implements the call: every cell's policy
has to look at least once at every task copy it evaluates (the n original
task times of a job, and r fresh copies per straggler under keep, r + 1
under kill), and the call has to read each stage's trace table and return
its rows.  Nothing counted here depends on how the program draws, fuses or
stores, so no later change can push the share over 100%.
"""

from __future__ import annotations

from chipbench.reference.fleet import is_baseline, num_stragglers

#: float32 bytes of one number
WORD = 4
#: numbers of one returned row are bounded below by its 12 job-level keys
ROW_WORDS = 12


def copies(n: int, policy) -> int:
    """Task copies one job's evaluation looks at under `policy`."""
    p, r, keep = policy
    if is_baseline(p, r, keep):
        return n
    return n + num_stragglers(n, p) * (r if keep else r + 1)


def counts(cell) -> tuple[float, float]:
    """(operations, bytes) of one call of a grid cell: `cell.stages` lists
    per stage its n, the policy of every grid cell, and its table size;
    `cell.m_trials * cell.n_jobs` jobs per grid cell."""
    jobs = cell.m_trials * cell.n_jobs
    ops = 0.0
    table_bytes = 0.0
    n_cells = 0
    for st in cell.stages:
        ops += jobs * sum(copies(st["n"], pol) for pol in st["cells"])
        table_bytes += WORD * st["table"]
        n_cells = len(st["cells"])
    return ops, table_bytes + WORD * ROW_WORDS * n_cells


def least_time_s(cell, peaks: dict) -> tuple[float, str]:
    """The least time of one call on a chip with `peaks`, and which bound
    sets it ("compute" or "memory")."""
    ops, nbytes = counts(cell)
    t_ops = ops / peaks["flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
