"""Cells that call `repro.fleet.frontier` with a q failure axis: a (policy
x rate x failure probability) grid on a single-stage deployment whose task
attempts fail and are relaunched at once, one call per window step, a new
key per call."""

from __future__ import annotations

import inspect

import numpy as np

from chipbench.entries import common, frontier
from chipbench.reference import fleet as ref
from chipbench.reference import relaunch

#: grid steps over the trace's range on which `relaunch_job_time` works
STEPS = 2**14


def relaunch_job_time(table, n: int, q: float, attempts: int, steps: int = STEPS) -> float:
    """Mean time of a job of n tasks with no replication under the relaunch
    law, in float64: the mean of the largest of n copy times, each the sum
    of K type-1 draws from the sorted `table`, with P(K = k) = q^(k-1) (1 -
    q) for k < attempts and q^(attempts-1) for k = attempts.

    The draws are rounded up to a grid of `steps` steps over the table's
    range, on which the law of a copy's time is the mixture of the powers of
    one draw's law (by FFT); so the result is an upper bound, by at most
    `attempts` steps, and a rate worked out from it holds the blocks for at
    most the occupancy it is worked out for."""
    x = np.asarray(table, np.float64)
    h = x[-1] / steps
    one = np.bincount(np.ceil(x / h).astype(np.int64), minlength=steps + 1) / x.size
    size = attempts * steps + 1
    fft = 1 << (size - 1).bit_length()
    f = np.fft.rfft(one, fft)
    weights = [q ** (k - 1) * (1.0 - q) for k in range(1, attempts)] + [q ** (attempts - 1)]
    law = np.fft.irfft(sum(w * f**k for k, w in enumerate(weights, 1)), fft)[:size]
    cdf = np.clip(np.cumsum(np.clip(law, 0.0, None)), 0.0, 1.0)
    # a time on the grid: E[max] = h * sum over grid points of P(max > point)
    return float(h * np.sum(1.0 - cdf**n))


class Cell(frontier.Cell):
    def __init__(self, config, traffic, seed):
        from repro.faults import FaultSpec
        from repro.fleet import lower_frontier

        if "fault" not in inspect.signature(lower_frontier).parameters:
            # the memory reading lowers the call's own program
            raise SystemExit("chipbench: this program cannot lower a failure grid "
                             "(lower_frontier takes no fault=)")
        super().__init__(config, traffic, seed)
        faults = config["faults"]
        if faults["backoff"] != 0.0:
            raise ValueError("the cell models immediate relaunch (backoff 0)")
        self.qs = [float(q) for q in faults["q"]]
        self.attempts = int(faults["max_attempts"])
        self.fault = [FaultSpec(q=q, max_attempts=self.attempts) for q in self.qs]
        # the rates at the largest q: every cell then holds a block for at
        # most its rho of the time
        hold = relaunch_job_time(ref.sorted_table(self.samples), self.n, max(self.qs),
                                 self.attempts)
        capacity = sum(b[0] for b in self.blocks) / hold
        self.rates = [float(rho) * capacity for rho in traffic["occupancy"]]
        self.cells = [(pol, lam, q) for pol in self.grid for lam in self.rates for q in self.qs]
        self.jobs_per_call = len(self.cells) * self.m_trials * self.n_jobs
        # the roofline counts the copies a policy looks at, not the attempts
        # they run: a lower bound on the work
        self.stages = [dict(n=self.n, cells=[pol for pol, _, _ in self.cells], r_cap=self.r_cap,
                            table=len(self.samples))]

    def call(self, i):
        from repro.fleet import frontier as run

        return run(
            self.samples, self.policies, self.rates, self.n, self.n_jobs,
            m_trials=self.m_trials, key=self.key, classes=self.classes, r_cap=self.r_cap,
            fault=self.fault,
        )

    def lowered(self):
        """The device program `call` runs, lowered, for its memory analysis."""
        from repro.fleet import lower_frontier

        return lower_frontier(
            self.samples, self.policies, self.rates, self.n, self.n_jobs,
            m_trials=self.m_trials, key=self.key, classes=self.classes, r_cap=self.r_cap,
            fault=self.fault,
        )

    def reference(self, i, dt=np.float64):
        """The reference's rows for call i, in the program's row format."""
        rows = relaunch.relaunch_cells(
            common.call_key(self.seed, i), ref.sorted_table(self.samples), self.cells,
            self.n, self.n_jobs, self.m_trials, self.r_cap, self.attempts, self.blocks, dt,
        )
        return rows, rows


def build(config, traffic, seed):
    return Cell(config, traffic, seed)
