"""Cells that call `repro.fleet.frontier`: a (policy x rate) grid on a
single-stage deployment, one call per window step, a new key per call."""

from __future__ import annotations

import numpy as np

from chipbench import synth
from chipbench.entries import common
from chipbench.reference import fleet as ref


class Cell:
    def __init__(self, config, traffic, seed):
        from repro.core.policy import SingleForkPolicy
        from repro.fleet import MachineClass

        (stage,) = config["stages"]
        self.seed = seed
        self.n = stage["n"]
        self.m_trials, self.n_jobs = config["m_trials"], config["n_jobs"]
        self.samples = synth.trace(stage["trace"], seed)
        self.blocks = [(k["speed"], k["name"]) for k in stage["classes"] for _ in range(k["blocks"])]
        self.classes = [MachineClass(k["name"], k["blocks"] * self.n, k["speed"]) for k in stage["classes"]]
        self.grid = [tuple(p) for p in traffic["policies"]]
        self.policies = [SingleForkPolicy(float(p), int(r), bool(keep)) for p, r, keep in self.grid]
        self.rates = common.rates(
            traffic["occupancy"],
            [dict(table=ref.sorted_table(self.samples), n=self.n, speeds=[b[0] for b in self.blocks])],
        )
        self.r_cap = traffic["r_cap"]
        self.cells = [(pol, lam) for pol in self.grid for lam in self.rates]
        self.jobs_per_call = len(self.cells) * self.m_trials * self.n_jobs
        self.stages = [dict(n=self.n, cells=[pol for pol, _ in self.cells], r_cap=self.r_cap,
                            table=len(self.samples))]

    def prepare(self, i):
        self.key = common.call_key(self.seed, i)

    def call(self, i):
        from repro.fleet import frontier

        return frontier(
            self.samples, self.policies, self.rates, self.n, self.n_jobs,
            m_trials=self.m_trials, key=self.key, classes=self.classes, r_cap=self.r_cap,
        )

    def lowered(self):
        """The device program `call` runs, lowered, for its memory analysis."""
        from repro.fleet import lower_frontier

        return lower_frontier(
            self.samples, self.policies, self.rates, self.n, self.n_jobs,
            m_trials=self.m_trials, key=self.key, classes=self.classes, r_cap=self.r_cap,
        )

    def release(self):
        self.key = None

    def reference(self, i, dt=np.float64):
        """The reference's rows for call i, in the program's row format."""
        rows = ref.fleet_cells(
            common.call_key(self.seed, i), ref.sorted_table(self.samples), self.cells,
            self.n, self.n_jobs, self.m_trials, self.r_cap, self.blocks, dt,
        )
        return rows, rows

    def compare(self, i, answer):
        _, detail = self.reference(i)
        return common.compare_rows(answer, detail)


def build(config, traffic, seed):
    return Cell(config, traffic, seed)
