"""Cells that call `repro.dag.dag_frontier`: a (per-stage policy vector x
rate) grid on a staged deployment, one call per window step, a new key per
call."""

from __future__ import annotations

import numpy as np

from chipbench import synth
from chipbench.entries import common
from chipbench.reference import dag as ref
from chipbench.reference import fleet as fleet_ref


class Cell:
    def __init__(self, config, traffic, seed):
        from repro.core.policy import SingleForkPolicy
        from repro.dag import JobDAG, StageSpec

        self.seed = seed
        self.m_trials, self.n_jobs = config["m_trials"], config["n_jobs"]
        self.ref_stages = []
        specs = []
        for st, r_cap in zip(config["stages"], traffic["r_caps"]):
            if any(k["speed"] != 1.0 for k in st["classes"]):
                raise ValueError("a DAG stage's blocks run at speed 1.0")
            c = sum(k["blocks"] for k in st["classes"])
            samples = synth.trace(st["trace"], seed)
            specs.append(StageSpec(st["name"], st["n"], samples, c=c, deps=tuple(st["deps"])))
            self.ref_stages.append(dict(name=st["name"], n=st["n"], c=c, r_cap=r_cap,
                                        table=fleet_ref.sorted_table(samples),
                                        deps=tuple(st["deps"])))
        self.dag = JobDAG(specs)
        self.grid = [tuple(tuple(p) for p in vec) for vec in traffic["vectors"]]
        self.vectors = [
            tuple(SingleForkPolicy(float(p), int(r), bool(keep)) for p, r, keep in vec)
            for vec in self.grid
        ]
        self.rates = common.rates(
            traffic["occupancy"], [dict(st, speeds=[1.0] * st["c"]) for st in self.ref_stages]
        )
        self.r_caps = tuple(traffic["r_caps"])
        self.cells = [(vec, lam) for vec in self.grid for lam in self.rates]
        self.jobs_per_call = len(self.cells) * self.m_trials * self.n_jobs
        self.stages = [
            dict(n=st["n"], cells=[vec[s] for vec, _ in self.cells], r_cap=st["r_cap"],
                 table=len(st["table"]))
            for s, st in enumerate(self.ref_stages)
        ]

    def prepare(self, i):
        self.key = common.call_key(self.seed, i)

    def call(self, i):
        from repro.dag import dag_frontier

        return dag_frontier(
            self.dag, self.vectors, self.rates, self.n_jobs, m_trials=self.m_trials,
            key=self.key, r_caps=self.r_caps,
        )

    def lowered(self):
        """The device program `call` runs, lowered, for its memory analysis."""
        from repro.dag import lower_dag_frontier

        return lower_dag_frontier(
            self.dag, self.vectors, self.rates, self.n_jobs, m_trials=self.m_trials,
            key=self.key, r_caps=self.r_caps,
        )

    def release(self):
        self.key = None

    def reference(self, i, dt=np.float64):
        rows = ref.dag_cells(
            common.call_key(self.seed, i), self.ref_stages, self.cells, self.n_jobs,
            self.m_trials, dt,
        )
        return rows, rows

    def compare(self, i, answer):
        _, detail = self.reference(i)
        return common.compare_rows(answer, detail)


def build(config, traffic, seed):
    return Cell(config, traffic, seed)
