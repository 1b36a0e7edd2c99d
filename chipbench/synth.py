"""Task-time traces of the benchmark's deployments, made from a seed.

The Google Cluster Trace jobs of the paper (Wang, Joshi, Wornell,
arXiv:1503.03128, §4.2, Fig. 7) are not public as task-time tables, so
they are synthesized: mixture models matched to the documented shape of
Fig. 7 (task counts, bimodal bulk, heavy straggler tail, job 1 heavier
than job 2), and job 3 is job 2 without its 3 longest samples (the
paper's tail-shortening ablation).  This is the benchmark's own copy of
the program's `repro.data.traces.synthesize_trace`, so that a change to
the program cannot move the benchmark's inputs.
"""

from __future__ import annotations

import hashlib

import numpy as np

#: documented task counts (paper Fig. 7)
N_TASKS = {"job1": 1026, "job2": 488}


def synthesize(job: str, seed: int) -> np.ndarray:
    """Execution-time samples (seconds) mimicking the Fig. 7 histograms."""
    if job == "job3":
        return np.sort(synthesize("job2", seed))[:-3]
    digest = hashlib.md5(f"trace|{job}|{seed}".encode()).digest()
    rng = np.random.default_rng(int.from_bytes(digest[:8], "little"))
    if job == "job1":
        n = N_TASKS["job1"]
        bulk = rng.normal(650.0, 110.0, size=int(n * 0.86))
        mid = rng.normal(1100.0, 150.0, size=int(n * 0.09))
        k = n - bulk.size - mid.size
        tail = 1300.0 + rng.pareto(1.8, size=k) * 900.0
        return np.clip(np.concatenate([bulk, mid, tail]), 400.0, None)
    if job == "job2":
        n = N_TASKS["job2"]
        bulk = rng.normal(210.0, 25.0, size=int(n * 0.90))
        mid = rng.normal(380.0, 50.0, size=int(n * 0.07))
        k = n - bulk.size - mid.size - 3
        tail = 550.0 + rng.uniform(0.0, 800.0, size=k)
        worst = np.array([1550.0, 1900.0, 2600.0])
        return np.clip(np.concatenate([bulk, mid, tail, worst]), 170.0, None)
    raise KeyError(f"unknown trace {job!r}")


def trace(job: str, seed: int) -> np.ndarray:
    """The job's trace rescaled to mean 1, the unit the arrival rates use."""
    x = synthesize(job, seed)
    return x / np.mean(x)
