"""The q relaunch cell at a size a CPU test can hold: the program agrees
with the plain reference inside the cell's limits, and the bfloat16 control
and programs that leave out part of the failure law fail them.

    JAX_PLATFORMS=cpu python -m pytest chipbench/checks/test_relaunch.py
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, synth
from chipbench.checks.limits import readings
from chipbench.checks.small import small_cell
from chipbench.entries import common
from chipbench.entries.frontier_relaunch import relaunch_job_time
from chipbench.reference import fleet as ref

CELL = "job1.relaunch"
SEED = 2**33 + 11


def build():
    workload, config, traffic = small_cell(CELL)
    harness.setup_jax(False, 1)
    return harness.load_entry(traffic["entry"]).build(config, traffic, SEED)


def run():
    workload, config, traffic = small_cell(CELL)
    jax.clear_caches()
    return harness.run_cell(workload, config, traffic, SEED, 0.5, False, require_chip=False)


def test_program_passes_and_control_fails_the_limits():
    cell = build()
    limits = harness.limits_for(CELL)
    program = readings(cell, [1, 2], control=False)
    assert all(program[k] <= limit for k, limit in limits.items()), program
    control = readings(cell, [1, 2], control=True)
    assert any(control[k] > limit for k, limit in limits.items()), control


def test_rows_cover_the_grid_in_the_program_order():
    cell = build()
    cell.prepare(1)
    rows = cell.call(1)
    assert len(rows) == 8 * 4 * 4
    assert [(r["lam"], r["q"]) for r in rows[:16]] == [
        (lam, q) for lam in cell.rates for q in cell.qs]


def no_failures(monkeypatch):
    """q forced to 0: every copy runs its first attempt only."""
    from repro.fleet import vector

    monkeypatch.setattr(vector, "retry_transform", lambda x, v, q: jnp.moveaxis(x[0], 0, -1))


def half_batch(monkeypatch):
    """The failure law's draws of the second half of the trials repeat the
    first half's."""
    from repro.fleet import vector

    orig = vector.retry_draws

    def bad(key, quantile, shape, attempts):
        x, v = orig(key, quantile, shape, attempts)
        h = shape[0] // 2  # trials: the third axis
        return x.at[:, :, h:].set(x[:, :, :h]), v.at[:, :, h:].set(v[:, :, :h])

    monkeypatch.setattr(vector, "retry_draws", bad)


def test_sound_run_is_correct():
    assert run()["correct"] is True


@pytest.mark.parametrize("fault", [no_failures, half_batch])
def test_broken_run_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    try:
        out = run()
    finally:
        jax.clear_caches()  # the broken program must not serve a later test
    assert out["correct"] is False, out["compared"]


def test_job_time_without_failures_is_the_exact_mean():
    table = ref.sorted_table(synth.trace("job1", 5))
    exact = common.mean_job_time(table, 1026)
    bound = relaunch_job_time(table, 1026, 0.0, 8)
    assert exact <= bound <= exact + table[-1] / 2**14


def test_job_time_matches_a_monte_carlo_of_the_law():
    """At q = 0.2 and 8 attempts, within 4 standard errors of 2^15 jobs of
    64 tasks drawn from the law as the reference applies it."""
    table = ref.sorted_table(synth.trace("job1", 5))
    rng = np.random.default_rng(0)
    jobs, n, q, attempts = 2**15, 64, 0.2, 8
    runs = np.minimum(rng.geometric(1.0 - q, size=(jobs, n)), attempts)
    times = table[rng.integers(0, table.size, size=(jobs, n, attempts))].astype(np.float64)
    busy = np.where(np.arange(attempts) < runs[..., None], times, 0.0).sum(axis=-1)
    job = busy.max(axis=-1)
    se = job.std() / np.sqrt(jobs)
    assert abs(relaunch_job_time(table, n, q, attempts) - job.mean()) < 4 * se
