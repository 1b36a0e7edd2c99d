"""A run of each cell, with the timed path broken underneath, reads
`correct: false`; unbroken, it reads true.  The run skips the look for a
chip and is otherwise the harness's own, at a size a CPU test can hold.

Faults, each planted in the program under the entry the cell calls:

* `altered`: the device program's statistics come out one grid cell off,
  each row carrying its neighbour's numbers;
* `half_batch`: the draws of the second half of the trials repeat the first
  half's, so every statistic is taken over half of the batch;
* `stale`: the entry returns its first answer again, a frontier that
  returns its first rows on every later call.

No cell spans chips, so no exchange between chips can be left out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness
from chipbench.checks.small import CELLS, small_cell

SEED = 2**33 + 5


def altered(monkeypatch):
    from repro.dag import rollout
    from repro.fleet import vector

    def wrap(fn):
        @functools.wraps(fn)
        def bad(*a, **k):
            stats, payload = fn(*a, **k)
            return jnp.roll(stats, 1, axis=0), payload

        bad.lower = fn.lower  # the memory reading lowers the program as it is
        return bad

    monkeypatch.setattr(vector, "_frontier_jit", wrap(vector._frontier_jit))
    monkeypatch.setattr(rollout, "_dag_stats_jit", wrap(rollout._dag_stats_jit))


def half_batch(monkeypatch):
    from repro.dag import rollout
    from repro.fleet import vector

    orig = vector.fork_draws

    def bad(key, quantile, shape, n, r_cap):
        x, fresh = orig(key, quantile, shape, n, r_cap)
        h = shape[0] // 2
        return x.at[h:].set(x[:h]), fresh.at[h:].set(fresh[:h])

    monkeypatch.setattr(vector, "fork_draws", bad)
    monkeypatch.setattr(rollout, "fork_draws", bad)


def stale(monkeypatch):
    import repro.dag
    import repro.fleet

    def first(fn):
        memo = []

        def bad(*a, **k):
            if not memo:
                memo.append(fn(*a, **k))
            return memo[0]

        return bad

    monkeypatch.setattr(repro.fleet, "frontier", first(repro.fleet.frontier))
    monkeypatch.setattr(repro.dag, "dag_frontier", first(repro.dag.dag_frontier))


def run(name):
    workload, config, traffic = small_cell(name)
    jax.clear_caches()
    return harness.run_cell(workload, config, traffic, SEED, 0.5, False, require_chip=False)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    assert run(name)["correct"] is True


@pytest.mark.parametrize("fault", [altered, half_batch, stale])
@pytest.mark.parametrize("name", CELLS)
def test_broken_run_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    out = run(name)
    assert out["correct"] is False, out["compared"]
