"""The main path's Pallas kernels compile for a TPU v5e at the widths
`chip_smoke.py` and the benchmark's cells run them, here, without the
chip: the TPU compiler is installed and compiles for a described topology.
This is the only file that describes one; it does so inside a fixture,
never at import."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ShiftedExp, SingleForkPolicy
from repro.core.policy import delayed_relaunch
from repro.core.distributions import Empirical
from repro.dag import JobDAG, StageSpec, rollout
from repro.fleet import MachineClass, vector
from repro.kernels.kw_queue import kw_queue, kw_queue_rows
from repro.kernels.residual_sampler import residual_sample


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return fn.lower(*args).compile()


@pytest.mark.parametrize(
    "queues,jobs,c",
    [
        (32 * 16, 512, 4),  # the frontier: 32 cells x 16 trials, 4 gang blocks
        (32 * 16, 512, 2),  # the DAG's reduce stage pool
        (1, 200, 3),  # the event-engine check: c = 3, 200 jobs
    ],
    ids=["frontier", "dag_reduce", "small"],
)
def test_kw_queue_compiles_for_v5e(one_chip, queues, jobs, c):
    f32 = jnp.float32
    compiled = _compiled(kw_queue, one_chip, ((queues, jobs), f32), ((queues, jobs), f32), ((c,), f32))
    assert "tpu_custom_call" in compiled.as_text()


def test_residual_sample_compiles_for_v5e(one_chip):
    # trace_kill_rollout at the smoke's width: 16 x 512 jobs, pi_kill(0.05, 2)
    # on n = 1026 tasks -> 51 stragglers x 3 fresh draws each
    f32 = jnp.float32
    compiled = _compiled(residual_sample, one_chip, ((16 * 512, 51, 3), f32), ((1026,), f32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "m,shape",
    [
        (1026, (1026, 16 * 512)),  # job1: the originals, last axis leading
        (1026, (3, 16 * 512 * 1026)),  # job1: the fresh copies, r_cap = 3
        (488, (488, 16 * 512)),  # the reduce stage of the DAG
        (488, (3, 16 * 512 * 488)),
        (Empirical.LANE_GATHER_MAX, (1026, 16 * 512)),  # the largest table it takes
    ],
    ids=["job1_x", "job1_fresh", "reduce_x", "reduce_fresh", "bound"],
)
def test_lane_gather_compiles_for_v5e(one_chip, m, shape):
    f32 = jnp.float32
    compiled = _compiled(jax.jit(Empirical.lane_gather), one_chip, ((m,), f32), (shape, f32))
    assert "tpu_custom_call" in compiled.as_text()


def _on(one_chip, args):
    """`args` with every array turned into its shape placed on `one_chip`."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
        if isinstance(a, jax.Array) else a,
        args,
    )


def _grid_hlo(one_chip, dist_or_samples, policies, lams, n, n_jobs, m_trials, c, qs=None,
              classes=None):
    """The optimized HLO of the fused frontier program (`_frontier_jit`,
    with a q axis of 8 attempts where `qs` is given) for a grid, compiled
    for a described v5e."""
    args, kwargs, _ = vector._cells_call(
        dist_or_samples, policies, lams, n, n_jobs, m_trials, None, c, classes, False, None,
        True, "exact", qs, None if qs is None else 8,
    )
    return vector._frontier_jit.lower(*_on(one_chip, args), **kwargs).compile().as_text()


def _kernels(hlo):
    """The names of the Pallas kernels a compiled program runs through
    Mosaic, one per call."""
    return re.findall(r'%(\w+?)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"', hlo)


def test_frontier_program_looks_up_empirical_draws_in_vmem_on_v5e(one_chip):
    """`_frontier_jit` lowered for a v5e with a trace table holds the
    lane-gather kernel in place of XLA's gather; an analytic law does not
    (its c = 2 queue is the kw_queue kernel)."""

    def hlo(dist_or_samples):
        return _grid_hlo(one_chip, dist_or_samples, [SingleForkPolicy(0.1, 1, True)] * 2,
                         [0.2, 0.4], 200, 64, 4, 2)

    trace = jnp.sort(jax.random.exponential(jax.random.PRNGKey(0), (200,)))
    assert 'custom_call_target="tpu_custom_call"' in hlo(trace)
    assert "emp_quantile" in hlo(trace)
    assert "emp_quantile" not in _kernels(hlo(ShiftedExp(1.0, 1.0)))


@pytest.mark.parametrize("grid", ["relaunch", "faults"])
def test_other_grid_programs_look_up_empirical_draws_in_vmem_on_v5e(one_chip, grid):
    """The programs the benchmark's cells do not run take the kernel too, at
    n = 1026 with 16 trials of 64 jobs and 32 cells: a grid with wall-clock
    relaunches (`policy_draws`, the general evaluator) and a q failure grid
    (8 cells at each of 4 q, 8 attempts a draw: as many uniforms as a
    cell's 16 x 512 jobs without failures)."""
    single = [SingleForkPolicy(p, 1, keep) for p in (0.02, 0.05) for keep in (True, False)]
    trace = jnp.sort(jax.random.exponential(jax.random.PRNGKey(0), (1026,)))
    if grid == "relaunch":
        policies = single + [delayed_relaunch(t, 1) for t in (1.0, 1.5, 2.0, 3.0)]
        cells, lams, qs = [p for p in policies for _ in range(4)], [0.05, 0.1, 0.15, 0.2] * 8, None
    else:
        cells, lams, qs = [p for p in single for _ in range(2)], [0.05, 0.1] * 4, [0.02, 0.05, 0.1, 0.2]
    hlo = _grid_hlo(one_chip, trace, cells, lams, 1026, 64, 16, 4, qs)
    assert 'custom_call_target="tpu_custom_call"' in hlo
    assert "emp_quantile" in hlo


@pytest.mark.parametrize("fleet", ["job1", "mapreduce", "c1"])
def test_grid_programs_run_the_kw_queue_kernel_on_v5e(one_chip, fleet):
    """Lowered for a v5e, the fused programs run each queue of c > 1 as
    the Pallas kw_queue kernel, one call per stage: job1's fleet (c = 4
    in two speed classes) and the DAG (map c = 4, reduce c = 2); a c = 1
    fleet keeps the closed-form Lindley recursion and holds none."""
    n, pol = 1026, SingleForkPolicy(0.05, 1, True)
    trace = jnp.sort(jax.random.exponential(jax.random.PRNGKey(0), (n,)))
    if fleet == "mapreduce":
        reduce = jnp.sort(jax.random.exponential(jax.random.PRNGKey(1), (488,)))
        dag = JobDAG([
            StageSpec("map", n, trace, c=4),
            StageSpec("reduce", 488, reduce, c=2, deps=("map",)),
        ])
        vectors = [dag.validate_policy_vector((pol, pol))] * 4
        args, kwargs, _ = rollout._dag_cells_call(
            dag, vectors, [0.05, 0.1] * 2, 64, 4, None, False, None, True, "exact", None, None
        )
        hlo = rollout._dag_stats_jit.lower(*_on(one_chip, args), **kwargs).compile().as_text()
        assert _kernels(hlo).count("kw_queue") == 2
        return
    if fleet == "job1":
        classes = (MachineClass("fast", 2 * n, 1.0), MachineClass("slow", 2 * n, 0.7))
        hlo = _grid_hlo(one_chip, trace, [pol] * 4, [0.05, 0.1] * 2, n, 64, 4, None,
                        classes=classes)
        assert _kernels(hlo).count("kw_queue") == 1
    else:
        hlo = _grid_hlo(one_chip, ShiftedExp(1.0, 1.0), [pol] * 4, [0.05, 0.1] * 2, n, 64, 4, 1)
        assert _kernels(hlo) == []


@pytest.mark.parametrize("kernel", ["lane_gather", "kw_queue"])
def test_lane_gather_kernel_is_the_same_from_any_caller(one_chip, kernel):
    """The kernel's serialised body holds no location of the code that
    traced it, so a program holding it reads the same from any script, and
    the persistent compile cache (which strips the enclosing program's
    locations only) gives it one key: the lane gather, and the KW queue at
    job1's 32 cells x 16 trials of 512 jobs on 4 slots."""
    f32 = jnp.float32
    if kernel == "lane_gather":
        fn, shapes = Empirical.lane_gather, ((1026,), (3, 4096))
    else:
        fn, shapes = kw_queue_rows, ((512, 512), (512, 512), (4,))
    args = [jax.ShapeDtypeStruct(s, f32, sharding=one_chip) for s in shapes]

    def lowered():  # a new function each time, so each call traces anew
        return jax.jit(lambda *a: fn(*a)).lower(*args).as_text()

    def from_elsewhere():
        return lowered()

    text = lowered()
    assert "tpu_custom_call" in text
    assert from_elsewhere() == text
