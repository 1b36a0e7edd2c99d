"""The main path's Pallas kernels compile for a TPU v5e at the widths
`chip_smoke.py` runs them, here, without the chip: the TPU compiler is
installed and compiles for a described topology.  This is the only file
that describes one; it does so inside a fixture, never at import."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.kw_queue import kw_queue
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
