"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distributions import Empirical
from repro.fleet.vector import emp_quantile
from repro.kernels import ops, ref
from repro.models.ssm import ssd_chunked

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------- flash attn
FLASH_CASES = [
    # (B, Sq, H, D, causal, dtype, block_q, block_k)
    (2, 256, 4, 64, True, jnp.float32, 128, 128),
    (1, 512, 2, 128, True, jnp.float32, 128, 128),
    (2, 200, 4, 64, True, jnp.float32, 128, 128),  # ragged seq
    (1, 128, 8, 64, False, jnp.float32, 64, 64),
    (2, 256, 4, 64, True, jnp.bfloat16, 128, 128),
    (1, 384, 4, 256, True, jnp.bfloat16, 128, 128),  # gemma head_dim
    (1, 96, 2, 80, True, jnp.float32, 32, 32),  # stablelm head_dim, small blocks
]


@pytest.mark.parametrize("case", FLASH_CASES, ids=lambda c: f"B{c[0]}S{c[1]}H{c[2]}D{c[3]}c{int(c[4])}{c[5].__name__}")
def test_flash_attention_matches_ref(case):
    B, S, H, D, causal, dtype, bq, bk = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, H, D), dtype)
    v = jax.random.normal(ks[2], (B, S, H, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    exp = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32), atol=tol, rtol=tol
    )


# ------------------------------------------------------------------ ssd scan
SSD_CASES = [
    # (Bt, S, H, P, G, N, chunk, dtype)
    (2, 256, 4, 32, 1, 16, 64, jnp.float32),
    (1, 128, 8, 64, 1, 64, 128, jnp.float32),
    (1, 100, 4, 16, 2, 8, 32, jnp.float32),  # ragged + grouped
    (2, 192, 4, 32, 4, 16, 64, jnp.float32),
    (1, 256, 4, 64, 1, 128, 128, jnp.bfloat16),  # mamba2-2.7b geometry
]


@pytest.mark.parametrize("case", SSD_CASES, ids=lambda c: f"B{c[0]}S{c[1]}H{c[2]}P{c[3]}G{c[4]}N{c[5]}q{c[6]}{c[7].__name__}")
def test_ssd_scan_matches_chunked(case):
    Bt, S, H, P, G, N, chunk, dtype = case
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (Bt, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B = jax.random.normal(ks[3], (Bt, S, G, N), dtype)
    C = jax.random.normal(ks[4], (Bt, S, G, N), dtype)
    D = jnp.ones((H,))
    y_k, h_k = ops.ssd_scan(x, dt, A, B, C, D, chunk=chunk)
    y_r, h_r = ssd_chunked(x, dt, A, B, C, D, chunk)
    # bf16 inputs with N=128-wide accumulations differ in reduction order
    atol = 2e-1 if dtype == jnp.bfloat16 else 1e-3
    rtol = 5e-2 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(np.asarray(y_k, np.float32), np.asarray(y_r, np.float32), atol=atol, rtol=rtol)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r), atol=atol, rtol=rtol)


def test_ssd_chunked_matches_recurrence():
    """The chunked oracle itself vs the literal O(S) recurrence."""
    Bt, S, H, P, G, N = 2, 128, 4, 16, 1, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (Bt, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B = jax.random.normal(ks[3], (Bt, S, G, N))
    C = jax.random.normal(ks[4], (Bt, S, G, N))
    D = jnp.ones((H,))
    y_c, h_c = ssd_chunked(x, dt, A, B, C, D, 32)
    y_r, h_r = ref.ssd_recurrence_ref(x, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_r), atol=2e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(h_c), np.asarray(h_r), atol=2e-3, rtol=1e-3)


# ----------------------------------------------------------------- kw queue
KW_CASES = [
    # (n_queues, n_jobs, c) — B deliberately not a multiple of block_b
    (4, 37, 1),
    (8, 64, 3),
    (13, 48, 4),
    (1, 200, 2),
]


def _kw_inputs(B, J, c, seed=0, lam=0.5):
    ka, ks = jax.random.split(jax.random.PRNGKey(seed))
    arr = jnp.cumsum(jax.random.exponential(ka, (B, J)) / lam, axis=1)
    svc = 0.5 + jax.random.exponential(ks, (B, J))
    speeds = jnp.sort(0.5 + jax.random.uniform(jax.random.PRNGKey(seed + 1), (c,)))[::-1]
    return arr, svc, speeds


@pytest.mark.parametrize("B,J,c", KW_CASES)
def test_kw_queue_kernel_matches_ref(B, J, c):
    """Pallas kernel ≡ the vmapped lax.scan oracle to 1e-5 (interpret)."""
    arr, svc, speeds = _kw_inputs(B, J, c)
    outs_k = ops.kw_queue(arr, svc, speeds)
    outs_r = ref.kw_queue_ref(arr, svc, speeds)
    for a, b in zip(outs_k[:3], outs_r[:3]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(outs_k[3]), np.asarray(outs_r[3]))  # slots


def _cell_batch():
    """A cell-like batch: 3 cells x 4 trials x 100 jobs on 4 slots in two
    speed classes.  Jobs 0-47 come in bursts of 12 at one instant (all
    slots idle at a burst, none idle after its first 4; equal services
    leave exact ties in the slots' free times), jobs 48-99 one at a time,
    spaced wider than any service (every slot idle).  The cells scale the
    clock; trials 0 and 1 have equal services, 2 and 3 random ones."""
    j = np.arange(100)
    clock = np.where(j < 48, (j // 12) * 50.0, 200.0 + (j - 48) * 20.0)
    arr = np.asarray([1.0, 0.5, 0.25])[:, None, None] * clock
    arr = np.broadcast_to(arr, (3, 4, 100))
    rand = 0.5 + np.asarray(jax.random.exponential(jax.random.PRNGKey(7), (3, 2, 100)))
    svc = np.concatenate([np.full((3, 1, 100), 2.0), np.full((3, 1, 100), 1.0), rand], axis=1)
    speeds = np.asarray([1.0, 1.0, 0.7, 0.7])
    return tuple(jnp.asarray(z, jnp.float32) for z in (arr, svc, speeds))


@pytest.mark.parametrize("case", ["random", "cell_batch"])
def test_kw_queue_kernel_matches_fleet_scan(case):
    """And ≡ the fleet fast path's own scan (`vector.kw_queue`, vmapped
    over the batch), through `batched_queue`: the kernel (`kernel=True`)
    against the default realization on a CPU, bit for bit; "cell_batch"
    holds exact ties and runs of all-idle and none-idle jobs."""
    from repro.fleet import vector as fleet_vector

    arr, svc, speeds = _kw_inputs(6, 50, 3, seed=5) if case == "random" else _cell_batch()
    outs_k = fleet_vector.batched_queue(arr, svc, speeds, kernel=True)
    outs_s = fleet_vector.batched_queue(arr, svc, speeds)
    flat = arr.reshape(-1, arr.shape[-1]), svc.reshape(-1, arr.shape[-1])
    for i in range(flat[0].shape[0]):  # the default is the scan on every row
        row = fleet_vector.kw_queue(flat[0][i], flat[1][i], speeds)
        for a, b in zip(outs_s, row):
            np.testing.assert_array_equal(np.asarray(a).reshape(-1, a.shape[-1])[i], np.asarray(b))
    for a, b in zip(outs_k, outs_s):
        assert a.shape == arr.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kw_queue_kernel_heterogeneous_speeds_scale_service():
    """Whatever slot serves a job, its service stretches by that slot's
    speed (the heterogeneous-class semantics of the fleet fast path)."""
    arr, svc, _ = _kw_inputs(5, 40, 3, seed=9)
    speeds = jnp.array([2.0, 1.0, 0.5])
    starts, fins, scaled, slots = ops.kw_queue(arr, svc, speeds)
    sl = np.asarray(slots)
    assert sl.min() >= 0 and sl.max() < 3
    np.testing.assert_allclose(
        np.asarray(scaled), np.asarray(svc) / np.asarray(speeds)[sl], rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(fins - starts), np.asarray(scaled), rtol=1e-5, atol=1e-5
    )


def test_kw_queue_kernel_c1_matches_lindley():
    """One slot: the kernel IS the closed-form Lindley recursion."""
    from repro.fleet.vector import lindley

    arr, svc, _ = _kw_inputs(7, 60, 1, seed=3)
    starts, fins, _, slots = ops.kw_queue(arr, svc, jnp.ones((1,)))
    assert np.all(np.asarray(slots) == 0)
    for i in range(arr.shape[0]):
        s_lin, f_lin = lindley(arr[i], svc[i])
        np.testing.assert_allclose(np.asarray(starts[i]), np.asarray(s_lin), rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(fins[i]), np.asarray(f_lin), rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------- residual sampler
@pytest.mark.parametrize("m,s,k,n", [(33, 50, 3, 1000), (8, 16, 1, 100), (100, 205, 4, 488)])
def test_residual_sampler_matches_ref(m, s, k, n):
    u = jax.random.uniform(jax.random.PRNGKey(7), (m, s, k))
    xs = jnp.sort(jax.random.exponential(jax.random.PRNGKey(8), (n,)))
    mx, sm = ops.residual_sample(u, xs)
    mx_r, sm_r = ref.residual_sample_ref(u, xs)
    np.testing.assert_allclose(np.asarray(mx), np.asarray(mx_r), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sm), np.asarray(sm_r), rtol=1e-5)


def test_residual_sampler_is_min_of_replicas_distribution():
    """Kernel draws follow F̄_Y = F̄_X^{r+1} (eq. 7, π_kill)."""
    n, m, s, r = 2000, 400, 100, 2
    xs = jnp.sort(jax.random.exponential(jax.random.PRNGKey(1), (n,)))
    u = jax.random.uniform(jax.random.PRNGKey(2), (m, s, r + 1))
    _, sm = ops.residual_sample(u, xs)
    mean_y = float(jnp.mean(sm)) / s
    # min of r+1 Exp(1) ~ Exp(r+1): mean 1/3
    assert mean_y == pytest.approx(1 / 3, rel=0.05)


# ------------------------------------------- empirical inverse in VMEM
def _emp_gather(xs, u):
    m = xs.shape[0]
    return xs[jnp.clip(jnp.ceil(u * m).astype(jnp.int32) - 1, 0, m - 1)]


def _emp_uniforms(m, shape):
    """0, 1 - 2**-24, every k/m in float32 and its two float32 neighbours,
    then random uniforms, in `shape`."""
    k = (np.arange(m + 1) / m).astype(np.float32)
    edges = np.concatenate([[0.0, 1 - 2**-24], k, np.nextafter(k, 2.0), np.nextafter(k, -1.0)])
    edges = edges[(edges >= 0) & (edges < 1)].astype(np.float32)
    size = int(np.prod(shape))
    assert edges.size <= size
    rest = np.asarray(jax.random.uniform(jax.random.PRNGKey(m), (size - edges.size,)))
    return jnp.asarray(np.concatenate([edges, rest]).reshape(shape))


def _bits(z):
    return np.asarray(z, np.float32).view(np.int32)


EMP_SIZES = [1, 127, 128, 129, 488, 1026, 2048, Empirical.LANE_GATHER_MAX]


@pytest.mark.parametrize("shape", [(3, 8192 + 5), (1026, 77)], ids=str)
@pytest.mark.parametrize("m", EMP_SIZES)
def test_lane_gather_is_the_gather_bit_for_bit(m, shape):
    xs = jnp.sort(jax.random.exponential(jax.random.PRNGKey(m + 1), (m,)))
    u = _emp_uniforms(m, shape)
    out = Empirical.lane_gather(xs, u)  # interpreted off the TPU
    assert out.shape == u.shape
    assert np.array_equal(_bits(out), _bits(_emp_gather(xs, u)))


@pytest.mark.parametrize("in_axes", [(None, 0), (0, 0)], ids=["table_shared", "table_batched"])
def test_lane_gather_under_vmap(in_axes):
    xs = jnp.sort(jax.random.exponential(KEY, (4, 1026)), axis=-1)
    u = _emp_uniforms(1026, (4, 1030, 3))
    table = xs[0] if in_axes[0] is None else xs
    lane = jax.vmap(Empirical.lane_gather, in_axes)
    assert np.array_equal(_bits(lane(table, u)), _bits(jax.vmap(_emp_gather, in_axes)(table, u)))


def _cpu_hlo(fn, *args):
    """Optimized HLO of `fn` on the CPU, without the module's name and the
    metadata and stack-frame tables that name the traced functions."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    frames = re.compile(r"^(HloModule |\d+ |FileNames|FunctionNames|FileLocations|StackFrames)")
    return "\n".join(line for line in text.splitlines() if not frames.match(line))


@pytest.mark.parametrize("m", [488, 1026, Empirical.LANE_GATHER_MAX + 1])
def test_emp_quantile_keeps_the_gather_on_cpu(m):
    xs = jnp.sort(jax.random.exponential(KEY, (m,)))
    u = jax.random.uniform(KEY, (3, 1026))
    hlo = _cpu_hlo(emp_quantile, xs, u)
    assert "gather(" in hlo and "custom-call" not in hlo
    assert hlo == _cpu_hlo(_emp_gather, xs, u)
    assert np.array_equal(_bits(emp_quantile(xs, u)), _bits(_emp_gather(xs, u)))
