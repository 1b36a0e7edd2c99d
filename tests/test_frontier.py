"""The fused frontier engine vs the per-cell loop it replaced.

`vector.frontier` evaluates a whole (λ × π) grid as ONE device program over
shared common-random-number draws; `vector.sweep_loop` dispatches one
`fleet_rollout` per cell.  The two draw independently, so they must agree
within Monte-Carlo error on every shared cell — and the engine's own knobs
(cell padding, r_cap pinning, the Pallas kw_queue switch, the `sweep`
wrapper, `policy_search` reuse) must not change results at all.
"""

import jax
import numpy as np
import pytest

from repro.core import Empirical, ShiftedExp, SingleForkPolicy
from repro.fleet import MachineClass, vector

DIST = ShiftedExp(1.0, 1.0)
POLICIES = (
    SingleForkPolicy(0.0, 0, True),
    SingleForkPolicy(0.1, 1, True),
    SingleForkPolicy(0.2, 1, False),
)
LAMS = (0.08, 0.16)
N, N_JOBS, M_TRIALS = 8, 200, 24


def _cells(rows):
    return {(r["policy"], r["lam"]): r for r in rows}


def test_frontier_matches_per_cell_loop_within_mc_error():
    fused = vector.frontier(
        DIST, POLICIES, LAMS, N, N_JOBS, m_trials=M_TRIALS, key=jax.random.PRNGKey(1)
    )
    loop = vector.sweep_loop(
        DIST, POLICIES, LAMS, N, N_JOBS, m_trials=M_TRIALS, key=jax.random.PRNGKey(2)
    )
    assert len(fused) == len(POLICIES) * len(LAMS)
    lf, ll = _cells(fused), _cells(loop)
    assert lf.keys() == ll.keys()
    for cell in lf:
        f, l = lf[cell], ll[cell]
        sigma = max(float(np.hypot(f["sojourn_std_err"], l["sojourn_std_err"])), 1e-12)
        assert abs(f["mean_sojourn"] - l["mean_sojourn"]) / sigma < 5.0, cell
        assert f["mean_cost"] == pytest.approx(l["mean_cost"], abs=0.1)
        # the loop's summary() keys are all present (sweep drop-in format)
        for key in ("mean_wait", "mean_service", "utilization", "p50", "p99",
                    "p999", "sojourn_std_err"):
            assert key in f


def test_frontier_kw_grid_matches_loop():
    """c > 1 (KW scan) and heterogeneous classes agree with the loop too."""
    mix = (MachineClass("fast", 2 * N, 1.0), MachineClass("slow", 2 * N, 0.5))
    for kwargs in (dict(c=3), dict(classes=mix)):
        fused = vector.frontier(
            DIST, POLICIES[:2], (0.4,), N, N_JOBS, m_trials=M_TRIALS,
            key=jax.random.PRNGKey(3), **kwargs,
        )
        loop = vector.sweep_loop(
            DIST, POLICIES[:2], (0.4,), N, N_JOBS, m_trials=M_TRIALS,
            key=jax.random.PRNGKey(4), **kwargs,
        )
        for f, l in zip(fused, loop):
            sigma = max(float(np.hypot(f["sojourn_std_err"], l["sojourn_std_err"])), 1e-12)
            assert abs(f["mean_sojourn"] - l["mean_sojourn"]) / sigma < 5.0
    # per-class utilization keys mirror VectorFleetResult.summary()
    assert "util_fast" in fused[0] and "util_slow" in fused[0]


def test_frontier_padding_does_not_change_results():
    """Bucket padding adds inert duplicate cells dropped on return —
    real-cell stats must be identical."""
    key = jax.random.PRNGKey(5)
    base = vector.frontier(
        DIST, POLICIES, LAMS, N, 100, m_trials=8, key=key, pad_cells=False
    )
    padded = vector.frontier(
        DIST, POLICIES, LAMS, N, 100, m_trials=8, key=key, pad_cells=True
    )
    for a, b in zip(base, padded):
        assert a["mean_sojourn"] == pytest.approx(b["mean_sojourn"], rel=1e-6)
        assert a["mean_cost"] == pytest.approx(b["mean_cost"], rel=1e-6)


def test_frontier_rcap_shifts_draws_within_mc_error():
    """Widening r_cap reshapes the fresh-draw tensor, so the draw stream —
    and hence the Monte-Carlo estimates — legitimately change; the masking
    guarantees the estimator stays unbiased, so results for the same grid
    must agree within MC error (NOT bit-for-bit)."""
    key = jax.random.PRNGKey(12)
    m_trials = 24
    tight = vector.frontier(DIST, POLICIES, LAMS, N, 200, m_trials=m_trials, key=key)
    wide = vector.frontier(
        DIST, POLICIES, LAMS, N, 200, m_trials=m_trials, key=key, r_cap=4
    )
    for a, b in zip(tight, wide):
        sigma = max(float(np.hypot(a["sojourn_std_err"], b["sojourn_std_err"])), 1e-12)
        assert abs(a["mean_sojourn"] - b["mean_sojourn"]) / sigma < 5.0


def test_frontier_kernel_switch_is_exact():
    """kernel=True routes the queue through the Pallas kw_queue kernel on
    identical draws: results match the scan path to float tolerance."""
    key = jax.random.PRNGKey(6)
    scan = vector.frontier(DIST, POLICIES, (0.4,), N, 120, m_trials=8, c=2, key=key)
    kern = vector.frontier(
        DIST, POLICIES, (0.4,), N, 120, m_trials=8, c=2, key=key, kernel=True
    )
    for a, b in zip(scan, kern):
        assert a["mean_sojourn"] == pytest.approx(b["mean_sojourn"], rel=1e-5)
        assert a["p99"] == pytest.approx(b["p99"], rel=1e-5)


def _backend_compiles(call):
    """Backend compilations `call()` triggers (JAX's own compile events)."""
    seen = []

    def listener(name, duration, **kwargs):
        if name == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        call()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    return len(seen)


@pytest.mark.parametrize("kernel", [False, True])
def test_lower_frontier_is_the_program_frontier_runs(kernel):
    """lower_frontier hands out the exact program frontier dispatches: once
    it is compiled, frontier and a same-shaped policy_search compile
    nothing more."""
    kw = dict(m_trials=5, c=2, kernel=kernel, r_cap=3)
    pols, lams = POLICIES[:2], (0.2, 0.3)
    compiled = vector.lower_frontier(DIST, pols, lams, N, 77, **kw).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
    assert _backend_compiles(lambda: vector.frontier(DIST, pols, lams, N, 77, **kw)) == 0
    # policy_search at one λ is the same engine: one more program, no more
    vector.lower_frontier(DIST, pols, (0.2,), N, 77, **kw).compile()
    assert _backend_compiles(lambda: vector.policy_search(DIST, pols, 0.25, N, 77, **kw)) == 0


def test_sweep_is_a_frontier_wrapper():
    key = jax.random.PRNGKey(7)
    s = vector.sweep(DIST, POLICIES, LAMS, N, 100, m_trials=8, key=key)
    f = vector.frontier(DIST, POLICIES, LAMS, N, 100, m_trials=8, key=key)
    assert [r["mean_sojourn"] for r in s] == [r["mean_sojourn"] for r in f]


def test_frontier_empirical_paths_agree():
    """Raw samples and Empirical(samples) drive the identical traced path."""
    x = np.random.default_rng(0).exponential(1.0, 400) + 1.0
    key = jax.random.PRNGKey(8)
    a = vector.frontier(x, POLICIES, (0.3,), N, 100, m_trials=8, key=key)
    b = vector.frontier(Empirical(x), POLICIES, (0.3,), N, 100, m_trials=8, key=key)
    for ra, rb in zip(a, b):
        assert ra["mean_sojourn"] == pytest.approx(rb["mean_sojourn"], rel=1e-6)


def test_policy_search_is_the_frontier_engine_at_one_lambda():
    x = np.random.default_rng(1).exponential(1.0, 400) + 1.0
    key = jax.random.PRNGKey(9)
    search = vector.policy_search(
        x, POLICIES, lam=0.3, n=N, n_jobs=100, m_trials=8, key=key
    )
    front = vector.frontier(x, POLICIES, (0.3,), N, 100, m_trials=8, key=key)
    for s, f in zip(search, front):
        assert s["mean_sojourn"] == pytest.approx(f["mean_sojourn"], rel=1e-6)
        assert s["rho"] == pytest.approx(f["rho"], rel=1e-6)
        assert s["policy"] in POLICIES  # search rows carry the policy object


def test_masked_single_fork_matches_static_sampler():
    """Dynamic-fork-point semantics ≡ `single_fork_batch` on shared draws
    (the quantile-transform route, analytic distribution)."""
    import jax.numpy as jnp

    n, s, r = 10, 3, 2
    key = jax.random.PRNGKey(10)
    for keep in (True, False):
        # reproduce single_fork_batch's draw structure through the shared
        # quantile transform so the comparison is exact, not statistical
        kx, ky = jax.random.split(key)
        x_sorted = jnp.sort(DIST.sample(kx, (64, n)), axis=-1)
        fresh_static = DIST.sample(ky, (64, s, r + 1))
        # masked path consumes an (n, r_cap) fresh block; place the static
        # draws in the straggler rows (iota >= k) it actually reads
        fresh = jnp.zeros((64, n, r + 1))
        fresh = fresh.at[:, n - s :, :].set(fresh_static)
        T_dyn, C_dyn = vector.masked_single_fork(
            x_sorted, fresh, jnp.int32(n - s), jnp.int32(r), keep
        )

        def ref_batch(x_sorted, fresh_static):
            k = n - s
            t1 = x_sorted[..., k - 1]
            c1 = jnp.sum(jnp.where(jnp.arange(n) < k, x_sorted, 0.0), axis=-1) + s * t1
            stragglers = x_sorted[..., k:]
            if keep:
                y = jnp.minimum(
                    stragglers - t1[..., None], jnp.min(fresh_static[..., :r], axis=-1)
                )
            else:
                y = jnp.min(fresh_static, axis=-1)
            return t1 + jnp.max(y, axis=-1), (c1 + (r + 1) * jnp.sum(y, axis=-1)) / n

        T_ref, C_ref = ref_batch(x_sorted, fresh_static)
        np.testing.assert_allclose(np.asarray(T_dyn), np.asarray(T_ref), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(C_dyn), np.asarray(C_ref), rtol=1e-6)


def test_frontier_validations():
    with pytest.raises(ValueError, match="lam"):
        vector.frontier(DIST, POLICIES, (0.0,), N, 50, m_trials=2)
    with pytest.raises(ValueError, match="candidate"):
        vector.frontier(DIST, [], (0.1,), N, 50, m_trials=2)
    with pytest.raises(ValueError, match="arrival rate"):
        vector.frontier(DIST, POLICIES, (), N, 50, m_trials=2)
    with pytest.raises(ValueError, match="r_cap"):
        vector.frontier(
            DIST, (SingleForkPolicy(0.1, 3, True),), (0.1,), N, 50, m_trials=2, r_cap=2
        )
    with pytest.raises(ValueError, match="2 samples"):
        vector.frontier(np.ones(1), POLICIES, (0.1,), N, 50, m_trials=2)


def test_slot_arrays_cache_hits():
    """(n, c, classes) geometry resolution is cached across re-plans."""
    vector._slot_arrays_cached.cache_clear()
    mix = (MachineClass("a", 16, 1.0), MachineClass("b", 16, 0.5))
    for _ in range(5):
        vector._slot_arrays(8, None, mix)
        vector._slot_arrays(8, 3, None)
    info = vector._slot_arrays_cached.cache_info()
    assert info.misses == 2 and info.hits == 8
    # cached arrays are the same objects — no per-call rebuilds
    a = vector._slot_arrays(8, 3, None)
    b = vector._slot_arrays(8, 3, None)
    assert a[0] is b[0]
