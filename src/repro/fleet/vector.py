"""Vectorized fleet rollouts: the JAX fast path for policy sweeps.

The event engine is exact but a Python loop; a sweep over (λ, c, p, r,
keep|kill) grids is thousands of runs.  This module fuses the whole sweep
into device programs for the *gang-aligned* regime: with `capacity =
c·n_tasks` split into c gang blocks ("job slots"), admission serializes
jobs onto whichever block frees first, so the fleet is a FIFO G/G/c queue
whose per-job service time is the single-job makespan T(π) and whose
per-job cost is C(π).  Concretely:

  * the heart of the module is one fused frontier engine: an entire
    (λ-grid × candidate-policy) cross-product is evaluated as ONE device
    program over shared common-random-number draws.  `masked_single_fork`
    implements the Definition 1/2 single-fork semantics with a *dynamic*
    fork point — (k, r, keep) enter via masks instead of shapes, so every
    grid cell is a traced vector entry and one compilation covers any
    same-shaped grid (any λ values, any candidate set, any reservoir
    content on the empirical path);
  * `frontier(dist_or_samples, policies, lams, ...)` is the public face of
    that engine (rows match the legacy `sweep` format); `policy_search`
    — the adaptive controller's inner loop — is the same engine at a
    single λ; `sweep` is now a thin wrapper over `frontier`, with the
    dispatch-per-cell legacy loop kept as `sweep_loop` (the baseline the
    `bench_fleet` fusion gate races against);
  * `c = 1` takes the Lindley recursion start_j = max(arrival_j,
    finish_{j-1}) in closed form (`lindley`: cumsum + cummax, no
    sequential scan at all);
  * `c > 1` is the Kiefer–Wolfowitz multi-server recursion: either the
    per-job `lax.scan` (`kw_queue`, vmapped over trials and cells) or —
    behind the `kernel=True` switch on `fleet_rollout` / `policy_search` /
    `frontier` — the Pallas kernel `repro.kernels.kw_queue`, which keeps
    the slot free-time vector in VMEM and tiles (trials × grid-cells)
    across the Pallas grid (interpret mode on CPU, Mosaic on TPU);
  * heterogeneous machine classes (`workload.MachineClass`) enter as
    per-slot speed multipliers: a job served by a speed-v slot stretches
    its whole sample path by 1/v — T, C and the slot's busy time all scale
    together, exactly matching the event engine's aligned placement
    (`FleetScheduler(placement="aligned")`), which is the oracle the
    agreement tests compare against;
  * for trace-driven workloads under π_kill, the residual draws
    Y = min of (r+1) fresh F̂_X samples go through the Pallas
    `kernels.residual_sampler` (eq. (7): F̄_Y = F̄_X^{r+1}), the same kernel
    Algorithm 1 uses — one kernel call covers every job of every trial.

Compilation-stability notes: grid cells are padded to power-of-two bucket
sizes (`pad_cells=True`) and the fresh-replica draw width can be pinned via
`r_cap`, so the adaptive controller's online re-plans never trigger a
recompile as its candidate set flexes.  On the empirical path everything
but (n, n_jobs, m_trials, r_cap, padded cell count, slot-array shapes) is
traced; analytic distributions are static (one compile per family+params).

Agreement with the event path on shared configs (same λ, π, n, aligned
placement, per-class slots a multiple of n) is within Monte-Carlo error;
tests/test_fleet.py enforces it, tests/test_fleet_properties.py checks the
queue recursions' invariants (c=1 reduction, monotonicity in c and λ,
Pallas kernel ≡ scan), tests/test_frontier.py pins the fused engine to the
per-cell loop.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributions import Distribution, Empirical, quantile_draws
from repro.core.policy import SingleForkPolicy, lower_policies, num_stragglers
from repro.core.simulate import lowered_policy_eval, policy_draws, single_fork_batch
from repro.obs.profile import jit_cache_size
from repro.obs.trace import get_recorder, host_span

from .workload import MachineClass

__all__ = [
    "VectorFleetResult",
    "as_quantile_source",
    "batched_queue",
    "cell_bucket",
    "emp_quantile",
    "fleet_rollout",
    "fork_draws",
    "frontier",
    "kw_queue",
    "lindley",
    "lower_frontier",
    "masked_single_fork",
    "policy_search",
    "retry_draws",
    "retry_transform",
    "sweep",
    "sweep_loop",
    "trace_kill_rollout",
]


@dataclasses.dataclass
class VectorFleetResult:
    sojourn: jnp.ndarray  # (m_trials, n_jobs)
    wait: jnp.ndarray  # (m_trials, n_jobs)
    service: jnp.ndarray  # (m_trials, n_jobs) per-job T (slot-speed scaled)
    cost: jnp.ndarray  # (m_trials, n_jobs) per-job C (slot-speed scaled)
    utilization: jnp.ndarray  # (m_trials,)
    slot: Optional[jnp.ndarray] = None  # (m_trials, n_jobs) serving job slot
    class_utilization: Optional[jnp.ndarray] = None  # (m_trials, n_classes)
    class_names: Optional[tuple] = None

    @property
    def mean_sojourn(self) -> float:
        return float(jnp.mean(self.sojourn))

    @property
    def mean_wait(self) -> float:
        return float(jnp.mean(self.wait))

    @property
    def mean_service(self) -> float:
        return float(jnp.mean(self.service))

    @property
    def mean_cost(self) -> float:
        return float(jnp.mean(self.cost))

    @property
    def sojourn_std_err(self) -> float:
        """Std error over per-trial means (trials are independent)."""
        per_trial = jnp.mean(self.sojourn, axis=1)
        m = per_trial.shape[0]
        return float(jnp.std(per_trial) / jnp.sqrt(max(m - 1, 1)))

    def percentile(self, q: float) -> float:
        return float(jnp.percentile(self.sojourn, q))

    def summary(self) -> dict:
        vals = _summary_jit(
            self.sojourn, self.wait, self.service, self.cost, self.utilization
        )
        out = dict(zip(_SUMMARY_KEYS, (float(v) for v in vals)))
        if self.class_utilization is not None and self.class_names is not None:
            per_class = jnp.mean(self.class_utilization, axis=0)
            for name, u in zip(self.class_names, per_class):
                out[f"util_{name}"] = float(u)
        return out


_SUMMARY_KEYS = (
    "mean_sojourn",
    "mean_wait",
    "mean_service",
    "mean_cost",
    "utilization",
    "p50",
    "p99",
    "p999",
    "sojourn_std_err",
)


@jax.jit
def _summary_jit(sojourn, wait, service, cost, util):
    """All summary scalars in one device program (one host transfer)."""
    per_trial = jnp.mean(sojourn, axis=1)
    m = per_trial.shape[0]
    return jnp.stack(
        [
            jnp.mean(sojourn),
            jnp.mean(wait),
            jnp.mean(service),
            jnp.mean(cost),
            jnp.mean(util),
            jnp.percentile(sojourn, 50.0),
            jnp.percentile(sojourn, 99.0),
            jnp.percentile(sojourn, 99.9),
            jnp.std(per_trial) / jnp.sqrt(max(m - 1, 1)),
        ]
    )


def lindley(arrivals, services):
    """Gang-serial (c = 1) queue: start_j = max(arrival_j, finish_{j-1}).

    Closed form of the recursion — finish_j = P_j + max_{k<=j}(A_k - P_{k-1})
    with P the service prefix sum — so the queue is a cumsum + cummax
    instead of an n_jobs-step sequential scan.  Returns (starts, finishes).
    """
    csum = jnp.cumsum(services)
    finishes = csum + jax.lax.cummax(arrivals - (csum - services))
    return finishes - services, finishes


def kw_queue(arrivals, services, speeds):
    """Kiefer–Wolfowitz FIFO G/G/c recursion with per-slot speeds.

    State is the c-vector of slot-free times; job j takes the fastest slot
    already idle at its arrival, else the earliest-freeing slot (ties break
    toward lower index, i.e. faster, since `speeds` is sorted descending).
    Its service requirement `services[j]` stretches to services[j]/speed on
    the chosen slot.  With homogeneous speeds the free-time vector is the
    (unsorted) Kiefer–Wolfowitz workload vector and the recursion is the
    classical one; c = 1 reduces exactly to `lindley`.

    This is the `lax.scan` realization; `repro.kernels.kw_queue` is the
    same recursion as a Pallas kernel over batches of independent queues
    (the `kernel=True` path of the rollout/search/frontier entry points).

    Returns (starts, finishes, scaled_services, slots), each (n_jobs,).
    """

    def step(free, inp):
        a, s = inp
        idle = free <= a
        slot = jnp.where(jnp.any(idle), jnp.argmax(idle), jnp.argmin(free))
        start = jnp.maximum(a, free[slot])
        svc = s / speeds[slot]
        finish = start + svc
        return free.at[slot].set(finish), (start, finish, svc, slot)

    init = jnp.zeros_like(speeds)
    _, outs = jax.lax.scan(step, init, (arrivals, services))
    return outs


def _queue_stats(arrivals, services, costs, n):
    starts, finishes = lindley(arrivals, services)
    sojourn = finishes - arrivals
    wait = starts - arrivals
    # capacity = n slots; busy slot-time per job = n * C_j (Definition 2)
    makespan = finishes[-1] - arrivals[0]
    util = jnp.sum(costs) * n / (n * jnp.maximum(makespan, 1e-12))
    return sojourn, wait, util


def _kw_stats(arrivals, starts, finishes, svc, slots, costs, speeds, slot_class, class_slots, n):
    """Per-trial G/G/c stats from an already-run queue recursion: the job's
    (T, C) stretch by its slot's speed, utilization aggregates busy
    copy-seconds per class."""
    sojourn = finishes - arrivals
    wait = starts - arrivals
    cost = costs / speeds[slots]
    makespan = jnp.max(finishes) - arrivals[0]  # last finish need not be job -1
    denom = jnp.maximum(makespan, 1e-12)
    busy = cost * n  # copy-seconds per job (Definition 2, wall-clock billed)
    slot_busy = jax.ops.segment_sum(busy, slots, num_segments=speeds.shape[0])
    class_busy = jax.ops.segment_sum(
        slot_busy, slot_class, num_segments=class_slots.shape[0]
    )
    util = jnp.sum(busy) / (speeds.shape[0] * n * denom)
    class_util = class_busy / (class_slots * denom)
    return sojourn, wait, svc, cost, util, slots, class_util


def _queue_stats_kw(arrivals, services, costs, speeds, slot_class, class_slots, n):
    starts, finishes, svc, slots = kw_queue(arrivals, services, speeds)
    return _kw_stats(
        arrivals, starts, finishes, svc, slots, costs, speeds, slot_class, class_slots, n
    )


@partial(jax.jit, static_argnames=("dist", "policy", "n", "n_jobs", "m_trials"))
def _rollout_jit(key, dist, policy, lam, n, n_jobs, m_trials):
    s = num_stragglers(n, policy.p)
    ka, ks = jax.random.split(key)
    inter = jax.random.exponential(ka, (m_trials, n_jobs)) / lam
    arrivals = jnp.cumsum(inter, axis=1)
    T, C = single_fork_batch(
        ks, dist, n, s, policy.r, policy.keep, shape=(m_trials, n_jobs)
    )
    sojourn, wait, util = jax.vmap(partial(_queue_stats, n=n))(arrivals, T, C)
    return sojourn, wait, T, C, util


@partial(jax.jit, static_argnames=("dist", "policy", "n", "n_jobs", "m_trials", "kernel"))
def _rollout_kw_jit(key, dist, policy, lam, n, n_jobs, m_trials, speeds, slot_class,
                    class_slots, kernel=False):
    s = num_stragglers(n, policy.p)
    ka, ks = jax.random.split(key)
    inter = jax.random.exponential(ka, (m_trials, n_jobs)) / lam
    arrivals = jnp.cumsum(inter, axis=1)
    T, C = single_fork_batch(
        ks, dist, n, s, policy.r, policy.keep, shape=(m_trials, n_jobs)
    )
    return _queue_kw_batch(arrivals, T, C, speeds, slot_class, class_slots, n, kernel=kernel)


@partial(jax.jit, static_argnames=("n", "kernel"))
def _queue_kw_batch(arrivals, T, C, speeds, slot_class, class_slots, n, kernel=False):
    """Batched KW queue over already-sampled (T, C): per-trial `lax.scan`s,
    or — `kernel=True` — one Pallas call covering every trial."""
    if kernel:
        from repro.kernels.kw_queue import kw_queue as kw_queue_pallas

        starts, fins, svc, slots = kw_queue_pallas(arrivals, T, speeds)
        return jax.vmap(
            lambda a, st, fi, sv, sl, c: _kw_stats(
                a, st, fi, sv, sl, c, speeds, slot_class, class_slots, n
            )
        )(arrivals, starts, fins, svc, slots, C)
    return jax.vmap(
        lambda a, t, c: _queue_stats_kw(a, t, c, speeds, slot_class, class_slots, n)
    )(arrivals, T, C)


@functools.lru_cache(maxsize=256)
def _slot_arrays_cached(n: int, c: Optional[int], classes: Optional[tuple]):
    if classes is None:
        if c is None or c == 1:
            return None
        if c < 1:
            raise ValueError("c (job slots) must be >= 1")
        speeds = jnp.ones((c,))
        slot_class = jnp.zeros((c,), jnp.int32)
        class_slots = jnp.array([float(c * n)])
        return speeds, slot_class, class_slots, ("default",)
    ordered = sorted(classes, key=lambda k: -k.speed)  # stable on ties
    speeds, slot_class, class_slots = [], [], []
    for i, k in enumerate(ordered):
        if k.slots % n:
            raise ValueError(
                f"class {k.name!r}: slots={k.slots} must be a multiple of "
                f"n_tasks={n} for the gang-aligned fast path"
            )
        speeds += [k.speed] * (k.slots // n)
        slot_class += [i] * (k.slots // n)
        class_slots.append(float(k.slots))
    if c is not None and c != len(speeds):
        raise ValueError(f"c={c} disagrees with classes providing {len(speeds)} job slots")
    if not speeds:
        raise ValueError("classes provide no job slots")
    return (
        jnp.array(speeds),
        jnp.array(slot_class, jnp.int32),
        jnp.array(class_slots),
        tuple(k.name for k in ordered),
    )


def _slot_arrays(n: int, c: Optional[int], classes: Optional[Sequence[MachineClass]]):
    """Resolve (c, classes) into per-job-slot arrays for the KW recursion.

    Returns (speeds, slot_class, class_slots, names) with job slots ordered
    fastest first — the same placement preference the aligned event engine
    uses — or None when the plain c=1 Lindley path applies.  Cached on the
    hashable (n, c, classes) geometry: the adaptive re-plan loop resolves
    the same fleet every few jobs, and rebuilding the jnp arrays each call
    was measurable re-plan overhead.
    """
    if classes is not None:
        classes = tuple(classes)
    return _slot_arrays_cached(n, c, classes)


def _c1_slot_arrays(n: int):
    """The degenerate slot geometry policy_search/frontier use when no c /
    classes are given: one unit-speed gang block."""
    return (
        jnp.ones((1,)),
        jnp.zeros((1,), jnp.int32),
        jnp.array([float(n)]),
        ("default",),
    )


def fleet_rollout(
    dist: Distribution,
    policy: SingleForkPolicy,
    lam: float,
    n: int,
    n_jobs: int,
    m_trials: int = 32,
    key=None,
    c: Optional[int] = None,
    classes: Optional[Sequence[MachineClass]] = None,
    kernel: bool = False,
) -> VectorFleetResult:
    """m_trials independent fleets of n_jobs Poisson(λ) arrivals.

    `c` is the number of concurrent gang blocks (capacity = c·n slots);
    `classes` optionally splits capacity into heterogeneous pools (each
    class's slot count must divide into whole gang blocks).  c=1 without
    classes takes the closed-form Lindley path; anything else runs the
    Kiefer–Wolfowitz recursion — as per-trial `lax.scan`s, or through the
    Pallas `kernels.kw_queue` kernel when `kernel=True` (which also covers
    the c=1 case, as a single-slot queue).  `dist` must be hashable (the
    analytic families are frozen dataclasses); trace workloads go through
    `trace_kill_rollout`.
    """
    if lam <= 0:
        raise ValueError("arrival rate lam must be > 0")
    if key is None:
        key = jax.random.PRNGKey(0)
    slot = _slot_arrays(n, c, classes)
    if slot is None and kernel:
        slot = _c1_slot_arrays(n)
    if slot is None:
        sojourn, wait, T, C, util = _rollout_jit(
            key, dist, policy, float(lam), n, n_jobs, m_trials
        )
        return VectorFleetResult(
            sojourn=sojourn, wait=wait, service=T, cost=C, utilization=util
        )
    speeds, slot_class, class_slots, names = slot
    sojourn, wait, T, C, util, slots, class_util = _rollout_kw_jit(
        key, dist, policy, float(lam), n, n_jobs, m_trials, speeds, slot_class,
        class_slots, kernel=kernel,
    )
    return VectorFleetResult(
        sojourn=sojourn,
        wait=wait,
        service=T,
        cost=C,
        utilization=util,
        slot=slots,
        class_utilization=class_util,
        class_names=names,
    )


# --------------------------------------------------------------------------
# fused frontier engine: (λ × π) cross-products as ONE device program
# --------------------------------------------------------------------------


def emp_quantile(xs, u):
    """Inverse-transform gather through the sorted empirical sample
    (type-1 inverse, identical to `core.distributions.Empirical.quantile`).

    In a program lowered for a TPU, a float32 table of at most
    `Empirical.LANE_GATHER_MAX` entries is looked up in VMEM by
    `Empirical.lane_gather`, with the same values; every other platform,
    and a larger table, keeps XLA's gather."""
    m = xs.shape[0]

    def gather(xs, u):
        return xs[Empirical.type1_index(u, m)]

    if m > Empirical.LANE_GATHER_MAX or not xs.dtype == u.dtype == jnp.float32:
        return gather(xs, u)
    return jax.lax.platform_dependent(xs, u, tpu=Empirical.lane_gather, default=gather)


def batched_queue(arrivals, services, speeds, kernel: bool = False):
    """FIFO G/G/c queues over an arbitrary batch: the one cell engine every
    stage of a composed rollout routes through.

    `arrivals` / `services` are (..., n_jobs) with any shared leading batch
    shape (trials, grid cells, both); each row is one independent queue with
    `speeds.shape[0]` job slots.  Three realizations, selected exactly as in
    the fused frontier: `kernel=True` flattens the batch into rows of ONE
    Pallas `kernels.kw_queue` call; c = 1 is the closed-form Lindley
    recursion (no sequential scan); c > 1 is the vmapped Kiefer–Wolfowitz
    `lax.scan`.  Returns (starts, finishes, scaled_services, slots), each
    with the input shape.  Rows must be sorted by arrival (FIFO order) —
    stage-composed callers sort by barrier-release time first and invert
    the permutation afterwards (`repro.dag.rollout`).
    """
    batch = arrivals.shape[:-1]
    J = arrivals.shape[-1]
    c = speeds.shape[0]
    flat = lambda z: z.reshape((-1, J))  # noqa: E731
    unflat = lambda z: z.reshape(batch + (J,))  # noqa: E731
    if kernel:
        # one Pallas call: every batch row tiled across the kernel grid
        from repro.kernels.kw_queue import kw_queue as kw_queue_pallas

        outs = kw_queue_pallas(flat(arrivals), flat(services), speeds)
        return tuple(unflat(z) for z in outs)
    if c == 1:
        svc = services / speeds[0]
        starts, fins = jax.vmap(lindley)(flat(arrivals), flat(svc))
        return (
            unflat(starts),
            unflat(fins),
            svc,
            jnp.zeros(arrivals.shape, jnp.int32),
        )
    outs = jax.vmap(lambda a, t: kw_queue(a, t, speeds))(flat(arrivals), flat(services))
    return tuple(unflat(z) for z in outs)


def masked_single_fork(x_sorted, fresh, k, r, keep):
    """Single-fork (T, C) with a *dynamic* fork point (Definitions 1–2).

    `x_sorted`: (..., n) sorted original task-time draws; `fresh`:
    (..., n, r_cap) fresh replica draws with r_cap >= r+1.  The fork index
    k = n - s, replica count r, and keep|kill flag may all be traced
    scalars: stragglers are selected by an `iota >= k` mask and unused
    fresh-replica columns are masked to +inf before the min, so a whole
    candidate grid vmaps over (k, r, keep) vectors into one device program
    — no per-policy recompiles.  Draw `fresh` at a common r_cap across
    candidates (see `fork_draws`); masking makes the extra columns inert.

    Same semantics as `core.simulate.single_fork_batch` (which specializes
    shapes per static policy); k = n (s = 0) degenerates to the baseline.
    Returns (T, C) with the batch shape of x_sorted[..., 0].
    """
    n = x_sorted.shape[-1]
    iota = jnp.arange(n)
    t1 = jnp.take(x_sorted, k - 1, axis=-1)  # (...) fork-point time
    straggler = iota >= k  # (n,)
    c1 = jnp.sum(jnp.where(straggler, 0.0, x_sorted), axis=-1) + (n - k) * t1
    # running min over the replica axis depends only on the draws, so under
    # a vmap over (k, r, keep) grids it is computed ONCE and each cell pays
    # a single dynamic gather — not an O(r_cap)-wide masked reduction
    cm = jax.lax.cummin(fresh, axis=fresh.ndim - 1)
    fresh_keep = jnp.where(r > 0, jnp.take(cm, jnp.maximum(r - 1, 0), axis=-1), jnp.inf)
    fresh_kill = jnp.take(cm, r, axis=-1)  # min over the first r+1 draws
    remaining = x_sorted - t1[..., None]
    y = jnp.where(keep, jnp.minimum(remaining, fresh_keep), fresh_kill)
    y = jnp.where(straggler, y, 0.0)
    T = t1 + jnp.max(y, axis=-1)
    C = (c1 + (r + 1.0) * jnp.sum(y, axis=-1)) / n
    return T, C


def retry_draws(key, quantile, shape, attempts: int):
    """Shared-CRN draw pair for the geometric-retry transform.

    Returns (x: (attempts, shape[-1]) + shape[:-1], v: (attempts-1,
    shape[-1]) + shape[:-1]): per logical draw of `shape`, `attempts`
    candidate service times through the inverse transform and `attempts-1`
    fate uniforms, numbered row-major over shape + (attempts,) and shape +
    (attempts-1,) as `quantile_draws` numbers them.  The attempts axis and
    the draw's last axis lead: on a TPU the two minor axes of an array are
    tiled by (8, 128), so trailing axes of r_cap copies and 8 attempts
    would pad the array 21 times over.  The draws carry no q — a whole
    (λ × q × π) grid shares ONE pair and each q applies `retry_transform`
    with its own traced q, which is exactly the common-random-numbers
    structure the fused frontier needs: the argmin over cells compares the
    same failure fates at different q thresholds.
    """
    *outer, last = shape
    size = math.prod(shape)
    ku, kv = jax.random.split(key)
    u = jax.random.uniform(ku, (size * attempts,)).reshape(-1, last, attempts)
    x = quantile(u.transpose(2, 1, 0).reshape((attempts, last, *outer)))
    v = jax.random.uniform(kv, (size * (attempts - 1),)).reshape(-1, last, attempts - 1)
    return x, v.transpose(2, 1, 0).reshape((attempts - 1, last, *outer))


def retry_transform(x, v, q):
    """Effective busy time of a copy under the q failure law (traced q),
    in the draw's own `shape`, from `retry_draws`' pair.

    Attempt k+1 runs iff attempts 1..k all failed (v[k-1] < q each), so
    alive = cumprod(v < q) and the effective duration is the geometric sum
    x[0] + Σ_k alive_k · x[k+1].  With immediate relaunch (backoff_base ==
    0) this IS the copy's slot busy time, so the result feeds
    `masked_single_fork` / `lowered_policy_eval` unchanged and both T and C
    (Definition 2 bills every attempt's wall-clock) stay exact against the
    event engine.  The final attempt is deemed successful — a truncation
    bias of order q**(attempts-1), negligible at the default
    max_attempts=8.  attempts=1 degenerates to x[0] (no retries).
    """
    alive = jnp.cumprod((v < q).astype(x.dtype), axis=0)
    busy = x[0] + jnp.sum(alive * x[1:], axis=0)
    return jnp.moveaxis(busy, 0, -1)


def fork_draws(key, quantile, shape, n: int, r_cap: int):
    """The common-random-number draw pair `masked_single_fork` consumes.

    `quantile` is any inverse-transform: an analytic distribution's
    `.quantile` or the empirical gather `partial(emp_quantile, xs)` — the
    one hook through which both kinds of service distribution enter the
    fused engine.  Returns (x_sorted: shape+(n,), fresh: shape+(n, r_cap)).
    """
    kx, ky = jax.random.split(key)
    x_sorted = jnp.sort(quantile_draws(kx, quantile, shape + (n,)), axis=-1)
    fresh = quantile_draws(ky, quantile, shape + (n, r_cap))
    return x_sorted, fresh


#: stats computed inside the fused program, in stack order; the percentile
#: keys (p50/p99/p999) are added host-side from the returned sojourns
_FRONTIER_JIT_KEYS = (
    "mean_sojourn",
    "mean_wait",
    "mean_service",
    "mean_cost",
    "utilization",
    "sojourn_std_err",
    "rho",
    "rho_work",
    "rho_block",
)


@partial(
    jax.jit,
    static_argnames=(
        "dist", "n", "n_jobs", "m_trials", "r_cap", "n_stages", "kernel", "attempts",
        "hist",
    ),
)
def _frontier_jit(
    key, xs, modes, ks, ts, rs, keeps, ds, lams, qs, speeds, slot_class, class_slots,
    dist, n, n_jobs, m_trials, r_cap, n_stages, kernel, attempts=None, hist=None,
):
    """Evaluate EVERY (policy, λ) cell on one shared set of random draws.

    The per-cell policy params are the LOWERED tensor rows from
    `core.policy.lower_policies` — (mode, k, t, r, keep) per stage plus the
    group width d — all *dynamic* vectors: the fork trigger enters via
    masks instead of shapes, λ scales one shared exponential inter-arrival
    draw, so a grid mixing any policy families (single-fork, delayed
    relaunch, (n, d) groups, multi-stage schedules) vmaps into a single
    device program and one compile covers any same-shaped grid (and, on
    the empirical path, any reservoir content).  Sharing the draws across
    cells is common-random-numbers variance reduction: frontier orderings
    and the argmin over candidates are far sharper than independent
    rollouts of equal size.

    `hist` (static, a `repro.obs.HistSpec`) switches the off-device tail
    payload: instead of the raw per-cell sojourn matrices (cells × m × J
    floats), the program accumulates fixed-size γ-bucket sojourn AND cost
    bincounts in-program and ships (cells × (2·n_bins + 6)) scalars — the
    device-side observability path for large sweeps.

    `qs` (None, or the Q failure probabilities of a q axis, traced) puts
    every cell under the q task-failure law with immediate relaunch: the
    program evaluates the (policy, λ) cells of the arguments at each q, Q ·
    len(lams) cells in all, q fastest.  Every copy's `attempts` (static)
    attempts and their fates are drawn once (`retry_draws`); the retry
    transform, the sort of the originals and the fresh copies' running
    minimum are formed once per q and shared by that q's cells.  The
    transform needs effective duration == slot busy time, which only holds
    for immediate relaunch — `frontier` rejects backoff_base != 0 before
    dispatch.  qs=None traces the fault-free program.
    """
    ka, kf = jax.random.split(key)
    quantile = dist.quantile if dist is not None else partial(emp_quantile, xs)
    if qs is not None:
        kx, ky = jax.random.split(kf)
        expo_cum = jnp.cumsum(jax.random.exponential(ka, (m_trials, n_jobs)), axis=1)
        xr, xv = retry_draws(kx, quantile, (m_trials, n_jobs, n), attempts)
        if modes is None:
            fr, fv = retry_draws(ky, quantile, (m_trials, n_jobs, n, r_cap), attempts)

            def tc(x_sorted, fresh, k, r, keep, lam):
                T, C = masked_single_fork(x_sorted, fresh, k, r, keep)
                return expo_cum / lam, T, C

            def per_q(q):
                # the cells' vmap leaves these unbatched: masked_single_fork
                # takes the running minimum of `fresh` once for all of them
                x_sorted = jnp.sort(retry_transform(xr, xv, q), axis=-1)
                fresh = retry_transform(fr, fv, q)
                return jax.vmap(partial(tc, x_sorted, fresh))(ks, rs, keeps, lams)

        else:
            fr, fv = retry_draws(
                ky, quantile, (m_trials, n_jobs, n_stages, n, r_cap), attempts
            )

            def tc(x, fresh, mode, k, t, r, keep, d, lam):
                T, C = lowered_policy_eval(x, fresh, mode, k, t, r, keep, d)
                return expo_cum / lam, T, C

            def per_q(q):
                x = retry_transform(xr, xv, q)
                fresh = retry_transform(fr, fv, q)
                return jax.vmap(partial(tc, x, fresh))(modes, ks, ts, rs, keeps, ds, lams)

        # (Q, cells, m, J) -> (cells · Q, m, J): frontier's order, q fastest
        arrivals, T, C = (
            jnp.swapaxes(z, 0, 1).reshape((-1,) + z.shape[2:])
            for z in jax.vmap(per_q)(qs)
        )
        lams = jnp.repeat(lams, qs.shape[0])
    elif modes is None:
        # the whole grid lowered into the single-stage-quantile/full-width
        # domain (every SingleForkPolicy grid does): trace the HISTORICAL
        # program verbatim — identical HLO means identical floats, which is
        # the bit-identity contract the bench gate pins.  Co-compiling the
        # general evaluator perturbs XLA fusion of this very expression by
        # ~1 ulp, so the selection must happen host-side, not via jnp.where.
        x_sorted, fresh = fork_draws(kf, quantile, (m_trials, n_jobs), n, r_cap)
        expo_cum = jnp.cumsum(jax.random.exponential(ka, (m_trials, n_jobs)), axis=1)

        def tc(k, r, keep, lam):
            T, C = masked_single_fork(x_sorted, fresh, k, r, keep)
            return expo_cum / lam, T, C

        arrivals, T, C = jax.vmap(tc)(ks, rs, keeps, lams)  # each (cells, m, J)
    else:
        x, fresh = policy_draws(kf, quantile, (m_trials, n_jobs), n, r_cap, n_stages)
        expo_cum = jnp.cumsum(jax.random.exponential(ka, (m_trials, n_jobs)), axis=1)

        def tc(mode, k, t, r, keep, d, lam):
            T, C = lowered_policy_eval(x, fresh, mode, k, t, r, keep, d)
            return expo_cum / lam, T, C

        # each (cells, m, J)
        arrivals, T, C = jax.vmap(tc)(modes, ks, ts, rs, keeps, ds, lams)

    c = speeds.shape[0]
    starts, fins, svc, slots = batched_queue(arrivals, T, speeds, kernel=kernel)

    n_classes = class_slots.shape[0]

    def cellstats(a, st, fi, sl, sv, Tc, Cc, lam):
        soj = fi - a
        wait = st - a
        cost = Cc / speeds[sl]
        makespan = jnp.max(fi, axis=1) - a[:, 0]  # per trial
        denom = jnp.maximum(makespan, 1e-12)
        busy = cost * n  # copy-seconds per job (Definition 2)
        total_busy = jnp.sum(busy, axis=1)  # per trial
        util = jnp.mean(total_busy / (c * n * denom))

        if c == 1:  # static: one slot, one class — no segment reductions
            class_util = jnp.mean(total_busy[:, None] / (class_slots * denom[:, None]), axis=0)
        else:

            def trial_class_util(b_row, sl_row, dn):
                slot_busy = jax.ops.segment_sum(b_row, sl_row, num_segments=c)
                class_busy = jax.ops.segment_sum(
                    slot_busy, slot_class, num_segments=n_classes
                )
                return class_busy / (class_slots * dn)

            class_util = jnp.mean(jax.vmap(trial_class_util)(busy, sl, denom), axis=0)
        per_trial = jnp.mean(soj, axis=1)
        m = per_trial.shape[0]
        # two saturation measures, both in base work units over Σ slot speeds:
        #   rho_work  = λ·n·E[C] / Σ slots·speed — copy-seconds offered vs
        #               served (the work-conserving / pooled bound; the n's
        #               cancel since each job slot carries n task slots);
        #   rho_block = λ·E[T] / Σ block speeds — gang-block occupancy: in
        #               the aligned/KW regime a job holds its whole block
        #               for T, so the queue diverges when THIS reaches 1
        #               even with idle task slots inside the block.
        rho_work = lam * jnp.mean(Cc) / jnp.sum(speeds)
        rho_block = lam * jnp.mean(Tc) / jnp.sum(speeds)
        base = jnp.stack(
            [
                jnp.mean(soj),
                jnp.mean(wait),
                jnp.mean(sv),
                jnp.mean(cost),
                util,
                jnp.std(per_trial) / jnp.sqrt(max(m - 1, 1)),
                jnp.maximum(rho_work, rho_block),
                rho_work,
                rho_block,
            ]
        )
        if hist is None:
            return jnp.concatenate([base, class_util]), soj
        from repro.obs.device import device_histogram

        s_counts, s_min, s_max, s_sum = device_histogram(soj, hist)
        c_counts, c_min, c_max, c_sum = device_histogram(cost, hist)
        return jnp.concatenate([base, class_util]), (
            s_counts, jnp.stack([s_min, s_max, s_sum]),
            c_counts, jnp.stack([c_min, c_max, c_sum]),
        )

    # exact mode: sojourn matrices come back to the host with the stats —
    # XLA's CPU sort is ~10x slower than np.partition, so the percentile
    # keys are computed host-side by _eval_cells (identical linear-
    # interpolation semantics).  hist mode keeps the samples on device and
    # ships fixed-size bincounts instead.
    return jax.vmap(cellstats)(arrivals, starts, fins, slots, svc, T, C, lams)


def as_quantile_source(dist_or_samples):
    """Normalize the frontier's first argument: (static_dist | None, xs).

    Hashable analytic distributions stay static (their quantile transform
    is traced into the program); `Empirical` instances and raw sample
    arrays go through the traced empirical gather, so fresh telemetry never
    recompiles.
    """
    if isinstance(dist_or_samples, Empirical):
        return None, jnp.asarray(dist_or_samples.sorted, jnp.float32)
    if isinstance(dist_or_samples, Distribution):
        return dist_or_samples, jnp.zeros((1,), jnp.float32)
    xs = jnp.sort(jnp.asarray(dist_or_samples, dtype=jnp.float32).ravel())
    if xs.shape[0] < 2:
        raise ValueError("need at least 2 samples to drive the empirical path")
    return None, xs


def cell_bucket(n_cells: int) -> int:
    """Next power-of-two bucket (>= 8): grids of any size up to the bucket
    share one compilation."""
    b = 8
    while b < n_cells:
        b *= 2
    return b


def _cells_per_q(n_cells: int, n_q: int, pad_cells: bool) -> int:
    """Program cells at each q for `n_cells` (policy, λ) cells on a q axis
    of `n_q` values: with `pad_cells`, the fewest that fill the whole grid's
    `cell_bucket` (n_q = 1 for a grid without the axis)."""
    if not pad_cells:
        return n_cells
    return -(-cell_bucket(n_cells * n_q) // n_q)


def _cells_call(
    dist_or_samples, cell_policies, cell_lams, n, n_jobs, m_trials, key, c,
    classes, kernel, r_cap, pad_cells, tail, qs, attempts,
):
    """Validate one grid and lower it onto `_frontier_jit`: returns its
    positional and keyword arguments and the class names — None for a grid
    without slot classes.  `qs` (with the static draw width `attempts`) is
    the q axis: the grid's cells are then the (policy, λ) cells at each q,
    q fastest."""
    if not cell_policies:
        raise ValueError("need at least one candidate policy")
    if any(lam <= 0 for lam in cell_lams):
        raise ValueError("arrival rate lam must be > 0")
    if qs is not None and (attempts is None or attempts < 1):
        raise ValueError("a q axis needs a static attempts >= 1")
    if key is None:
        key = jax.random.PRNGKey(0)
    dist, xs = as_quantile_source(dist_or_samples)
    slot = _slot_arrays(n, c, classes)
    speeds, slot_class, class_slots, names = slot if slot is not None else _c1_slot_arrays(n)

    n_cells = len(cell_policies)
    n_padded = _cells_per_q(n_cells, 1 if qs is None else len(qs), pad_cells)
    # lower the (padded) grid to the canonical fixed-width param tensor:
    # the fork indices, wall-clock triggers, replica counts and group
    # widths all derive from the one rounding contract in core.policy
    padded = list(cell_policies) + [cell_policies[0]] * (n_padded - n_cells)
    lowered = lower_policies(padded, n)
    if any(name is not None for name in lowered.class_names):
        raise ValueError(
            "class-restricted (OnClass) placement changes queue geometry, "
            "not the single-job law — model the class mix via `classes=` "
            "or use the event engine (FleetSim)"
        )
    r_max = lowered.r_max
    if r_cap is None:
        r_cap = r_max + 1
    elif r_cap < r_max + 1:
        raise ValueError(f"r_cap={r_cap} < r_max+1={r_max + 1}")
    lams = [float(lam) for lam in cell_lams]
    lams.extend([lams[0]] * (n_padded - n_cells))

    from repro.obs.device import HistSpec, DEFAULT_HIST

    if tail == "exact":
        hist = None
    elif tail == "hist":
        hist = DEFAULT_HIST
    elif isinstance(tail, HistSpec):
        hist = tail
    else:
        raise ValueError(f'tail must be "exact", "hist", or a HistSpec, got {tail!r}')

    # grids entirely in the single-stage-quantile/full-width domain take the
    # historical program (modes=None → bit-identical HLO to the pre-algebra
    # engine); anything else takes the general lowered evaluator.  Either
    # way the whole mixed grid is ONE dispatch.
    general = lowered.multi_stage or lowered.has_time or lowered.has_group
    if general:
        pol_args = (
            jnp.asarray(lowered.mode), jnp.asarray(lowered.k),
            jnp.asarray(lowered.t), jnp.asarray(lowered.r),
            jnp.asarray(lowered.keep), jnp.asarray(lowered.d),
        )
    else:
        pol_args = (
            None, jnp.asarray(lowered.k[:, 0]), None,
            jnp.asarray(lowered.r[:, 0]), jnp.asarray(lowered.keep[:, 0]), None,
        )
    args = (
        key, xs, *pol_args, jnp.array(lams), None if qs is None else jnp.array(qs),
        speeds, slot_class, class_slots,
    )
    kwargs = dict(
        dist=dist, n=n, n_jobs=n_jobs, m_trials=m_trials, r_cap=r_cap,
        n_stages=lowered.n_stages, kernel=kernel, attempts=attempts, hist=hist,
    )
    return args, kwargs, names if slot is not None else None


def _eval_cells(
    dist_or_samples,
    cell_policies: Sequence,
    cell_lams: Sequence[float],
    n: int,
    n_jobs: int,
    m_trials: int,
    key,
    c: Optional[int],
    classes: Optional[Sequence[MachineClass]],
    kernel: bool,
    r_cap: Optional[int],
    pad_cells: bool,
    tail="exact",
    qs: Optional[Sequence[float]] = None,
    attempts: Optional[int] = None,
) -> list[dict]:
    """Shared engine behind `frontier` and `policy_search`: one stats dict
    per (policy, λ) cell, computed by a single `_frontier_jit` dispatch.
    `qs` (with the static draw width `attempts`) is a q axis: each cell is
    evaluated at every q, q fastest, under the q failure law via the
    geometric-retry transform, and rows gain a "q" key; qs=None runs the
    fault-free program.

    `tail` selects how the percentile keys are computed: "exact" pulls the
    full sojourn matrices host-side (np.partition semantics, bit-exact);
    "hist" (or a `repro.obs.HistSpec`) keeps samples on device and ships
    γ-bucket bincounts — p50/p99/p999 then carry the sketch's relative-
    accuracy guarantee, the off-device transfer is fixed-size per cell,
    and rows additionally get cost_p50/cost_p99/cost_p999."""
    with host_span("grid.lower"):
        args, kwargs, names = _cells_call(
            dist_or_samples, cell_policies, cell_lams, n, n_jobs, m_trials, key, c,
            classes, kernel, r_cap, pad_cells, tail, qs, attempts,
        )
    n_q = 1 if qs is None else len(qs)
    n_cells = len(cell_policies) * n_q
    span_args = dict(cells=n_cells, padded=_cells_per_q(len(cell_policies), n_q, pad_cells) * n_q)
    rec = get_recorder()
    if rec.enabled:
        # re-trace detection (obs.retrace): the padded-grid contract promises
        # that re-plans inside one geometry never recompile — observe it by
        # watching the jit cache across the dispatch
        cache_before = jit_cache_size(_frontier_jit)
    if qs is not None:
        span_args.update(qs=n_q, attempts=attempts)
        # the uniforms the failure law draws: every attempt of the n
        # originals and of each stage's r_cap fresh copies, and the fates
        # of all attempts but the last
        rec.count("fault.retry_draws", m_trials * n_jobs * n
                  * (1 + kwargs["n_stages"] * kwargs["r_cap"]) * (2 * attempts - 1))
    with host_span("grid.dispatch", **span_args):
        stats, payload = _frontier_jit(*args, **kwargs)
    if rec.enabled:
        cache_after = jit_cache_size(_frontier_jit)
        if cache_before is not None and cache_after is not None and cache_after > cache_before:
            rec.count("obs.retrace", cache_after - cache_before)
    hist = kwargs["hist"]
    with host_span("grid.fetch"):
        stats, payload = _fetch_grid(stats, payload, hist, n_cells)
    with host_span("grid.tail"):
        pcts, cost_pcts, cell_evt = _grid_tails(payload, hist, n_cells)
        rows = []
        nk = len(_FRONTIER_JIT_KEYS)
        for i in range(n_cells):
            pol, lam = cell_policies[i // n_q], cell_lams[i // n_q]
            row = stats[i]
            d = dict(lam=float(lam), policy=pol.label(),
                     **dict(zip(_FRONTIER_JIT_KEYS, map(float, row[:nk]))))
            if qs is not None:
                d["q"] = float(qs[i % n_q])
            d["p50"], d["p99"], d["p999"] = (float(pcts[j, i]) for j in range(3))
            if cost_pcts is not None:
                d["cost_p50"], d["cost_p99"], d["cost_p999"] = (
                    float(cost_pcts[j, i]) for j in range(3)
                )
                d.update(cell_evt[i])
            if names is not None:  # mirror VectorFleetResult.summary(): per-class util
                for name, u in zip(names, row[nk:]):
                    d[f"util_{name}"] = float(u)
            rows.append(d)
    return rows


def _fetch_grid(stats, payload, hist, n_cells: int):
    """One grid's stats rows and tail payload on the host, cut to its real
    cells: the wait for the program and the device-to-host copy.  The
    payload is the sojourn matrix for the exact tail, else the tuple of
    sojourn and cost bincounts with their (min, max, sum) aggregates."""
    stats = np.asarray(stats)[:n_cells]
    if hist is None:
        return stats, np.asarray(payload)[:n_cells]
    return stats, tuple(np.asarray(p)[:n_cells] for p in payload)


def _grid_tails(payload, hist, n_cells: int):
    """p50/p99/p999 of each cell's sojourns, a (3, n_cells) array, from
    `_fetch_grid`'s payload.  The exact tail gives (pcts, None, None).  Hist
    cells carry the whole tail shape, so they also give the cost
    percentiles and each cell's EVT extension (evt_xi / evt_p999 /
    evt_p9999): a GPD fitted on the reconstructed sketch's exceedance
    buckets extrapolates past the (n_jobs × m_trials) sample's resolution."""
    if hist is None:
        soj = payload.reshape(n_cells, -1)
        return np.percentile(soj, (50.0, 99.0, 99.9), axis=1), None, None
    from repro.obs.device import sketch_from_device
    from repro.obs.evtail import evt_keys

    s_counts, s_agg, c_counts, c_agg = payload
    pcts = np.empty((3, n_cells))
    cost_pcts = np.empty((3, n_cells))
    cell_evt = []
    for i in range(n_cells):
        sk = sketch_from_device(s_counts[i], *s_agg[i], spec=hist)
        pcts[:, i] = sk.quantiles((0.5, 0.99, 0.999))
        cell_evt.append(evt_keys(sk))
        ck = sketch_from_device(c_counts[i], *c_agg[i], spec=hist)
        cost_pcts[:, i] = ck.quantiles((0.5, 0.99, 0.999))
    return pcts, cost_pcts, cell_evt


def _fault_qs(fault):
    """Normalize `frontier`'s fault argument to (qs, attempts).

    Accepts one `repro.faults.FaultSpec` or a sequence of them (a q grid
    axis).  The fused engines model exactly the q law with immediate
    relaunch — anything else is event-engine territory, rejected here with
    a pointer at the right tool rather than silently approximated.
    """
    from repro.faults.model import FaultSpec

    specs = [fault] if isinstance(fault, FaultSpec) else list(fault)
    if not specs:
        raise ValueError("need at least one FaultSpec")
    qs = []
    attempts = None
    for f in specs:
        if not isinstance(f, FaultSpec):
            raise TypeError(f"fault entries must be FaultSpec, got {type(f)}")
        if f.fail_dist is not None:
            raise ValueError(
                "the fused engines model the q failure law only; fail_dist "
                "runs exactly on the event engine (FleetSim)"
            )
        if f.machine_faults:
            raise ValueError(
                "machine crashes run exactly on the event engine (FleetSim); "
                "for a fused grid fold the crash hazard into q via "
                "repro.faults.effective_fail_prob"
            )
        if f.backoff_base != 0.0:
            raise ValueError(
                "the fused retry transform models immediate relaunch "
                "(backoff_base == 0); nonzero backoff runs on the event engine"
            )
        if attempts is None:
            attempts = f.max_attempts
        elif f.max_attempts != attempts:
            raise ValueError(
                "all FaultSpecs in one fused grid must share max_attempts "
                "(it is the static retry-draw width)"
            )
        qs.append(float(f.q))
    return qs, attempts


def _q_axis(fault):
    """(qs, attempts) of `fault` for `_frontier_jit`'s q axis: (None, None)
    for no fault and for a single disabled spec (q=0, no machine faults),
    which take the fault-free program."""
    if fault is None:
        return None, None
    qs, attempts = _fault_qs(fault)
    if qs == [0.0]:
        return None, None
    return qs, attempts


def frontier(
    dist_or_samples,
    policies: Sequence,
    lams,
    n: int,
    n_jobs: int,
    m_trials: int = 32,
    key=None,
    c: Optional[int] = None,
    classes: Optional[Sequence[MachineClass]] = None,
    kernel: bool = False,
    r_cap: Optional[int] = None,
    pad_cells: bool = True,
    tail="exact",
    fault=None,
) -> list[dict]:
    """Latency–cost frontier: the whole (policy × λ) cross-product as ONE
    fused device program over shared common-random-number draws.

    `dist_or_samples` is an analytic `Distribution` (static; enters via its
    quantile transform), an `Empirical`, or a raw sample array (both
    traced).  Rows come back policy-major in `sweep`'s format — the
    `_SUMMARY_KEYS` plus `rho` / `rho_work` / `rho_block` saturation
    estimates and per-class `util_*` when c > 1 or classes are given.

    `policies` may mix ANY algebra families — `SingleForkPolicy`,
    `MultiForkPolicy`, and `ForkPolicy` points such as `delayed_relaunch`
    or `group_replication` — in one grid: each lowers to a row of the
    canonical param tensor (`core.policy.lower_policies`) and the whole
    mixed grid is still one dispatch.  Single-fork cells are bit-identical
    to the historical single-fork-only path on the same key.

    One compilation covers any same-shaped grid: λ and the lowered policy
    params are traced per-cell vectors, cell counts are padded to
    power-of-two buckets (`pad_cells`), and `r_cap` pins the fresh-draw
    width (pass the largest r you will ever search, e.g. the adaptive
    controller's `r_max + 1`).
    `kernel=True` routes the queue recursions through the Pallas
    `kernels.kw_queue` kernel, (trials × cells) tiled across its grid.
    `tail="hist"` computes the percentile keys from in-program γ-bucket
    histograms instead of the raw sojourn matrices (see `_eval_cells`).

    `fault` — a `repro.faults.FaultSpec` or a sequence of them — adds a q
    failure-law axis: cells = policies × λs × faults (q fastest), every
    draw goes through the geometric-retry transform with its cell's q, and
    rows gain a "q" key.  A single disabled spec (q=0, no machine faults)
    takes the fault-free program, so the rows are bitwise identical to
    fault=None (the reduction `bench_fleet` gates).
    """
    policies = list(policies)
    lams = [float(lam) for lam in lams]
    if not lams:
        raise ValueError("need at least one arrival rate")
    qs, attempts = _q_axis(fault)
    rows = _eval_cells(
        dist_or_samples, [pol for pol in policies for _ in lams], lams * len(policies),
        n, n_jobs, m_trials, key, c, classes, kernel, r_cap, pad_cells, tail=tail,
        qs=qs, attempts=attempts,
    )
    if fault is not None and qs is None:
        for row in rows:
            row["q"] = 0.0
    return rows


def lower_frontier(
    dist_or_samples,
    policies: Sequence,
    lams,
    n: int,
    n_jobs: int,
    m_trials: int = 32,
    key=None,
    c: Optional[int] = None,
    classes: Optional[Sequence[MachineClass]] = None,
    kernel: bool = False,
    r_cap: Optional[int] = None,
    pad_cells: bool = True,
    tail="exact",
    fault=None,
):
    """The device program `frontier` runs for these arguments, lowered but
    not run (a `jax.stages.Lowered`).  `.compile()` gives its memory
    analysis and optimized HLO, and a later `frontier` (or, at one λ and
    one q, `policy_search`) call of the same shapes reuses that
    executable."""
    lams = [float(lam) for lam in lams]
    qs, attempts = _q_axis(fault)
    args, kwargs, _ = _cells_call(
        dist_or_samples, [pol for pol in policies for _ in lams],
        lams * len(policies), n, n_jobs, m_trials, key, c, classes, kernel,
        r_cap, pad_cells, tail, qs, attempts,
    )
    return _frontier_jit.lower(*args, **kwargs)


def sweep(
    dist: Distribution,
    policies,
    lams,
    n: int,
    n_jobs: int,
    m_trials: int = 32,
    key=None,
    c: Optional[int] = None,
    classes: Optional[Sequence[MachineClass]] = None,
    kernel: bool = False,
) -> list[dict]:
    """Load × policy frontier: one summary row per (λ, π) cell.

    Thin wrapper over the fused `frontier` engine — the entire grid is one
    device dispatch and one compilation.  The legacy dispatch-per-cell loop
    survives as `sweep_loop` (the baseline `bench_fleet` races the fusion
    gate against).
    """
    return frontier(
        dist, policies, lams, n, n_jobs, m_trials, key=key, c=c, classes=classes,
        kernel=kernel,
    )


def sweep_loop(
    dist: Distribution,
    policies,
    lams,
    n: int,
    n_jobs: int,
    m_trials: int = 32,
    key=None,
    c: Optional[int] = None,
    classes: Optional[Sequence[MachineClass]] = None,
) -> list[dict]:
    """Legacy per-cell sweep: one `fleet_rollout` dispatch per (λ, π) cell
    (plus a recompile per policy — `policy` is a static argname on the
    rollout jits).  Kept as the baseline the fused `frontier` is gated
    against in `bench_fleet`.

    CRN across policies: one key per λ, shared by every policy at that λ,
    so frontier comparisons at fixed load are variance-reduced even on this
    fallback path (previously each (λ, π) cell drew an independent key).
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    lams = list(lams)
    lam_keys = jax.random.split(key, len(lams))
    rows = []
    for policy in policies:
        for j, lam in enumerate(lams):
            res = fleet_rollout(
                dist, policy, lam, n, n_jobs, m_trials, key=lam_keys[j], c=c,
                classes=classes,
            )
            rows.append(dict(lam=float(lam), policy=policy.label(), **res.summary()))
    return rows


# --------------------------------------------------------------------------
# fused empirical policy search: the adaptive controller's inner loop
# --------------------------------------------------------------------------


def policy_search(
    samples,
    candidates: Sequence,
    lam: float,
    n: int,
    n_jobs: int = 192,
    m_trials: int = 8,
    key=None,
    c: Optional[int] = None,
    classes: Optional[Sequence[MachineClass]] = None,
    kernel: bool = False,
    r_cap: Optional[int] = None,
    pad_candidates: bool = True,
    tail="exact",
    fault=None,
) -> list[dict]:
    """Score candidate policies on an empirical trace at an estimated load.

    This is the adaptive controller's inner loop: per-job (T, C) under each
    π(p, r, keep|kill) are bootstrap-resampled from `samples` (Algorithm 1
    semantics) and pushed through the Kiefer–Wolfowitz G/G/c queue at
    arrival rate `lam` — so a policy is judged by its *fleet* sojourn under
    queueing, not its single-job latency.  It is the fused frontier engine
    at a single λ: the entire candidate grid runs as one device program
    over shared bootstrap draws (common-random-numbers, so the argmin over
    candidates is far sharper than independent rollouts of equal size), and
    with `pad_candidates` (power-of-two cell buckets) plus a pinned `r_cap`
    an online re-plan never recompiles as the candidate set flexes.
    `kernel=True` runs the queue recursions through the Pallas
    `kernels.kw_queue` kernel.

    Returns one dict per candidate: the policy itself, its label, mean
    sojourn/wait/service/cost, utilization, percentile sojourns, and
    saturation estimates — `rho_work` (copy-seconds: λ·n·E[C] / Σ
    slots·speed), `rho_block` (gang-block occupancy: λ·E[T] / Σ block
    speeds, the bound that actually governs the aligned/KW queue), and
    `rho` = max of the two; `rho >= 1` marks a policy this fleet cannot
    absorb at `lam`.

    `fault` (a single `repro.faults.FaultSpec`, q law only) makes the
    search failure-aware: every candidate is scored under the geometric-
    retry transform at the spec's q — the controller's re-plan on
    failure-rate drift passes its estimated q̂ here.
    """
    if lam <= 0:
        raise ValueError("arrival rate lam must be > 0")
    candidates = list(candidates)
    qs, attempts = _q_axis(fault)
    if qs is not None and len(qs) != 1:
        raise ValueError("policy_search takes a single FaultSpec")
    rows = _eval_cells(
        samples, candidates, [float(lam)] * len(candidates), n, n_jobs, m_trials,
        key, c, classes, kernel, r_cap, pad_candidates, tail=tail,
        qs=qs, attempts=attempts,
    )
    out = []
    for pol, row in zip(candidates, rows):
        row.pop("policy", None)
        row.pop("lam", None)
        out.append(dict(policy=pol, label=pol.label(), **row))
    return out


# --------------------------------------------------------------------------
# trace-driven π_kill path through the Pallas residual sampler
# --------------------------------------------------------------------------


def trace_kill_rollout(
    samples,
    policy: SingleForkPolicy,
    lam: float,
    n: int,
    n_jobs: int,
    m_trials: int = 32,
    key=None,
    c: Optional[int] = None,
    classes: Optional[Sequence[MachineClass]] = None,
    kernel: bool = False,
) -> VectorFleetResult:
    """Fleet rollout where task times bootstrap an empirical trace, π_kill.

    Original draws are the empirical inverse-transform gather
    F̂_X^{-1}(u) = xs[ceil(u·n)-1]; the straggler residuals (min over r+1
    fresh draws, eq. (7)) run through `kernels.residual_sampler` — a single
    kernel call of shape (m_trials·n_jobs, s, r+1) covers the whole fleet.
    `kernel=True` additionally runs the queue through `kernels.kw_queue`.
    """
    from repro.kernels.residual_sampler import residual_sample

    if policy.keep and not policy.is_baseline:
        raise ValueError("the residual-sampler fast path models π_kill only")
    if lam <= 0:
        raise ValueError("arrival rate lam must be > 0")
    if key is None:
        key = jax.random.PRNGKey(0)

    emp = Empirical(samples)
    xs = emp.sorted
    s = num_stragglers(n, policy.p)
    r = policy.r
    M = m_trials * n_jobs
    k0, k1, k2 = jax.random.split(key, 3)

    # originals: (M, n) draws through the one true inverse-transform gather
    x_sorted = jnp.sort(quantile_draws(k0, emp.quantile, (M, n)), axis=1)
    if s == 0:  # baseline: no residual phase, nothing for the kernel to do
        T = x_sorted[:, -1].reshape(m_trials, n_jobs)
        C = (jnp.sum(x_sorted, axis=1) / n).reshape(m_trials, n_jobs)
    else:
        k = n - s
        t1 = x_sorted[:, k - 1]
        c1 = jnp.sum(jnp.where(jnp.arange(n)[None, :] < k, x_sorted, 0.0), axis=1) + s * t1

        # residuals via the Pallas kernel: per job, max_j Y_j and Σ_j Y_j
        u = jax.random.uniform(k1, (M, s, r + 1), dtype=xs.dtype)
        max_y, sum_y = residual_sample(u, xs)
        T = (t1 + max_y).reshape(m_trials, n_jobs)
        C = ((c1 + (r + 1) * sum_y) / n).reshape(m_trials, n_jobs)

    inter = jax.random.exponential(k2, (m_trials, n_jobs)) / lam
    arrivals = jnp.cumsum(inter, axis=1)
    slot = _slot_arrays(n, c, classes)
    if slot is None and kernel:
        slot = _c1_slot_arrays(n)
    if slot is None:
        sojourn, wait, util = jax.vmap(partial(_queue_stats, n=n))(arrivals, T, C)
        return VectorFleetResult(
            sojourn=sojourn, wait=wait, service=T, cost=C, utilization=util
        )
    speeds, slot_class, class_slots, names = slot
    sojourn, wait, T, C, util, slots, class_util = _queue_kw_batch(
        arrivals, T, C, speeds, slot_class, class_slots, n, kernel=kernel
    )
    return VectorFleetResult(
        sojourn=sojourn,
        wait=wait,
        service=T,
        cost=C,
        utilization=util,
        slot=slots,
        class_utilization=class_util,
        class_names=names,
    )
