"""Execution-time distributions (paper §2.2, §3.2).

Every distribution exposes the quintet the paper's analysis needs:

  tail(x)      = Pr(X > x)                      (F̄_X)
  cdf(x)       = Pr(X <= x)
  quantile(u)  = F_X^{-1}(u)                    (inverse c.d.f.)
  mean()       = E[X]
  sample(key, shape)                            (inverse-transform sampling)

All math is jnp so the whole analysis/bootstrap stack jits and vmaps.
Parameters are stored as Python floats (static under jit closures).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import run_kernel, without_callers

__all__ = [
    "Distribution",
    "ShiftedExp",
    "Pareto",
    "Uniform",
    "Weibull",
    "Empirical",
    "quantile_draws",
    "upper_end_point",
]


def quantile_draws(key, quantile, shape):
    """`quantile(jax.random.uniform(key, shape))` with the same values,
    since threefry numbers the draws in row-major order, but transformed
    with the last axis leading.  For a TPU, an empirical quantile's gather
    into an array whose last axis is short or not a multiple of 128 (r+1
    replicas, a task count such as 1026) takes the compiler 40 s and more;
    gathering into the transposed array takes about two."""
    u = jax.random.uniform(key, (math.prod(shape),)).reshape(-1, shape[-1])
    return quantile(u.T).T.reshape(shape)


class Distribution:
    """Base class; subclasses implement tail/quantile analytically."""

    def tail(self, x):
        raise NotImplementedError

    def cdf(self, x):
        return 1.0 - self.tail(x)

    def quantile(self, u):
        raise NotImplementedError

    def mean(self):
        raise NotImplementedError

    def support(self) -> Tuple[float, float]:
        """(lower, upper) end points; upper may be inf."""
        raise NotImplementedError

    def sample(self, key, shape=()):
        u = jax.random.uniform(key, shape)
        return self.quantile(u)

    # -- numeric helpers shared by subclasses ------------------------------
    def mean_numeric(self, num: int = 4096):
        """E[X] = lower + ∫ tail(x) dx over [lower, hi] for nonneg X."""
        lo, hi = self.support()
        hi = jnp.where(jnp.isinf(hi), self._finite_upper(), hi)
        xs = jnp.linspace(lo, hi, num)
        return lo + jnp.trapezoid(self.tail(xs), xs)

    def _finite_upper(self, eps: float = 1e-7):
        return self.quantile(1.0 - eps)


def upper_end_point(dist: Distribution) -> float:
    """ω(F_X) = sup{x : F_X(x) < 1}  (paper eq. (1))."""
    return dist.support()[1]


@dataclasses.dataclass(frozen=True)
class ShiftedExp(Distribution):
    """ShiftedExp(Δ, μ): F̄(x) = exp(-μ(x-Δ)) for x >= Δ (paper eq. (9)).

    Exponential tail ⇒ DA(Λ) (Gumbel domain). 'New-longer-than-used' for
    Δ > 0, so π_keep is always preferred (paper §3.2.1).
    """

    delta: float
    mu: float

    def tail(self, x):
        x = jnp.asarray(x, jnp.float64 if jax.config.read("jax_enable_x64") else jnp.float32)
        return jnp.where(x >= self.delta, jnp.exp(-self.mu * (x - self.delta)), 1.0)

    def quantile(self, u):
        u = jnp.clip(u, 0.0, 1.0 - 1e-12)
        return self.delta - jnp.log1p(-u) / self.mu

    def mean(self):
        return self.delta + 1.0 / self.mu

    def support(self):
        return (self.delta, float("inf"))


@dataclasses.dataclass(frozen=True)
class Pareto(Distribution):
    """Pareto(α, x_m): F̄(x) = (x_m/x)^α for x >= x_m (paper eq. (13)).

    Polynomially decaying (heavy) tail ⇒ DA(Φ_α) (Fréchet domain).
    """

    alpha: float
    xm: float

    def tail(self, x):
        x = jnp.asarray(x)
        safe = jnp.maximum(x, self.xm)
        return jnp.where(x >= self.xm, (self.xm / safe) ** self.alpha, 1.0)

    def quantile(self, u):
        u = jnp.clip(u, 0.0, 1.0 - 1e-12)
        return self.xm * (1.0 - u) ** (-1.0 / self.alpha)

    def mean(self):
        if self.alpha <= 1.0:
            return float("inf")
        return self.alpha * self.xm / (self.alpha - 1.0)

    def support(self):
        return (self.xm, float("inf"))


@dataclasses.dataclass(frozen=True)
class Uniform(Distribution):
    """Uniform(a, b): finite upper end point ⇒ DA(Ψ_1) (reversed-Weibull)."""

    a: float
    b: float

    def tail(self, x):
        x = jnp.asarray(x)
        return jnp.clip((self.b - x) / (self.b - self.a), 0.0, 1.0)

    def quantile(self, u):
        return self.a + (self.b - self.a) * jnp.clip(u, 0.0, 1.0)

    def mean(self):
        return 0.5 * (self.a + self.b)

    def support(self):
        return (self.a, self.b)


@dataclasses.dataclass(frozen=True)
class Weibull(Distribution):
    """Weibull(k, lam): F̄(x) = exp(-(x/λ)^k); DA(Λ) for any k > 0."""

    k: float
    lam: float

    def tail(self, x):
        x = jnp.asarray(x)
        return jnp.exp(-jnp.maximum(x, 0.0) ** self.k / self.lam**self.k)

    def quantile(self, u):
        u = jnp.clip(u, 0.0, 1.0 - 1e-12)
        return self.lam * (-jnp.log1p(-u)) ** (1.0 / self.k)

    def mean(self):
        import math

        return self.lam * math.gamma(1.0 + 1.0 / self.k)

    def support(self):
        return (0.0, float("inf"))


class Empirical(Distribution):
    """Empirical distribution F̂_X from n execution-time samples (paper §4).

    tail/cdf are the right-continuous step functions of the sample; quantile
    is the standard inverse (type-1). Sampling = bootstrap resampling (draw
    uniformly among the samples), exactly what Algorithm 1 prescribes.
    """

    def __init__(self, samples):
        samples = jnp.asarray(samples)
        if samples.ndim != 1:
            raise ValueError("Empirical expects a 1-D sample vector")
        self.sorted = jnp.sort(samples)
        self.n = int(samples.shape[0])

    def tail(self, x):
        # Pr(X > x) = (# samples strictly greater than x) / n
        idx = jnp.searchsorted(self.sorted, jnp.asarray(x), side="right")
        return 1.0 - idx / self.n

    def cdf(self, x):
        idx = jnp.searchsorted(self.sorted, jnp.asarray(x), side="right")
        return idx / self.n

    def quantile(self, u):
        u = jnp.clip(jnp.asarray(u), 0.0, 1.0)
        return self.sorted[Empirical.type1_index(u, self.n)]

    def mean(self):
        return jnp.mean(self.sorted)

    def support(self):
        return (float(self.sorted[0]), float(self.sorted[-1]))

    def sample(self, key, shape=()):
        idx = jax.random.randint(key, shape, 0, self.n)
        return self.sorted[idx]

    @staticmethod
    def type1_index(u, m: int):
        """Index of the type-1 inverse at uniforms `u` into a sorted table
        of m entries: the one expression `quantile`, the gather of
        `fleet.vector.emp_quantile` and `lane_gather` share, so that all
        pick the same entry."""
        return jnp.clip(jnp.ceil(u * m).astype(jnp.int32) - 1, 0, m - 1)

    #: the largest table `lane_gather` takes.  The table sits in VMEM as one
    #: (32, 128) float32 block, 16 KiB, per 128 entries, and every lookup
    #: visits each block: at this bound 64 blocks, 1 MiB of the 16 MiB of
    #: scoped VMEM a TPU v5e kernel gets by default.  The kernel's cost
    #: grows with the blocks; on a v5e it still looked up 8192 entries
    #: about 23 times as fast as XLA's gather (0.38 against 8.7 ns a lookup).
    LANE_GATHER_MAX = 64 * 128

    @staticmethod
    def lane_gather(xs, u):
        """`xs[clip(ceil(u·m) − 1, 0, m − 1)]` for a table `xs` of m ≤
        `LANE_GATHER_MAX` float32 entries and float32 uniforms `u` of any
        shape, as a Pallas TPU kernel (interpreted off the TPU);
        bit-identical to the gather, since the index is computed the same
        way and only values move.

        The table, padded to chunks of 128 and each chunk broadcast to 32
        rows (four vregs), stays in VMEM for the whole grid; `u`, padded
        and laid out as rows of 128, streams through it in blocks.  For 32
        rows of uniforms at a time the kernel splits each index into its
        chunk (hi) and lane (lo), takes lane lo of every chunk with the
        in-register lane gather, and keeps the chunk's value where hi
        matches: a few vector operations per chunk for 1024 lookups, where
        XLA's gather reads HBM once per element.  Elementwise, so it takes
        `u` in whatever layout it comes."""
        lanes, rows = 128, 32
        m = xs.shape[0]
        chunks = -(-m // lanes)
        table = jnp.pad(xs, (0, chunks * lanes - m)).reshape(chunks, 1, lanes)
        table = jnp.broadcast_to(table, (chunks, rows, lanes))
        n = u.size
        block = min(512, -(-n // (rows * lanes)) * rows)  # rows of u a grid step
        steps = -(-n // (block * lanes))
        flat = jnp.pad(u.reshape(-1), (0, steps * block * lanes - n))
        call = functools.partial(Empirical._lane_gather_call, m=m, block=block)
        out = run_kernel(call, table, flat.reshape(steps * block, lanes))
        return out.reshape(-1)[:n].reshape(u.shape)

    @staticmethod
    def _lane_gather_call(table, flat, *, m, block, interpret):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        chunks, rows, lanes = table.shape

        @without_callers
        def kernel(t_ref, u_ref, o_ref):
            def step(i, carry):
                r = pl.ds(pl.multiple_of(i * rows, rows), rows)
                idx = Empirical.type1_index(u_ref[r, :], m)
                hi, lo = idx >> 7, idx & (lanes - 1)
                take = lambda j: jnp.take_along_axis(  # noqa: E731
                    t_ref[j], lo, axis=1, mode="promise_in_bounds"
                )
                out = take(0)
                for j in range(1, chunks):
                    out = jnp.where(hi == j, take(j), out)
                o_ref[r, :] = out
                return carry

            jax.lax.fori_loop(0, block // rows, step, 0)

        return pl.pallas_call(
            kernel,
            grid=(flat.shape[0] // block,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec((block, lanes), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((block, lanes), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(flat.shape, table.dtype),
            interpret=interpret,
            name="emp_quantile",
        )(table, flat)
