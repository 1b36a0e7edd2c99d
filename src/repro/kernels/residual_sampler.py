"""Residual-replica sampling Pallas kernel — the paper's own hot loop.

Algorithm 1 draws, for each of m bootstrap replicates, pn residual times
Y = min over (r+1) replicas of fresh draws from the empirical F̂_X, then
reduces max_j Y_j (the latency tail term) and sum_j Y_j (the cost term).
Empirical inverse-transform sampling is an integer gather:
F̂_X^{-1}(u) = xs[ceil(u·n)-1] with xs the sorted trace.

The gather runs in XLA (an arbitrary gather from a trace of ~1000 values
does not lower through Mosaic); the kernel fuses what follows it: the min
over replicas and the max / sum reductions per trial.  Trials sit on the
128-wide lane axis, stragglers on the sublane axis and replicas on the
leading axis of each (k, s, 128) VMEM tile, so every reduction is
elementwise or over sublanes and each output block is a (1, 128) row.

Used by the π_kill path of the vectorized estimator (eq. (7):
F̄_Y = F̄_X^{r+1} — i.e. Y is exactly a min of r+1 fresh draws); the
general path (π_keep) goes through the tabulated-cdf route in
`repro.core.bootstrap`.  Oracle: kernels/ref.py::residual_sample_ref.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import run_kernel

#: trials per grid step (the lane width)
BLOCK_M = 128


def _kernel(d_ref, mx_ref, sm_ref):
    y = jnp.min(d_ref[...], axis=0)  # (s, block_m): min over r+1 replicas
    mx_ref[...] = jnp.max(y, axis=0, keepdims=True)
    sm_ref[...] = jnp.sum(y, axis=0, keepdims=True)


def _call(draws, *, interpret):
    k, s, mp = draws.shape
    row = pl.BlockSpec((1, BLOCK_M), lambda i: (0, i))
    return pl.pallas_call(
        _kernel,
        grid=(mp // BLOCK_M,),
        in_specs=[pl.BlockSpec((k, s, BLOCK_M), lambda i: (0, 0, i))],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((1, mp), draws.dtype)] * 2,
        interpret=interpret,
    )(draws)


@jax.jit
def residual_sample(u, xs):
    """u: (m, s, k) uniforms; xs: (n,) sorted trace.
    Returns (max_y: (m,), sum_y: (m,))."""
    m = u.shape[0]
    n = xs.shape[0]
    # transpose before the gather: gathering into the (m, s, k) layout pads
    # its k = r+1 minor axis to 128 lanes, ~40x the memory on a TPU
    ut = jnp.pad(jnp.transpose(u, (2, 1, 0)), ((0, 0), (0, 0), (0, (-m) % BLOCK_M)))
    draws = xs[jnp.clip(jnp.ceil(ut * n).astype(jnp.int32) - 1, 0, n - 1)]  # (k, s, m)
    mx, sm = run_kernel(_call, draws)
    return mx[0, :m], sm[0, :m]
