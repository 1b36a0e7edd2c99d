"""Mamba2 SSD chunked-scan Pallas TPU kernel.

Grid = (B, H, num_chunks); the chunk dimension is 'arbitrary' (sequential)
and the SSM state h (P x N) is carried across chunks in VMEM scratch.  Each
grid step does the intra-chunk quadratic form (two (Q,N)x(Q,N)->(Q,Q)-class
matmuls — MXU work) plus the state update, i.e. the same math as
`repro.models.ssm.ssd_chunked` (the oracle) but with the inter-chunk scan
fused into the kernel instead of a separate lax.scan.

Shapes per block: x (Q,P), dt (Q,), B/C (Q,N) with Q the chunk length
(128-aligned), P the head dim, N the state dim.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import run_kernel


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, hfin_ref, h_ref, *, chunk):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)  # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)  # (Q,)
    A = a_ref[0, 0]  # scalar (negative)
    Bm = b_ref[0, 0, 0].astype(jnp.float32)  # (Q, N)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)  # (Q, N)
    D = d_ref[0, 0]  # scalar

    log_a = dt * A  # (Q,)
    csum = jnp.cumsum(log_a)  # prefix sums
    # L[i,j] = exp(sum_{k=j+1..i} log_a) for i>=j
    diff = csum[:, None] - csum[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(ii >= jj, jnp.exp(diff), 0.0)

    # intra-chunk: y[i] = sum_j (C_i.B_j) L[i,j] dt_j x_j
    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q)
    gated = scores * L * dt[None, :]
    y = jax.lax.dot_general(
        gated, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    # inter-chunk: y[i] += (prod_{k<=i} a_k) C_i . h_prev
    h_prev = h_ref[...]  # (P, N)
    a_pref = jnp.exp(csum)  # (Q,)
    ch = jax.lax.dot_general(
        Cm, h_prev, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, P)
    y = y + ch * a_pref[:, None] + x * D

    # state update: h = a_total * h_prev + sum_j (prod_{k>j} a_k) dt_j x_j^T B_j
    a_tail = jnp.exp(csum[-1] - csum)  # prod_{k>j} a_k
    w = (a_tail * dt)[:, None] * x  # (Q, P)
    new_state = jax.lax.dot_general(
        w, Bm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (P, N)
    h_ref[...] = h_prev * jnp.exp(csum[-1]) + new_state

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _finish():
        hfin_ref[0, 0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 128):
    """x: (Bt,S,H,P)  dt: (Bt,S,H)  A,D: (H,)  B,C: (Bt,S,G,N).
    Returns (y: (Bt,S,H,P), h_final: (Bt,H,P,N)).  Matches
    `repro.models.ssm.ssd_chunked` (zero initial state)."""
    Bt, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    S0 = S
    if S % chunk:
        pad = chunk - S % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S = x.shape[1]
    nc = S // chunk

    # expand groups to heads and lay out as (Bt, H, nc, chunk, ·)
    rep = H // G
    Bh = jnp.repeat(B, rep, axis=2).transpose(0, 2, 1, 3).reshape(Bt, H, nc, chunk, N)
    Ch = jnp.repeat(C, rep, axis=2).transpose(0, 2, 1, 3).reshape(Bt, H, nc, chunk, N)
    xh = x.transpose(0, 2, 1, 3).reshape(Bt, H, nc, chunk, P)
    dth = dt.astype(jnp.float32).transpose(0, 2, 1).reshape(Bt, H, nc, chunk)
    Ah = jnp.broadcast_to(A.astype(jnp.float32)[None, :], (Bt, H))
    Dh = jnp.broadcast_to(D.astype(jnp.float32)[None, :], (Bt, H))

    grid = (Bt, H, nc)
    kernel = functools.partial(_kernel, chunk=chunk)
    call = lambda *args, interpret: pl.pallas_call(  # noqa: E731
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1), lambda b, h, c: (b, h)),
            pl.BlockSpec((1, 1, 1, chunk, N), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, N), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1), lambda b, h, c: (b, h)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bt, H, nc, chunk, P), x.dtype),
            jax.ShapeDtypeStruct((Bt, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)
    y, h_final = run_kernel(call, xh, dth, Ah, Bh, Ch, Dh)
    y = y.reshape(Bt, H, S, P).transpose(0, 2, 1, 3)[:, :S0]
    return y, h_final
