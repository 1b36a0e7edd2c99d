"""Flash attention Pallas TPU kernel.

Tiling: grid = (B, H, num_q_blocks, num_kv_blocks); the kv dimension is
'arbitrary' (sequential) so the running softmax state (m, l, acc) lives in
VMEM scratch and is carried across kv steps.  Block shapes are multiples of
128 on the lane dim so the MXU sees aligned matmuls; q/k/v tiles stream
HBM->VMEM per BlockSpec.

Causal jobs skip fully-masked kv blocks via @pl.when — the kernel does no
work above the diagonal, matching the FLOP count of the chunked-jnp path.

Oracle: kernels/ref.py::flash_attention_ref (pure jnp, fp32 softmax).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import run_kernel

NEG_INF = -2.0**30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, causal, block_q, block_k, scale, kv_len):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    run = True
    if causal:
        # kv block strictly above the diagonal: nothing to do
        run = k_start <= q_start + block_q - 1

    @pl.when(run if causal else True)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        span_q = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        span_k = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = span_k < kv_len
        if causal:
            mask = mask & (span_k <= span_q)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128, block_k: int = 128):
    """q,k,v: (B, S, H, D) with H already GQA-expanded.  Returns (B, S, H, D)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    # (B,H,S,D) layout for tiling
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = qt.shape[2] // block_q
    nk = kt.shape[2] // block_k

    grid = (B, H, nq, nk)
    scale = 1.0 / (D**0.5)
    kernel = functools.partial(
        _kernel, causal=causal, block_q=block_q, block_k=block_k,
        scale=scale, kv_len=Sk,
    )
    call = lambda *args, interpret: pl.pallas_call(  # noqa: E731
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)
    out = run_kernel(call, qt, kt, vt)
    if pad_q:
        out = out[:, :, :Sq]
    return out.transpose(0, 2, 1, 3)
