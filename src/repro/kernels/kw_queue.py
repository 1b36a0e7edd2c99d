"""Multi-server Kiefer–Wolfowitz queue recursion as a Pallas kernel.

The G/G/c recursion start_j = max(arrival_j, free-time of the chosen slot)
is inherently sequential over jobs, but a frontier evaluation runs
(trials × grid-cells) *independent* queues — the fused `fleet.vector`
engine flattens that batch into rows and this kernel tiles them across
the Pallas grid.  Layout:

  * independent queues sit on the 128-wide lane axis and jobs on the
    sublane axis: the wrapper transposes (n_queues, n_jobs) to
    (n_jobs, n_queues), so the per-job read and write are whole rows of
    a VMEM tile, taken 8 jobs (one f32 sublane tile) at a time at aligned
    offsets — no lane-axis dynamic slice, which Mosaic cannot lower;
  * the grid is (queue blocks, job blocks); job blocks run in order
    ("arbitrary") and the (c, 128) tile of slot free-times lives in a
    VMEM scratch carried across them, so n_jobs is not bounded by VMEM
    and the free-time state never touches HBM;
  * slot selection is branch-free min/where reductions over the c slots
    (the sublane axis of the free-time tile): no gather, no argmin.

`repro.fleet.vector.batched_queue` runs it for every c > 1 float32 queue
batch of a program lowered for a TPU; the body is traced under
`repro.kernels.without_callers`, so a program holding it gets one
compile-cache key from any caller.

Semantics are identical to `repro.fleet.vector.kw_queue` (the lax.scan
reference): job j takes the lowest-indexed slot already idle at its
arrival — slots are ordered fastest first — else the earliest-freeing
slot (ties toward lower index); its service requirement stretches by the
chosen slot's speed.  Oracle: kernels/ref.py::kw_queue_ref.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import run_kernel, without_callers

#: queues per grid step (the lane width)
BLOCK_B = 128
#: jobs per grid step, at most; a multiple of the 8-row f32 sublane tile
BLOCK_J = 512
_ROWS = 8


def _kernel(a_ref, s_ref, sp_ref, start_ref, fin_ref, svc_ref, slot_ref, free_ref, *, c):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        free_ref[...] = jnp.zeros_like(free_ref)

    bb = a_ref.shape[1]
    speeds = sp_ref[...]  # (c, bb)
    slot_id = jax.lax.broadcasted_iota(jnp.int32, (c, bb), 0)
    row_id = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, bb), 0)
    none = jnp.int32(c)  # sentinel slot: "no idle slot"

    def group(g, free):
        rows = pl.ds(pl.multiple_of(g * _ROWS, _ROWS), _ROWS)
        a8 = a_ref[rows, :]
        s8 = s_ref[rows, :]
        st8 = fi8 = sv8 = jnp.zeros((_ROWS, bb), a8.dtype)
        sl8 = jnp.zeros((_ROWS, bb), jnp.int32)
        for r in range(_ROWS):
            aj = a8[r : r + 1, :]
            sj = s8[r : r + 1, :]
            first_idle = jnp.min(jnp.where(free <= aj, slot_id, none), axis=0, keepdims=True)
            min_free = jnp.min(free, axis=0, keepdims=True)
            soonest = jnp.min(jnp.where(free == min_free, slot_id, none), axis=0, keepdims=True)
            slot = jnp.where(first_idle < none, first_idle, soonest)
            hit = slot_id == slot
            free_sel = jnp.sum(jnp.where(hit, free, 0.0), axis=0, keepdims=True)
            speed_sel = jnp.sum(jnp.where(hit, speeds, 0.0), axis=0, keepdims=True)
            start = jnp.maximum(aj, free_sel)
            svc = sj / speed_sel
            finish = start + svc
            free = jnp.where(hit, finish, free)
            at = row_id == r
            st8 = jnp.where(at, start, st8)
            fi8 = jnp.where(at, finish, fi8)
            sv8 = jnp.where(at, svc, sv8)
            sl8 = jnp.where(at, slot, sl8)
        start_ref[rows, :] = st8
        fin_ref[rows, :] = fi8
        svc_ref[rows, :] = sv8
        slot_ref[rows, :] = sl8
        return free

    free_ref[...] = jax.lax.fori_loop(0, a_ref.shape[0] // _ROWS, group, free_ref[...])


def _call(a, s, sp, *, block_j, interpret):
    (Jp, Bp), c = a.shape, sp.shape[0]
    tile = pl.BlockSpec((block_j, BLOCK_B), lambda i, j: (j, i))
    return pl.pallas_call(
        without_callers(functools.partial(_kernel, c=c)),
        grid=(Bp // BLOCK_B, Jp // block_j),
        in_specs=[tile, tile, pl.BlockSpec((c, BLOCK_B), lambda i, j: (0, 0))],
        out_specs=[tile] * 4,
        out_shape=[jax.ShapeDtypeStruct((Jp, Bp), a.dtype)] * 3
        + [jax.ShapeDtypeStruct((Jp, Bp), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((c, BLOCK_B), a.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="kw_queue",
    )(a, s, sp)


def kw_queue_rows(arrivals, services, speeds):
    """arrivals, services: (n_queues, n_jobs) independent FIFO queues;
    speeds: (c,) per-slot speed multipliers, sorted descending.
    Returns (starts, finishes, scaled_services, slots), each (n_queues, n_jobs).

    Traced into its caller's program with no jit boundary, so the kernel's
    operation carries the caller's source location (`batched_queue`'s)."""
    B, J = arrivals.shape
    c = speeds.shape[0]
    block_j = min(BLOCK_J, -(-J // _ROWS) * _ROWS)
    pad = ((0, (-J) % block_j), (0, (-B) % BLOCK_B))
    # padded jobs queue after every real one and padded queues are sliced
    # off, so neither changes a real output; unit services keep them finite
    a = jnp.pad(arrivals.T, pad)
    s = jnp.pad(services.T, pad, constant_values=1.0)
    sp = jnp.broadcast_to(speeds.astype(a.dtype)[:, None], (c, BLOCK_B))
    outs = run_kernel(functools.partial(_call, block_j=block_j), a, s, sp)
    return tuple(z[:J, :B].T for z in outs)


#: `kw_queue_rows` as a program of its own
kw_queue = jax.jit(kw_queue_rows)
