# Pallas TPU kernels for the framework's compute hot-spots (attention,
# Mamba2 SSD) plus the paper's own hot loops (residual sampler, KW queue).
# Each kernel ships with ops.py (jit'd wrapper) and ref.py (pure-jnp oracle).
import concurrent.futures
import functools

import jax
from jax.extend import source_info_util


def run_kernel(call, *args):
    """`call(*args, interpret=...)`, compiled through Mosaic where the
    enclosing program is lowered for a TPU and in interpret mode on any
    other platform.

    The choice is made when the program is lowered, from the platform its
    arrays are placed on (`lax.platform_dependent`), not from the process's
    default backend: a program compiled for a TPU always carries the
    compiled kernel, and only the chosen branch is lowered."""
    return jax.lax.platform_dependent(
        *args,
        tpu=lambda *a: call(*a, interpret=False),
        default=lambda *a: call(*a, interpret=True),
    )


def without_callers(kernel):
    """`kernel`, a Pallas kernel body, traced as if no code of this program
    had called it.

    Mosaic serialises a kernel together with the source locations of its
    trace, and each location holds the Python stack that traced it, up to
    the entry script; the persistent compile cache strips locations from
    the enclosing program only.  So a program holding a kernel would get
    another cache key from every script that traces it.  Traced under a
    traceback taken on a fresh thread, whose stack is the standard
    library's alone, the kernel's operations carry no location of this
    program, and every caller gets one key."""

    @functools.wraps(kernel)
    def traced(*refs):
        with source_info_util.user_context(_no_caller()):
            return kernel(*refs)

    return traced


@functools.cache
def _no_caller():
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        return pool.submit(source_info_util.current).result().traceback
