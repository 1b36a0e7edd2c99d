# Pallas TPU kernels for the framework's compute hot-spots (attention,
# Mamba2 SSD) plus the paper's own hot loops (residual sampler, KW queue).
# Each kernel ships with ops.py (jit'd wrapper) and ref.py (pure-jnp oracle).
import jax


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
