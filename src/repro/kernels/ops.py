"""jit'd public wrappers for the Pallas kernels.

A kernel compiles through Mosaic in a program lowered for a TPU and runs
in interpret mode (the kernel body as traced jnp) in one lowered for any
other platform; `repro.kernels.run_kernel` makes that choice per program.
"""

from .flash_attention import flash_attention  # noqa: F401
from .kw_queue import kw_queue  # noqa: F401
from .residual_sampler import residual_sample  # noqa: F401
from .ssd_scan import ssd_scan  # noqa: F401
