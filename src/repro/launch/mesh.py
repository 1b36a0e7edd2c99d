"""Production meshes.

Built by FUNCTIONS (never at module import) so importing this module does
not touch jax device state — the dry-run must set XLA_FLAGS before any jax
initialization.
"""

from __future__ import annotations

import jax

#: published per-chip peaks, keyed by `device_kind` as JAX reports it
#: (Google Cloud documentation, "TPU v5e"): bf16 FLOP/s, HBM bytes/s, and
#: interconnect bytes/s per link (1,600 Gbit/s over four links)
PEAKS = {
    "TPU v5 lite": dict(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of `device_kind`; a kind with no
    entry is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Small mesh for CI-scale sharding tests (needs 8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch is sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
