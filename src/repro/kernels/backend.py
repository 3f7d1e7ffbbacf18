"""Where Pallas kernels run: the one place interpret mode is decided.

Kernels compile with Mosaic on a TPU backend and run in the Pallas
interpreter everywhere else (the CPU test suite). Every ``pallas_call``
in ``repro.kernels`` reads this at trace time; no caller passes it.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

# v5e has 128 MiB of VMEM per core and a 16 MiB default scoped limit; the
# fused serving blocks keep a whole sample resident, so they ask for most
# of the physical VMEM and leave headroom for Mosaic's own scratch
VMEM_LIMIT_BYTES = 100 * 2**20


def interpret() -> bool:
    """True when kernels must run in the Pallas interpreter (no TPU)."""
    return jax.default_backend() != "tpu"


def compiler_params(n_grid_axes: int):
    """Mosaic parameters for a kernel whose grid axes are all independent."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_grid_axes,
        vmem_limit_bytes=VMEM_LIMIT_BYTES,
    )
