"""Shared helpers for the Pallas TPU kernels.

All kernels target TPU (pl.pallas_call + explicit BlockSpec VMEM tiling) and
are validated on CPU with ``interpret=True`` against their pure-jnp oracles
in ``ref.py``. ``INTERPRET`` flips automatically when no TPU is present so
the same call sites work in both environments.
"""

from __future__ import annotations

import jax

# MXU/VPU-aligned tile sizes (v5e: 128x128 MXU, (8,128) VREG lanes).
LANE = 128
SUBLANE = 8


def interpret_default() -> bool:
    """True when running without a TPU (kernels execute in interpret mode).

    A failed device probe raises: silently interpreting would hide a broken
    accelerator behind a slow CPU emulation."""
    return jax.devices()[0].platform != "tpu"


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def paged_impl_default() -> str:
    """Default implementation for the paged-native decode kernels.

    On TPU the Pallas kernels own the hot path (the scalar-prefetched page
    table drives the HBM→VMEM stream). Without a TPU the XLA reference —
    which fetches the same per-block operands with plain gathers — is both
    the correctness oracle and much faster than interpret-mode emulation,
    so the serving engine defaults to it on CPU CI.
    """
    return "ref" if interpret_default() else "pallas"
